"""Random weights of a dense decoder, drawn on the device from the seed.

One jitted call makes every parameter in the type it is served in (bf16),
layer by layer under ``lax.map`` so that no float32 copy of a whole stack is
ever held.  Every tensor is keyed by its name and its layer, so the plain
reference can draw any single layer again, bit for bit, without touching
what the program holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LAYER_PARAMS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                "w_gate", "w_up", "w_down")
GLOBAL_PARAMS = ("embed/table", "final_norm", "lm_head")


def _shapes(cfg: dict) -> dict:
    """name -> (per-layer shape, std); std None means ones (norm scales)."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    ff = cfg["intermediate_size"]
    return {
        "attn_norm": ((d,), None),
        "wq": ((d, q), d ** -0.5),
        "wk": ((d, kv), d ** -0.5),
        "wv": ((d, kv), d ** -0.5),
        "wo": ((q, d), q ** -0.5),
        "mlp_norm": ((d,), None),
        "w_gate": ((d, ff), d ** -0.5),
        "w_up": ((d, ff), d ** -0.5),
        "w_down": ((ff, d), ff ** -0.5),
        "embed/table": ((cfg["vocab_size"], d), 1.0),
        "final_norm": ((d,), None),
        "lm_head": ((d, cfg["vocab_size"]), d ** -0.5),
    }


def _draw(key, shape, std, dtype):
    if std is None:
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _key(seed32, name: str, layer=None):
    # the name's index in the fixed lists keys it; seed32 may be traced
    names = LAYER_PARAMS + GLOBAL_PARAMS
    k = jax.random.fold_in(jax.random.PRNGKey(seed32), names.index(name))
    return k if layer is None else jax.random.fold_in(k, layer)


def layer_weights(cfg: dict, seed32: int, layer, dtype=jnp.bfloat16) -> dict:
    """One layer's tensors (traceable in ``layer``)."""
    shapes = _shapes(cfg)
    return {n: _draw(_key(seed32, n, layer), *shapes[n], dtype)
            for n in LAYER_PARAMS}


def global_weights(cfg: dict, seed32: int, dtype=jnp.bfloat16) -> dict:
    shapes = _shapes(cfg)
    return {n: _draw(_key(seed32, n), *shapes[n], dtype)
            for n in GLOBAL_PARAMS}


def make_params(cfg: dict, seed32: int, dtype=jnp.bfloat16, device=None):
    """Every parameter, named as the program's dense schema names them
    (``layers/<name>`` stacked over layers), in one jitted call."""
    L = cfg["num_hidden_layers"]

    def build(seed):
        stack = jax.lax.map(lambda l: layer_weights(cfg, seed, l, dtype),
                            jnp.arange(L))
        out = {f"layers/{n}": v for n, v in stack.items()}
        out.update(global_weights(cfg, seed, dtype))
        return out

    kw = {} if device is None else {
        "out_shardings": jax.sharding.SingleDeviceSharding(device)}
    # the seed is an argument, so every seed runs the one compiled program
    return jax.jit(build, **kw)(jnp.asarray(seed32, jnp.uint32))
