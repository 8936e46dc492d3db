"""Family-dispatch API: one uniform surface over the five model families.

The launch layer (dry-run, trainer, server) talks only to these functions;
each returns both abstract structure (ShapeDtypeStruct + PartitionSpec, for
the no-allocation dry-run) and the concrete step callables.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .config import ModelConfig, ParallelCtx
from . import schema as sch
from .layers import local_kv_heads
from .transformer import (init_cache, transformer_decode, transformer_loss,
                          transformer_prefill)
from .rwkv import rwkv_decode, rwkv_init_state, rwkv_loss
from .ssm import zamba_decode, zamba_init_state, zamba_loss

__all__ = [
    "loss_fn", "decode_fn", "batch_structs", "cache_structs", "has_decode",
]

TRANSFORMER_FAMILIES = ("dense", "moe", "vlm", "audio")


def loss_fn(cfg: ModelConfig) -> Callable:
    if cfg.family in TRANSFORMER_FAMILIES:
        return transformer_loss
    if cfg.family == "ssm":
        return rwkv_loss
    if cfg.family == "hybrid":
        return zamba_loss
    raise ValueError(cfg.family)


def decode_fn(cfg: ModelConfig) -> Callable:
    """(params, tokens(B,1), cfg, ctx, cache, *, seq_sharded) -> (logits, cache)."""
    if cfg.family in TRANSFORMER_FAMILIES:
        return lambda p, t, cfg, ctx, cache, seq_sharded=False: (
            transformer_decode(p, t, cfg, ctx, cache, seq_sharded=seq_sharded))
    if cfg.family == "ssm":
        return lambda p, t, cfg, ctx, cache, seq_sharded=False: (
            rwkv_decode(p, t, cfg, ctx, cache))
    if cfg.family == "hybrid":
        return lambda p, t, cfg, ctx, cache, seq_sharded=False: (
            zamba_decode(p, t, cfg, ctx, cache, seq_sharded=seq_sharded))
    raise ValueError(cfg.family)


def has_decode(cfg: ModelConfig) -> bool:
    return cfg.family != "audio"  # encoder-only archs have no decode step


def supports_long_context(cfg: ModelConfig) -> bool:
    """long_500k runs only for sub-quadratic decode-state archs."""
    return cfg.family in ("ssm", "hybrid")


# ---------------------------------------------------------------------------
# abstract batch / cache structure (dry-run currency)
# ---------------------------------------------------------------------------

def _batch_axes(mesh: Mesh, B: int,
                dp_axes: Tuple[str, ...] = ("pod", "data")) -> Tuple[str, ...]:
    """DP axes the batch dim shards over: none when ``B`` does not divide
    over them, or when they hold one device in all (sharding over size-1
    axes is replication, and would only mark the batch varying over axes
    that the rest of the step treats as invariant)."""
    axes = tuple(a for a in dp_axes if a in mesh.shape)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return axes if (axes and n > 1 and B % n == 0) else ()


def _sharding_axis(mesh: Mesh, name: str) -> Optional[str]:
    """``name`` if that mesh axis splits anything, else None: a size-1 axis
    shards nothing, and naming it would mark the value varying over an
    axis the collectives treat as invariant (see
    :meth:`repro.distributed.sharding.ShardingRules.lookup`)."""
    return name if mesh.shape.get(name, 1) > 1 else None


def batch_structs(cfg: ModelConfig, mesh: Mesh, B: int, S: int,
                  dtype=jnp.bfloat16, dp_axes=("pod", "data")):
    """(ShapeDtypeStruct pytree, PartitionSpec pytree) for one train batch."""
    ba = _batch_axes(mesh, B, dp_axes)
    bspec = P(ba if ba else None)
    if cfg.family == "audio":
        structs = {
            "embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model), dtype),
            "targets": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
        }
        specs = {"embeds": bspec, "targets": bspec, "mask": bspec}
    elif cfg.family == "vlm":
        Ptoks = cfg.prefix_tokens
        structs = {
            "tokens": jax.ShapeDtypeStruct((B, S - Ptoks), jnp.int32),
            "prefix_embeds": jax.ShapeDtypeStruct((B, Ptoks, cfg.d_model), dtype),
        }
        specs = {"tokens": bspec, "prefix_embeds": bspec}
    else:
        structs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        specs = {"tokens": bspec}
    return structs, specs


def cache_structs(cfg: ModelConfig, mesh: Mesh, ctx: ParallelCtx, B: int,
                  S: int, *, seq_sharded: bool = False, dtype=jnp.bfloat16):
    """Global-view decode cache (structs, specs).

    Local shapes inside shard_map are produced by init_cache /
    *_init_state; the global view multiplies sharded dims back up.  For
    head-parallel archs with replicated KV weights the cache's global KV dim
    is local_kv_heads·tp (each device holds its q-block's kv group).
    """
    ba = _batch_axes(mesh, B)
    bspec = ba if ba else None
    sspec = _sharding_axis(mesh, "data") if seq_sharded else None
    S_glob = S
    kd = cfg.first_k_dense if cfg.moe else 0
    L = cfg.num_layers - kd

    def k_struct_spec():
        KH_loc = local_kv_heads(cfg, ctx)
        # the cache is model-sharded whenever heads are parallel (each device
        # then holds only its q-block's kv group), else fully replicated
        kv_model = sch.kv_sharded(cfg) or (
            sch.head_parallel(cfg) and ctx.tp > 1)
        KH_glob = KH_loc * ctx.tp if kv_model else cfg.kv_heads
        spec = P(None, bspec, sspec,
                 _sharding_axis(mesh, "model") if kv_model else None, None)
        return (jax.ShapeDtypeStruct((L, B, S_glob, KH_glob, cfg.head_dim),
                                     dtype), spec)

    if cfg.family in TRANSFORMER_FAMILIES:
        if cfg.attention == "mla":
            structs = {
                "c": jax.ShapeDtypeStruct((L, B, S, cfg.kv_lora_rank), dtype),
                "kr": jax.ShapeDtypeStruct((L, B, S, cfg.qk_rope_head_dim), dtype),
                "pos": jax.ShapeDtypeStruct((), jnp.int32),
            }
            specs = {"c": P(None, bspec, None, None),
                     "kr": P(None, bspec, None, None), "pos": P()}
            if kd:
                structs["dense_c"] = jax.ShapeDtypeStruct(
                    (kd, B, S, cfg.kv_lora_rank), dtype)
                structs["dense_kr"] = jax.ShapeDtypeStruct(
                    (kd, B, S, cfg.qk_rope_head_dim), dtype)
                specs["dense_c"] = P(None, bspec, None, None)
                specs["dense_kr"] = P(None, bspec, None, None)
            return structs, specs
        ks, kp = k_struct_spec()
        return ({"k": ks, "v": ks, "pos": jax.ShapeDtypeStruct((), jnp.int32)},
                {"k": kp, "v": kp, "pos": P()})

    if cfg.family == "ssm":
        d = cfg.d_model
        hd = cfg.rwkv_head_dim
        H = d // hd
        Lr = cfg.num_layers
        structs = {
            "x_tm": jax.ShapeDtypeStruct((Lr, B, d), dtype),
            "x_cm": jax.ShapeDtypeStruct((Lr, B, d), dtype),
            "S": jax.ShapeDtypeStruct((Lr, B, H, hd, hd), jnp.float32),
        }
        specs = {"x_tm": P(None, bspec, None), "x_cm": P(None, bspec, None),
                 "S": P(None, bspec, "model", None, None)}
        return structs, specs

    if cfg.family == "hybrid":
        d = cfg.d_model
        din = 2 * d
        nh = din // 64
        Lh = cfg.num_layers
        n_app = Lh // max(cfg.attn_every, 1)
        KH_loc = local_kv_heads(cfg, ctx)
        kv_model = sch.kv_sharded(cfg)
        KH_glob = cfg.kv_heads
        kspec = P(None, bspec, sspec, "model" if kv_model else None, None)
        structs = {
            "mamba": {
                "conv": jax.ShapeDtypeStruct(
                    (Lh, B, cfg.conv_width - 1, din), dtype),
                "S": jax.ShapeDtypeStruct((Lh, B, nh, 64, cfg.ssm_state),
                                          jnp.float32),
            },
            "k": jax.ShapeDtypeStruct((n_app, B, S, KH_glob, cfg.head_dim),
                                      dtype),
            "v": jax.ShapeDtypeStruct((n_app, B, S, KH_glob, cfg.head_dim),
                                      dtype),
            "pos": jax.ShapeDtypeStruct((), jnp.int32),
        }
        specs = {
            "mamba": {"conv": P(None, bspec, None, "model"),
                      "S": P(None, bspec, "model", None, None)},
            "k": kspec, "v": kspec, "pos": P(),
        }
        return structs, specs
    raise ValueError(cfg.family)


def decode_batch_structs(cfg: ModelConfig, mesh: Mesh, B: int):
    ba = _batch_axes(mesh, B)
    return ({"tokens": jax.ShapeDtypeStruct((B, 1), jnp.int32)},
            {"tokens": P(ba if ba else None)})
