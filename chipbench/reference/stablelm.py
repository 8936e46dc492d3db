"""Plain float32 forward of a dense decoder of the StableLM family.

Written from the published description of stablelm-3b-4e1t (Hugging Face
``StableLmForCausalLM``) in straightforward ``jax.numpy``, with every matrix
product at ``Precision.HIGHEST``.  It imports nothing of the program and
takes no tensor the program made: weights are drawn again, layer by layer,
from the seed by ``chipbench.weights``.  The whole sequence is one forward
pass with full causal softmax attention: no cache, no chunks, no batching
tricks.

Departures from the published model, each matching what the program
serves: RMSNorm with a scale and no bias in place of LayerNorm with a bias,
and random weights.  Kept as published: rotary embedding on the first 25%
of each head's dimensions (rotate-half, base 10000), SwiGLU MLP, no QKV
bias, untied output head.

``mode="fp8"`` is the control: every matrix product takes its operands in
float8 e4m3 (per-row activation scales, per-column weight scales) and
accumulates in float32, the lower precision a later change might be
tempted to serve in.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _mm(a, w, mode: str):
    if mode == "highest":
        return jnp.matmul(a, w, precision=HIGHEST)
    if mode == "fp8":
        sa = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / F8_MAX
        sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / F8_MAX
        sa = jnp.where(sa == 0, 1.0, sa)
        sw = jnp.where(sw == 0, 1.0, sw)
        q = jnp.matmul((a / sa).astype(F8), (w / sw).astype(F8),
                       preferred_element_type=jnp.float32)
        return q * sa * sw
    raise ValueError(f"unknown mode {mode!r}")


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, pct: float, theta: float):
    """x: (B, T, H, D); rotate-half on the first ``pct`` of D."""
    D = x.shape[-1]
    rot = int(D * pct) // 2 * 2
    half = rot // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]   # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           axis=-1)


def _layer(cfg: dict, w: dict, x, mode: str):
    B, T, d = x.shape
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = d // H
    eps = cfg["norm_eps"]
    h = _rmsnorm(x, w["attn_norm"], eps)
    q = _mm(h, w["wq"], mode).reshape(B, T, H, hd)
    k = _mm(h, w["wk"], mode).reshape(B, T, KV, hd)
    v = _mm(h, w["wv"], mode).reshape(B, T, KV, hd)
    pos = jnp.arange(T)
    q = _rope(q, pos, cfg["rope_pct"], cfg["rope_theta"])
    k = _rope(k, pos, cfg["rope_pct"], cfg["rope_theta"])
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(B, T, H * hd), w["wo"], mode)
    h = _rmsnorm(x, w["mlp_norm"], eps)
    g = _mm(h, w["w_gate"], mode)
    u = _mm(h, w["w_up"], mode)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], mode)


class Reference:
    """The forward pass of one configuration, compiled once per batch
    shape and run layer by layer so that it fits beside nothing else."""

    def __init__(self, cfg: dict, mode: str = "highest", device=None):
        self.cfg, self.mode = cfg, mode
        put = {} if device is None else {
            "out_shardings": jax.sharding.SingleDeviceSharding(device)}
        f32 = jnp.float32

        def embed(seed, tokens):
            table = W.global_weights(cfg, seed)["embed/table"].astype(f32)
            return table[tokens]

        def layer(seed, l, x):
            w = {k: v.astype(f32)
                 for k, v in W.layer_weights(cfg, seed, l).items()}
            return _layer(cfg, w, x, mode)

        def head(seed, x, rows, cols):
            g = {k: v.astype(f32)
                 for k, v in W.global_weights(cfg, seed).items()}
            h = _rmsnorm(x[rows, cols], g["final_norm"], cfg["norm_eps"])
            return _mm(h, g["lm_head"], mode)

        self._embed = jax.jit(embed, **put)
        self._layer = jax.jit(layer, **put)
        self._head = jax.jit(head, **put)

    def logits(self, seed32: int, tokens: np.ndarray,
               where: Sequence[Tuple[int, int]]):
        """Logits (len(where), V) at the (row, position) pairs ``where`` of
        the (B, T) token batch ``tokens``; rows are padded at the end,
        which causal attention never lets a real position see."""
        seed = jnp.asarray(seed32, jnp.uint32)
        x = self._embed(seed, jnp.asarray(tokens, jnp.int32))
        for l in range(self.cfg["num_hidden_layers"]):
            x = self._layer(seed, jnp.asarray(l, jnp.int32), x)
        rows = jnp.asarray([r for r, _ in where], jnp.int32)
        cols = jnp.asarray([c for _, c in where], jnp.int32)
        return self._head(seed, x, rows, cols)


def served_batch(prompts: List[np.ndarray], served: List[List[int]],
                 width: int):
    """Pack prompt + served tokens into a (B, width) batch, and list the
    positions whose logits chose each served token."""
    B = len(prompts)
    tokens = np.zeros((B, width), np.int32)
    where, chosen = [], []
    for b, (p, s) in enumerate(zip(prompts, served)):
        seq = np.concatenate([np.asarray(p, np.int32),
                              np.asarray(s[:-1], np.int32)])
        if len(seq) > width:
            raise ValueError(f"sequence {len(seq)} exceeds width {width}")
        tokens[b, :len(seq)] = seq
        for j, t in enumerate(s):
            where.append((b, len(p) - 1 + j))
            chosen.append(int(t))
    return tokens, where, np.asarray(chosen, np.int32)


def widest_gap(ref_logits, chosen) -> float:
    """Largest amount by which a chosen token's reference logit lies below
    the reference's best logit at that position."""
    ref = np.asarray(ref_logits, np.float64)
    best = ref.max(axis=-1)
    got = ref[np.arange(len(chosen)), np.asarray(chosen)]
    return float((best - got).max())
