"""Pallas TPU flash attention (GQA, causal/prefix-LM, decode offsets).

Tiling: grid = (B, H, Tq/bq, Tk/bk); the Tk dimension is innermost and TPU
grids execute it sequentially, so the online-softmax state (running max,
denominator, accumulator) lives in VMEM scratch and persists across Tk steps.
GQA needs no KV copy: the k/v BlockSpec index_map folds the q-head -> kv-head
mapping (h // group) so each q-head grid row DMAs its group's KV block only.

VMEM working set per step: q tile (bq, D) + k/v tiles (bk, D) + scores
(bq, bk) + accumulators (bq, D) — for bq = bk = 256, D = 128 in f32 that is
~0.7 MiB, far under the ~16 MiB/core budget, leaving room for the pipeline's
double buffering (the StreamPool.plan_slots contract).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vma import out_struct

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _attn_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, bq: int, bk: int, nk: int, causal: bool, q_offset: int,
    prefix_len: int, valid_len: int,
):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, D) — scale pre-folded
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, D)

    s = jax.lax.dot_general(                      # (bq, bk) on the MXU
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    vis = k_pos < valid_len
    if causal:
        vis &= (k_pos <= q_pos) | ((k_pos < prefix_len) & (q_pos < prefix_len))
    s = jnp.where(vis, s, NEG_INF)

    m_prev = m_scr[...]                           # (bq, 1)
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(vis, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q, k, v,
    *,
    causal: bool = True,
    q_offset: int = 0,
    prefix_len: int = 0,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 256,
    valid_len: Optional[int] = None,
    interpret: bool = False,
):
    """q: (B, H, Tq, D); k: (B, KH, Tk, D); v: (B, KH, Tk, Dv) -> (B, H, Tq, Dv).

    Static q_offset/valid_len only (the kernel bakes the masks); decode loops
    with traced offsets use the ref path.
    """
    B, H, Tq, D = q.shape
    KH, Tk = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    assert H % KH == 0
    G = H // KH
    if scale is None:
        scale = D ** -0.5
    if valid_len is None:
        valid_len = Tk

    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    # pad to tile multiples (padded keys masked by valid_len / positions)
    pq, pk = (-Tq) % bq, (-Tk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk = q.shape[2] // bq, k.shape[2] // bk

    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    kernel = functools.partial(
        _attn_kernel,
        bq=bq, bk=bk, nk=nk, causal=causal, q_offset=q_offset,
        prefix_len=prefix_len, valid_len=min(valid_len, Tk),
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=out_struct((B, H, q.shape[2], Dv), q.dtype, q, k, v),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(qs, k, v)
    if pq:
        out = out[:, :, :Tq]
    return out
