"""Pallas TPU blocked matmul — the local compute of the ring collective matmul.

Classic MXU tiling: grid = (M/bm, N/bn, K/bk) with K innermost (sequential on
TPU), f32 accumulator in VMEM scratch.  Tile defaults are MXU-aligned
(multiples of 128 on the minor dims); VMEM working set for (256, 512, 256)
tiles in bf16 is 256·512·2 + 512·256·2 + 256·256·4 ≈ 0.8 MiB — comfortably
double-bufferable.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vma import out_struct

__all__ = ["matmul_pallas"]


def _mm_kernel(x_ref, w_ref, o_ref, acc_scr, *, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ik == nk - 1)
    def _emit():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def matmul_pallas(x, w, *, bm: int = 256, bk: int = 512, bn: int = 256,
                  interpret: bool = False):
    """x: (M, K) @ w: (K, N) -> (M, N), f32 accumulation."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    pm, pk, pn = (-M) % bm, (-K) % bk, (-N) % bn
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    Mp, Kp, Np = x.shape[0], x.shape[1], w.shape[1]
    nk = Kp // bk

    out = pl.pallas_call(
        functools.partial(_mm_kernel, nk=nk),
        grid=(Mp // bm, Np // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=out_struct((Mp, Np), x.dtype, x, w),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w)
    if pm or pn:
        out = out[:M, :N]
    return out
