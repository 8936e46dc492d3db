"""jit'd public wrapper for the unified linear-recurrence scan.

``interpret=None`` resolves from the backend before the jit boundary, like
every other kernel wrapper: compiled on TPU, interpreted elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.plan import resolve_interpret
from .kernel import linear_scan_pallas
from .ref import linear_scan_ref

__all__ = ["linear_scan"]


def linear_scan(
    p, q, a, r,
    s0=None,
    *,
    readout_pre: bool = True,
    impl: str = "ref",
    chunk: int = 64,
    interpret: Optional[bool] = None,
):
    """p: (BH, T, M); q, a, r: (BH, T, N); s0: (BH, M, N) or None (zeros).

    Returns (y: (BH, T, M), s_final: (BH, M, N) f32).  The Pallas path
    requires s0=None (training chunks start from zero state); decode steps
    carry state through the ref path (T=1, scan cost is trivial).
    """
    interpret = resolve_interpret(interpret) if impl == "pallas" else None
    return _linear_scan_jit(p, q, a, r, s0, readout_pre=readout_pre,
                            impl=impl, chunk=chunk, interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("readout_pre", "impl", "chunk", "interpret")
)
def _linear_scan_jit(p, q, a, r, s0, *, readout_pre: bool, impl: str,
                     chunk: int, interpret: Optional[bool]):
    BH, _, M = p.shape
    N = q.shape[-1]
    if s0 is None:
        s0 = jnp.zeros((BH, M, N), jnp.float32)
    elif impl == "pallas":
        raise ValueError("pallas linear_scan requires s0=None (zero state)")
    if impl == "ref":
        return linear_scan_ref(p, q, a, r, s0, readout_pre=readout_pre)
    if impl == "pallas":
        return linear_scan_pallas(
            p, q, a, r, None, readout_pre=readout_pre, chunk=chunk,
            interpret=interpret,
        )
    raise ValueError(f"unknown impl {impl!r}")
