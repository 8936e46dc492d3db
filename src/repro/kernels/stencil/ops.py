"""jit'd public wrapper for the acoustic wave step.

``impl="pallas"`` plans the kernel's ``(bz, by)`` tile through the shared
OverlapPlanner against the VMEM limit the kernel compiles with (``bz``
caps the tile's Z depth); ``interpret=None`` resolves from the backend at
call time.

The resolution happens HERE, before the jit boundary, so the jit cache is
keyed on the *resolved* flag rather than on ``None``: a cached trace can
never pin a stale backend resolution (the silent-interpretation bug class
PR 2 fixed for the matmul path), and calling with ``interpret=None`` vs the
explicitly resolved value hits the same cache entry.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax

from repro.kernels.plan import (VMEM_LIMIT_BYTES, default_planner,
                                resolve_interpret)
from .fused import exchange_halos, fused_wave_step  # noqa: F401 - re-export
from .kernel import wave_step_pallas
from .ref import RADIUS
from .ref import wave_step_ref

__all__ = ["wave_step", "fused_wave_step", "exchange_halos"]


@functools.partial(jax.jit, static_argnames=("dx", "impl", "tile",
                                             "interpret"))
def _wave_step_jit(u, u_prev, c2dt2, *, dx: float, impl: str,
                   tile: Optional[Tuple[int, int]],
                   interpret: Optional[bool]):
    if impl == "ref":
        return wave_step_ref(u, u_prev, c2dt2, dx=dx)
    if impl == "pallas":
        return wave_step_pallas(u, u_prev, c2dt2, dx=dx, tile=tile,
                                interpret=interpret)
    raise ValueError(impl)


def wave_step(u, u_prev, c2dt2, *, dx: float = 1.0, impl: str = "ref",
              bz: Optional[int] = None, interpret: Optional[bool] = None):
    """u, u_prev: (Z, Y, X) f32; c2dt2 scalar or (Z, Y, X).  One leapfrog step."""
    tile = None
    if impl == "pallas":
        interpret = resolve_interpret(interpret)
        Z, Y, X = u.shape
        tile = default_planner().plan_stencil_tile(
            Z, Y, X, u.dtype, radius=RADIUS, bz=bz or 8,
            budget=VMEM_LIMIT_BYTES)
        if tile is None:
            raise ValueError(
                f"no stencil tile of a {Y}x{X} plane fits "
                f"{VMEM_LIMIT_BYTES} B of VMEM: use impl='ref'")
    else:
        # the ref path ignores both knobs: normalize them out of the jit key
        # so explicit values cannot mint duplicate cache entries
        interpret = None
    return _wave_step_jit(u, u_prev, c2dt2, dx=dx, impl=impl, tile=tile,
                          interpret=interpret)
