"""Pallas TPU kernel for the Minimod 25-point acoustic stencil.

TPU adaptation of Minimod's GPU kernel (DESIGN.md §2): instead of a thread
block per tile with shared-memory halos, we slab the Z axis across the grid
and DMA each (bz + 2R)-plane halo slab HBM -> VMEM explicitly with
``pltpu.make_async_copy`` — the TPU analogue of the paper's stream-managed
transfers (the DMA slot count is what StreamPool.plan_slots bounds).  The
compute walks the slab one output plane at a time: a vectorized 25-point
star over (Y, X) (VPU work, one multiply-add chain per radius), so the live
values stay a few planes wide whatever ``bz`` is.

Layout: the padded field's minor dims are rounded up to the (8, 128) tile
(Y + 2R to a sublane multiple, X + 2R to a lane multiple), so the slab DMA
only ever slices the major Z axis; the star's shifted views are loads at
unaligned offsets from that slab.

VMEM: the slab is (bz + 2R)·Yp·Xp·4 B of scratch, plus the auto-pipelined
(bz, Y, X) u_prev / velocity / output blocks, double-buffered.  At a 512²
plane that outgrows the compiler's default scoped limit, so the kernel asks
for :data:`~repro.kernels.plan.VMEM_LIMIT_BYTES` and ``ops.wave_step``
plans ``bz`` against that same limit.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vma import out_struct
from repro.kernels.plan import VMEM_LIMIT_BYTES, padded_plane
from .ref import COEFFS, RADIUS

__all__ = ["leap_plane", "wave_step_pallas"]


def leap_plane(ext, zc, prev, c2, *, oy: int, ox: int, Y: int, X: int,
               dx: float):
    """One output plane of the leapfrog step, read from a halo-extended
    VMEM ref ``ext``: the plane's center sits at ``ext[zc, oy:oy+Y,
    ox:ox+X]`` with at least R planes / rows / lanes of halo around it.
    ``prev``/``c2`` are that plane's (Y, X) u_prev and velocity values.
    The term order mirrors :func:`repro.kernels.stencil.ref.laplacian_ref`
    (radius, then axis)."""
    def view(dz, dy, dx_):
        return ext[zc + dz, pl.ds(oy + dy, Y), pl.ds(ox + dx_, X)]

    c0, *cs = COEFFS
    center = view(0, 0, 0)
    lap = 3.0 * c0 * center
    for r, c in zip(range(1, RADIUS + 1), cs):
        lap = lap + c * (view(-r, 0, 0) + view(r, 0, 0))
        lap = lap + c * (view(0, -r, 0) + view(0, r, 0))
        lap = lap + c * (view(0, 0, -r) + view(0, 0, r))
    lap = lap / (dx * dx)
    return 2.0 * center - prev + c2 * lap


def _stencil_kernel(upad_hbm, uprev_ref, c2dt2_ref, out_ref, slab, sem,
                    *, bz: int, Y: int, X: int, dx: float):
    iz = pl.program_id(0)
    R = RADIUS

    # explicit HBM -> VMEM DMA of the halo slab for this Z block
    cp = pltpu.make_async_copy(
        upad_hbm.at[pl.ds(iz * bz, bz + 2 * R)], slab, sem
    )
    cp.start()
    cp.wait()

    def plane(z, carry):
        out_ref[z] = leap_plane(
            slab, R + z, uprev_ref[z], c2dt2_ref[z], oy=R, ox=R, Y=Y, X=X,
            dx=dx).astype(out_ref.dtype)
        return carry

    lax.fori_loop(0, bz, plane, 0)


def wave_step_pallas(u, u_prev, c2dt2, *, dx: float = 1.0, bz: int = 8,
                     interpret: bool = False):
    """u, u_prev: (Z, Y, X) f32; c2dt2 scalar or (Z, Y, X).  One leapfrog step."""
    Z, Y, X = u.shape
    R = RADIUS
    bz = min(bz, Z)
    pz = (-Z) % bz
    Yp, Xp = padded_plane(Y, X, R)
    c2 = jnp.broadcast_to(jnp.asarray(c2dt2, u.dtype), u.shape)

    # zero halo (+ Z-slab overrun) and tile-aligned minor dims
    upad = jnp.pad(u, ((R, R + pz), (R, Yp - Y - R), (R, Xp - X - R)))
    if pz:
        u_prev = jnp.pad(u_prev, ((0, pz), (0, 0), (0, 0)))
        c2 = jnp.pad(c2, ((0, pz), (0, 0), (0, 0)))
    Zp = Z + pz

    out = pl.pallas_call(
        functools.partial(_stencil_kernel, bz=bz, Y=Y, X=X, dx=dx),
        name="stencil_step",
        grid=(Zp // bz,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),              # padded u in HBM
            pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0)),     # u_prev block
            pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0)),     # velocity block
        ],
        out_specs=pl.BlockSpec((bz, Y, X), lambda i: (i, 0, 0)),
        out_shape=out_struct((Zp, Y, X), u.dtype, upad, u_prev, c2),
        scratch_shapes=[
            pltpu.VMEM((bz + 2 * R, Yp, Xp), u.dtype),
            pltpu.SemaphoreType.DMA,
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(upad, u_prev, c2)
    return out[:Z]
