"""Pallas TPU kernel for the Minimod 25-point acoustic stencil.

TPU adaptation of Minimod's GPU kernel (DESIGN.md §2): instead of a thread
block per tile with shared-memory halos, the grid walks Y tiles and, within
each, the output planes in Z order.  The kernel keeps a ring of
``2R + 2`` planes of its Y tile in VMEM and DMAs one new plane of u from
HBM per output plane (``pltpu.make_async_copy``, one semaphore per slot),
started before the current plane computes — the TPU analogue of the
paper's stream-managed transfers.  u is read once, plus the 8-row Y halo
of each tile; u_prev, the velocity field and the output stream through
auto-pipelined ``BlockSpec`` blocks of ``bz`` planes.

Layout: X stays whole and lane-aligned.  A ring slot holds the tile's
``by`` rows at sublane offset 8, with 8 halo rows above and below (the
neighbor tiles' rows, or Dirichlet zeros at the grid's Y faces).  The star
walks the tile 8 rows at a time so its live values stay in vregs: the Z
taps are aligned loads from other slots, the Y taps sublane rolls of the
rows' aligned window (8 rows above, 8 below), and the X taps lane rolls
of each 128-lane tile, the wrapped lanes taken from the neighbor tile or
zeroed at the X faces by an iota mask.  Planes outside ``[0, Z)`` are
zeroed slots, so nothing pads the field in HBM.

One kernel serves the whole field and, with an output Z range, the
halo-independent interior of the split step (planes ``[R, Z − R)`` of the
local shard, read from the local u alone).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.vma import out_struct
from repro.kernels.plan import (LANES, SUBLANES, VMEM_LIMIT_BYTES,
                                stencil_halo_rows)
from .ref import COEFFS, RADIUS

__all__ = ["wave_step_pallas"]


def _tile_copies(Y: int, by: int, hy: int):
    """The Y-tile classes of the plane DMA: ``(src_row, rows, dst_row)``
    per class, ``src_row`` relative to the tile's first row.  Rows of a
    slot outside ``[dst_row, dst_row + rows)`` are Dirichlet zeros."""
    nty = -(-Y // by)
    if nty == 1:
        return {"single": (0, Y, hy)}
    last0 = (nty - 1) * by
    classes = {"first": (0, min(by + hy, Y), hy),
               "last": (-hy, Y - last0 + hy, 0)}
    if nty > 2:
        classes["middle"] = (-hy, by + 2 * hy, 0)
    return classes


def _stencil_kernel(u_hbm, uprev_ref, c2_ref, out_ref, ring, sems, *,
                    Z: int, Y: int, X: int, by: int, bz: int, z0: int,
                    z_end: int, rows: int, dx: float):
    R = RADIUS
    ns = ring.shape[0]
    hy = stencil_halo_rows(R)
    j, k = pl.program_id(0), pl.program_id(1)
    nty = -(-Y // by)
    classes = _tile_copies(Y, by, hy)

    def in_class(name):
        return {"single": True, "first": j == 0, "last": j == nty - 1,
                "middle": (j > 0) & (j < nty - 1)}[name]

    def when(cond, fn):
        if cond is True:
            fn()
        else:
            pl.when(cond)(fn)

    def copy(p, slot, name):
        src, n, dst = classes[name]
        y0 = {"single": 0, "first": 0, "last": (nty - 1) * by}.get(name)
        y0 = j * by if y0 is None else y0
        return pltpu.make_async_copy(
            u_hbm.at[p, pl.ds(y0 + src, n)],
            ring.at[slot, pl.ds(dst, n)], sems.at[slot])

    def plane_dma(p, op):
        """Start (or wait for) plane ``p`` of the tile into its slot; a
        plane outside the grid is a zeroed slot and needs no wait."""
        slot = lax.rem(p + ns, ns)
        inside = (p >= 0) & (p < Z)

        @pl.when(inside)
        def _():
            for name in classes:
                when(in_class(name),
                     lambda name=name: getattr(copy(p, slot, name), op)())

        if op == "start":
            @pl.when(jnp.logical_not(inside))
            def _():
                ring[slot] = jnp.zeros(ring.shape[1:], ring.dtype)

    @pl.when(k == 0)
    def _prime():
        # the slot rows no DMA of this tile writes are its Y halo beyond
        # the grid: zero them once, for every slot
        for name, (_, n, dst) in classes.items():
            def zero_edges(n=n, dst=dst):
                if dst:
                    ring[:, pl.ds(0, dst)] = jnp.zeros(
                        (ns, dst, X), ring.dtype)
                if dst + n < ring.shape[1]:
                    m = ring.shape[1] - dst - n
                    ring[:, pl.ds(dst + n, m)] = jnp.zeros(
                        (ns, m, X), ring.dtype)
            when(in_class(name), zero_edges)
        for dz in range(-R, R + 1):
            plane_dma(z0 + dz, "start")
        for dz in range(-R, R):
            plane_dma(z0 + dz, "wait")

    c0, *cs = COEFFS
    # X taps per 128-lane tile: a lane roll of each tile, with the lanes
    # that wrap taken from the neighbor tile's roll, or zero at the X faces
    xt = LANES if X % LANES == 0 else X
    lane = lax.broadcasted_iota(jnp.int32, (rows, xt), 1)

    def xtaps(center, r):
        tiles = [center[:, t:t + xt] for t in range(0, X, xt)]
        zero = jnp.zeros_like(tiles[0])
        lo = [zero] + [pltpu.roll(t, r, 1) for t in tiles]
        hi = [pltpu.roll(t, xt - r, 1) for t in tiles] + [zero]
        x_lo = [jnp.where(lane >= r, lo[t + 1], lo[t])
                for t in range(len(tiles))]
        x_hi = [jnp.where(lane < xt - r, hi[t], hi[t + 1])
                for t in range(len(tiles))]
        return (jnp.concatenate(x_lo, 1) if len(tiles) > 1 else x_lo[0],
                jnp.concatenate(x_hi, 1) if len(tiles) > 1 else x_hi[0])

    def plane(i, carry):
        z = z0 + k * bz + i

        @pl.when(z + 1 < z_end)
        def _():
            plane_dma(z + R + 1, "start")

        plane_dma(z + R, "wait")
        slots = [lax.rem(z + dz + ns, ns) for dz in range(-R, R + 1)]

        def chunk(c, carry):
            r0 = pl.multiple_of(c * rows, rows)
            # the chunk's rows with hy rows of halo above and below
            win = ring[slots[R], pl.ds(r0, rows + 2 * hy), :]
            center = win[hy:hy + rows]

            def ztap(dz):
                return ring[slots[R + dz], pl.ds(r0 + hy, rows), :]

            def ytap(dy):
                return pltpu.roll(win, (-dy) % win.shape[0], 0)[hy:hy + rows]

            lap = 3.0 * c0 * center
            for r, c in zip(range(1, R + 1), cs):
                lap = lap + c * (ztap(-r) + ztap(r))
                lap = lap + c * (ytap(-r) + ytap(r))
                x_lo, x_hi = xtaps(center, r)
                lap = lap + c * (x_lo + x_hi)
            lap = lap / (dx * dx)
            prev = uprev_ref[i, pl.ds(r0, rows), :]
            c2 = c2_ref[i, pl.ds(r0, rows), :]
            out_ref[i, pl.ds(r0, rows), :] = (
                2.0 * center - prev + c2 * lap).astype(out_ref.dtype)
            return carry

        lax.fori_loop(0, by // rows, chunk, 0)
        return carry

    lax.fori_loop(0, bz, plane, 0)


def wave_step_pallas(u, u_prev, c2dt2, *, dx: float = 1.0,
                     tile: Tuple[int, int], z_range: Optional[Tuple[int, int]]
                     = None, interpret: bool = False):
    """One leapfrog step of output planes ``z_range`` (default: all of
    them) of the (Z, Y, X) field ``u``; ``u_prev``/``c2dt2`` are the whole
    field's (c2dt2 may be a scalar).  ``tile`` is the planner's
    ``(bz, by)`` (:meth:`~repro.kernels.plan.OverlapPlanner.
    plan_stencil_tile`).  Planes and rows outside the field read as zeros.
    Returns the ``(z1 - z0, Y, X)`` output planes."""
    Z, Y, X = u.shape
    R = RADIUS
    z0, z1 = (0, Z) if z_range is None else z_range
    bz, by = tile
    # u_prev/velocity/output blocks are indexed in whole blocks from z0
    bz = max(min(bz, z1 - z0), 1)
    while z0 % bz:
        bz -= 1
    nzb = -(-(z1 - z0) // bz)
    rows = SUBLANES if by % SUBLANES == 0 else by
    hy = stencil_halo_rows(R)
    c2 = jnp.broadcast_to(jnp.asarray(c2dt2, u.dtype), u.shape)
    zb = z0 // bz
    return pl.pallas_call(
        functools.partial(_stencil_kernel, Z=Z, Y=Y, X=X, by=by, bz=bz,
                          z0=z0, z_end=z0 + nzb * bz, rows=rows, dx=dx),
        name="stencil_step" if z_range is None else "stencil_interior",
        grid=(-(-Y // by), nzb),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),                  # u in HBM
            pl.BlockSpec((bz, by, X), lambda j, k: (zb + k, j, 0)),
            pl.BlockSpec((bz, by, X), lambda j, k: (zb + k, j, 0)),
        ],
        out_specs=pl.BlockSpec((bz, by, X), lambda j, k: (k, j, 0)),
        out_shape=out_struct((z1 - z0, Y, X), u.dtype, u, u_prev, c2),
        scratch_shapes=[
            pltpu.VMEM((2 * R + 2, by + 2 * hy, X), u.dtype),
            pltpu.SemaphoreType.DMA((2 * R + 2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(u, u_prev, c2)
