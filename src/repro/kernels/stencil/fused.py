"""Fused halo-overlapped Minimod wave step (paper §4.5, Listings 1–2).

The host-loop Minimod (``benchmarks/bench_minimod.py`` seed shape) exchanged
halos OUTSIDE the step: every step was exchange → fence → full-grid
stencil, with compute and communication strictly serialized.  This module
makes the ring matmul's move for the paper's flagship application: the
halo exchange becomes one-sided puts inside the step, and
the step is split so the interior — which needs no halo at all — computes
under the in-flight exchange.

One schedule (:meth:`repro.kernels.plan.HaloPlan.schedule`), run by XLA:
each remote copy is an ``ompx_put`` (a ``collective-permute`` remote DMA)
started before the interior compute.  Where a tile of the streamed Pallas
stencil (:func:`repro.kernels.stencil.kernel.wave_step_pallas`) fits, that
kernel computes the interior planes of a Z-only split, or the whole field
of a lone rank; everything else in the step stays XLA.  The XLA path is
differentiable, runs under ``shard_map`` on any backend, and supports 2-D
(Z×Y) decomposition, **asymmetric** per-rank Z extents (heterogeneous
ranks own proportional subdomains — the paper's asymmetric-allocation
scenario), and carried halos for the multi-step time loop.

Carried-halo time loop (``return_halos=True``): the halos of the *current*
field landed during the previous step, so each step computes the R-thick
boundary output slabs FIRST, puts them one-sided to the neighbors (they are
exactly the neighbors' next-step halos), computes the interior under the
in-flight exchange, and fences.  Every put is recorded against the active
context's :class:`~repro.core.rma.RMATracker` halo windows, so the wire
traffic is auditable against the OMPCCL call log byte for byte.

Asymmetric extents: SPMD tracing requires one static local shape, so every
rank's shard is padded to the maximum extent and ``z_extents`` (a static
per-rank tuple) marks the valid rows; slab extraction/placement happens at
the traced valid edge and invalid rows are kept at zero.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.backends import payload_bytes
from repro.core.groups import DiompGroup
from repro.core.rma import RMAError, halo_window_names, ompx_fence, ompx_put
from repro.core.spans import recorder
from repro.core.vma import zeros_varying
from repro.kernels.plan import (LANES, VMEM_LIMIT_BYTES, HaloPlan,
                                default_planner, resolve_interpret)
from .kernel import wave_step_pallas
from .ref import COEFFS, RADIUS

__all__ = [
    "DISPATCH_EMULATION",
    "DISPATCH_PALLAS",
    "Halos",
    "exchange_halos",
    "fused_wave_step",
]


# trace-time counters of fused_wave_step's dispatch
DISPATCH_PALLAS = "stencil.dispatch.pallas"
DISPATCH_EMULATION = "stencil.dispatch.emulation"


class Halos(NamedTuple):
    """The four halo slabs of one shard (``None`` where the axis is whole).

    ``z_lo``/``z_hi`` are (R, Y, X) slabs from the Z neighbors, ``y_lo``/
    ``y_hi`` (Z, R, X) strips from the Y neighbors.  A pytree, so a Halos
    rides directly in a ``lax.scan`` carry for the multi-step time loop.
    """

    z_lo: Optional[jax.Array] = None
    z_hi: Optional[jax.Array] = None
    y_lo: Optional[jax.Array] = None
    y_hi: Optional[jax.Array] = None


def _tracker():
    from repro.core.context import default_context

    return default_context().rma


def _put_slab(slab, group: DiompGroup, *, shift: int, window: str):
    """One-sided slab put, recorded against the tracker's halo window."""
    tr = _tracker()
    tr.ensure(window)
    tr.on_put(window, payload_bytes(slab))
    return ompx_put(slab, group, shift=shift)


# ---------------------------------------------------------------------------
# the 25-point star on halo-extended slabs (shared by every phase)
# ---------------------------------------------------------------------------


def _leap(uext, prev, c2, *, dx: float, dtype):
    """One leapfrog update of the core of an already halo-extended slab.

    ``uext`` carries R rows/cols of halo (real neighbor data or Dirichlet
    zeros) on every axis; ``prev``/``c2`` are core-shaped.  The arithmetic
    mirrors :func:`repro.kernels.stencil.ref.wave_step_ref` term for term
    so the fused step stays within rounding of the oracle.
    """
    R = RADIUS
    bz = uext.shape[0] - 2 * R
    by = uext.shape[1] - 2 * R
    bx = uext.shape[2] - 2 * R
    zc, yc, xc = slice(R, R + bz), slice(R, R + by), slice(R, R + bx)
    center = uext[zc, yc, xc]
    c0, *cs = COEFFS
    lap = 3.0 * c0 * center
    for r, c in zip(range(1, R + 1), cs):
        lap = lap + c * (uext[slice(R - r, R - r + bz), yc, xc]
                         + uext[slice(R + r, R + r + bz), yc, xc])
        lap = lap + c * (uext[zc, slice(R - r, R - r + by), xc]
                         + uext[zc, slice(R + r, R + r + by), xc])
        lap = lap + c * (uext[zc, yc, slice(R - r, R - r + bx)]
                         + uext[zc, yc, slice(R + r, R + r + bx)])
    lap = lap / (dx * dx)
    return (2.0 * center - prev + c2 * lap).astype(dtype)


def _mask_valid(a, zv, Z: int):
    """Zero every row at or beyond the valid Z extent (the padding rows of
    an asymmetric shard must stay zero — they are other ranks' Dirichlet
    boundary as far as the star is concerned)."""
    if isinstance(zv, int) and zv == Z:
        return a
    ziota = lax.broadcasted_iota(jnp.int32, (Z, 1, 1), 0)
    return jnp.where(ziota < zv, a, jnp.zeros((), a.dtype))


def _assemble(upad, halos: Halos, *, zv, Z: int, Y: int, X: int):
    """Place the landed halos into the zero-padded field at the valid edge."""
    R = RADIUS
    uext = upad
    if halos.z_lo is not None:
        uext = lax.dynamic_update_slice(uext, halos.z_lo, (0, R, R))
        uext = lax.dynamic_update_slice(uext, halos.z_hi, (zv + R, R, R))
    if halos.y_lo is not None:
        uext = lax.dynamic_update_slice(uext, halos.y_lo, (R, 0, R))
        uext = lax.dynamic_update_slice(uext, halos.y_hi, (R, Y + R, R))
    return uext


# ---------------------------------------------------------------------------
# halo exchange over one-sided puts (asymmetric- and 2-D-aware)
# ---------------------------------------------------------------------------


def _slabs_of(u, *, zv, nz: int, ny: int):
    """(z_lo, z_hi, y_lo, y_hi) boundary slabs of a field, at the valid edge."""
    R = RADIUS
    Z, Y, X = u.shape
    z_lo = z_hi = y_lo = y_hi = None
    if nz > 1:
        z_lo = lax.slice_in_dim(u, 0, R, axis=0)
        z_hi = lax.dynamic_slice(u, (zv - R, 0, 0), (R, Y, X))
    if ny > 1:
        y_lo = _mask_valid(lax.slice_in_dim(u, 0, R, axis=1), zv, Z)
        y_hi = _mask_valid(
            lax.slice_in_dim(u, Y - R, Y, axis=1), zv, Z)
    return z_lo, z_hi, y_lo, y_hi


def _halo_puts(slabs, zgroup: DiompGroup, ygroup: Optional[DiompGroup],
               *, nz: int, ny: int) -> Halos:
    """Issue the one-sided puts of a step; returns the (un-fenced) halos.

    Every put is a full-ring permute with the wrap-around edge masked to
    zeros after landing: non-periodic boundaries.
    """
    z_lo = z_hi = y_lo = y_hi = None
    if nz > 1:
        lo_w, hi_w = halo_window_names(zgroup, 0)
        iz = lax.axis_index(zgroup.axes[0])
        z_lo = _put_slab(slabs[1], zgroup, shift=1, window=lo_w)
        z_hi = _put_slab(slabs[0], zgroup, shift=-1, window=hi_w)
        z_lo = jnp.where(iz == 0, jnp.zeros_like(z_lo), z_lo)
        z_hi = jnp.where(iz == nz - 1, jnp.zeros_like(z_hi), z_hi)
    if ny > 1:
        lo_w, hi_w = halo_window_names(ygroup, 1)
        iy = lax.axis_index(ygroup.axes[0])
        y_lo = _put_slab(slabs[3], ygroup, shift=1, window=lo_w)
        y_hi = _put_slab(slabs[2], ygroup, shift=-1, window=hi_w)
        y_lo = jnp.where(iy == 0, jnp.zeros_like(y_lo), y_lo)
        y_hi = jnp.where(iy == ny - 1, jnp.zeros_like(y_hi), y_hi)
    return Halos(z_lo, z_hi, y_lo, y_hi)


def _fence_halos(halos: Halos, zgroup: DiompGroup,
                 ygroup: Optional[DiompGroup]) -> Halos:
    """Complete the step's puts; advances the tracker's window epochs so the
    subsequent halo reads satisfy the put→fence→read discipline."""
    live = [h for h in halos if h is not None]
    if not live:
        return halos
    fenced = iter(ompx_fence(*live) if len(live) > 1
                  else (ompx_fence(*live),))
    out = Halos(*(next(fenced) if h is not None else None for h in halos))
    tr = _tracker()
    windows = []
    if halos.z_lo is not None:
        windows += list(halo_window_names(zgroup, 0))
    if halos.y_lo is not None:
        windows += list(halo_window_names(ygroup, 1))
    tr.on_fence(*windows)
    for w in windows:
        tr.on_read(w)
    return out


def exchange_halos(u, zgroup: DiompGroup, ygroup: Optional[DiompGroup] = None,
                   *, z_extents: Optional[Tuple[int, ...]] = None) -> Halos:
    """One complete halo exchange of the current field (puts + one fence).

    The time loop's prologue — and the whole exchange of the non-overlapped
    fallback schedule.  Inside ``shard_map``.
    """
    from repro.core.compat import axis_size

    nz = axis_size(zgroup.axes[0])
    ny = axis_size(ygroup.axes[0]) if ygroup is not None else 1
    Z = u.shape[0]
    zv = Z if z_extents is None else \
        jnp.asarray(z_extents, jnp.int32)[lax.axis_index(zgroup.axes[0])]
    slabs = _slabs_of(u, zv=zv, nz=nz, ny=ny)
    return _fence_halos(_halo_puts(slabs, zgroup, ygroup, nz=nz, ny=ny),
                        zgroup, ygroup)


# ---------------------------------------------------------------------------
# the schedule's phases over ompx_put
# ---------------------------------------------------------------------------


def _boundary(uext, u_prev, c2, *, zv, nz: int, ny: int, dx: float, dtype):
    """The R-thick boundary output slabs (phase "boundary" of the plan)."""
    R = RADIUS
    Z, Y, X = u_prev.shape
    lo = hi = y_lo = y_hi = None
    if nz > 1:
        lo = _leap(uext[0:3 * R], u_prev[0:R], c2[0:R], dx=dx, dtype=dtype)
        hi = _leap(
            lax.dynamic_slice(uext, (zv - R, 0, 0), (3 * R, Y + 2 * R, X + 2 * R)),
            lax.dynamic_slice(u_prev, (zv - R, 0, 0), (R, Y, X)),
            lax.dynamic_slice(c2, (zv - R, 0, 0), (R, Y, X)),
            dx=dx, dtype=dtype)
    if ny > 1:
        y_lo = _mask_valid(
            _leap(uext[:, 0:3 * R], u_prev[:, 0:R], c2[:, 0:R],
                  dx=dx, dtype=dtype), zv, Z)
        y_hi = _mask_valid(
            _leap(uext[:, Y - R:Y + 2 * R], u_prev[:, Y - R:Y],
                  c2[:, Y - R:Y], dx=dx, dtype=dtype), zv, Z)
    return lo, hi, y_lo, y_hi


def _interior(u, upad, u_prev, c2, *, nz: int, ny: int, dx: float, dtype,
              tile: Optional[Tuple[int, int]] = None):
    """The halo-independent interior (phase "interior"): computed from the
    local field alone, so it runs entirely under the in-flight exchange.
    With a ``tile`` (Z-only split), the streamed Pallas kernel computes
    its output planes ``[R, Z - R)`` from ``u`` itself."""
    R = RADIUS
    Z, Y, X = u_prev.shape
    if tile is not None:
        return wave_step_pallas(u, u_prev, c2, dx=dx, tile=tile,
                                z_range=(R, Z - R))
    zsl = slice(R, Z + R) if nz > 1 else slice(0, Z + 2 * R)
    ysl = slice(R, Y + R) if ny > 1 else slice(0, Y + 2 * R)
    pz = slice(R, Z - R) if nz > 1 else slice(0, Z)
    py = slice(R, Y - R) if ny > 1 else slice(0, Y)
    return _leap(upad[zsl, ysl, :], u_prev[pz, py, :], c2[pz, py, :],
                 dx=dx, dtype=dtype)


def _combine(interior, boundary, like, *, zv, nz: int, ny: int):
    """Stitch the passes back into one shard; invalid rows forced to zero."""
    R = RADIUS
    Z, Y, X = like.shape
    out = zeros_varying((Z, Y, X), like.dtype, like)
    if interior is not None:
        out = lax.dynamic_update_slice(
            out, interior, (R if nz > 1 else 0, R if ny > 1 else 0, 0))
    lo, hi, y_lo, y_hi = boundary
    if y_lo is not None:
        out = lax.dynamic_update_slice(out, y_lo, (0, 0, 0))
        out = lax.dynamic_update_slice(out, y_hi, (0, Y - R, 0))
    if lo is not None:
        out = lax.dynamic_update_slice(out, lo, (0, 0, 0))
        out = lax.dynamic_update_slice(out, hi, (zv - R, 0, 0))
    return _mask_valid(out, zv, Z)


def _run_schedule(
    u, u_prev, c2dt2, zgroup: DiompGroup,
    ygroup: Optional[DiompGroup], *,
    plan: HaloPlan, dx: float, halos: Optional[Halos],
    z_extents: Optional[Tuple[int, ...]], return_halos: bool,
    tile: Optional[Tuple[int, int]],
):
    """Execute :meth:`HaloPlan.schedule` with ``ompx_put`` as the remote copy.

    With ``return_halos=True`` the step returns ``(u_next,
    halos_of_u_next)`` for the carried time loop.  A ``tile`` (Z-only
    split) hands the interior phase to the streamed Pallas stencil; the
    rest of the step is unchanged.
    """
    R = plan.halo
    Z, Y, X = u.shape
    nz, ny = plan.nz, plan.ny
    dtype = u.dtype
    c2 = jnp.broadcast_to(jnp.asarray(c2dt2, dtype), u.shape)
    zv = Z if z_extents is None else \
        jnp.asarray(z_extents, jnp.int32)[lax.axis_index(zgroup.axes[0])]
    u = _mask_valid(u, zv, Z)
    u_prev = _mask_valid(u_prev, zv, Z)
    upad = jnp.pad(u, R)

    if halos is None and return_halos and plan.overlap:
        # entering the carried loop: prologue exchange of the current field
        halos = exchange_halos(u, zgroup, ygroup, z_extents=z_extents)
    sched = plan.schedule(carried=halos is not None)

    if sched == ("all",):                      # no exchanging axis at all
        out = _mask_valid(_leap(upad, u_prev, c2, dx=dx, dtype=dtype), zv, Z)
        return (out, None) if return_halos else out

    if sched == ("put", "fence", "all"):       # planner fallback: no overlap
        if halos is None:
            halos = exchange_halos(u, zgroup, ygroup, z_extents=z_extents)
        uext = _assemble(upad, halos, zv=zv, Z=Z, Y=Y, X=X)
        out = _mask_valid(_leap(uext, u_prev, c2, dx=dx, dtype=dtype), zv, Z)
        # fallback halos are of the INPUT field — stale after the step, so
        # the time loop re-exchanges next step rather than carrying them
        return (out, None) if return_halos else out

    if sched == ("put", "interior", "fence", "boundary"):
        # single step, no carried halos: exchange the current field's slabs
        # while the interior computes under it
        started = _halo_puts(_slabs_of(u, zv=zv, nz=nz, ny=ny),
                             zgroup, ygroup, nz=nz, ny=ny)
        interior = _interior(u, upad, u_prev, c2, nz=nz, ny=ny, dx=dx,
                             dtype=dtype, tile=tile)
        landed = _fence_halos(started, zgroup, ygroup)
        uext = _assemble(upad, landed, zv=zv, Z=Z, Y=Y, X=X)
        bnd = _boundary(uext, u_prev, c2, zv=zv, nz=nz, ny=ny, dx=dx,
                        dtype=dtype)
        out = _combine(interior, bnd, u, zv=zv, nz=nz, ny=ny)
        return (out, None) if return_halos else out

    assert sched == ("boundary", "put", "interior", "fence"), sched
    # carried halos: boundary first (it has everything it needs), its fresh
    # values go straight onto the wire, the interior hides the transfer
    uext = _assemble(upad, halos, zv=zv, Z=Z, Y=Y, X=X)
    bnd = _boundary(uext, u_prev, c2, zv=zv, nz=nz, ny=ny, dx=dx, dtype=dtype)
    started = _halo_puts((bnd[0], bnd[1], bnd[2], bnd[3]), zgroup, ygroup,
                         nz=nz, ny=ny)
    interior = _interior(u, upad, u_prev, c2, nz=nz, ny=ny, dx=dx,
                         dtype=dtype, tile=tile)
    new_halos = _fence_halos(started, zgroup, ygroup)
    out = _combine(interior, bnd, u, zv=zv, nz=nz, ny=ny)
    return (out, new_halos) if return_halos else out


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def fused_wave_step(
    u, u_prev, c2dt2, zgroup: DiompGroup,
    ygroup: Optional[DiompGroup] = None, *,
    dx: float = 1.0,
    plan: Optional[HaloPlan] = None,
    halos: Optional[Halos] = None,
    z_extents: Optional[Tuple[int, ...]] = None,
    interpret: Optional[bool] = None,
    return_halos: bool = False,
):
    """The fused halo-overlapped wave step entry point (inside shard_map).

    ``u``/``u_prev``: (Z, Y, X) local shards; ``plan`` defaults to the
    process planner's :meth:`~repro.kernels.plan.OverlapPlanner.
    plan_halo_slots` for the traced shapes; ``interpret=None`` resolves
    from the backend at call time.  ``z_extents`` (static per-rank tuple)
    enables asymmetric Z decomposition; ``halos``/``return_halos`` thread
    the carried-halo state of the multi-step time loop.

    Off the interpreter, the streamed Pallas stencil (:func:`~repro.
    kernels.stencil.kernel.wave_step_pallas`) takes the star wherever X
    is on the lane tile and the planner finds it a tile (:meth:`~repro.
    kernels.plan.OverlapPlanner.plan_stencil_tile`): the whole field of a
    lone rank, or the interior planes of a symmetric Z-only split whose
    plan overlaps.  Every other case runs the schedule in XLA alone.  Each
    trace counts the path it took in :func:`repro.core.spans.recorder`
    (``stencil.dispatch.pallas`` where the Pallas kernel computes the
    star, ``stencil.dispatch.emulation`` where XLA's fusion of it does).
    No Pallas path has a VJP: differentiate through ``interpret=True``.
    """
    from repro.core.compat import axis_size

    nz = axis_size(zgroup.axes[0])
    ny = axis_size(ygroup.axes[0]) if ygroup is not None else 1
    Z, Y, X = u.shape
    if z_extents is not None:
        z_extents = tuple(int(e) for e in z_extents)
        if len(z_extents) != nz:
            raise ValueError(
                f"z_extents has {len(z_extents)} entries for {nz} Z ranks")
        if max(z_extents) > Z:
            raise ValueError(
                f"z_extents {z_extents} exceed the padded shard extent {Z}")
    min_z = Z if z_extents is None else min(z_extents)
    if nz > 1 and min_z < RADIUS:
        raise RMAError(
            f"halo {RADIUS} exceeds the smallest local Z extent {min_z}: "
            "the exchange would wrap non-neighbor data into the slab "
            "(merge ranks or grow the grid)")
    if ny > 1 and Y < RADIUS:
        raise RMAError(
            f"halo {RADIUS} exceeds the local Y extent {Y}")
    if plan is None:
        plan = default_planner().plan_halo_slots(
            Z, Y, X, u.dtype, nz, ny=ny, halo=RADIUS)
    if (plan.nz, plan.ny) != (nz, ny):
        raise ValueError(
            f"plan for (nz={plan.nz}, ny={plan.ny}) used on a "
            f"(nz={nz}, ny={ny}) decomposition")
    if plan.halo != RADIUS:
        raise ValueError(f"plan.halo={plan.halo} != stencil radius {RADIUS}")

    # the streamed kernel's tile: the whole field of a lone rank, or the
    # interior planes [R, Z - R) of a split; the kernel keeps X whole on
    # the lanes
    tile = None
    if (not resolve_interpret(interpret) and ny == 1 and z_extents is None
            and X % LANES == 0 and (nz == 1 or plan.overlap)):
        tile = default_planner().plan_stencil_tile(
            Z if nz == 1 else Z - 2 * RADIUS, Y, X, u.dtype, radius=RADIUS,
            budget=VMEM_LIMIT_BYTES)
    recorder().count(DISPATCH_EMULATION if tile is None else DISPATCH_PALLAS)
    if tile is not None and nz == 1:
        out = wave_step_pallas(u, u_prev, c2dt2, dx=dx, tile=tile)
        return (out, None) if return_halos else out
    return _run_schedule(
        u, u_prev, c2dt2, zgroup, ygroup, plan=plan, dx=dx, halos=halos,
        z_extents=z_extents, return_halos=return_halos, tile=tile)
