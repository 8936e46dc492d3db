"""Minimod — the paper's flagship application as a real driver (§4.5).

The seed kept Minimod as a host-loop example: 1-D symmetric Z sharding,
halo exchange outside the kernel, no overlap.  This driver is the full
vertical slice:

* **2-D (Z×Y) domain decomposition** with **asymmetric** Z extents —
  heterogeneous ranks own subdomains proportional to their ``weights``
  (the paper's asymmetric-allocation scenario); the wavefield regions are
  registered through :meth:`~repro.core.pgas.GlobalMemory.alloc_asymmetric`
  so the PGAS mapping table carries the real per-rank byte plan.
* **Three execution modes** (the benchmark sweep):

  - ``none``  — two-sided MPI-shaped exchange (paper Listing 2: gather the
    slabs, select, barrier), compute strictly after;
  - ``host``  — one-sided puts + one fence (paper Listing 1), full-grid
    compute after the fence — overlap left to the XLA scheduler;
  - ``fused`` — the halo-overlapped step of
    :mod:`repro.kernels.stencil.fused`: carried halos, boundary computed
    first and put one-sided while the interior runs under the exchange,
    per-step neighbor fence, schedule from
    :meth:`~repro.kernels.plan.OverlapPlanner.plan_halo_slots`.

* **Audit trail**: every one-sided put is recorded both on the OMPCCL
  communicator byte log and on the RMATracker's halo windows; the result
  carries both so callers can assert exact put-traffic parity.

SPMD note: asymmetric extents are realized as max-extent shards with a
static ``z_extents`` tuple marking the valid rows (invalid rows pinned to
zero); :func:`pad_shards`/:func:`unpad_shards` convert between the logical
grid and the padded device layout.
"""

from __future__ import annotations

import dataclasses
import inspect
import time
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import ompccl, rma
from repro.core.compat import axis_size, make_mesh, shard_map
from repro.core.coordination import fetch_global
from repro.core.context import DiompContext, use_default
from repro.core.groups import DiompGroup
from repro.kernels.plan import HaloPlan, default_planner, split_extents
from repro.kernels.stencil.fused import (Halos, exchange_halos,
                                         fused_wave_step)
from repro.kernels.stencil.ref import RADIUS, wave_step_ref
from repro.launch.shapes import STENCIL_SHAPES, StencilShape

__all__ = [
    "MODES",
    "MinimodResult",
    "pad_shards",
    "run_minimod",
    "split_extents",
    "unpad_shards",
]

MODES = ("none", "host", "fused")


# ---------------------------------------------------------------------------
# decomposition
# ---------------------------------------------------------------------------


# split_extents lives in repro.kernels.plan (it now also sizes the MoE
# dispatch planner's per-expert capacities); re-exported here unchanged so
# the driver API and existing imports keep working.


def pad_shards(a: np.ndarray, z_extents: Sequence[int]) -> np.ndarray:
    """(Z, Y, X) logical grid -> (nz·zmax, Y, X) padded device layout."""
    zmax = max(z_extents)
    blocks, off = [], 0
    for e in z_extents:
        blocks.append(np.pad(a[off:off + e], ((0, zmax - e), (0, 0), (0, 0))))
        off += e
    return np.concatenate(blocks, axis=0)


def unpad_shards(a: np.ndarray, z_extents: Sequence[int]) -> np.ndarray:
    """Inverse of :func:`pad_shards`: drop every rank's padding rows."""
    zmax = max(z_extents)
    return np.concatenate(
        [a[i * zmax:i * zmax + e] for i, e in enumerate(z_extents)], axis=0)


# ---------------------------------------------------------------------------
# the two baseline halo styles (the paper's programmability comparison)
# ---------------------------------------------------------------------------


def _host_step_listing1(u, u_prev, c2dt2, zgroup, *, dx=1.0):
    """Minimod step, DiOMP style (paper Listing 1): two one-sided puts +
    one fence, then the full-grid stencil — exchange and compute strictly
    serialized (the ``host`` benchmark mode)."""
    R = RADIUS
    left, right = rma.halo_exchange(u, zgroup, halo=R, axis=0)
    up = jnp.concatenate([left, u, right], axis=0)
    prev = jnp.pad(u_prev, ((R, R), (0, 0), (0, 0)))
    return wave_step_ref(up, prev, c2dt2, dx=dx)[R:-R]


def _two_sided_halos(u, zgroup, *, zv):
    """MPI style (paper Listing 2): explicit sends, receives and Waitall —
    every slab materialized on every rank, then selected and barriered."""
    R = RADIUS
    Z, Y, X = u.shape
    n = axis_size(zgroup.axes[0])
    iz = lax.axis_index(zgroup.axes[0])
    down = lax.dynamic_slice(u, (zv - R, 0, 0), (R, Y, X))
    up_slab = lax.slice_in_dim(u, 0, R, axis=0)
    all_down = ompccl.allgather(down, zgroup, axis=0)
    all_up = ompccl.allgather(up_slab, zgroup, axis=0)
    left = lax.dynamic_slice_in_dim(
        all_down, lax.rem(iz + n - 1, n) * R, R, axis=0)
    right = lax.dynamic_slice_in_dim(
        all_up, lax.rem(iz + 1, n) * R, R, axis=0)
    left = jnp.where(iz == 0, jnp.zeros_like(left), left)
    right = jnp.where(iz == n - 1, jnp.zeros_like(right), right)
    token = ompccl.barrier_value(zgroup)        # MPI_Waitall
    wait = (0 * token).astype(u.dtype)
    return Halos(left + wait, right + wait, None, None)


def halo_loc() -> Dict[str, int]:
    """Lines of code of the two halo styles (the paper's Fig. 8 claim)."""
    one = len(inspect.getsource(_host_step_listing1).strip().splitlines())
    two = len(inspect.getsource(_two_sided_halos).strip().splitlines())
    return {"diomp": one, "two_sided": two}


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class MinimodResult:
    """One Minimod run plus its audit trail."""

    field: np.ndarray                  # (Z, Y, X) logical wavefield
    wall_s: float                      # the time loop, compile excluded
    compile_s: float
    # Pallas kernels (tpu_custom_call ops) in the compiled program: 0 means
    # the XLA path ran (always so off the TPU)
    kernel_calls: int
    mode: str
    grid: Tuple[int, int, int]
    steps: int
    nz: int
    ny: int
    z_extents: Tuple[int, ...]
    plan: HaloPlan
    # OMPCCL communicator log (trace-time: one entry per call site)
    puts: int
    put_bytes: int
    # RMATracker halo-window accounting
    tracker_puts: int
    tracker_put_bytes: int
    fences: int
    window_bytes: Dict[str, int]
    # PGAS plan of the wavefield regions
    region_sizes: Tuple[int, ...]
    alloc_counts: Dict[str, int]

    @property
    def energy(self) -> float:
        return float(np.square(self.field).sum())


def run_minimod(
    grid: Tuple[int, int, int] = (64, 64, 64),
    steps: Optional[int] = None,
    nz: int = 8,
    ny: int = 1,
    weights: Optional[Sequence[float]] = None,
    *,
    mode: str = "fused",
    dtype=jnp.float32,
    c2dt2: float = 0.1,
    dx: float = 1.0,
    interpret: Optional[bool] = None,
    shape: Optional[StencilShape] = None,
    u0: Optional[np.ndarray] = None,
    u_prev0: Optional[np.ndarray] = None,
) -> MinimodResult:
    """Run ``steps`` of Minimod on an (nz × ny) decomposition.

    ``shape`` (a :data:`~repro.launch.shapes.STENCIL_SHAPES` cell or name)
    overrides grid/steps/nz/ny/weights in one go.  The default initial
    condition is the point source at the grid center; pass ``u0``/
    ``u_prev0`` (logical (Z, Y, X) arrays) for custom fields.
    """
    if isinstance(shape, str):
        shape = STENCIL_SHAPES[shape]
    if shape is not None:
        grid = shape.grid
        steps = shape.steps if steps is None else steps
        nz, ny = shape.nz, shape.ny
        # an explicitly passed decomposition wins over the shape default
        weights = shape.weights if weights is None else weights
    steps = 10 if steps is None else steps
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")
    Z, Y, X = grid
    if Y % ny:
        raise ValueError(f"Y={Y} not divisible by ny={ny} (Y is symmetric)")
    if mode == "none" and ny > 1:
        raise ValueError("the two-sided baseline is 1-D only (use ny=1)")
    z_extents = split_extents(Z, nz, weights, minimum=RADIUS)
    symmetric = len(set(z_extents)) == 1
    zmax = max(z_extents)
    y_loc = Y // ny

    mesh = make_mesh((nz, ny), ("z", "y"), axis_types="auto")
    ctx = DiompContext(mesh=mesh)
    with use_default(ctx):
        zg = DiompGroup(("z",), name="z")
        yg = DiompGroup(("y",), name="y") if ny > 1 else None
        grid_group = DiompGroup(("z", "y"), name="grid")

        # PGAS registration: heterogeneous ranks own proportional bytes —
        # rank (iz, iy) holds z_extents[iz]·y_loc·X cells, addressed through
        # the second-level pointer like every asymmetric region
        item = jnp.dtype(dtype).itemsize
        sizes = [z_extents[r // ny] * y_loc * X * item
                 for r in range(nz * ny)]
        handles = [
            ctx.memory.alloc_asymmetric(f"minimod.{nm}", sizes, grid_group,
                                        logical_axes=("z", "y", None),
                                        dtype=str(jnp.dtype(dtype)))
            for nm in ("u", "u_prev")
        ]
        region_sizes = tuple(handles[0].region.sizes)

        plan = default_planner().plan_halo_slots(
            zmax, y_loc, X, dtype, nz, ny=ny, halo=RADIUS)
        ext_arg = None if symmetric else tuple(z_extents)

        if u0 is None:
            u0 = np.zeros(grid, np.float64)
            u0[Z // 2, Y // 2, X // 2] = 1.0      # point source
        if u_prev0 is None:
            u_prev0 = np.zeros(grid, np.float64)
        u_in = pad_shards(np.asarray(u0, jnp.dtype(dtype)), z_extents)
        up_in = pad_shards(np.asarray(u_prev0, jnp.dtype(dtype)), z_extents)

        def fused_run(u, up):
            if plan.overlap:
                halos = exchange_halos(u, zg, yg, z_extents=ext_arg)

                def body(carry, _):
                    u, up, h = carry
                    un, hn = fused_wave_step(
                        u, up, c2dt2, zg, yg, dx=dx, plan=plan, halos=h,
                        z_extents=ext_arg, interpret=interpret,
                        return_halos=True)
                    return (un, u, hn), None

                (u, up, _), _ = lax.scan(body, (u, up, halos), None,
                                         length=steps)
            else:                 # degenerate grid: planner fell back
                def body(carry, _):
                    u, up = carry
                    un = fused_wave_step(
                        u, up, c2dt2, zg, yg, dx=dx, plan=plan,
                        z_extents=ext_arg, interpret=interpret)
                    return (un, u), None

                (u, up), _ = lax.scan(body, (u, up), None, length=steps)
            return u

        serial_plan = dataclasses.replace(plan, overlap=False)

        def host_run(u, up):
            def body(carry, _):
                u, up = carry
                if symmetric and ny == 1:     # the paper-verbatim listing
                    un = _host_step_listing1(u, up, c2dt2, zg, dx=dx)
                else:
                    un = fused_wave_step(
                        u, up, c2dt2, zg, yg, dx=dx, plan=serial_plan,
                        z_extents=ext_arg, interpret=interpret)
                return (un, u), None

            (u, up), _ = lax.scan(body, (u, up), None, length=steps)
            return u

        def none_run(u, up):
            iz = lax.axis_index("z")
            zv = zmax if ext_arg is None else \
                jnp.asarray(ext_arg, jnp.int32)[iz]

            def body(carry, _):
                u, up = carry
                halos = _two_sided_halos(u, zg, zv=zv)
                un = fused_wave_step(
                    u, up, c2dt2, zg, yg, dx=dx, plan=serial_plan,
                    halos=halos, z_extents=ext_arg, interpret=interpret)
                return (un, u), None

            (u, up), _ = lax.scan(body, (u, up), None, length=steps)
            return u

        run = {"fused": fused_run, "host": host_run, "none": none_run}[mode]
        # the plan the chosen mode actually executes: the serialized
        # baselines run the fallback schedule, never the overlapped one
        used_plan = plan if mode == "fused" else serial_plan
        f = jax.jit(shard_map(run, mesh=mesh,
                              in_specs=(P("z", "y"), P("z", "y")),
                              out_specs=P("z", "y")))
        t0 = time.perf_counter()
        compiled = f.lower(u_in, up_in).compile()
        t1 = time.perf_counter()
        out = jax.block_until_ready(compiled(u_in, up_in))
        wall = time.perf_counter() - t1

        for h in handles:
            ctx.memory.free(h)
        stats = ctx.stats()
        bstats = ctx.byte_stats()
        result = MinimodResult(
            field=unpad_shards(fetch_global(out), z_extents),
            wall_s=wall, compile_s=t1 - t0,
            kernel_calls=compiled.as_text().count("tpu_custom_call"),
            mode=mode, grid=grid, steps=steps, nz=nz, ny=ny,
            z_extents=z_extents, plan=used_plan,
            puts=sum(ops.get("put", 0) for ops in stats.values()),
            put_bytes=sum(ops.get("put", 0) for ops in bstats.values()),
            tracker_puts=ctx.rma.puts,
            tracker_put_bytes=ctx.rma.put_bytes,
            fences=ctx.rma.fences,
            window_bytes=dict(ctx.rma.window_bytes),
            region_sizes=region_sizes,
            alloc_counts=dict(ctx.memory.alloc_counts),
        )
    return result
