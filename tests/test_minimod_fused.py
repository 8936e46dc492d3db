"""Fused halo-overlapped Minimod: kernel, planner, app driver.

Tier-1 subset: the fused step must equal the host-loop path AND the
single-device oracle across non-divisible grids, 1-rank groups, bf16, 2-D
decomposition and asymmetric extents; its put traffic must match the
RMATracker halo windows exactly; gradients must flow through it; and the
planner must fall back (never emit an invalid slab plan) on degenerate
grids.  The exhaustive mode×rank sweep is marked ``slow`` (RUN_SLOW=1).
"""

import dataclasses
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.apps.minimod import (MODES, pad_shards, run_minimod,
                                split_extents, unpad_shards)
from repro.core.compat import make_mesh, shard_map
from repro.core.context import DiompContext, use_default
from repro.core.groups import DiompGroup
from repro.core.rma import RMAError
from repro.core.streams import StreamPool
from repro.core.spans import recorder
from repro.kernels.plan import (VMEM_LIMIT_BYTES, HaloPlan, OverlapPlanner,
                                default_planner, stencil_halo_rows,
                                stencil_tile_bytes)
from repro.kernels.stencil import fused as stencil_fused
from repro.kernels.stencil import ops as stencil_ops
from repro.kernels.stencil.fused import (DISPATCH_EMULATION, DISPATCH_PALLAS,
                                         Halos, exchange_halos,
                                         fused_wave_step)
from repro.kernels.stencil.kernel import wave_step_pallas
from repro.kernels.stencil.ref import RADIUS, wave_step_ref

RNG = np.random.RandomState(0)
ZG = DiompGroup(("z",), name="z")
YG = DiompGroup(("y",), name="y")

slow_sweep = pytest.mark.skipif(
    not os.environ.get("RUN_SLOW"),
    reason="slow sweep; tier-1 runs the equivalence subset (set RUN_SLOW=1)")


def _reference(u, up, c2, steps, dx=1.0):
    for _ in range(steps):
        u, up = np.asarray(wave_step_ref(
            jnp.asarray(u), jnp.asarray(up), c2, dx=dx)), u
    return u


def _run_step(Z, Y, X, nz, ny=1, z_extents=None, dtype=np.float32,
              c2=0.1, ctx=None, interpret=None, check_vma=True):
    """One fused step under shard_map; returns (got, want) logical grids."""
    mesh = make_mesh((nz, ny), ("z", "y"), axis_types="auto")
    ext = z_extents or (Z // nz,) * nz
    u = (RNG.randn(Z, Y, X) * 0.1).astype(dtype)
    up = (RNG.randn(Z, Y, X) * 0.1).astype(dtype)
    u_in, up_in = pad_shards(u, ext), pad_shards(up, ext)

    def step(a, b):
        return fused_wave_step(a, b, c2, ZG, YG if ny > 1 else None,
                               z_extents=z_extents, interpret=interpret)

    f = jax.jit(shard_map(step, mesh=mesh,
                          in_specs=(P("z", "y"), P("z", "y")),
                          out_specs=P("z", "y"), check_vma=check_vma))
    with use_default(ctx or DiompContext(mesh=mesh)):
        got = unpad_shards(np.asarray(f(u_in, up_in)), ext)
    want = _reference(u, up, c2, 1)
    return got, want


# ---------------------------------------------------------------------------
# fused == host-loop == single-device reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Z,Y,X,nz,ny,ext", [
    (64, 12, 10, 4, 1, None),            # symmetric 1-D, overlapped
    (32, 12, 10, 4, 1, None),            # no interior: planner fallback
    (16, 8, 8, 1, 1, None),              # 1-rank group: no exchange at all
    (64, 32, 8, 2, 2, None),             # 2-D (Z×Y) decomposition
    (22, 10, 8, 4, 1, (6, 6, 5, 5)),     # non-divisible -> asymmetric
    (44, 10, 8, 4, 1, (14, 10, 10, 10)), # heterogeneous extents
])
def test_fused_step_matches_reference(Z, Y, X, nz, ny, ext):
    got, want = _run_step(Z, Y, X, nz, ny, z_extents=ext)
    np.testing.assert_allclose(got, want, atol=3e-6)


@pytest.mark.parametrize("Z,Y,X,nz,pallas", [
    (64, 16, 128, 4, 1),   # single-step split: puts under the kernel's interior
    (32, 16, 128, 4, 0),   # no interior: the fallback schedule, all XLA
    (16, 8, 8, 1, 0),      # one rank, X off the lane tile: the XLA step
    (16, 16, 128, 1, 1),   # one rank: the streamed stencil kernel
])
def test_tpu_kernels_match_reference_in_tpu_interpreter(Z, Y, X, nz, pallas):
    """The path the chip takes off the interpreter, the streamed kernel's
    body simulated by Pallas' TPU interpreter (whose own ops carry no vma
    types)."""
    from jax.experimental.pallas import tpu as pltpu

    n0 = _dispatch_counts()
    with pltpu.force_tpu_interpret_mode():
        got, want = _run_step(Z, Y, X, nz, interpret=False, check_vma=False)
    assert list(_dispatch_counts() - n0) == [pallas, 1 - pallas]
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_fused_step_bf16():
    got, want = _run_step(64, 12, 8, 4, dtype=jnp.bfloat16)
    scale = np.abs(want.astype(np.float64)).max()
    assert np.abs(got.astype(np.float64)
                  - want.astype(np.float64)).max() / scale < 2e-2


def test_fused_multi_step_all_modes_match_reference():
    """The app driver's time loop (carried halos for fused) == the oracle,
    for every halo mode, including asymmetric extents."""
    grid, steps = (48, 16, 16), 4
    u0 = np.zeros(grid, np.float64)
    u0[24, 8, 8] = 1.0
    want = _reference(u0.astype(np.float32), np.zeros(grid, np.float32),
                      0.1, steps)
    for weights in (None, (3, 2, 2, 1)):
        for mode in MODES:
            r = run_minimod(grid=grid, steps=steps, nz=4, weights=weights,
                            mode=mode)
            np.testing.assert_allclose(
                r.field, want, atol=3e-6,
                err_msg=f"mode={mode} weights={weights}")


def test_fused_2d_app_loop():
    r = run_minimod(shape="minimod_2d", steps=3, mode="fused")
    assert r.plan.overlap and r.plan.ny == 2
    u0 = np.zeros(r.grid, np.float32)
    u0[r.grid[0] // 2, r.grid[1] // 2, r.grid[2] // 2] = 1.0
    want = _reference(u0, np.zeros_like(u0), 0.1, 3)
    np.testing.assert_allclose(r.field, want, atol=3e-6)
    # 2-D exchanges both axes: 2 puts per axis per step (+ prologue)
    assert r.plan.puts_per_step == 4
    assert r.put_bytes == r.tracker_put_bytes


# ---------------------------------------------------------------------------
# put-traffic parity: OMPCCL call log == RMATracker halo windows
# ---------------------------------------------------------------------------

def test_put_traffic_parity_with_tracker():
    r = run_minimod(grid=(64, 12, 10), steps=5, nz=4, mode="fused")
    assert r.plan.overlap
    # 2 put call sites in the carried step + 2 in the prologue exchange
    assert r.puts == r.tracker_puts == 4
    assert r.put_bytes == r.tracker_put_bytes > 0
    # per-window accounting: one lo + one hi window, equal volume
    lo, hi = sorted(w for w in r.window_bytes if w.startswith("halo:z"))
    assert r.window_bytes[lo] == r.window_bytes[hi]
    assert r.window_bytes[lo] + r.window_bytes[hi] == r.put_bytes
    # every put fenced: prologue + carried step each end in one fence
    assert r.fences == 2


def test_asymmetric_pgas_regions_proportional():
    r = run_minimod(grid=(44, 8, 8), steps=2, nz=4,
                    weights=(14, 10, 10, 10), mode="fused")
    assert r.z_extents == (14, 10, 10, 10)
    item = 4
    assert r.region_sizes == tuple(e * 8 * 8 * item for e in r.z_extents)
    assert r.alloc_counts["asymmetric"] == 2      # u and u_prev
    assert r.alloc_counts["free"] == 2            # both released at exit


# ---------------------------------------------------------------------------
# gradients flow through the fused step (it is differentiable end to end)
# ---------------------------------------------------------------------------

def test_fused_gradients_flow():
    Z, Y, X, nz = 48, 8, 6, 4
    mesh = make_mesh((nz, 1), ("z", "y"), axis_types="auto")
    u = (RNG.randn(Z, Y, X) * 0.1).astype(np.float32)
    up = (RNG.randn(Z, Y, X) * 0.1).astype(np.float32)

    def loss(a, b):
        y = fused_wave_step(a, b, 0.1, ZG)
        return (y * y).sum()

    g = jax.jit(shard_map(
        lambda a, b: jax.grad(loss, argnums=(0, 1))(a, b),
        mesh=mesh, in_specs=(P("z", "y"), P("z", "y")),
        out_specs=(P("z", "y"), P("z", "y"))))
    ga, gb = g(u, up)

    def ref_loss(ab):
        y = wave_step_ref(ab[0], ab[1], 0.1)
        return (y * y).sum()

    want_a, want_b = jax.grad(ref_loss)((jnp.asarray(u), jnp.asarray(up)))
    np.testing.assert_allclose(np.asarray(ga), np.asarray(want_a),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(want_b),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# planner: degenerate cases fall back, never an invalid slab plan
# ---------------------------------------------------------------------------

def test_plan_halo_slots_degenerate_grid_falls_back():
    planner = default_planner()
    # local extent == 2*R: no interior -> fallback schedule
    plan = planner.plan_halo_slots(2 * RADIUS, 16, 16, jnp.float32, 4)
    assert not plan.overlap
    assert plan.schedule() == ("put", "fence", "all")
    # single rank: nothing to exchange at all
    lone = planner.plan_halo_slots(32, 16, 16, jnp.float32, 1)
    assert not lone.overlap and lone.schedule() == ("all",)
    assert lone.puts_per_step == 0
    # 2-D with a degenerate Y extent also falls back
    flat = planner.plan_halo_slots(32, 2 * RADIUS, 16, jnp.float32, 2, ny=2)
    assert not flat.overlap


_CARRIED = ("boundary", "put", "interior", "fence")
_SINGLE = ("put", "interior", "fence", "boundary")
_FALLBACK = ("put", "fence", "all")


@pytest.mark.parametrize("z,y,x,nz,ny,budget,schedules", [
    (32, 16, 16, 4, 1, None, (_CARRIED, _SINGLE)),      # overlapped 1-D
    (2 * RADIUS, 16, 16, 4, 1, None, (_FALLBACK,) * 2),  # no Z interior
    (32, 16, 16, 1, 1, None, (("all",),) * 2),          # lone rank
    (32, 2 * RADIUS, 16, 2, 2, None, (_FALLBACK,) * 2),  # 2-D, flat Y
    (32, 32, 16, 2, 2, None, (_CARRIED, _SINGLE)),      # 2-D with an interior
    (128, 1024, 1024, 8, 1, None, (_CARRIED, _SINGLE)),  # paper scale
    (64, 64, 64, 4, 1, 1024, (_CARRIED, _SINGLE)),      # budget plays no part
])
def test_plan_halo_slots_overlap_rule(z, y, x, nz, ny, budget, schedules):
    """``overlap``: some axis exchanges and every exchanging axis has an
    interior; the VMEM budget does not enter.  The put sizes are one
    halo-thick slab (Z) or strip (Y) of the local shard."""
    planner = default_planner() if budget is None else OverlapPlanner(
        pool=StreamPool(max_active=8), vmem_budget=budget)
    plan = planner.plan_halo_slots(z, y, x, jnp.float32, nz, ny=ny)
    assert plan.overlap == (schedules[0] == _CARRIED)
    assert (plan.schedule(carried=True),
            plan.schedule(carried=False)) == schedules
    assert plan.slab_bytes == (RADIUS * y * x * 4 if nz > 1 else 0)
    assert plan.strip_bytes == (z * RADIUS * x * 4 if ny > 1 else 0)
    assert plan.puts_per_step == 2 * ((nz > 1) + (ny > 1))
    assert plan.halo_bytes_per_step == 2 * (plan.slab_bytes
                                            + plan.strip_bytes)


def test_plan_stencil_tile_degenerate():
    planner = default_planner()
    # bz exceeding the Z extent clamps to it
    assert planner.plan_stencil_tile(3, 8, 8, jnp.float32, bz=64) == (3, 8)
    # grid smaller than the stencil support still yields a positive slab
    assert planner.plan_stencil_tile(2, 2, 2, jnp.float32) == (2, 2)
    # a budget too small for one plane's tile has no tile at all: the
    # dispatcher takes the XLA path
    tiny = OverlapPlanner(pool=StreamPool(max_active=8), vmem_budget=256)
    assert tiny.plan_stencil_tile(64, 64, 64, jnp.float32) is None
    # not even 8 rows of a very wide plane fit the kernel's limit
    assert planner.plan_stencil_tile(8, 64, 131072, jnp.float32,
                                     budget=VMEM_LIMIT_BYTES) is None
    # whole planes where they fit: 512² and Minimod's 1024² share
    for z, y in ((512, 512), (256, 1024)):
        bz, by = planner.plan_stencil_tile(z, y, y, jnp.float32,
                                           budget=VMEM_LIMIT_BYTES)
        assert by == y and 1 <= bz <= 8
        assert stencil_tile_bytes(bz, by, y, jnp.float32, RADIUS) \
            <= VMEM_LIMIT_BYTES
    # a plane whose ring does not fit whole tiles Y on sublanes, with
    # the Y halo (8 rows a side) re-read under 7%
    bz, by = planner.plan_stencil_tile(256, 2048, 2048, jnp.float32,
                                       budget=VMEM_LIMIT_BYTES)
    hy = stencil_halo_rows(RADIUS)
    assert by < 2048 and by % 8 == 0 and (by + 2 * hy) / by <= 1.07
    assert stencil_tile_bytes(bz, by, 2048, jnp.float32, RADIUS) \
        <= VMEM_LIMIT_BYTES


@pytest.mark.parametrize("Z,Y,X,tile,z_range", [
    (12, 16, 128, (4, 16), None),        # whole plane, by == Y
    (12, 40, 128, (5, 16), None),        # by < Y, ragged Y tile and Z block
    (17, 32, 256, (8, 8), None),         # 4 Y tiles of 8 rows, 256 lanes
    (16, 24, 256, (4, 8), (4, 12)),      # interior, first/middle/last tiles
    (20, 24, 128, (8, 24), (4, 16)),     # interior, bz cut to divide R
])
def test_streamed_stencil_in_tpu_interpreter(Z, Y, X, tile, z_range):
    """The streamed kernel's body (DMA ring, semaphores, Dirichlet faces)
    against the oracle, and its output-Z-range variant against the
    emulation's interior phase, which it replaces on the chip."""
    from jax.experimental.pallas import tpu as pltpu

    u = RNG.randn(Z, Y, X).astype(np.float32)
    up = RNG.randn(Z, Y, X).astype(np.float32)
    c2 = RNG.uniform(0.05, 0.2, (Z, Y, X)).astype(np.float32)
    with pltpu.force_tpu_interpret_mode():
        got = np.asarray(jax.jit(lambda a, b, c: wave_step_pallas(
            a, b, c, tile=tile, z_range=z_range))(u, up, c2))
    want = np.asarray(wave_step_ref(u, up, c2))
    if z_range is not None:
        z0, z1 = z_range
        interior = stencil_fused._interior(
            u, jnp.pad(u, RADIUS), up, c2, nz=2, ny=1, dx=1.0,
            dtype=jnp.float32)
        np.testing.assert_allclose(got, np.asarray(interior), atol=2e-6)
        want = want[z0:z1]
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-6)


def _dispatch_counts():
    c = recorder().counters()
    return np.array([c.get(DISPATCH_PALLAS, 0), c.get(DISPATCH_EMULATION, 0)])


def test_dispatch_counts_pallas_and_emulation():
    """Off the interpreter the dispatcher takes the streamed kernel where
    the planner has a tile (counted ``stencil.dispatch.pallas``): the
    whole field of a lone rank, and the interior of the carried split
    step; a plane too wide for any tile takes the emulation (counted
    ``stencil.dispatch.emulation``)."""
    from jax.experimental.pallas import tpu as pltpu

    n0 = _dispatch_counts()
    with pltpu.force_tpu_interpret_mode():
        _run_step(16, 16, 128, 1, interpret=False, check_vma=False)
    assert list(_dispatch_counts() - n0) == [1, 0]

    # carried halos over four ranks: the interior in the kernel
    grid, steps = (64, 16, 128), 3
    u0 = (RNG.randn(*grid) * 0.1).astype(np.float32)
    want = _reference(u0, np.zeros(grid, np.float32), 0.1, steps)
    mesh = make_mesh((4, 1), ("z", "y"), axis_types="auto")

    def loop(u, up):
        h = exchange_halos(u, ZG)
        for _ in range(steps):
            un, h = fused_wave_step(u, up, 0.1, ZG, halos=h,
                                    return_halos=True, interpret=False)
            u, up = un, u
        return u

    f = jax.jit(shard_map(loop, mesh=mesh, in_specs=(P("z", "y"),) * 2,
                          out_specs=P("z", "y"), check_vma=False))
    n0 = _dispatch_counts()
    with use_default(DiompContext(mesh=mesh)), \
            pltpu.force_tpu_interpret_mode():
        got = np.asarray(f(u0, np.zeros(grid, np.float32)))
    assert list(_dispatch_counts() - n0) == [steps, 0]
    np.testing.assert_allclose(got, want, atol=3e-6)

    # no 8-row tile of this plane fits VMEM_LIMIT_BYTES: the emulation
    mesh1 = make_mesh((1, 1), ("z", "y"), axis_types="auto")
    wide = jax.ShapeDtypeStruct((8, 8, 131072), jnp.float32)
    step = shard_map(
        lambda a, b: fused_wave_step(a, b, 0.1, ZG, interpret=False),
        mesh=mesh1, in_specs=(P("z", "y"),) * 2, out_specs=P("z", "y"))
    n0 = _dispatch_counts()
    with use_default(DiompContext(mesh=mesh1)):
        jax.eval_shape(step, wide, wide)
    assert list(_dispatch_counts() - n0) == [0, 1]


def test_fused_step_rejects_halo_wider_than_shard():
    mesh = make_mesh((4, 1), ("z", "y"), axis_types="auto")
    u = np.zeros((8, 8, 8), np.float32)    # 2 valid rows/rank < RADIUS

    def step(a, b):
        return fused_wave_step(a, b, 0.1, ZG, z_extents=(2, 2, 2, 2))

    with pytest.raises(RMAError):
        shard_map(step, mesh=mesh, in_specs=(P("z", "y"), P("z", "y")),
                  out_specs=P("z", "y"))(u, u)


def test_fused_step_rejects_mismatched_plan():
    mesh = make_mesh((4, 1), ("z", "y"), axis_types="auto")
    u = (RNG.randn(64, 8, 8) * 0.1).astype(np.float32)
    bad = dataclasses.replace(
        default_planner().plan_halo_slots(16, 8, 8, jnp.float32, 2), nz=2)

    def step(a, b):
        return fused_wave_step(a, b, 0.1, ZG, plan=bad)

    with pytest.raises(ValueError):
        shard_map(step, mesh=mesh, in_specs=(P("z", "y"), P("z", "y")),
                  out_specs=P("z", "y"))(u, u)


def test_split_extents():
    assert split_extents(64, 4) == (16, 16, 16, 16)
    assert split_extents(22, 4) == (6, 6, 5, 5)
    assert sum(split_extents(60, 4, (3, 2, 2, 1))) == 60
    ext = split_extents(60, 4, (30, 1, 1, 1), minimum=RADIUS)
    assert min(ext) >= RADIUS and sum(ext) == 60
    with pytest.raises(ValueError):
        split_extents(8, 4, minimum=RADIUS)   # 4 ranks x 4 rows > 8
    with pytest.raises(ValueError):
        split_extents(16, 4, (1, 1), minimum=1)


# ---------------------------------------------------------------------------
# satellite: interpret=None resolved BEFORE the jit boundary
# ---------------------------------------------------------------------------

def test_wave_step_interpret_resolved_in_jit_key():
    """The jit cache must be keyed on the RESOLVED interpret flag: calling
    with None and with the explicitly resolved value hits ONE entry (the
    silent-interpretation bug class PR 2 fixed for matmul)."""
    from repro.kernels.plan import resolve_interpret

    u = RNG.randn(16, 12, 10).astype(np.float32)
    up = RNG.randn(16, 12, 10).astype(np.float32)
    stencil_ops._wave_step_jit._clear_cache()
    stencil_ops.wave_step(u, up, 0.1, impl="pallas", interpret=None)
    n_after_none = stencil_ops._wave_step_jit._cache_size()
    stencil_ops.wave_step(u, up, 0.1, impl="pallas",
                          interpret=resolve_interpret(None))
    assert stencil_ops._wave_step_jit._cache_size() == n_after_none, \
        "interpret=None leaked into the jit key instead of the resolved flag"


# ---------------------------------------------------------------------------
# slow sweep (excluded from tier-1; the bench covers the modeled gate)
# ---------------------------------------------------------------------------

@slow_sweep
@pytest.mark.slow
@pytest.mark.parametrize("nz", [1, 2, 4, 8])
@pytest.mark.parametrize("mode", MODES)
def test_mode_rank_sweep(nz, mode):
    grid, steps = (64, 16, 16), 5
    u0 = np.zeros(grid, np.float64)
    u0[32, 8, 8] = 1.0
    want = _reference(u0.astype(np.float32), np.zeros(grid, np.float32),
                      0.1, steps)
    r = run_minimod(grid=grid, steps=steps, nz=nz, mode=mode)
    np.testing.assert_allclose(r.field, want, atol=5e-6)
