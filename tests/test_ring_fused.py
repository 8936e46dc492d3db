"""Fused ring collective matmul + OverlapPlanner.

The fused path must (a) match the all-gather reference everywhere the
emulation runs — non-divisible shapes, bf16, group size 1, both ring
directions — (b) finish the bidirectional ring in ``ceil((n - 1) / 2)``
exchange steps, and (c) actually consume ``StreamPool.plan_slots`` through
the planner (the §3.2 contract the seed only documented).
"""

import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.core.compat import make_mesh, shard_map
from repro.core.context import DiompContext, use_default
from repro.core.groups import DiompGroup
from repro.core.streams import StreamPool
from repro.kernels.plan import (OverlapPlanner, RingPlan, default_planner,
                                resolve_interpret, resolve_ring_impl)
from repro.kernels.ring_matmul.fused import fused_ring_allgather_matmul
from repro.kernels.ring_matmul.ops import matmul, ring_allgather_matmul
from repro.kernels.ring_matmul.ref import ring_allgather_matmul_ref

RNG = np.random.RandomState(0)
GROUP = DiompGroup(("x",), name="ring")


def _run(T, K, N, ndev, dtype=np.float32, check_vma=True, **kwargs):
    """Fused matmul + reference on an ndev ring; returns (got, want, full)."""
    mesh = make_mesh((ndev,), ("x",), axis_types="auto")
    A = RNG.randn(T, K).astype(dtype)
    B = RNG.randn(K, N).astype(dtype)
    f = jax.jit(shard_map(
        lambda a, b: ring_allgather_matmul(a, b, GROUP, **kwargs),
        mesh=mesh, in_specs=(P("x", None), P(None, "x")),
        out_specs=P(None, "x"), check_vma=check_vma))
    r = jax.jit(shard_map(
        lambda a, b: ring_allgather_matmul_ref(a, b, GROUP),
        mesh=mesh, in_specs=(P("x", None), P(None, "x")),
        out_specs=P(None, "x")))
    return np.asarray(f(A, B)), np.asarray(r(A, B)), (A, B)


# ---------------------------------------------------------------------------
# schedule / plan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", list(range(1, 10)))
def test_bidirectional_step_count(n):
    plan = RingPlan(n=n, direction="bidi", slots=2)
    assert plan.exchange_steps == math.ceil((n - 1) / 2)
    # exchange steps counted off the schedule itself, not the formula
    moving = [st for st in plan.schedule() if st.send_cw or st.send_ccw]
    assert len(moving) == plan.exchange_steps


@pytest.mark.parametrize("n", list(range(1, 10)))
@pytest.mark.parametrize("direction", ["bidi", "cw", "ccw"])
def test_schedule_covers_every_stripe_once(n, direction):
    plan = RingPlan(n=n, direction=direction, slots=3)
    if direction != "bidi":
        assert plan.exchange_steps == n - 1
    for rank in range(n):
        srcs = plan.sources(rank)
        assert sorted(srcs) == list(range(n)), (rank, srcs)


def test_schedule_sends_before_they_are_needed():
    """A stripe computed at step s must have been forwarded at step s-1."""
    for n in range(2, 9):
        plan = RingPlan(n=n, direction="bidi", slots=2)
        sched = plan.schedule()
        for prev, cur in zip(sched, sched[1:]):
            if cur.compute_cw:
                assert prev.send_cw
            if cur.compute_ccw:
                assert prev.send_ccw


def test_planner_consumes_plan_slots():
    """The plan's slot count comes from StreamPool.plan_slots (spied)."""
    calls = []

    class SpyPool(StreamPool):
        def plan_slots(self, working_set_bytes, vmem_budget=64 * 2**20):
            calls.append((working_set_bytes, vmem_budget))
            return super().plan_slots(working_set_bytes, vmem_budget)

    planner = OverlapPlanner(pool=SpyPool(max_active=4))
    plan = planner.plan_ring_matmul(8, 32, 16, jnp.float32, 8)
    assert calls, "plan_slots was never queried"
    assert 2 <= plan.slots <= 8
    assert plan.stripe_bytes == 8 * 32 * 4
    # a tighter pool bound means fewer slots
    small = OverlapPlanner(pool=StreamPool(max_active=2))
    assert small.plan_ring_matmul(8, 32, 16, jnp.float32, 8).slots == 2


def test_planner_respects_vmem_budget():
    planner = OverlapPlanner(pool=StreamPool(max_active=8),
                             vmem_budget=2 * 2**20)
    # a huge stripe: slots clamp to double buffering, never overflow count
    plan = planner.plan_ring_matmul(1024, 4096, 256, jnp.float32, 4)
    assert plan.slots == 2
    # tiles shrink under a tiny budget
    bm, bk, bn = planner.plan_matmul_tiles(4096, 4096, 4096, jnp.float32)
    assert (bm * bk + bk * bn) * 4 + bm * bn * 4 < 8 * 2**20


def test_planner_attention_and_stencil_plans():
    planner = default_planner()
    # decode shape: block must track the KV extent, not Tq=1
    assert planner.plan_attention_block(1, 48, 64, 64, jnp.float32) == 48
    assert planner.plan_attention_block(512, 8192, 128, 128,
                                        jnp.bfloat16) >= 128
    bz, by = planner.plan_stencil_tile(24, 20, 28, jnp.float32)
    assert 1 <= bz <= 8 and by == 20


def test_resolvers():
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # on the CPU CI backend, None must resolve to interpret mode
    assert resolve_interpret(None) is (jax.default_backend() != "tpu")
    assert resolve_ring_impl(None) == resolve_ring_impl("auto") == "fused"
    assert resolve_ring_impl("host") == "host"
    with pytest.raises(ValueError):
        resolve_ring_impl("warp")


def test_plan_rejects_bad_direction_and_mismatched_ring():
    with pytest.raises(ValueError):
        RingPlan(n=4, direction="diagonal")
    mesh = make_mesh((4,), ("x",), axis_types="auto")
    A = RNG.randn(8, 16).astype(np.float32)
    B = RNG.randn(16, 8).astype(np.float32)
    bad = RingPlan(n=2, direction="bidi", slots=2)
    f = jax.jit(shard_map(
        lambda a, b: fused_ring_allgather_matmul(a, b, GROUP, plan=bad),
        mesh=mesh, in_specs=(P("x", None), P(None, "x")),
        out_specs=P(None, "x")))
    with pytest.raises(ValueError):
        f(A, B)


# ---------------------------------------------------------------------------
# numerical equivalence (interpret emulation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T,K,N,ndev", [
    (64, 64, 64, 8),        # divisible everything
    (24, 33, 40, 8),        # odd t_loc, ragged K, odd N/n
    (8, 17, 8, 4),          # tiny stripes
    (30, 64, 36, 2),        # n = 2: one exchange step
    (16, 32, 16, 1),        # group size 1: no exchange at all
])
def test_fused_matches_reference(T, K, N, ndev):
    got, want, (A, B) = _run(T, K, N, ndev, impl="fused")
    scale = np.abs(A @ B).max()
    assert np.abs(got - want).max() / scale < 1e-4
    assert np.abs(got - A @ B).max() / scale < 1e-4


@pytest.mark.parametrize("T,K,N,ndev,direction", [
    (64, 64, 64, 8, "bidi"),
    (8, 17, 8, 4, "bidi"),      # ragged K, tiny stripes
    (30, 64, 36, 2, "bidi"),    # one exchange step
    (24, 32, 16, 4, "cw"),
])
def test_tpu_kernel_matches_reference_in_tpu_interpreter(T, K, N, ndev,
                                                         direction):
    """The compiled kernel's body, its remote copies and semaphores
    simulated across the CPU devices by Pallas' TPU interpreter (whose own
    ops carry no vma types)."""
    from jax.experimental.pallas import tpu as pltpu

    plan = RingPlan(n=ndev, direction=direction, slots=2)
    with pltpu.force_tpu_interpret_mode():
        got, want, (A, B) = _run(T, K, N, ndev, impl="fused", plan=plan,
                                 interpret=False, check_vma=False)
    scale = np.abs(A @ B).max()
    assert np.abs(got - want).max() / scale < 1e-4


def test_fused_bf16():
    got, want, (A, B) = _run(24, 48, 32, 8, dtype=jnp.bfloat16, impl="fused")
    ref64 = A.astype(np.float64) @ B.astype(np.float64)
    scale = np.abs(ref64).max()
    assert np.abs(got.astype(np.float64) - want.astype(np.float64)
                  ).max() / scale < 2e-2
    assert np.abs(got.astype(np.float64) - ref64).max() / scale < 2e-2


@pytest.mark.parametrize("direction", ["cw", "ccw"])
def test_unidirectional_rings_both_ways(direction):
    mesh = make_mesh((8,), ("x",), axis_types="auto")
    A = RNG.randn(24, 33).astype(np.float32)
    B = RNG.randn(33, 40).astype(np.float32)
    plan = RingPlan(n=8, direction=direction, slots=2)
    assert plan.exchange_steps == 7
    f = jax.jit(shard_map(
        lambda a, b: fused_ring_allgather_matmul(a, b, GROUP, plan=plan),
        mesh=mesh, in_specs=(P("x", None), P(None, "x")),
        out_specs=P(None, "x")))
    got = np.asarray(f(A, B))
    scale = np.abs(A @ B).max()
    assert np.abs(got - A @ B).max() / scale < 1e-4


def test_host_impl_still_matches():
    got, want, (A, B) = _run(24, 33, 40, 8, impl="host")
    scale = np.abs(A @ B).max()
    assert np.abs(got - want).max() / scale < 1e-4


def test_overlap_false_is_reference():
    got, want, _ = _run(16, 16, 16, 4, overlap=False)
    np.testing.assert_array_equal(got, want)


def test_fused_total_put_traffic_matches_host_ring():
    """Bidirectionality halves the steps, not the bytes: the emulation must
    issue exactly n-1 stripe puts overall (counted off the OMPCCL call log
    at trace time), same as the host ring."""
    mesh = make_mesh((8,), ("x",), axis_types="auto")
    A = RNG.randn(16, 16).astype(np.float32)
    B = RNG.randn(16, 16).astype(np.float32)
    counts = {}
    for impl in ("host", "fused"):
        ctx = DiompContext()
        with use_default(ctx):
            jax.jit(shard_map(
                lambda a, b: ring_allgather_matmul(a, b, GROUP, impl=impl),
                mesh=mesh, in_specs=(P("x", None), P(None, "x")),
                out_specs=P(None, "x"))).lower(A, B)
        counts[impl] = ctx.stats()[GROUP.descriptor()]["put"]
    assert counts == {"host": 7, "fused": 7}


def test_fused_gradients_flow():
    """The emulation is differentiable (it is the TP layers' train path)."""
    mesh = make_mesh((4,), ("x",), axis_types="auto")
    A = RNG.randn(8, 12).astype(np.float32)
    B = RNG.randn(12, 8).astype(np.float32)

    def loss(a, b):
        y = ring_allgather_matmul(a, b, GROUP, impl="fused")
        return (y * y).sum()

    g = jax.jit(shard_map(
        lambda a, b: jax.grad(loss, argnums=(0, 1))(a, b),
        mesh=mesh, in_specs=(P("x", None), P(None, "x")),
        out_specs=(P("x", None), P(None, "x"))))
    ga, gb = g(A, B)
    want_a, want_b = jax.grad(lambda ab: ((ab[0] @ ab[1]) ** 2).sum())((A, B))
    np.testing.assert_allclose(np.asarray(ga), np.asarray(want_a),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(want_b),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# satellite: interpret default resolves from the backend
# ---------------------------------------------------------------------------

def test_matmul_pallas_defaults_resolve():
    """impl='pallas' with no tiles/interpret given: planner tiles + backend-
    resolved interpret mode still match the oracle."""
    x = RNG.randn(100, 130).astype(np.float32)
    w = RNG.randn(130, 70).astype(np.float32)
    got = matmul(x, w, impl="pallas")
    want = x.astype(np.float64) @ w.astype(np.float64)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=1e-4, atol=1e-4 * np.abs(want).max())
