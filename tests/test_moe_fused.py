"""Fused dropless MoE dispatch (kernels/moe_dispatch) + AllToAllPlan.

Contract under test:

* the fused one-sided dispatch is DROPLESS — bit-equivalent to the
  single-device oracle under load-imbalanced routing when the plan's
  asymmetric capacities come from measured load;
* the serialized ``host`` mode issues the identical traffic and numbers;
* gradients flow through the fenced schedule (it is the MoE train path);
* the OMPCCL byte log and the RMATracker's dispatch/combine window bytes
  agree exactly (the PGAS accounting the paper's asymmetric story needs);
* ``moe_capacity`` is the true ceiling (the old ``int(q + 1)`` overshot
  exact products), and the host capacity paths surface their overflow
  drops through ``DispatchStats`` while the dropless path records zero.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core import ompccl
from repro.core.compat import make_mesh, shard_map
from repro.core.context import DiompContext, default_context, use_default
from repro.core.groups import DiompGroup
from repro.core.rma import dispatch_window_names
from repro.kernels.moe_dispatch import (measure_expert_load, moe_dispatch,
                                        moe_ref, route_topk)
from repro.kernels.plan import (AllToAllPlan, default_planner,
                                resolve_dispatch_impl)
from repro.models import api as model_api
from repro.models import schema as sch
from repro.models.config import ModelConfig, ParallelCtx
from repro.models.layers import moe_block, moe_capacity

RNG = np.random.RandomState(0)
GROUP = DiompGroup(("x",), name="epx")


# ---------------------------------------------------------------------------
# satellite: the capacity formula is the true ceiling
# ---------------------------------------------------------------------------

def test_moe_capacity_exact_products_do_not_overshoot():
    # exactly integral quotients: the old int(q + 1) returned 17 / 21 / 16
    assert moe_capacity(64, 2, 8, 1.0) == 16
    assert moe_capacity(64, 2, 8, 1.25) == 20
    assert moe_capacity(60, 2, 8, 1.0) == 15


def test_moe_capacity_non_exact_still_ceils():
    assert moe_capacity(50, 2, 8, 1.0) == 13      # ceil(12.5)
    assert moe_capacity(7, 2, 4, 1.1) == 4        # ceil(3.85)
    assert moe_capacity(1, 1, 64, 1.0) == 1       # floor clamp


def test_resolve_dispatch_impl():
    assert resolve_dispatch_impl(None) == "a2a"
    assert resolve_dispatch_impl("auto") == "a2a"
    assert resolve_dispatch_impl("fused") == "fused"
    assert resolve_dispatch_impl("host") == "host"
    with pytest.raises(ValueError):
        resolve_dispatch_impl("warp")


# ---------------------------------------------------------------------------
# plan: asymmetric capacities from measured load
# ---------------------------------------------------------------------------

def test_plan_caps_reproduce_measured_load():
    loads = (6, 5, 8, 6, 7, 6, 3, 5)
    plan = default_planner().plan_alltoall(16, 32, 2, 8, 4, jnp.float32,
                                           loads=loads)
    # slack = 1.0: the largest-remainder split reproduces the loads exactly
    assert plan.caps == loads
    assert plan.cap_pad == 8
    assert plan.region_rows == tuple(4 * c for c in loads)
    assert plan.block_bytes == plan.E_loc * 8 * 32 * 4
    # true (asymmetric) rows per destination vs the padded wire block
    assert plan.block_rows(0) == 6 + 5 and plan.block_rows(2) == 7 + 6


def test_plan_slack_grows_caps_but_never_below_load():
    loads = (6, 5, 8, 6, 7, 6, 3, 5)
    plan = default_planner().plan_alltoall(16, 32, 2, 8, 4, jnp.float32,
                                           loads=loads, slack=1.5)
    assert sum(plan.caps) >= int(np.ceil(sum(loads) * 1.5))
    assert all(c >= l for c, l in zip(plan.caps, loads))


def test_plan_zero_load_experts_keep_a_slot():
    plan = default_planner().plan_alltoall(32, 16, 2, 8, 4, jnp.float32,
                                           loads=(32, 0, 0, 0, 0, 0, 0, 0))
    assert plan.caps[0] >= 32 and all(c >= 1 for c in plan.caps)


def test_plan_fallback_is_worst_case():
    plan = default_planner().plan_alltoall(16, 32, 2, 8, 4, jnp.float32)
    assert plan.caps == (16,) * 8          # no measurement: t_loc everywhere
    assert plan.slots >= 2


def test_plan_validation():
    with pytest.raises(ValueError):
        default_planner().plan_alltoall(16, 32, 2, 6, 4, jnp.float32)
    with pytest.raises(ValueError):
        AllToAllPlan(ep=4, E=8, t_loc=8, k=2, d=16, caps=(2,) * 7)
    with pytest.raises(ValueError):
        AllToAllPlan(ep=4, E=8, t_loc=8, k=2, d=16, caps=(0,) * 8)


def test_schedule_overlap_order():
    plan = AllToAllPlan(ep=4, E=8, t_loc=8, k=2, d=16, caps=(2,) * 8)
    sched = plan.schedule()
    for s in range(1, 4):
        # the put feeding step s is issued before step s-1's GEMM (overlap),
        # its landing is fenced before its own GEMM, and the combine put
        # rides after the GEMM that produced it
        assert sched.index(("put", s)) < sched.index(("gemm", s - 1))
        assert sched.index(("fence", s)) < sched.index(("gemm", s))
        assert sched.index(("ret", s)) > sched.index(("gemm", s))
    assert sched[-1] == ("fence_ret", 0)
    host = dataclasses.replace(plan, overlap=False).schedule()
    assert sorted(host) == sorted(sched)   # same traffic, serialized
    last_put = max(i for i, (p, _) in enumerate(host) if p == "put")
    first_gemm = min(i for i, (p, _) in enumerate(host) if p == "gemm")
    assert last_put < first_gemm
    one = AllToAllPlan(ep=1, E=4, t_loc=8, k=2, d=16, caps=(2,) * 4)
    assert one.schedule() == (("gemm", 0),)


# ---------------------------------------------------------------------------
# numerical equivalence vs the single-device oracle
# ---------------------------------------------------------------------------

def _dispatch_case(ndev, E, t_loc, d, f, k=2, skew=2.0):
    """Imbalanced-routing test case: full arrays + a load-sized plan."""
    toks = RNG.randn(ndev * t_loc, d).astype(np.float32)
    router = (RNG.randn(d, E) + skew * RNG.randn(1, E)).astype(np.float32)
    wg = (RNG.randn(E, d, f) / np.sqrt(d)).astype(np.float32)
    wu = (RNG.randn(E, d, f) / np.sqrt(d)).astype(np.float32)
    wd = (RNG.randn(E, f, d) / np.sqrt(f)).astype(np.float32)
    top_w, top_e = jax.jit(route_topk, static_argnums=2)(toks, router, k)
    loads = measure_expert_load(
        np.asarray(top_e).reshape(ndev, t_loc, k), E, sources=ndev)
    plan = default_planner().plan_alltoall(t_loc, d, k, E, ndev,
                                           jnp.float32, loads=loads)
    want = np.asarray(moe_ref(toks, top_e, top_w, wg, wu, wd))
    return toks, router, (wg, wu, wd), plan, loads, want


def _run_dispatch(mesh, impl, plan, toks, router, weights, k=2,
                  interpret=None, check_vma=True):
    def f(tk, rt, g, u, dn):
        w, e = route_topk(tk, rt, k)
        with default_context().dispatch_stats.collect() as ds:
            out = moe_dispatch(tk, e, w, g, u, dn, GROUP,
                               impl=impl, plan=plan, interpret=interpret)
        return out, ds["moe_dropped"].reshape(1)

    fn = jax.jit(shard_map(
        f, mesh=mesh,
        in_specs=(P("x", None), P(None, None), P("x", None, None),
                  P("x", None, None), P("x", None, None)),
        out_specs=(P("x", None), P("x")), check_vma=check_vma))
    out, dropped = fn(toks, router, *weights)
    return np.asarray(out), float(np.asarray(dropped).sum())


def test_fused_and_host_match_oracle_under_imbalance():
    ndev = 8
    mesh = make_mesh((ndev,), ("x",), axis_types="auto")
    toks, router, weights, plan, loads, want = _dispatch_case(
        ndev, E=16, t_loc=12, d=16, f=24)
    assert max(loads) > min(loads)         # the skew actually skewed
    fused, d_fused = _run_dispatch(mesh, "fused", plan, toks, router, weights)
    host, d_host = _run_dispatch(mesh, "host", plan, toks, router, weights)
    # dropless by construction: zero drops, bit-equal to the oracle
    assert d_fused == 0.0 and d_host == 0.0
    np.testing.assert_array_equal(fused, want)
    np.testing.assert_array_equal(host, want)


@pytest.mark.parametrize("ndev", [2, 4])
def test_tpu_kernel_matches_oracle_in_tpu_interpreter(ndev):
    """The compiled kernel's body, its remote copies and semaphores
    simulated across the CPU devices by Pallas' TPU interpreter (whose own
    ops carry no vma types)."""
    from jax.experimental.pallas import tpu as pltpu

    mesh = make_mesh((ndev,), ("x",), axis_types="auto")
    toks, router, weights, plan, _, want = _dispatch_case(
        ndev, E=2 * ndev, t_loc=8, d=16, f=24)
    with pltpu.force_tpu_interpret_mode():
        out, dropped = _run_dispatch(mesh, "fused", plan, toks, router,
                                     weights, interpret=False,
                                     check_vma=False)
    assert dropped == 0.0
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_undersized_plan_records_drops():
    """Starved capacities (caps == 1) must surface as a positive drop count
    — the stat the dropless path pins to zero."""
    ndev = 4
    mesh = make_mesh((ndev,), ("x",), axis_types="auto")
    toks, router, weights, plan, _, want = _dispatch_case(
        ndev, E=8, t_loc=8, d=16, f=16)
    starved = dataclasses.replace(plan, caps=(1,) * 8)
    out, dropped = _run_dispatch(mesh, "fused", starved, toks, router, weights)
    assert dropped > 0
    assert np.abs(out - want).max() > 0    # and it is a real quality tax


def test_fused_gradients_match_oracle():
    ndev = 4
    mesh = make_mesh((ndev,), ("x",), axis_types="auto")
    toks, router, weights, plan, _, _ = _dispatch_case(
        ndev, E=8, t_loc=8, d=12, f=16)
    router_c = jnp.asarray(router)

    def dist_loss(tk, wgt):
        # per-rank local loss: AD of the SPMD program sums the seeds, so
        # the grads are those of the GLOBAL loss (the train-step pattern)
        w, e = route_topk(tk, router_c, 2)
        y = moe_dispatch(tk, e, w, *wgt, GROUP, impl="fused", plan=plan)
        return (y.astype(jnp.float32) ** 2).sum()

    g = jax.jit(shard_map(
        lambda tk, wgt: jax.grad(dist_loss, argnums=(0, 1))(tk, wgt),
        mesh=mesh,
        in_specs=(P("x", None), (P("x", None, None),) * 3),
        out_specs=(P("x", None), (P("x", None, None),) * 3)))
    gt, gw = g(toks, tuple(map(jnp.asarray, weights)))

    def ref_loss(tk, wgt):
        w, e = route_topk(tk, router_c, 2)
        return (moe_ref(tk, e, w, *wgt).astype(jnp.float32) ** 2).sum()

    rt, rw = jax.jit(jax.grad(ref_loss, argnums=(0, 1)))(
        jnp.asarray(toks), tuple(map(jnp.asarray, weights)))
    np.testing.assert_allclose(np.asarray(gt), np.asarray(rt),
                               rtol=1e-4, atol=1e-5)
    for got, ref in zip(gw, rw):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# PGAS accounting: OMPCCL byte log == RMATracker window bytes
# ---------------------------------------------------------------------------

def test_put_byte_parity_with_tracker_windows():
    ndev = 4
    mesh = make_mesh((ndev,), ("x",), axis_types="auto")
    toks, router, weights, plan, _, _ = _dispatch_case(
        ndev, E=8, t_loc=8, d=16, f=16)

    def f(tk, rt, g, u, dn):
        w, e = route_topk(tk, rt, 2)
        return moe_dispatch(tk, e, w, g, u, dn, GROUP, impl="fused",
                            plan=plan)

    dctx = DiompContext()
    with use_default(dctx):
        jax.jit(shard_map(
            f, mesh=mesh,
            in_specs=(P("x", None), P(None, None), P("x", None, None),
                      P("x", None, None), P("x", None, None)),
            out_specs=P("x", None))).lower(toks, router, *weights)
    desc = GROUP.descriptor()
    # (ep-1) dispatch puts + (ep-1) combine puts, one padded block each
    assert dctx.stats()[desc]["put"] == 2 * (ndev - 1)
    put_bytes = dctx.byte_stats()[desc]["put"]
    assert put_bytes == 2 * (ndev - 1) * plan.block_bytes
    dwin, cwin = dispatch_window_names(GROUP, ndev)
    win_bytes = sum(dctx.rma.window_bytes[w] for w in dwin + cwin)
    assert put_bytes == win_bytes == dctx.rma.put_bytes


# ---------------------------------------------------------------------------
# satellite: moe_block regime coverage (a2a / replicated / local) vs oracle
# ---------------------------------------------------------------------------

def _moe_cfg(E, shared=0, cf=8.0):
    return ModelConfig(name="tiny-moe", family="moe", num_layers=1,
                       d_model=32, num_heads=4, d_ff=64, vocab_size=128,
                       moe=True, num_experts=E, experts_per_token=2,
                       moe_d_ff=24, shared_experts=shared,
                       capacity_factor=cf, dtype="float32")


def _moe_lp(cfg, seed=0):
    rng = np.random.RandomState(seed)
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    lp = {
        "router": rng.randn(d, E).astype(np.float32) * 2.0,
        "w_gate_e": (rng.randn(E, d, f) / np.sqrt(d)).astype(np.float32),
        "w_up_e": (rng.randn(E, d, f) / np.sqrt(d)).astype(np.float32),
        "w_down_e": (rng.randn(E, f, d) / np.sqrt(f)).astype(np.float32),
    }
    if cfg.shared_experts:
        fs = cfg.moe_d_ff * cfg.shared_experts
        lp["w_gate_s"] = (rng.randn(d, fs) / np.sqrt(d)).astype(np.float32)
        lp["w_up_s"] = (rng.randn(d, fs) / np.sqrt(d)).astype(np.float32)
        lp["w_down_s"] = (rng.randn(fs, d) / np.sqrt(fs)).astype(np.float32)
    return lp


def _moe_oracle(x, lp, cfg):
    """Dropless reference for an ample-capacity moe_block call."""
    B, T, d = x.shape
    flat = jnp.asarray(x.reshape(B * T, d))
    top_w, top_e = route_topk(flat, jnp.asarray(lp["router"]),
                              cfg.experts_per_token)
    out = moe_ref(flat, top_e, top_w, jnp.asarray(lp["w_gate_e"]),
                  jnp.asarray(lp["w_up_e"]), jnp.asarray(lp["w_down_e"]))
    if cfg.shared_experts:
        h = jax.nn.silu(flat @ lp["w_gate_s"]) * (flat @ lp["w_up_s"])
        out = out + h @ lp["w_down_s"]
    return np.asarray(out).reshape(B, T, d)


def _run_moe_block(mesh, cfg, lp, x, sharded_experts, **knobs):
    ctx = ParallelCtx.from_mesh(mesh, **knobs)
    espec = (P("model", None, None) if sharded_experts
             else P(None, None, None))
    lspecs = {"router": P(None, None), "w_gate_e": espec, "w_up_e": espec,
              "w_down_e": espec}
    if "w_gate_s" in lp:
        lspecs.update({"w_gate_s": P(None, "model"),
                       "w_up_s": P(None, "model"),
                       "w_down_s": P("model", None)})

    def f(xx, pp):
        out = moe_block(xx, pp, cfg, ctx)
        return lax.pmean(out, "model")     # ranks agree; make it invariant

    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P(), lspecs),
                           out_specs=P()))
    return np.asarray(fn(x, lp))


@pytest.mark.parametrize("case", ["a2a", "a2a_shared", "replicated", "local"])
def test_moe_block_regimes_match_dropless_oracle(case):
    """With ample capacity every dispatch regime equals the dropless oracle:
    a2a (tokens sliced over the EP ring), replicated (decode-shaped B*T <
    tp), and the non-divisible-E local fallback."""
    mesh = make_mesh((1, 8), ("data", "model"), axis_types="auto")
    E, shared = (8, 0)
    B, T = 2, 32                           # B*T = 64: a2a regime
    sharded = True
    if case == "a2a_shared":
        shared = 1
    elif case == "replicated":
        B, T = 1, 4                        # B*T < tp: replicated regime
    elif case == "local":
        E, sharded = 6, False              # E % ep != 0: local fallback
    cfg = _moe_cfg(E, shared=shared)
    lp = _moe_lp(cfg)
    x = RNG.randn(B, T, cfg.d_model).astype(np.float32)
    got = _run_moe_block(mesh, cfg, lp, x, sharded)
    want = _moe_oracle(x, lp, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("impl", ["fused", "host"])
def test_moe_block_dropless_impls_match_oracle(impl):
    """dispatch_impl='fused'/'host' swap the a2a collective for the one-sided
    ring inside moe_block itself — same dropless numbers, shared experts
    included."""
    mesh = make_mesh((1, 8), ("data", "model"), axis_types="auto")
    cfg = _moe_cfg(8, shared=1, cf=1.0)    # tight capacity: a2a would drop
    lp = _moe_lp(cfg)
    x = RNG.randn(2, 32, cfg.d_model).astype(np.float32)
    got = _run_moe_block(mesh, cfg, lp, x, True, dispatch_impl=impl)
    want = _moe_oracle(x, lp, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# model level: both MoE configs, every dispatch_impl
# ---------------------------------------------------------------------------

def _model_loss(cfg, mesh, params, batch, **knobs):
    ctx = ParallelCtx.from_mesh(mesh, remat=False, **knobs)
    pspecs = sch.partition_specs(cfg, mesh)
    bspecs = {k: P("data") for k in batch}
    loss_fn = model_api.loss_fn(cfg)

    def step(p, b):
        return ompccl.allreduce(loss_fn(p, b, cfg, ctx), ctx.world,
                                op="mean")

    return float(jax.jit(shard_map(step, mesh=mesh,
                                   in_specs=(pspecs, bspecs),
                                   out_specs=P()))(params, batch))


@pytest.mark.parametrize("arch", ["deepseek-v3-671b", "qwen3-moe-235b-a22b"])
def test_model_loss_across_dispatch_impls(arch):
    """The dropless modes agree with each other exactly (same schedule, same
    numerics) and sit within routing-drop distance of the capacity a2a."""
    cfg = configs.get_reduced(arch)
    params = sch.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": np.random.RandomState(1).randint(
        0, cfg.vocab_size, (8, 16)).astype(np.int32)}
    mesh = make_mesh((1, 8), ("data", "model"), axis_types="auto")
    losses = {impl: _model_loss(cfg, mesh, params, batch,
                                dispatch_impl=impl)
              for impl in ("a2a", "fused", "host")}
    assert np.isfinite(losses["a2a"])
    assert abs(losses["fused"] - losses["host"]) < 1e-6, losses
    assert abs(losses["fused"] - losses["a2a"]) < 0.1, losses


# ---------------------------------------------------------------------------
# satellite: drop stats surface in the train step's metrics
# ---------------------------------------------------------------------------

def test_train_step_moe_drop_metrics():
    from repro.train.optim import adamw, cosine_schedule
    from repro.train.step import build_train_step

    cfg = configs.get_reduced("qwen3-moe-235b-a22b")
    mesh = make_mesh((4, 2), ("data", "model"), axis_types="auto")
    params = sch.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw(cosine_schedule(5e-3, warmup=2, total=40))
    ostate = jax.jit(opt.init)(params)
    batch = {"tokens": np.random.RandomState(1).randint(
        0, cfg.vocab_size, (8, 16)).astype(np.int32)}

    # capacity a2a, overlapped-reduction scan branch: real drops surface
    ctx = ParallelCtx.from_mesh(mesh, remat=True, microbatch=2)
    _, _, m = build_train_step(cfg, mesh, ctx, opt, donate=False,
                               global_batch=8)(params, ostate, batch,
                                               jnp.asarray(0))
    assert float(m["moe_dropped"]) > 0
    assert 0.0 < float(m["moe_drop_rate"]) < 1.0
    # dropless fused dispatch, plain accumulation scan branch: exactly zero
    ctx = ParallelCtx.from_mesh(mesh, remat=True, microbatch=2,
                                overlap_grad_reduce=False,
                                dispatch_impl="fused")
    _, _, m = build_train_step(cfg, mesh, ctx, opt, donate=False,
                               global_batch=8)(params, ostate, batch,
                                               jnp.asarray(0))
    assert float(m["moe_dropped"]) == 0.0
    assert float(m["moe_drop_rate"]) == 0.0
    assert np.isfinite(float(m["loss"]))
