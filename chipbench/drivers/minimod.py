"""Minimod: the program's time step, as ``run_minimod`` composes it in
mode ``fused``, driven for the window K steps per call.

``run_minimod`` builds its field on the host and cannot carry state from
one call to the next, so this driver composes the same pieces itself:
``exchange_halos`` once, then a compiled program that runs a ``lax.scan``
of ``fused_wave_step(..., return_halos=True)`` under ``shard_map`` over the
(``z``, ``y``) mesh, with a ``DiompContext`` and the ``default_planner()``
halo plan.  (u, u_prev, halos) stay on the devices between calls.

The first call runs in set-up from the seeded state, and its output is
what the check compares, after the window, with the blocked oracle.
"""

from __future__ import annotations

import gc
import sys
import time

from chipbench import fields, traffic as T
from chipbench.runtime import Check, Window
from chipbench.trace import Capture


def build(run):
    """(initial state, K-step program, prologue) on the cell's devices."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.compat import make_mesh, shard_map
    from repro.core.context import DiompContext, use_default
    from repro.core.groups import DiompGroup
    from repro.kernels.plan import default_planner
    from repro.kernels.stencil import fused
    from repro.kernels.stencil.ref import RADIUS

    cfg, tr = run.cell.config_data, run.cell.traffic_data
    nzc = int(cfg["z_split"])
    if nzc != run.cell.chips:
        raise ValueError(f"z_split {nzc} != chips {run.cell.chips}")
    Z, Y, X = int(cfg["nz"]), int(cfg["ny"]), int(cfg["nx"])
    K, dx = int(tr["steps_per_call"]), float(cfg["dx"])
    mesh = make_mesh((nzc, 1), ("z", "y"), axis_types="auto",
                     devices=run.devices)
    ctx = DiompContext(mesh=mesh)
    sh = NamedSharding(mesh, P("z", "y"))
    spec = P("z", "y")
    zg, yg = DiompGroup(("z",), name="z"), None
    plan = default_planner().plan_halo_slots(Z // nzc, Y, X, jnp.float32,
                                             nzc, ny=1, halo=RADIUS)

    def init(seed, prof):
        return fields.planes(jax.random.PRNGKey(seed), jnp.arange(Z), prof,
                             Y, X)

    if plan.overlap:
        def kprog(u, up, zlo, zhi, c2):
            def body(carry, _):
                u, up, h = carry
                un, hn = fused.fused_wave_step(
                    u, up, c2, zg, yg, dx=dx, plan=plan, halos=h,
                    return_halos=True)
                return (un, u, hn), None

            h = fused.Halos(zlo, zhi, None, None)
            (u, up, h), _ = lax.scan(body, (u, up, h), None, length=K)
            return u, up, h.z_lo, h.z_hi

        def prologue(u):
            h = fused.exchange_halos(u, zg, yg)
            return h.z_lo, h.z_hi

        n_state = 4
    else:                       # no exchanging axis: the planner's fallback
        def kprog(u, up, c2):
            def body(carry, _):
                u, up = carry
                un = fused.fused_wave_step(u, up, c2, zg, yg, dx=dx,
                                           plan=plan)
                return (un, u), None

            (u, up), _ = lax.scan(body, (u, up), None, length=K)
            return u, up

        prologue = None
        n_state = 2

    with use_default(ctx):
        step = jax.jit(shard_map(kprog, mesh=mesh,
                                 in_specs=(spec,) * (n_state + 1),
                                 out_specs=(spec,) * n_state))
        pro = (jax.jit(shard_map(prologue, mesh=mesh, in_specs=(spec,),
                                 out_specs=(spec, spec)))
               if prologue is not None else None)
    init_j = jax.jit(init, out_shardings=(sh, sh, sh))
    return ctx, init_j, step, pro, K, (Z, Y, X), nzc


def run(run) -> Window:
    import jax
    import jax.numpy as jnp
    from repro.core.context import use_default

    cfg, tr = run.cell.config_data, run.cell.traffic_data
    ctx, init_j, step, pro, K, (Z, Y, X), chips = build(run)
    seed32 = T.derive_seed32("minimod", run.seed)
    profile = fields.velocity_profile(cfg, run.seed)
    with use_default(ctx):
        u, up, c2 = init_j(jnp.asarray(seed32, jnp.uint32),
                           jnp.asarray(profile))
        state = (u, up) + (pro(u) if pro is not None else ())
        del u, up
        state = step(*state, c2)          # compiles; the checked call
        jax.block_until_ready(state)
        checked = state[0]
        capture = Capture(run.trace, float(tr.get("trace_after_s", 2.0)),
                          float(tr.get("trace_s", 3.0)))
        compiles0 = run.clock.compiles
        t0 = time.perf_counter()
        setup_s = t0 - run.t_start
        t1 = t0 + run.seconds
        calls = traced_calls = 0
        now = t0
        while True:
            capture.poll(t0, now)
            if now >= t1 or capture.done:
                break
            with run.spans.span("bench.call"):
                state = step(*state, c2)
                jax.block_until_ready(state)
            calls += 1
            traced_calls += capture.active
            now = time.perf_counter()
        t_last = capture.end(now)
        capture.stop()
    cells = Z * Y * X
    w = Window(setup_s=setup_s, t0=t0, t1=t_last,
               compiles_in_window=run.clock.compiles - compiles0,
               overhead_s=capture.overhead_s, spans=list(run.spans.items))
    w.counters = {"calls": calls, "steps": calls * K, "chips": chips,
                  "cells": cells, "cells_per_chip": cells // chips,
                  "traced_steps": traced_calls * K}
    w.attempted = calls
    w.memory_peak_bytes = run.memory_peak()
    w.trace = capture.summary()
    del state, c2
    gc.collect()
    w.checks = check(run, checked, K)
    return w


def check(run, got, K: int):
    """max |program - oracle| over max |oracle|, over the whole field after
    the first call, computed block by block on the chip that holds it."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference.stencil import BlockedOracle

    cfg, tr = run.cell.config_data, run.cell.traffic_data
    oracle = BlockedOracle(cfg, K, int(tr["check_block"]))
    profile = fields.velocity_profile(cfg, run.seed)
    seed32 = T.derive_seed32("minimod", run.seed)
    diff = ref_max = 0.0
    shards = sorted(got.addressable_shards, key=lambda s: s.index[0].start
                    or 0)
    for z0, z1 in oracle.blocks():
        sh = next(s for s in shards
                  if (s.index[0].start or 0) <= z0 < (s.index[0].stop
                                                      or cfg["nz"]))
        off = sh.index[0].start or 0
        if z1 > (sh.index[0].stop or cfg["nz"]):
            raise ValueError("check_block must divide the Z extent per chip")
        ref = oracle.block_field(seed32, profile, z0, z1, device=sh.device)
        mine = sh.data[z0 - off:z1 - off]
        d, m = jax.device_get((jnp.max(jnp.abs(mine - ref)),
                               jnp.max(jnp.abs(ref))))
        diff, ref_max = max(diff, float(d)), max(ref_max, float(m))
        del ref, mine
    err = diff / ref_max if ref_max > 0 else float("inf")
    print(f"[check] {len(oracle.blocks())} blocks, max|ref| {ref_max}",
          file=sys.stderr)
    return {"field_rel_err": Check(err, float(tr["limits"]["field_rel_err"]))}
