#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process
on the chip: for each seed, the number the check compares for the program
and for the control (the plain reference in the next precision below the
configuration's).

    python3 chipbench/tools/control.py --workload <name> --seeds 1,2,3 \\
        --seconds 10

Serving: a short window at the cell's own load, drained until every
request has finished, then the widest logit gap of a seeded sample of
served tokens (program) and of the tokens the float8 reference puts first
at the same positions (control).  Minimod: the field after the first call
against the float32 oracle (program) and the bfloat16 oracle against the
float32 oracle (control).  One JSON line per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.tools.common import start  # noqa: E402


def serve_readings(run, cell, seed: int, seconds: float,
                   with_control: bool) -> dict:
    from chipbench import serving, traffic as T
    from chipbench.trace import Capture

    run.seed = seed
    tr = cell.traffic_data
    server = serving.Server(run)
    server.warm_up()
    loop = serving.Loop(server, run)
    t0 = time.perf_counter()
    off = Capture(False, 0, 0)
    if cell.kind == "serve_open":
        serving.open_window(loop, T.open_loop(tr, seconds, seed), t0,
                            t0 + seconds, off)
    else:
        serving.closed_window(loop, T.closed_pool(tr),
                              int(tr["outstanding"]), t0, t0 + seconds, off)
    serving.drain(loop, t0 + seconds, 120.0, first_only=False)
    finished = loop.finished
    server.free()
    del loop
    gc.collect()
    prompts, served = serving.sample(run, finished, int(tr["check_requests"]))
    g = serving.gaps(server.cfg, server.seed32, prompts, served,
                     int(tr["max_len"]), run.devices[0],
                     control="fp8" if with_control else None)
    return {"seed": seed, "program": g["served"],
            "control": g.get("control"), "tokens": g["tokens"],
            "finished": len(finished)}


def minimod_readings(run, spec, cell, seed: int, with_control: bool) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core.context import use_default

    from chipbench import fields, spec as S, traffic as T
    from chipbench.reference.stencil import BlockedOracle

    run.seed = seed
    drv = S.driver(spec, "minimod")
    ctx, init_j, step, pro, K, _, _ = drv.build(run)
    cfg, tr = cell.config_data, cell.traffic_data
    seed32 = T.derive_seed32("minimod", seed)
    profile = fields.velocity_profile(cfg, seed)
    with use_default(ctx):
        u, up, c2 = init_j(jnp.asarray(seed32, jnp.uint32),
                           jnp.asarray(profile))
        state = (u, up) + (pro(u) if pro is not None else ())
        del u, up
        got = step(*state, c2)[0]
        del state, c2
    program = drv.check(run, got, K)["field_rel_err"].value
    del got
    gc.collect()
    if not with_control:
        return {"seed": seed, "program": program, "control": None}
    f32 = BlockedOracle(cfg, K, int(tr["check_block"]))
    bf16 = BlockedOracle(cfg, K, int(tr["check_block"]), dtype=jnp.bfloat16)
    diff = ref_max = 0.0
    for z0, z1 in f32.blocks():
        a = f32.block_field(seed32, profile, z0, z1, device=run.devices[0])
        b = bf16.block_field(seed32, profile, z0, z1, device=run.devices[0])
        d, m = jax.device_get((jnp.max(jnp.abs(a - b)), jnp.max(jnp.abs(a))))
        diff, ref_max = max(diff, float(d)), max(ref_max, float(m))
    return {"seed": seed, "program": program, "control": diff / ref_max}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control on the first N seeds only")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    spec, cell, run = start(args.workload, seeds[0], args.seconds)
    n_control = len(seeds) if args.control_seeds is None \
        else args.control_seeds
    for i, seed in enumerate(seeds):
        if cell.kind == "minimod":
            out = minimod_readings(run, spec, cell, seed, i < n_control)
        else:
            out = serve_readings(run, cell, seed, args.seconds,
                                 i < n_control)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
