"""Production training driver.

Wires the full DiOMP substrate: runtime registration (PGAS planning),
synthetic-shard data pipeline with async prefetch, the shard_map'd train
step (explicit OMPCCL gradient reduction), async atomic checkpointing with
auto-resume + elastic re-shard, and straggler monitoring with a CLOSED
eviction loop: when the monitor escalates (timing outliers, or a rank
death scheduled via ``--chaos-seed``/``--kill-rank-step``), the driver
checkpoints, shrinks the mesh to the surviving devices, restores from the
latest verified checkpoint, and keeps training (docs/RESILIENCE.md).

Smoke scale (default):
  PYTHONPATH=src python -m repro.launch.train --arch stablelm-3b --reduced \\
      --steps 30 --batch 8 --seq 64

Full scale runs the same code path on the production mesh (remove
--reduced and set --mesh production under a real TPU runtime).
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core.runtime import DiompRuntime
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh, make_smoke_mesh
from repro.models import api as model_api
from repro.models import schema as sch
from repro.models.config import ParallelCtx
from repro.train.checkpoint import CheckpointManager
from repro.train.optim import (adafactor, adafactor_dim_axes, adamw,
                               cosine_schedule)
from repro.train.step import build_train_step
from repro.train.straggler import StragglerMonitor


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b",
                    choices=configs.all_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--mesh", choices=["smoke", "production"],
                    default="smoke")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-codec", default="none", choices=["none", "int8"])
    ap.add_argument("--dp-backend", default="hierarchical",
                    choices=["flat", "hierarchical"])
    ap.add_argument("--chaos-seed", type=int, default=None,
                    help="enable deterministic fault injection (FaultPlan)")
    ap.add_argument("--chaos-p", type=float, default=0.05,
                    help="per-dispatch fault probability under --chaos-seed")
    ap.add_argument("--kill-rank-step", type=int, default=None,
                    help="schedule a rank death at this step (elastic "
                         "restore exercise; requires --checkpoint-dir)")
    ap.add_argument("--max-restarts", type=int, default=1)
    args = ap.parse_args(argv)

    enable_compile_cache()
    fault_plan = None
    if args.chaos_seed is not None:
        from repro.core.faults import FaultPlan
        fault_plan = FaultPlan(args.chaos_seed, p=args.chaos_p,
                               kinds=("drop", "fail", "timeout"))
        if args.kill_rank_step is not None:
            fault_plan.kill_rank(args.kill_rank_step,
                                 rank=len(jax.devices()) - 1)

    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get(args.arch)
    ndev = len(jax.devices())
    mesh = (make_production_mesh(multi_pod=True) if args.mesh == "production"
            else make_smoke_mesh(ndev))
    ctx = ParallelCtx.from_mesh(mesh, remat=True, microbatch=args.microbatch,
                                grad_codec=args.grad_codec,
                                dp_backend=args.dp_backend)
    print(f"arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"mesh={dict(mesh.shape)} dp={ctx.dp} tp={ctx.tp}")

    # -- runtime: register every parameter into the PGAS plan ----------------
    from repro.core.context import DiompContext
    rt = DiompRuntime(mesh, context=DiompContext(
        mesh=mesh, segment_bytes=1 << 30, fault_plan=fault_plan))
    schema = sch.build_schema(cfg)
    for name, spec in schema.items():
        rt.register(name, spec.shape, spec.dtype, spec.axes)
    print(f"PGAS plan: {rt.bytes_in_use()/2**20:.1f} MiB/device in "
          f"{len(rt.table())} regions")

    # -- optimizer + step ------------------------------------------------------
    lr = cosine_schedule(args.lr, warmup=max(args.steps // 10, 1),
                         total=args.steps)
    if cfg.param_count() >= 30e9:
        opt, opt_name = adafactor(lr, dim_axes=adafactor_dim_axes(cfg, mesh)), \
            "adafactor"
    else:
        opt, opt_name = adamw(lr), "adamw"

    def build_step(mesh, ctx):
        return build_train_step(cfg, mesh, ctx, opt, optimizer_name=opt_name,
                                donate=False, global_batch=args.batch)

    step_fn = build_step(mesh, ctx)

    # -- init or resume ----------------------------------------------------------
    ckpt = CheckpointManager(args.checkpoint_dir, pool=rt.streams) \
        if args.checkpoint_dir else None
    start = 0
    if ckpt and args.resume and ckpt.latest() is not None:
        start, params, opt_state, extra = ckpt.restore(
            shard_fn=lambda name, arr: jax.device_put(arr))  # elastic re-shard
        params = {k: jnp.asarray(v) for k, v in params.items()}
        print(f"resumed from step {start}")
    else:
        params = sch.init_params(cfg, jax.random.PRNGKey(0))
        opt_state = jax.jit(opt.init)(params)

    # -- data + monitoring ---------------------------------------------------------
    source = SyntheticLM(cfg, args.batch, args.seq, seed=17)
    prefetch = Prefetcher(source, depth=2, pool=rt.streams, start_step=start)
    # the eviction loop is CLOSED: on_evict raises a flag the driver acts on
    # (checkpoint -> shrink mesh -> restore), instead of only reporting
    evict_flag = {"requested": False}
    monitor = StragglerMonitor(
        on_prefetch_boost=lambda n: prefetch.boost(1),
        on_evict=lambda: evict_flag.update(requested=True))

    # -- the loop -------------------------------------------------------------------
    t_start = time.time()
    restarts = 0
    end = start + args.steps
    i = start
    while i < end:
        monitor.step_start()
        _, batch = prefetch.get()
        params, opt_state, metrics = step_fn(
            params, opt_state, batch, jnp.asarray(i))
        loss = float(metrics["loss"])
        if fault_plan is not None and fault_plan.deaths_at(i):
            monitor.escalate(i, "rank-death")
        else:
            monitor.step_end(i)
        if i % 5 == 0 or i == end - 1:
            print(f"step {i:5d}  loss {loss:.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"({(time.time()-t_start)/max(i-start+1,1):.2f}s/step)")
        if ckpt and (i + 1) % args.checkpoint_every == 0:
            ckpt.save(i + 1, jax.device_get(params),
                      jax.device_get(opt_state))
        i += 1
        if evict_flag["requested"]:
            evict_flag["requested"] = False
            if ckpt is None or restarts >= args.max_restarts or ndev <= 2:
                print(f"[elastic] eviction at step {i} but no restart "
                      "possible (need --checkpoint-dir, restart budget, "
                      ">2 devices); continuing degraded")
                continue
            # elastic restore: persist, shrink to the surviving devices,
            # resume from the latest VERIFIED checkpoint on the new mesh
            ckpt.wait()
            if ckpt.latest() is None:
                ckpt.save(i, jax.device_get(params),
                          jax.device_get(opt_state), blocking=True)
            ndev = max(ndev // 2, 2)
            mesh = make_smoke_mesh(ndev)
            ctx = ParallelCtx.from_mesh(
                mesh, remat=True, microbatch=args.microbatch,
                grad_codec=args.grad_codec, dp_backend=args.dp_backend)
            step_fn = build_step(mesh, ctx)
            i, params, opt_state, _ = ckpt.restore(
                shard_fn=lambda name, arr: jax.device_put(arr))
            params = {k: jnp.asarray(v) for k, v in params.items()}
            prefetch = Prefetcher(source, depth=2, pool=rt.streams,
                                  start_step=i)
            monitor.reset()
            restarts += 1
            print(f"[elastic] restart {restarts}: resumed step {i} on "
                  f"{ndev} devices (mesh {dict(mesh.shape)})")
    if ckpt:
        ckpt.wait()
        print(f"checkpoints: steps {ckpt.steps()}")
    if monitor.events:
        print(f"straggler events: {[(e.step, e.action) for e in monitor.events]}")
    if restarts:
        print(f"elastic restarts: {restarts}")
    rt.close()
    print("train driver done")
    return loss


if __name__ == "__main__":
    main()
