"""Serving driver: continuous batching with chunked prefill on the DiOMP
runtime (engine lifecycle + knob reference: docs/SERVING.md; overload
controls: docs/SERVING.md "Overload & SLOs").

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --reduced \\
      --requests 6 --max-new 8 --prefill-chunk 16

``--no-reduced`` serves the published widths (random weights, seed 0);
``--max-len`` sizes the KV cache and the page arena.

Passing any of --ttft-deadline-s / --total-deadline-s / --rate-per-s
arms the SLO layer: deadline-aware admission, bounded queue with
backpressure, load shedding, and staged degraded modes.  With deadlines
active, late requests are shed instead of served late, so the driver
reports done + shed == submitted rather than done == submitted.
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import time

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_smoke_mesh
from repro.models import schema as sch
from repro.models.config import ParallelCtx
from repro.serve.engine import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b", choices=configs.all_archs())
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the reduced config (--no-reduced: the "
                         "published widths)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-len", type=int, default=96,
                    help="KV-cache rows per slot (prompt + generated)")
    ap.add_argument("--min-prompt", type=int, default=2)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens per prefill device call "
                         "(1 = token-by-token baseline)")
    ap.add_argument("--page-tokens", type=int, default=64,
                    help="KV tokens per PGAS page")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples (with --top-k)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--high-watermark", type=float, default=0.92,
                    help="KV pressure above which the engine preempts")
    ap.add_argument("--ttft-deadline-s", type=float, default=None,
                    help="shed requests whose first token would miss this")
    ap.add_argument("--total-deadline-s", type=float, default=None,
                    help="cancel requests that cannot finish by this")
    ap.add_argument("--rate-per-s", type=float, default=None,
                    help="token-bucket admission rate limit")
    ap.add_argument("--burst", type=float, default=8.0,
                    help="token-bucket depth for --rate-per-s")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="hard queue bound: submissions beyond it reject")
    ap.add_argument("--queue-high", type=int, default=16,
                    help="backpressure/degrade watermark")
    ap.add_argument("--queue-low", type=int, default=4,
                    help="hysteresis watermark clearing backpressure")
    args = ap.parse_args(argv)

    slo = None
    if (args.ttft_deadline_s is not None or args.total_deadline_s is not None
            or args.rate_per_s is not None):
        from repro.serve.slo import SLOPolicy, TierPolicy
        slo = SLOPolicy(
            default_tier=TierPolicy(ttft_deadline_s=args.ttft_deadline_s,
                                    total_deadline_s=args.total_deadline_s,
                                    rate_per_s=args.rate_per_s,
                                    burst=args.burst),
            max_queue=args.max_queue, queue_high=args.queue_high,
            queue_low=args.queue_low)

    enable_compile_cache()
    cfg = configs.get_reduced(args.arch) if args.reduced \
        else configs.get(args.arch)
    mesh = make_smoke_mesh(len(jax.devices()))
    ctx = ParallelCtx.from_mesh(mesh, remat=False, inference=True)
    params = sch.init_params(cfg, jax.random.PRNGKey(0))

    eng = ServeEngine(cfg, mesh, ctx, params, slots=args.slots,
                      max_len=args.max_len,
                      prefill_chunk=args.prefill_chunk,
                      page_tokens=args.page_tokens,
                      temperature=args.temperature, top_k=args.top_k,
                      high_watermark=args.high_watermark, slo=slo)
    rng = np.random.RandomState(0)
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size,
                                   size=rng.randint(args.min_prompt,
                                                    args.max_prompt + 1)),
                       max_new=args.max_new)
            for _ in range(args.requests)]
    t0 = time.time()
    eng.run()
    dt = time.time() - t0
    done = sum(r.done for r in reqs)
    shed = sum(r.shed_reason is not None for r in reqs)
    toks = sum(len(r.out) for r in reqs)
    print(f"served {done}/{len(reqs)} requests, {toks} tokens in "
          f"{eng.steps} engine steps / {eng.device_calls} device calls "
          f"({dt:.1f}s incl. compile)")
    for i, r in enumerate(reqs[:4]):
        print(f"  req{i} prompt[{len(r.prompt)}] -> {r.out} "
              f"(prefill_steps={r.prefill_steps})")
    print("kv stats:", eng.kv_stats)
    print("latency:", json.dumps(eng.latency_stats(), default=float))
    if slo is not None:
        print(f"slo: {shed} shed, {len(eng.slo_log)} decision-log entries")
        assert done + shed == len(reqs)
    else:
        assert done == len(reqs)
    print("serve driver done")
    return eng, reqs


if __name__ == "__main__":
    main()
