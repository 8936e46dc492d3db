#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell: the highest offered rate
at which the engine keeps up, in one process on the chip.

    python3 chipbench/tools/sweep.py --workload serve-stablelm-3b-short \\
        --rates 2,3,4,5,6 --seconds 20 --seed 1

For each rate it offers the cell's traffic at that rate for ``--seconds``,
then prints one JSON line: requests due, those still queued when the
window closed, time to first token (median and 90th percentile) of the
first and the second half of the window, and how late submissions ran.
A backlog that grows shows as a second half far slower than the first
and requests left queued.  The engine is emptied between rates.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.tools.common import start  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    spec, cell, run = start(args.workload, args.seed, args.seconds)
    from chipbench import serving, traffic as T
    from chipbench.runtime import percentile
    from chipbench.trace import Capture

    server = serving.Server(run)
    server.warm_up()
    for rate in [float(r) for r in args.rates.split(",")]:
        loop = serving.Loop(server, run)
        reqs = T.open_loop(cell.traffic_data, args.seconds, args.seed,
                           rate_rps=rate)
        t0 = time.perf_counter()
        t1 = t0 + args.seconds
        serving.open_window(loop, reqs, t0, t1, Capture(False, 0, 0))
        queued = len(server.eng.queue)
        serving.drain(loop, t1, 60.0, first_only=True)
        mid = t0 + args.seconds / 2
        halves = [[], []]
        for r in loop.records:
            if r.token_times:
                halves[r.due >= mid].append(1e3 * (r.token_times[0] - r.due))
        late = [1e3 * (r.submit - r.due) for r in loop.records]
        waits = [1e3 * (r.admit - r.due) for r in loop.records
                 if r.admit is not None]
        print(json.dumps({
            "rate_rps": rate, "due": len(loop.records),
            "queued_at_close": queued,
            "no_first_token": sum(not r.token_times for r in loop.records),
            "ttft_ms_p90": percentile(halves[0] + halves[1], 90),
            "queue_wait_ms_p90": percentile(waits, 90),
            "ttft_ms_first_half": [percentile(halves[0], 50),
                                   percentile(halves[0], 90)],
            "ttft_ms_second_half": [percentile(halves[1], 50),
                                    percentile(halves[1], 90)],
            "submit_late_ms_p50_max": [percentile(late, 50), max(late or [0])],
            "tokens_per_s": loop.counters["tokens_out"] / args.seconds}),
            flush=True)
        serving.drain(loop, t1, 120.0, first_only=False)
    return 0


if __name__ == "__main__":
    sys.exit(main())
