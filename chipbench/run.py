#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is looked up by name in ``BENCHMARK.json``; its configuration,
traffic mix, driver and metric readers are files found by name (see
``chipbench/spec.py``).  The run finds its chips (a TPU, or it exits 3 with
no result), sets up and warms up every shape it will use, measures for
``--seconds``, checks the output against the plain reference, and prints
one JSON object as the last line of standard output.  ``--trace 0`` reports
the cell's end-to-end metrics, ``--trace 1`` its per-layer metrics from a
profiler trace of part of the window.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4


class Run:
    """What a driver is given for one run."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 devices, clock, t_start: float):
        from chipbench.runtime import Spans

        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.devices, self.clock = trace, devices, clock
        self.t_start = t_start
        self.spans = Spans()

    def memory_peak(self) -> int:
        from chipbench.runtime import memory_peak

        return memory_peak(self.devices)


def metric_values(spec, cell, window, names_of) -> dict:
    """Each metric's reader applied to the window; a reader that finds
    nothing to read returns None and the metric is left out."""
    from chipbench.spec import reader

    out = {}
    for m in names_of(cell):
        v = reader(spec, m.name).read(window)
        if v is None:
            continue
        v = float(v)
        if not math.isfinite(v):
            continue
        out[m.name] = {"value": v, "unit": m.unit}
    return out


def result(spec, cell, window, devices, trace: bool) -> dict:
    names_of = spec.per_layer if trace else spec.end_to_end
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": window.memory_peak_bytes}
    res = {"correct": all(c.ok for c in window.checks.values())
           and bool(window.checks),
           "attempted": window.attempted, "failed": window.failed,
           "metrics": metric_values(spec, cell, window, names_of),
           "device": device}
    if trace and window.trace is not None:
        device["busy_s"] = window.trace.busy_s
        device["window_s"] = window.trace.window_s
        res["breakdown"] = {
            "device_ops": [[n, s] for n, s in window.trace.device_ops],
            "idle_gaps": [[n, s] for n, s in window.trace.idle_gaps]}
    res["checks"] = {k: {"value": c.value, "limit": c.limit}
                     for k, c in window.checks.items()}
    return res


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            root: Path = ROOT, platform: str = "tpu", t_start=None):
    """Run one cell; returns the result dict.  ``platform`` other than
    ``tpu`` is for tests that drive a run on the CPU at a small size."""
    from chipbench import spec as S
    from chipbench.runtime import (CompileClock, device_check,
                                   enable_compile_cache)
    from chipbench.peaks import peaks_for

    spec = S.load_spec(root)
    cell = spec.cell(workload)
    devices = device_check(cell.chips, platform)
    enable_compile_cache(root)
    peaks = peaks_for(devices[0].device_kind) if platform == "tpu" else {}
    clock = CompileClock()
    run = Run(cell, seed, seconds, trace, devices, clock,
              T_START if t_start is None else t_start)
    window = S.driver(spec, cell.kind).run(run)
    window.peaks = peaks
    print(f"[run] {workload} seed {seed}: setup_s {window.setup_s:.3f}, "
          f"window_s {window.window_s:.3f}, compiles in window "
          f"{window.compiles_in_window}, backend compile "
          f"{clock.seconds:.2f}s ({clock.compiles} compiles, "
          f"{clock.hits} cache hits), counters {window.counters}",
          file=sys.stderr)
    return result(spec, cell, window, devices, trace)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no program next to the benchmark "
              f"(expected {ROOT / 'src' / 'repro'})", file=sys.stderr)
        return EXIT_NO_PROGRAM
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from chipbench.runtime import NoChip

    try:
        res = execute(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for name, c in res["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
