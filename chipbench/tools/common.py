"""Set-up that the chip tools share with ``chipbench/run.py``."""

from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def start(workload: str, seed: int, seconds: float, platform: str = "tpu"):
    """(spec, cell, Run) for one workload, on the machine's chips."""
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from chipbench import spec as S
    from chipbench.run import Run
    from chipbench.runtime import (CompileClock, device_check,
                                   enable_compile_cache)

    spec = S.load_spec(ROOT)
    cell = spec.cell(workload)
    devices = device_check(cell.chips, platform)
    enable_compile_cache(ROOT)
    run = Run(cell, seed, seconds, False, devices, CompileClock(),
              time.perf_counter())
    return spec, cell, run
