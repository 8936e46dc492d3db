"""CPU tests of the benchmark.  Nothing here touches a TPU: the multi-
device cases run on 8 virtual CPU devices, set before JAX starts."""

import os
import sys
from pathlib import Path

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
