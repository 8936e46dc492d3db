"""Paper §4.5: Minimod — acoustic wave propagation with one-sided halos.

Thin CLI over the real application driver (:mod:`repro.apps.minimod`):
25-point acoustic stencil, 2-D (Z×Y) domain decomposition with optionally
asymmetric Z extents over heterogeneous ranks (PGAS asymmetric regions),
and three halo modes — ``none`` (two-sided, paper Listing 2), ``host``
(one-sided puts + fence, paper Listing 1) and ``fused`` (in-kernel
one-sided exchange overlapped with the interior stencil; see
docs/PERF.md, "Minimod & halo overlap").

Run:  PYTHONPATH=src python examples/minimod.py [--shape minimod_hetero]
      [--mode fused] [--grid 64] [--steps 10] [--nz 8] [--ny 1]
"""

import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.apps.minimod import MODES, run_minimod
from repro.launch.shapes import STENCIL_SHAPES


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=sorted(STENCIL_SHAPES), default=None,
                    help="a predefined Minimod cell (overrides grid/nz/ny)")
    ap.add_argument("--mode", choices=MODES, default="fused")
    ap.add_argument("--grid", type=int, default=64)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--nz", type=int, default=8)
    ap.add_argument("--ny", type=int, default=1)
    ap.add_argument("--weights", type=str, default=None,
                    help="comma-separated per-rank Z proportions, e.g. 3,2,2,1")
    args = ap.parse_args()

    weights = tuple(float(w) for w in args.weights.split(",")) \
        if args.weights else None
    r = run_minimod(grid=(args.grid,) * 3, steps=args.steps, nz=args.nz,
                    ny=args.ny, weights=weights, mode=args.mode,
                    shape=args.shape)
    G = "x".join(str(g) for g in r.grid)
    print(f"minimod[{r.mode}]: grid {G}, {r.steps} steps on "
          f"{r.nz}x{r.ny} ranks -> {r.wall_s * 1e3:.0f} ms "
          f"(+{r.compile_s * 1e3:.0f} ms compile)")
    print(f"  decomposition: z_extents={r.z_extents} "
          f"(PGAS region bytes/rank: {r.region_sizes})")
    print(f"  halo plan: overlap={r.plan.overlap} "
          f"schedule={'/'.join(r.plan.schedule())} "
          f"puts/step={r.plan.puts_per_step} "
          f"halo B/step={r.plan.halo_bytes_per_step}")
    print(f"  wire audit: {r.puts} put call sites, {r.put_bytes} B on the "
          f"OMPCCL log; tracker windows {r.tracker_put_bytes} B, "
          f"{r.fences} fences")
    print(f"  wavefield energy {r.energy:.4e}, max |u| "
          f"{np.abs(r.field).max():.3e} "
          f"(finite: {np.isfinite(r.field).all()})")
    assert np.isfinite(r.field).all() and np.abs(r.field).max() > 0
    if r.mode == "fused":
        assert r.put_bytes == r.tracker_put_bytes, "put-traffic parity broken"
    print("minimod OK")


if __name__ == "__main__":
    main()
