"""A copy of the benchmark with small configurations and traffic, for
whole runs on the CPU.  Limits stay the cells' own."""

import json
import shutil

from chipbench import spec as S

TINY_LM = {"hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 4, "vocab_size": 256}
TINY_OPEN = {"rate_rps": 20.0,
             "prompt": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
             "output": {"median": 6, "sigma": 0.5, "min": 3, "max": 10},
             "slots": 2, "max_len": 64, "prefill_chunk": 16,
             "check_requests": 4}


def make_root(root, lm=TINY_LM, open_mix=TINY_OPEN):
    """``root`` gets ``chipbench/`` and a ``BENCHMARK.json`` whose cells
    keep their names but run small sizes: serving on ``lm``, Minimod on a
    32 x 16 x 128 grid, 3 steps per call, on 1 and 4 devices."""
    shutil.copytree(S.ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    raw = json.loads((S.ROOT / "BENCHMARK.json").read_text())

    def edit(kind, name, new, **kw):
        d = json.loads((root / "chipbench" / kind / f"{name}.json")
                       .read_text())
        d.update(kw)
        (root / "chipbench" / kind / f"{new}.json").write_text(json.dumps(d))

    edit("configs", "stablelm-3b", "tiny-lm", **lm)
    edit("configs", "minimod-1024-z256", "tiny-mm", nz=32, ny=16, nx=128,
         z_split=4)
    edit("configs", "minimod-1024-z256", "tiny-mm1", nz=32, ny=16, nx=128,
         z_split=1)
    edit("traffic", "chat-short-open", "tiny-open", **open_mix)
    edit("traffic", "minimod-k10", "tiny-k3", steps_per_call=3,
         check_block=8)
    raw["configs"] += [
        {"name": n, "source": "test", "file": f"chipbench/configs/{n}.json",
         "reduced": [], "why": "test"} for n in ("tiny-lm", "tiny-mm",
                                                  "tiny-mm1")]
    raw["workloads"] = [
        {"name": "serve-stablelm-3b-short", "config": "tiny-lm",
         "traffic": "tiny-open", "chips": 1, "why": "test"},
        {"name": "minimod-1024-1chip", "config": "tiny-mm1",
         "traffic": "tiny-k3", "chips": 1, "why": "test"},
        {"name": "minimod-1024-4chip", "config": "tiny-mm",
         "traffic": "tiny-k3", "chips": 4, "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(raw))
    return root
