"""The controls come out not correct against each cell's own limit: the
reference in the next precision below the configuration's, put in the
program's place.  Run on the CPU at the smallest size at which the
float8 control's error is as large as at the cell's size on the chip."""

import time

import pytest

import chipbench.runtime as runtime
from chipbench import spec as S
from chipbench.run import Run
from chipbench.tests.tiny import TINY_OPEN, make_root
from chipbench.tools import control

# d 256 and 16 layers, some 250 served tokens: on the CPU the float8
# control's widest gap here (0.4-0.7) is of the size measured on the chip
# at the full width (0.55-0.73)
LM = {"hidden_size": 256, "intermediate_size": 704, "num_hidden_layers": 16,
      "num_attention_heads": 4, "num_key_value_heads": 4,
      "vocab_size": 1024}
MIX = dict(TINY_OPEN, rate_rps=8.0, max_len=128, prefill_chunk=32,
           prompt={"median": 40, "sigma": 0.5, "min": 16, "max": 64},
           output={"median": 28, "sigma": 0.3, "min": 16, "max": 48},
           check_requests=10)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"), lm=LM, open_mix=MIX)


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(runtime, "enable_compile_cache", lambda root: "off")


def _run(root, workload, seed):
    spec = S.load_spec(root)
    cell = spec.cell(workload)
    devices = runtime.device_check(cell.chips, "cpu")
    return spec, cell, Run(cell, seed, 2.0, False, devices,
                           runtime.CompileClock(), time.perf_counter())


def test_float8_control_fails_the_serving_limit(root):
    spec, cell, run = _run(root, "serve-stablelm-3b-short", 11)
    limit = cell.traffic_data["limits"]["max_logit_gap"]
    out = control.serve_readings(run, cell, 11, 2.0, True)
    assert out["tokens"] >= 200
    assert out["program"] <= limit < out["control"], out


@pytest.mark.parametrize("seed", [21, 22])
def test_bfloat16_control_fails_the_minimod_limit(root, seed):
    spec, cell, run = _run(root, "minimod-1024-1chip", seed)
    limit = cell.traffic_data["limits"]["field_rel_err"]
    out = control.minimod_readings(run, spec, cell, seed, True)
    assert out["program"] <= limit < out["control"], out
