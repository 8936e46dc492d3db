"""A run refuses a machine without a TPU, and a checkout without the
program, with a non-zero exit and no result line."""

import json
import shutil
import subprocess
import sys

import pytest

from chipbench import run as R
from chipbench import spec as S
from chipbench.runtime import NoChip, device_check


def test_device_check_refuses_a_cpu():
    with pytest.raises(NoChip):
        device_check(1)


def test_device_check_refuses_too_few_chips():
    with pytest.raises(NoChip):
        device_check(10**6, platform="cpu")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(capsys):
    rc = R.main(["--workload", "serve-stablelm-3b-short", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert rc == R.EXIT_NO_CHIP
    assert capsys.readouterr().out == ""


def test_run_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copytree(S.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(S.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    cmd = json.loads((tmp_path / "BENCHMARK.json").read_text())["command"]
    p = subprocess.run([sys.executable] + cmd[1:] + [
        "--workload", "minimod-1024-1chip", "--seed", "1", "--seconds", "1",
        "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout == ""
