"""Minimod's cells as ``BENCHMARK.json`` declares them, and the readers of
the four-chip cell on a hand-made window and trace."""

import json

import jax
import pytest

import chipbench.runtime as runtime
from chipbench import run as R, spec as S
from chipbench.runtime import Window
from chipbench.tests.tiny import make_root
from chipbench.trace import Summary

SP = S.load_spec(S.ROOT)
MINIMOD_CELLS = [w["name"] for w in SP.raw["workloads"]
                 if SP.cell(w["name"]).kind == "minimod"]


@pytest.mark.parametrize("name", MINIMOD_CELLS)
def test_minimod_cell_splits_its_grid_over_its_chips(name):
    """``drivers/minimod.py`` refuses a ``z_split`` other than the chips
    once a run has found its chips, and a per-chip Z extent that the
    check's blocks do not tile only at the run's end: both are held here,
    before any chip time is spent."""
    cell = SP.cell(name)
    cfg, tr = cell.config_data, cell.traffic_data
    assert cfg["z_split"] == cell.chips
    assert cfg["nz"] % cfg["z_split"] == 0
    assert (cfg["nz"] // cfg["z_split"]) % tr["check_block"] == 0


def test_the_four_chip_cell_is_the_whole_grid_of_the_one_chip_share():
    one, four = SP.cell("minimod-1024-1chip"), SP.cell("minimod-1024-4chip")
    assert four.traffic == one.traffic
    a, b = dict(one.config_data), dict(four.config_data)
    assert b["nz"] // b["z_split"] == a["nz"] and a["z_split"] == 1
    for key in ("nz", "z_split", "deployment", "assumed"):
        a.pop(key), b.pop(key)
    assert a == b
    assert four.config_data["assumed"]["velocity_model"] \
        == one.config_data["assumed"]["velocity_model"]


def _summary(**kw):
    base = dict(window_s=3.0, chips=4, busy_s=2.99, compute_s=2.9,
                collective_s=0.05, exposed_collective_s=0.02,
                device_ops=[], idle_gaps=[])
    base.update(kw)
    return Summary(**base)


def _window(trace, traced_steps=50, peaks=None):
    w = Window(setup_s=10.0, t0=0.0, t1=3.0)
    w.trace = trace
    w.counters = {"calls": 5, "steps": 50, "chips": 4,
                  "cells": 1024 ** 3, "cells_per_chip": 256 * 1024 ** 2,
                  "traced_steps": traced_steps}
    w.peaks = {"hbm_bytes_per_s": 819e9} if peaks is None else peaks
    return w


def _read(name, w):
    return S.reader(SP, name).read(w)


def test_exposed_exchange_is_ms_per_traced_step():
    # 0.02 s exposed over 50 steps: 0.4 ms a step
    w = _window(_summary())
    assert _read("minimod.exchange_exposed_ms.4chip", w) \
        == pytest.approx(0.4)
    w = _window(_summary(exposed_collective_s=0.0))
    assert _read("minimod.exchange_exposed_ms.4chip", w) == 0.0


def test_split_step_roofline_counts_one_chips_share():
    # 16 B x 2^28 cells x 50 steps at 819 GB/s, over 2.9 s of compute
    w = _window(_summary())
    want = 100.0 * 16 * 256 * 1024 ** 2 * 50 / 819e9 / 2.9
    assert _read("minimod.stencil_hbm_roofline.4chip", w) \
        == pytest.approx(want)
    assert 0 < want < 100


@pytest.mark.parametrize("name", ["minimod.exchange_exposed_ms.4chip",
                                  "minimod.stencil_hbm_roofline.4chip"])
def test_readers_read_nothing_without_a_trace_or_traced_steps(name):
    assert _read(name, _window(None)) is None
    assert _read(name, _window(_summary(), traced_steps=0)) is None


def test_exposed_exchange_reads_nothing_without_a_collective_op():
    w = _window(_summary(collective_s=0.0, exposed_collective_s=0.0))
    assert _read("minimod.exchange_exposed_ms.4chip", w) is None


def test_split_step_roofline_needs_the_chips_peaks():
    assert _read("minimod.stencil_hbm_roofline.4chip",
                 _window(_summary(), peaks={})) is None
    assert _read("minimod.stencil_hbm_roofline.4chip",
                 _window(_summary(compute_s=0.0))) is None


def test_the_four_chip_cell_reports_its_readers():
    cell = SP.cell("minimod-1024-4chip")
    assert [m.name for m in SP.end_to_end(cell)] \
        == ["setup_s", "minimod.gpts_per_s_per_chip"]
    assert [m.name for m in SP.per_layer(cell)] \
        == ["minimod.exchange_exposed_ms.4chip",
            "minimod.stencil_hbm_roofline.4chip"]


def test_traced_four_chip_run_is_correct_with_its_readers(tmp_path,
                                                         monkeypatch):
    """A ``--trace 1`` run of the four-chip cell at a small size on four
    CPU devices: correct, and each per-layer metric it prints is one of
    the cell's (the CPU trace has no TPU plane, so the device readers
    find nothing and leave their metrics out)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 CPU devices")
    monkeypatch.setattr(runtime, "enable_compile_cache", lambda root: "off")
    root = make_root(tmp_path)
    k3 = root / "chipbench/traffic/tiny-k3.json"
    k3.write_text(json.dumps(dict(json.loads(k3.read_text()),
                                  trace_after_s=0.2, trace_s=0.5)))
    res = R.execute("minimod-1024-4chip", 2**31 + 9, 2.0, True, root=root,
                    platform="cpu")
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4 and res["attempted"] > 0
    sp = S.load_spec(root)
    mine = {m.name for m in sp.per_layer(sp.cell("minimod-1024-4chip"))}
    assert set(res["metrics"]) <= mine
