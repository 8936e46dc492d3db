"""The loader finds every configuration, workload and metric by name, and
a new cell is only new files plus entries."""

import json
import shutil

import pytest

from chipbench import spec as S


def test_every_cell_metric_and_reader_is_found():
    sp = S.load_spec(S.ROOT)
    names = [w["name"] for w in sp.raw["workloads"]]
    assert len(names) == len(set(names)) >= 1
    for name in names:
        cell = sp.cell(name)
        assert S.driver(sp, cell.kind).run
        e2e = sp.end_to_end(cell)
        assert "setup_s" in [m.name for m in e2e] and len(e2e) >= 2
        layer = sp.per_layer(cell)
        assert layer, name
        for m in layer:
            assert m.moves in [e.name for e in e2e]
    for m in sp.metrics:
        assert callable(S.reader(sp, m.name).read)


def test_at_most_half_the_cells_take_four_chips():
    raw = S.load_json(S.ROOT / "BENCHMARK.json")
    four = sum(w["chips"] == 4 for w in raw["workloads"])
    assert four <= max(1, len(raw["workloads"]) // 2)


@pytest.mark.parametrize("bad", ["has space", "a/b", "", "x" * 65, "é"])
def test_malformed_names_are_refused(bad):
    with pytest.raises(S.SpecError):
        S.check_name(bad, "test")


@pytest.mark.parametrize("bad", ["tokens per s", "", "u" * 17, "µs"])
def test_malformed_units_are_refused(bad):
    with pytest.raises(S.SpecError):
        S.check_unit(bad, "test")


def _copy(tmp_path):
    shutil.copytree(S.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(S.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return json.loads((tmp_path / "BENCHMARK.json").read_text())


def test_a_new_cell_is_files_and_entries_only(tmp_path):
    raw = _copy(tmp_path)
    tr = json.loads((tmp_path / "chipbench/traffic/chat-short-open.json")
                    .read_text())
    tr["rate_rps"] = 1.0
    (tmp_path / "chipbench/traffic/chat-slow-open.json").write_text(
        json.dumps(tr))
    raw["workloads"].append({"name": "serve-stablelm-3b-slow",
                             "config": "stablelm-3b",
                             "traffic": "chat-slow-open", "chips": 1,
                             "why": "a cell added as data"})
    (tmp_path / "chipbench/metrics/serve.new_count.py").write_text(
        "def read(w):\n    return None\n")
    raw["per_layer"].append({"name": "serve.new_count", "unit": "tokens",
                             "better": "higher", "source": "program_counter",
                             "layer": "engine (serve/engine.py)",
                             "moves": "serve.itl_p95_ms"})
    itl = next(m for m in raw["end_to_end"] if m["name"] == "serve.itl_p95_ms")
    itl["workloads"].append("serve-stablelm-3b-slow")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(raw))
    sp = S.load_spec(tmp_path)
    cell = sp.cell("serve-stablelm-3b-slow")
    assert cell.kind == "serve_open" and cell.traffic_data["rate_rps"] == 1.0
    assert "serve.new_count" in [m.name for m in sp.per_layer(cell)]
    assert "serve.new_count" in [m.name for m in sp.per_layer(
        sp.cell("serve-stablelm-3b-short"))]


def test_a_missing_file_is_refused(tmp_path):
    raw = _copy(tmp_path)
    raw["workloads"][0]["traffic"] = "no-such-mix"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(raw))
    with pytest.raises(S.SpecError):
        S.load_spec(tmp_path)


def test_a_metric_without_a_reader_is_refused(tmp_path):
    raw = _copy(tmp_path)
    raw["per_layer"][0]["name"] = "serve.unread"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(raw))
    with pytest.raises(S.SpecError):
        S.load_spec(tmp_path)


def test_benchmark_json_keeps_to_its_shape():
    raw = S.load_json(S.ROOT / "BENCHMARK.json")
    assert set(raw) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert raw["paths"] == ["chipbench"] and 1 <= raw["run_seconds"] <= 51
    assert raw["command"][1].startswith("chipbench/")
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"}}
    for part, want in keys.items():
        for entry in raw[part]:
            assert set(entry) == want, entry
            assert 1 <= len(entry["why"]) <= 200
    for m in raw["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in raw["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    configs = {c["name"] for c in raw["configs"]}
    assert configs == {w["config"] for w in raw["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in raw["workloads"]]
    assert len(pairs) == len(set(pairs))
