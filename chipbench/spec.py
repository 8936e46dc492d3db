"""Load ``BENCHMARK.json`` and find every piece of a cell by its name.

Nothing here is specific to one cell: a cell names a configuration and a
traffic mix, and both are data files found by name,

    chipbench/configs/<config>.json     sizes of the configuration
    chipbench/traffic/<traffic>.json    driver kind and traffic parameters
    chipbench/drivers/<kind>.py         the window loop of that kind
    chipbench/metrics/<metric>.py       one reader per metric

so a cell, a traffic mix or a metric added later is new files plus entries
in ``BENCHMARK.json``, with no edit to the harness.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class SpecError(ValueError):
    """BENCHMARK.json or a file it names is malformed or missing."""


def check_name(name, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(f"{what}: {name!r} is not a valid name")
    return name


def check_unit(unit, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what}: unit {unit!r} is not valid")
    return unit


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    end_to_end: bool
    workloads: Optional[tuple]      # None: every cell that reports `moves`
    moves: Optional[str] = None
    bound: Optional[float] = None

    def applies_to(self, cell: "Cell", e2e_of_cell) -> bool:
        if self.workloads is not None:
            return cell.name in self.workloads
        if self.end_to_end:
            return True
        return self.moves in e2e_of_cell


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    why: str
    config_data: dict
    traffic_data: dict

    @property
    def kind(self) -> str:
        return self.traffic_data["driver"]


@dataclasses.dataclass
class Spec:
    raw: dict
    root: Path
    configs: Dict[str, dict]
    metrics: List[Metric]

    @property
    def bench_dir(self) -> Path:
        return self.root / "chipbench"

    def cell(self, name: str) -> Cell:
        for w in self.raw["workloads"]:
            if w["name"] == name:
                cfg = self.configs[w["config"]]
                traffic = load_json(
                    self.bench_dir / "traffic" / f"{w['traffic']}.json")
                if "driver" not in traffic:
                    raise SpecError(
                        f"traffic {w['traffic']}: no 'driver' key")
                return Cell(name=name, config=w["config"],
                            traffic=w["traffic"], chips=int(w["chips"]),
                            why=w["why"], config_data=cfg,
                            traffic_data=traffic)
        raise SpecError(f"no workload named {name!r} in BENCHMARK.json")

    def end_to_end(self, cell: Cell) -> List[Metric]:
        return [m for m in self.metrics
                if m.end_to_end and m.applies_to(cell, ())]

    def per_layer(self, cell: Cell) -> List[Metric]:
        e2e = {m.name for m in self.end_to_end(cell)}
        return [m for m in self.metrics
                if not m.end_to_end and m.applies_to(cell, e2e)]


def load_json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def _metric(entry: dict, end_to_end: bool) -> Metric:
    name = check_name(entry.get("name"), "metric")
    check_unit(entry.get("unit"), name)
    if entry.get("better") not in ("lower", "higher"):
        raise SpecError(f"{name}: better must be lower or higher")
    if entry.get("source") not in SOURCES:
        raise SpecError(f"{name}: source {entry.get('source')!r}")
    wl = entry.get("workloads")
    return Metric(name=name, unit=entry["unit"], better=entry["better"],
                  source=entry["source"], end_to_end=end_to_end,
                  workloads=tuple(wl) if wl is not None else None,
                  moves=entry.get("moves"), bound=entry.get("bound"))


def load_spec(root: Path = ROOT) -> Spec:
    """Parse and check ``<root>/BENCHMARK.json`` and the files it names."""
    raw = load_json(root / "BENCHMARK.json")
    configs = {}
    for c in raw.get("configs", []):
        check_name(c.get("name"), "config")
        for key in c.get("reduced", []):
            check_name(key, f"config {c['name']} reduced key")
        configs[c["name"]] = load_json(root / c["file"])
    metrics = ([_metric(m, True) for m in raw.get("end_to_end", [])]
               + [_metric(m, False) for m in raw.get("per_layer", [])])
    names = [m.name for m in metrics]
    if len(set(names)) != len(names):
        raise SpecError("two metrics share a name")
    e2e = {m.name for m in metrics if m.end_to_end}
    for m in metrics:
        if not m.end_to_end and m.moves not in e2e:
            raise SpecError(f"{m.name}: moves {m.moves!r}, which is no "
                            f"end-to-end metric")
    for w in raw.get("workloads", []):
        check_name(w.get("name"), "workload")
        check_name(w.get("traffic"), f"workload {w['name']} traffic")
        if w.get("config") not in configs:
            raise SpecError(f"workload {w['name']}: unknown config "
                            f"{w.get('config')!r}")
        if w.get("chips") not in (1, 4):
            raise SpecError(f"workload {w['name']}: chips must be 1 or 4")
        if not (root / "chipbench" / "traffic"
                / f"{w['traffic']}.json").is_file():
            raise SpecError(f"workload {w['name']}: no traffic file for "
                            f"{w['traffic']!r}")
    for m in metrics:
        if not (root / "chipbench" / "metrics" / f"{m.name}.py").is_file():
            raise SpecError(f"metric {m.name}: no reader "
                            f"chipbench/metrics/{m.name}.py")
    return Spec(raw=raw, root=root, configs=configs, metrics=metrics)


def load_module(path: Path, name: str):
    """Import a file by path (metric and driver files are named after
    dotted metric names, which the import system cannot spell)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(spec: Spec, kind: str):
    return load_module(spec.bench_dir / "drivers" / f"{check_name(kind, 'driver')}.py",
                       f"driver_{kind}")


def reader(spec: Spec, metric: str):
    return load_module(spec.bench_dir / "metrics" / f"{metric}.py",
                       f"metric_{metric}")
