"""What every driver shares: the device check, the compile cache, the
compile clock, and the record of one window that metric readers read."""

from __future__ import annotations

import dataclasses
import os
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench.trace import Summary


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at ``<checkout>/.jax_cache``, a fixed path
    (the path is part of the cache's key), for every program however
    quickly it compiles, so a second run of a cell compiles nothing.  Set
    before the first compile, and over any directory from the environment:
    the cache stays inside the checkout."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def device_check(chips: int, platform: str = "tpu"):
    """The first ``chips`` devices; raises where JAX finds no TPU or too
    few.  Never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"no TPU: jax.devices()[0].platform is "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class CompileClock:
    """Backend-compile seconds, compiles and persistent-cache hits, from
    JAX's own monitoring events (as ``chip_smoke.py`` counts them)."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``, with its limit."""
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class RequestRecord:
    """One served request, on the host's clock (perf_counter seconds)."""
    index: int
    due: float
    submit: float = 0.0
    admit: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Window:
    """Everything one run measured; metric readers take their numbers
    from here and from nothing else."""
    setup_s: float
    t0: float                              # window start, perf_counter
    t1: float                              # window end
    compiles_in_window: int = 0
    overhead_s: float = 0.0                # profiler start-up in the window
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    requests: List[RequestRecord] = dataclasses.field(default_factory=list)
    spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)
    trace: Optional[Summary] = None
    checks: Dict[str, Check] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    peaks: dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def work_s(self) -> float:
        """The window less what starting the profiler took; the same as
        ``window_s`` in a run without a trace."""
        return self.window_s - self.overhead_s

    def span_durations(self, name: str) -> List[float]:
        return [b - a for n, a, b in self.spans
                if n == name and a >= self.t0 and b <= self.t1]


class Spans:
    """Host spans of the benchmark's own calls into the program: kept in
    memory on the host clock, and written into the profiler's trace as
    ``TraceAnnotation``s, so that device gaps can be attributed."""

    def __init__(self):
        self.items: List[Tuple[str, float, float]] = []

    def span(self, name: str):
        return _Span(self, name)


class _Span:
    def __init__(self, owner: Spans, name: str):
        import jax

        self.owner, self.name = owner, name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self.t = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.owner.items.append((self.name, self.t, time.perf_counter()))
        self._ann.__exit__(*exc)
        return False


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile by linear interpolation (numpy's default)."""
    import numpy as np

    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))
