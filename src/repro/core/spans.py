"""Host spans and counters of the program, on the profiler's clock.

A span is one named interval of host time, ``(name, t0, t1)`` in
``time.perf_counter`` seconds.  Each is kept twice: in a bounded ring on
the recorder, which code in the same process reads back by window, and as a
``jax.profiler.TraceAnnotation``, so that a running profiler trace holds
it on its host plane next to the device ops it dispatched.

    from repro.core.spans import recorder
    rec = recorder()
    with rec.span("engine.step"):
        ...
    rec.durations("engine.step", t0, t1)

Backend compiles are recorded as ``jax.compile`` spans (recorder only: the
event arrives after the compile), so a compile in the middle of serving
shows inside the step that caused it.  Recording is on by default;
``set_enabled(False)`` turns spans, counters and annotations off.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import time
import weakref
from typing import Dict, Iterable, List, Tuple

import jax

__all__ = ["Recorder", "recorder", "set_enabled", "COMPILE_SPAN"]

COMPILE_SPAN = "jax.compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

Span = Tuple[str, float, float]

_Annotation = jax.profiler.TraceAnnotation
_enabled = True
_recorders: "weakref.WeakSet[Recorder]" = weakref.WeakSet()


def set_enabled(flag: bool) -> None:
    global _enabled
    _enabled = bool(flag)


class _Open:
    __slots__ = ("rec", "name", "ann", "t0")

    def __init__(self, rec: "Recorder", name: str):
        self.rec, self.name = rec, name
        # an annotation only while a trace is being taken: it costs more
        # than the rest of the span
        self.ann = _Annotation(name) if _Annotation.is_enabled() else None

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.rec.items.append((self.name, self.t0, t1))
        return False


_OFF = contextlib.nullcontext()


class Recorder:
    """The last ``maxlen`` spans, in the order they closed, and cumulative
    counters."""

    def __init__(self, maxlen: int = 65_536):
        self.items: collections.deque = collections.deque(maxlen=maxlen)
        self._counts: Dict[str, int] = collections.Counter()
        _recorders.add(self)

    def span(self, name: str):
        return _Open(self, name) if _enabled else _OFF

    def count(self, name: str, n: int = 1) -> None:
        if _enabled:
            self._counts[name] += n

    def counters(self) -> Dict[str, int]:
        return dict(self._counts)

    def between(self, t0: float, t1: float) -> List[Span]:
        """Spans that end inside ``[t0, t1]``."""
        return [s for s in self.items if t0 <= s[2] <= t1]

    def durations(self, name: str, t0: float, t1: float) -> List[float]:
        return [b - a for n, a, b in self.between(t0, t1) if n == name]

    def exclusive(self, name: str, inner: Iterable[str], t0: float,
                  t1: float) -> List[float]:
        """Durations of the ``name`` spans that end inside ``[t0, t1]``,
        each less the time of the ``inner`` spans that lie within it."""
        inner = set(inner)
        spans = self.between(t0, t1)
        nested = sorted((c, d) for m, c, d in spans if m in inner)
        starts = [c for c, _ in nested]
        out = []
        for n, a, b in spans:
            if n != name:
                continue
            i, own = bisect.bisect_left(starts, a), b - a
            while i < len(nested) and nested[i][0] <= b:
                c, d = nested[i]
                if d <= b:
                    own -= d - c
                i += 1
            out.append(own)
        return out


_default = Recorder()


def recorder() -> Recorder:
    """The process-wide recorder, the default of every instrumented
    component."""
    return _default


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _COMPILE_EVENT and _enabled:
        t1 = time.perf_counter()
        for rec in list(_recorders):
            rec.items.append((COMPILE_SPAN, t1 - secs, t1))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
