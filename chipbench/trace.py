"""Profiler capture of part of the window, and its reduction to numbers.

The reduction reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but ``jax.profiler.ProfileData``:

* device ops: the events of each device plane's ``XLA Ops`` line, named
  by their HLO instruction (``%fusion.39 fusion``); ops nest (a ``while``
  holds its body's ops), and only leaves are classed and ranked;
* busy time: the union of all those intervals inside the traced window,
  per chip, averaged over the chips;
* op classes: collectives (all-reduce, all-gather, collective-permute,
  reduce-scatter, all-to-all, send/recv) against everything else, by the
  instruction's own name and opcode, never its operands;
* idle gaps: the stretches with no device op, each attributed to the
  innermost host span (``bench.*`` ``TraceAnnotation``) open at its middle.

The traced window is the host span ``bench.trace_window``, which the
capture opens right after the profiler starts and closes right before it
stops, so profiler start-up and flush fall outside it.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.trace_window"
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all"
    r"|\bsend\b|\brecv\b|send-done|recv-done", re.IGNORECASE)
TPU_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE_RE = re.compile(r"^XLA Ops$")

Interval = Tuple[float, float]           # (start_s, end_s)


@dataclasses.dataclass
class Event:
    name: str
    start: float                          # seconds, trace clock
    end: float


@dataclasses.dataclass
class Recorded:
    """What a trace holds, before any reduction."""
    device_ops: Dict[str, List[Event]]    # chip -> ops
    host_spans: List[Event]               # bench.* annotations


OPCODE_RE = re.compile(r"\s([a-z][\w\-]*)\(")


def op_label(text: str) -> str:
    """``%name opcode`` of an HLO instruction as the trace spells it
    (``%fusion.39 = f32[...]{...} fusion(...)``); other text unchanged."""
    lhs, eq, rhs = text.partition(" = ")
    if not eq:
        return text
    m = OPCODE_RE.search(" " + rhs)
    return f"{lhs} {m.group(1)}" if m else lhs


def is_collective(label: str) -> bool:
    return bool(COLLECTIVE_RE.search(label))


def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that hold no other event: sorted by start (longest first),
    a parent is followed by a child that starts and ends inside it."""
    evs = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for e, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt.start >= e.end or nxt.end > e.end]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the union of ``a`` that no interval of ``b`` covers."""
    out, b = [], union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur or bs >= e:
                continue
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def read_xplane(path: str, *, device_plane=TPU_PLANE_RE,
                op_line=OPS_LINE_RE, host_prefix: str = "bench.") -> Recorded:
    """Device ops per chip and the benchmark's host spans from one trace.

    ``device_plane`` and ``op_line`` select what counts as the device; the
    defaults read a TPU.  A test on the CPU points them at the CPU
    client's threads instead."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in pd.planes:
        m = device_plane.match(plane.name)
        for line in plane.lines:
            if m is not None and op_line.match(line.name):
                lst = ops.setdefault(m.group(1), [])
                for ev in line.events:
                    if ev.duration_ns > 0:
                        lst.append(Event(op_label(ev.name), ev.start_ns * 1e-9,
                                         (ev.start_ns + ev.duration_ns) * 1e-9))
            if plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        spans.append(Event(ev.name, ev.start_ns * 1e-9,
                                           (ev.start_ns + ev.duration_ns)
                                           * 1e-9))
    return Recorded(ops, spans)


@dataclasses.dataclass
class Summary:
    """The reduced trace: what metric readers and the breakdown use."""
    window_s: float
    chips: int
    busy_s: float                          # mean over chips
    compute_s: float                       # union of non-collective ops
    collective_s: float                    # union of collective ops
    exposed_collective_s: float            # collective with no compute
    device_ops: List[Tuple[str, float]]    # leaf ops by seconds, per chip
    idle_gaps: List[Tuple[str, float]]     # by host span, per chip


def reduce(rec: Recorded) -> Optional[Summary]:
    """None where the trace holds no window or no device op."""
    win = [s for s in rec.host_spans if s.name == WINDOW_SPAN]
    if not win or not rec.device_ops:
        return None
    lo, hi = win[0].start, win[0].end
    spans = [s for s in rec.host_spans if s.name != WINDOW_SPAN]
    busy = comp = coll = exposed = 0.0
    by_op: Dict[str, float] = collections.Counter()
    by_gap: Dict[str, float] = collections.Counter()
    for chip, evs in rec.device_ops.items():
        leaf = leaves(evs)
        all_iv = clip(union([(e.start, e.end) for e in evs]), lo, hi)
        c_iv = clip(union([(e.start, e.end) for e in leaf
                           if not is_collective(e.name)]), lo, hi)
        x_iv = clip(union([(e.start, e.end) for e in leaf
                           if is_collective(e.name)]), lo, hi)
        busy += length(all_iv)
        comp += length(c_iv)
        coll += length(x_iv)
        exposed += length(subtract(x_iv, c_iv))
        for e in leaf:
            d = min(e.end, hi) - max(e.start, lo)
            if d > 0:
                by_op[e.name] += d
        for gs, ge in gaps(all_iv, lo, hi):
            by_gap[_innermost(spans, (gs + ge) / 2)] += ge - gs
    n = len(rec.device_ops)
    top = lambda c: [(k, v / n) for k, v in  # noqa: E731
                     sorted(c.items(), key=lambda kv: -kv[1])[:10]]
    return Summary(window_s=hi - lo, chips=n, busy_s=busy / n,
                   compute_s=comp / n, collective_s=coll / n,
                   exposed_collective_s=exposed / n,
                   device_ops=top(by_op), idle_gaps=top(by_gap))


def _innermost(spans: Sequence[Event], t: float) -> str:
    best = None
    for s in spans:
        if s.start <= t <= s.end and (
                best is None or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best is not None else "no_host_span"


class Capture:
    """Traces ``length_s`` seconds of the window, starting ``after_s``
    seconds into it; ``poll`` is called from the window loop.  Stopping
    the profiler takes seconds, so a traced run's window ends when the
    trace stops (``done``); its start-up is counted in ``overhead_s``."""

    def __init__(self, enabled: bool, after_s: float, length_s: float):
        self.after_s, self.length_s = after_s, length_s
        self.state = "idle" if enabled else "off"
        self.dir: Optional[str] = None
        self._ann = None
        self.t_on = self.t_off = None
        self.overhead_s = 0.0      # window time spent starting the trace

    @property
    def active(self) -> bool:
        return self.state == "on"

    @property
    def done(self) -> bool:
        return self.state == "done"

    def end(self, t1: float) -> float:
        """The window's end: ``t1``, or the moment the trace stopped."""
        return self.t_off if self.done else t1

    def poll(self, t_window0: float, now: float) -> None:
        import jax

        if self.state == "idle" and now - t_window0 >= self.after_s:
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            self._ann.__enter__()
            self.state, self.t_on = "on", time.perf_counter()
            self.overhead_s += self.t_on - now
        elif self.state == "on" and now - self.t_on >= self.length_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state != "on":
            return
        self._ann.__exit__(None, None, None)
        self.t_off = time.perf_counter()
        jax.profiler.stop_trace()
        self.state = "done"

    def summary(self, **read_kw) -> Optional[Summary]:
        """Reduce the trace and delete it."""
        if self.state == "on":
            self.stop()
        if self.dir is None:
            return None
        try:
            files = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb")))
            if not files:
                return None
            return reduce(read_xplane(files[-1], **read_kw))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
