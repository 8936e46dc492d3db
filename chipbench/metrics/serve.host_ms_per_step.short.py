"""Median host time of one engine step: each ``engine.step`` span less the
time inside its ``engine.decode.call`` and ``engine.prefill.wait`` spans,
where the host waits on the chip.

The spans are the program's own (``repro.core.spans``), read from its
process-wide recorder over the window: a departure from ``Window``'s "from
here and from nothing else", since a reader may not change ``Window``.  A
program without that recorder gives nothing to read."""

from chipbench.runtime import percentile


def read(w):
    try:
        from repro.core.spans import recorder
    except ImportError:
        return None
    p = percentile(recorder().exclusive(
        "engine.step", ("engine.decode.call", "engine.prefill.wait"),
        w.t0, w.t1), 50)
    return None if p is None else 1e3 * p
