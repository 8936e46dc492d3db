"""Median of the engine's ``engine.decode.call`` span: the decode program's
dispatch through its logits being ready, timed inside the engine.

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
    p = percentile(recorder().durations("engine.decode.call", w.t0, w.t1),
                   50)
    return None if p is None else 1e3 * p
