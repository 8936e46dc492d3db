"""Median of the engine's ``engine.decode.sample`` span: the logits brought
to the host, one token sampled and committed per decoding slot, finished
requests released, and the positions uploaded again.

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
    p = percentile(recorder().durations("engine.decode.sample", w.t0, w.t1),
                   50)
    return None if p is None else 1e3 * p
