"""Median of the benchmark's span around the engine's decode program
call, through its logits being ready (the engine fetches them next)."""

from chipbench.runtime import percentile


def read(w):
    p = percentile(w.span_durations("bench.decode_call"), 50)
    return None if p is None else 1e3 * p
