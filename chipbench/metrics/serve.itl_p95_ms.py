"""95th percentile of the gap between consecutive tokens of one request,
over every gap whose later token came in the window (host clock,
timestamped after each engine step)."""

from chipbench.runtime import percentile
from chipbench.serving import itl_ms


def read(w):
    return percentile(itl_ms(w), 95)
