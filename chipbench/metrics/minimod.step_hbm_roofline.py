"""The whole step's share of the chip's HBM peak: the window's rate of
grid-point updates per chip times the least bytes per update (16 B), over
the peak.  It bounds any gain once the kernel that runs has changed."""

from chipbench.flops import stencil_bytes_per_cell


def read(w):
    c = w.counters
    if not c.get("steps") or not w.peaks:
        return None
    rate = c["steps"] * c["cells_per_chip"] / w.work_s
    return 100.0 * rate * stencil_bytes_per_cell() \
        / w.peaks["hbm_bytes_per_s"]
