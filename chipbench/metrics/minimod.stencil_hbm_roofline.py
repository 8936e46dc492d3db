"""The stencil step's share of its HBM roofline: the least bytes the
traced steps need (16 B per cell and step, ``chipbench/flops.py``) at the
chip's HBM peak, over the device time of the step's non-collective ops in
the traced window."""

from chipbench.flops import stencil_bytes_per_cell


def read(w):
    t, c = w.trace, w.counters
    if t is None or not c.get("traced_steps") or t.compute_s <= 0 \
            or not w.peaks:
        return None
    need = stencil_bytes_per_cell() * c["cells_per_chip"] * c["traced_steps"]
    return 100.0 * need / w.peaks["hbm_bytes_per_s"] / t.compute_s
