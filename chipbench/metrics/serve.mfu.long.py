"""Model FLOPs of the prompt and output tokens computed in the window
(``chipbench/flops.py``: weights, attention over valid positions only, the
output head where logits are used) over the window and the chip's bf16
peak."""


def read(w):
    f = w.counters.get("model_flops")
    if not f or not w.peaks:
        return None
    return 100.0 * f / w.work_s / w.peaks["bf16_flops"]
