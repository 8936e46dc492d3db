"""Published peaks of each accelerator, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM2 at 819 GB/s, and 1,600 Gbit/s
of inter-chip interconnect per chip.  Every share of a peak that the
benchmark reports reads this table; a device that is not in it is an error,
never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e)",
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}; add its published "
            f"numbers to chipbench/peaks.py") from None
