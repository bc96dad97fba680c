"""Published peaks of one chip, keyed by a substring of ``device_kind``.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture
page (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s per chip.  A device that is not in the table is an
error, never a default: add it here with its source.
"""

from __future__ import annotations

PEAKS = {
    # device_kind as jax reports it on a v5e is "TPU v5 lite"
    "v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    kind = device_kind.lower()
    for name, peaks in PEAKS.items():
        if name in kind:
            return dict(peaks, table_key=name)
    raise KeyError(
        f"device_kind {device_kind!r} is not in benchmark/lib/peaks.py; "
        "add its published peaks there, with their source"
    )
