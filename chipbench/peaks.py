"""Published per-chip peaks, keyed by the exact ``device_kind`` string.

The benchmark's own copy (the program's is `tpu_dist/train/flops.py`
`CHIPS`, which a later PR may change).  A device that is not in the table
is an error, never a default.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        197e12, 819e9, 16e9,
        'Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "16 GB HBM2e at 819 GB/s per chip",
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            f"chipbench/peaks.py with its source (known: {sorted(PEAKS)})"
        ) from None
