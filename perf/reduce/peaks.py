"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page —
197 TFLOP/s dense bf16, 16 GB of HBM at 819 GB/s per chip. Copied from the
program's ``utils/flops.py`` table (PERF.md section 7 lists the original
for deletion). A device that is not in the table is an error, not a
default.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peak(device_kind: str, what: str) -> float:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; add it to "
            "perf/reduce/peaks.py with its source")
    return PEAKS[device_kind][what]
