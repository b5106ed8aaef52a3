"""Published peaks of the chips the benchmark knows, keyed by
``device_kind`` as JAX reports it. No metric reads it yet: roofline
shares need per-chunk operations and bytes from counters the program
does not have (``PERF.md``, Open questions). It is here so that the PR
which adds them adds data only. A device that is not in the table is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
