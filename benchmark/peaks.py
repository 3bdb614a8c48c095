"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per
chip.  Copied from ``bench.py::_PEAK_FLOPS`` / ``_PEAK_HBM_BPS`` (the
original is listed in PERF.md's Open questions for a later PR to delete).
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.py "
            f"(known: {sorted(PEAKS)}); add its published peaks with "
            f"their source before measuring on it"
        ) from None
