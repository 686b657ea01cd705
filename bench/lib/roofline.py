"""Peaks of the chips the benchmark runs on, and the least time the chip
could take for a count of operations and bytes.  The counts themselves
are the model family's (``bench/families/<family>.py``).

Peaks: Google Cloud documentation, "TPU v5e" (one chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A device kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def roofline_seconds(ops: float, nbytes: float, pk: dict) -> tuple[float,
                                                                    str]:
    """The least time the chip could take, and which bound sets it."""
    tc, tb = ops / pk["flops"], nbytes / pk["hbm_bytes_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")
