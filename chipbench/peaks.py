"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

A device that is not in the table is an error, never a default: a share of
a peak is only meaningful against the chip that ran.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,      # bf16 MXU peak
        "bytes_per_s": 819e9,       # HBM bandwidth
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e' (per chip: "
                  "197 TFLOP/s bf16, 16 GB HBM at 819 GB/s)",
    },
}


def peak_for(device_kind: str) -> dict:
    """The peaks of one chip; raises ``KeyError`` for a kind not listed."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f"; known: {sorted(PEAKS)}") from None
