"""The one table of chip peaks the benchmark divides by.

Keyed by ``device_kind`` as JAX reports it.  A device that is not here
is an error: there is no default and no override.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM at 819 GB/s, per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks known for device kind {device_kind!r}; add it to "
            "benchmarks/lib/peaks.py with its source") from None
