"""Published peaks of one chip, keyed by the exact ``device_kind`` JAX
reports.  A device that is not here is an error, not a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e
    # at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "ici_bits_per_s": 1600e9, "hbm_bytes": 16e9},
}


def peaks_of(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
