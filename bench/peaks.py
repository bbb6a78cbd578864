"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (bf16 197 TFLOP/s, int8 393
TOP/s, 16 GB of HBM at 819 GB/s).  No int32 vector peak is published, so
the LMA location math (uint32 hashing) has no compute roofline here: the
embedding lookup is held to the HBM bound alone.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a kind not in the table is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
