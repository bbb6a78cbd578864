"""Published chip-to-chip interconnect (ICI) bandwidth of one chip, keyed
by JAX's ``device_kind``, beside the peaks of ``bench.peaks``.

Source: Google Cloud documentation, "TPU v5e": 1,600 Gbps of interchip
interconnect bandwidth per chip.  The exchange's roofline share holds the
bytes a chip sends to this bound.
"""
from __future__ import annotations

ICI_BYTES_PER_S = {
    "TPU v5 lite": 1600e9 / 8,
    "TPU v5e": 1600e9 / 8,
}


def ici_bytes_per_s(device_kind: str) -> float:
    """The chip's ICI bandwidth in bytes/s; a kind not in the table is an
    error."""
    if device_kind not in ICI_BYTES_PER_S:
        raise KeyError(f"no published ICI bandwidth for device kind "
                       f"{device_kind!r}; known: {sorted(ICI_BYTES_PER_S)}")
    return ICI_BYTES_PER_S[device_kind]
