"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  Source: Google Cloud documentation,
"TPU v5e" (system architecture): per chip 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip links.
No integer peak of the vector unit is published."""
from __future__ import annotations

_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks(device_kind: str) -> dict:
    """The chip's peaks; an unknown chip is an error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}")
    return PEAKS[device_kind]
