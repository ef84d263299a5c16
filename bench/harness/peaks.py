"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``. A device that is not here is an error, not a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip, 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s.
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; ValueError when unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device_kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
