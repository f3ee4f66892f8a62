"""Published peaks of the chips the benchmark runs on, by the
``device_kind`` jax reports.  A device that is not here is an error, not
a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" system architecture: 16 GB of HBM2e
# at 819 GB/s a chip (197 TFLOP/s bf16, 393 TOP/s int8).
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}


def hbm_bytes_per_s(device_kind: str) -> float:
    if device_kind not in HBM_BYTES_PER_S:
        raise KeyError(f"no published HBM bandwidth for device kind {device_kind!r}")
    return HBM_BYTES_PER_S[device_kind]
