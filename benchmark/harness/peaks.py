"""Published peaks of the chips the benchmark knows, keyed by JAX's
``device_kind``.  A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, per chip.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peak(197e12, 819e9, 16e9,
                        "cloud.google.com/tpu/docs/v5e (per chip)"),
    "TPU v5e": Peak(197e12, 819e9, 16e9,
                    "cloud.google.com/tpu/docs/v5e (per chip)"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to "
            f"benchmark/harness/peaks.py with its source") from None
