"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not in the table is an error, not a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture): one
chip gives 197 TFLOP/s in bfloat16 (393 TOP/s in int8; 16 GiB of HBM2e at 819
GB/s: not in the table until a metric reads them).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]  # the same chip under its other name


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them to "
            "benchmark/peaks.py with their source"
        ) from None
