"""Published peaks of the chips the benchmark knows, keyed by the
`device_kind` JAX reports. A device that is not here is an error, never a
default."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB HBM2e at
    # 819 GB/s. The compute peak is the MXU's; a kernel on the VPU cannot
    # reach it, so a share of it says more by moving than by its level.
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to perfbench/harness/peaks.py with its source")
