"""The chip's peaks and the roofline.  The work a served step requires
is counted by each configuration's family (``families/<family>.py``,
``prefill_work`` and ``decode_work``) from its shapes and the true
lengths of the real rows."""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, HBM at
# 819 GB/s.  Keyed by JAX's ``device_kind``.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown device is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def least_seconds(flops: int, nbytes: int, peak: Dict[str, float]) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])
