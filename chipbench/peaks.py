"""Published peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
819 GB/s of HBM bandwidth, 16 GB of HBM). jax 0.9.0 reports a v5e as
"TPU v5 lite".
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind: str, what: str) -> float:
    try:
        return PEAKS[device_kind][what]
    except KeyError:
        raise ValueError(
            f"no {what!r} peak recorded for device_kind {device_kind!r}; "
            f"add it to chipbench/peaks.py with its source "
            f"(known: {sorted(PEAKS)})") from None
