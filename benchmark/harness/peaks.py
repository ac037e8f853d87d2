"""Published peaks per chip, keyed by ``jax.Device.device_kind``. A kind that
is not here is an error, never a default."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,  # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a row "
            "with its source to benchmark/harness/peaks.py"
        ) from None
