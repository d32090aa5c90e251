"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s bf16,
    # 393 TOP/s int8, 16 GB HBM at 819 GB/s.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud, TPU v5e"},
}


class UnknownDevice(Exception):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in the peak table "
            f"({sorted(PEAKS)})") from None
