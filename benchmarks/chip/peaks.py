"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e (``device_kind`` "TPU v5 lite"): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB of HBM at 819 GB/s (Google Cloud documentation, "TPU v5e").  A
device that is not here has no roofline: :func:`peaks` raises rather than
assume one.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}") from None
