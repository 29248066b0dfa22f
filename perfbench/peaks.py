"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect per chip. A kind that is not listed is an error:
a share of an unknown peak is no number.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "ici_bytes_per_s": 1600e9 / 8,
        "source": "Google Cloud TPU documentation, 'TPU v5e'",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
