"""Published peaks per chip, keyed by JAX's ``device_kind``.

A device that is not in the table is an error, never a default: a
roofline share against the wrong peak is a wrong number.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,       # bf16 MXU peak
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e",
    },
}


class UnknownDevice(RuntimeError):
    """The chip's ``device_kind`` has no entry in `PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in the peaks table "
            f"({sorted(PEAKS)}); add its published peaks before measuring "
            f"on it") from None
