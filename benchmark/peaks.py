"""Published peaks of the chips the benchmark may run on, by `device_kind`.

A device that is not in the table is an error, never a default: a number
taken on another device (the CPU included) must not be divided by a TPU's
peak.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} (add a row with its source, never a default)"
        ) from None
