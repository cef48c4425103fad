"""The look for the chip, and the `device` block of the result line."""

from __future__ import annotations

import sys

from benchmark import peaks


class NoChip(SystemExit):
    pass


def require_chips(chips: int) -> list:
    """The accelerators this run may use; exits nonzero (no result line)
    on any other backend, an unknown chip, or too few chips."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"benchmark: backend is {platform!r}, not a TPU; the benchmark "
              f"measures only on the chip (no CPU fallback)", file=sys.stderr)
        raise NoChip(2)
    peaks.peaks_for(devices[0].device_kind)
    if len(devices) < chips:
        print(f"benchmark: cell needs {chips} chip(s), JAX sees "
              f"{len(devices)}", file=sys.stderr)
        raise NoChip(2)
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the allocator reports it."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak

