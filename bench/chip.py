"""The benchmark runs on a TPU only: the device check, and the record of
the device a run used."""
from __future__ import annotations

import sys


class NoChip(SystemExit):
    """Raised (exit code 3, no result printed) when the chips a cell needs
    are not there."""

    def __init__(self, msg: str):
        print(f"bench: {msg}", file=sys.stderr)
        super().__init__(3)


def require(chips: int):
    """The first device, if JAX sees ``chips`` TPUs of a kind the peaks
    table knows; else exits."""
    import jax

    from bench import peaks

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"needs {chips} chips, found {len(devs)}")
    try:
        peaks.for_kind(devs[0].device_kind)
    except peaks.UnknownDevice as e:
        raise NoChip(str(e.args[0]))
    return devs[0]


def record(chips: int) -> dict:
    """``device`` of the result line; ``memory_peak_bytes`` is the
    fullest chip's high-water mark."""
    import jax

    devs = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak)}
