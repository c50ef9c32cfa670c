"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).  A device that is not in
the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # dense bf16 matmul, operations per second
    int8_ops: float       # int8 matmul, operations per second
    hbm_bytes_s: float    # HBM bandwidth, bytes per second
    source: str

    def flops(self, int8: bool) -> float:
        return self.int8_ops if int8 else self.bf16_flops


_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_s=819e9,
             source='Google Cloud documentation, "TPU v5e"')

TABLE: dict[str, Peaks] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


class UnknownDevice(KeyError):
    pass


def for_kind(kind: str) -> Peaks:
    """Peaks of ``kind``; raises :class:`UnknownDevice` for any other."""
    try:
        return TABLE[kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {kind!r}; known: "
            f"{sorted(TABLE)}") from None
