"""The work a model requires of one serving step, counted from its
configuration and the rows the step served — never from the program's
stored format, so a change of format cannot move it.

- Weights: the nonzeros at the recipe's value width (bf16 2 B, int8
  1 B, plus a float32 scale per output row for int8), and for a z:l
  pattern the least any z:l format can store of their positions,
  ceil(log2 C(l, z)) bits per group (5 per 8 at 6:8: C(8, 6) = 28 <
  2**5).  A dense model (``pattern`` ``null``) keeps every weight, with
  no positions.
- Other bytes: the activations into and out of every linear, and the
  K/V bytes each active lane's attention reads and writes.
- Operations: 2 x nonzeros x the rows the step served (active lanes,
  not ``max_batch``), plus attention over each lane's visible context.

Which linears and layers a step runs is the configuration's family's
(``bench/families/<family>.py``, its ``step_work``); the counts here
are shared by all.  The least time a step can take is the larger of its
operations over the peak rate (the int8 rate for the GEMMs of an int8
recipe) and its bytes over the peak bandwidth.
"""
from __future__ import annotations

import dataclasses
import math

from bench import cells
from bench.peaks import Peaks

VALUE_BYTES = {"none": 2, "int8": 1}
ACT_BYTES = 2              # bf16 activations
DENSE = (1, 1)             # a dense weight: every 1 of 1 kept


@dataclasses.dataclass
class Work:
    gemm_ops: float = 0.0
    gemm_bytes: float = 0.0
    attn_ops: float = 0.0
    attn_bytes: float = 0.0

    def __iadd__(self, o: "Work") -> "Work":
        self.gemm_ops += o.gemm_ops
        self.gemm_bytes += o.gemm_bytes
        self.attn_ops += o.attn_ops
        self.attn_bytes += o.attn_bytes
        return self

    def gemm_s(self, peaks: Peaks, int8: bool) -> float:
        """Least time of the GEMM work alone."""
        return max(self.gemm_ops / peaks.flops(int8),
                   self.gemm_bytes / peaks.hbm_bytes_s)

    def step_s(self, peaks: Peaks, int8: bool) -> float:
        """Least time of all of the work."""
        return max(self.gemm_ops / peaks.flops(int8)
                   + self.attn_ops / peaks.bf16_flops,
                   (self.gemm_bytes + self.attn_bytes) / peaks.hbm_bytes_s)


def pattern(c: dict) -> tuple[int, int]:
    """(kept, group) of the configuration's weights."""
    return tuple(c["sparsity"]["pattern"] or DENSE)


def position_bits(z: int, l: int) -> int:
    return math.ceil(math.log2(math.comb(l, z)))


def linears(c: dict) -> list[tuple[int, int, int]]:
    """(input width, output width, count per step-row pass) of every
    projection of a GQA attention and SwiGLU MLP layer."""
    d, f, hd = c["hidden_size"], c["intermediate_size"], c["head_dim"]
    qd, kvd = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    n = c["num_hidden_layers"]
    return [(d, qd, n), (d, kvd, n), (d, kvd, n), (qd, d, n),
            (d, f, n), (d, f, n), (f, d, n)]


def weight_bytes(c: dict, k_in: int, m_out: int) -> float:
    z, l = pattern(c)
    recipe = c["sparsity"]["recipe"]
    groups = m_out * k_in / l
    b = groups * z * VALUE_BYTES[recipe] + groups * position_bits(z, l) / 8
    if recipe == "int8":
        b += 4 * m_out
    return b


def _gemm(c: dict, k_in: int, m_out: int, rows: int) -> Work:
    z, l = pattern(c)
    x_bytes = 1 if c["sparsity"]["recipe"] == "int8" else ACT_BYTES
    return Work(gemm_ops=2.0 * m_out * k_in * z / l * rows,
                gemm_bytes=weight_bytes(c, k_in, m_out)
                + rows * (k_in * x_bytes + m_out * ACT_BYTES))


def _kv_bytes(c: dict) -> int:
    width = 2 if c["kv_cache_dtype"] == "bfloat16" else 1
    return 2 * c["num_key_value_heads"] * c["head_dim"] * width  # K and V


def _keys(before: int, r: int, window: float) -> int:
    """Keys attended by ``r`` causal rows after ``before`` positions."""
    if before + r <= window:
        return r * before + r * (r + 1) // 2
    return sum(min(before + i + 1, window) for i in range(r))


def _attention(c: dict, rows: list[tuple[int, int]]) -> Work:
    """``rows``: per served lane (new positions, keys before them).  Each
    lane reads the cached K/V its rows can see once and writes its new
    positions' K/V once."""
    window = c.get("sliding_window") or math.inf
    qd = c["num_attention_heads"] * c["head_dim"]
    keys = read = new = 0
    for r, before in rows:
        keys += _keys(before, r, window)
        read += min(before, window - 1)
        new += r
    n = c["num_hidden_layers"]
    return Work(attn_ops=n * 4.0 * qd * keys,
                attn_bytes=n * (_kv_bytes(c) * (read + new)
                                + 2 * ACT_BYTES * qd * new))


def step_work(c: dict, lanes: list[tuple[int, int]], head_rows: int) -> Work:
    """Work of one step serving ``lanes`` (per lane: new positions, and
    context already in the cache) whose logits are taken at
    ``head_rows`` positions."""
    return cells.family(c).step_work(c, lanes, head_rows)


def decode_work(c: dict, contexts: list[int]) -> Work:
    """A decode step: one new position per lane after ``contexts``."""
    return step_work(c, [(1, ctx) for ctx in contexts], len(contexts))


def prefill_work(c: dict, start: int, length: int) -> Work:
    """A prefill chunk of ``length`` prompt positions after ``start``;
    the program takes logits at the chunk's last position only."""
    return step_work(c, [(length, start)], 1)
