"""Per-token dynamic quantization (paper §3.3/§4.2 "synergy with quantization").

LLM inference already pays a per-token quantization pass (INT8/FP8); the fused
kernel piggybacks activation lifting on its store phase.  These are the pure
jnp semantics shared by the models, the kernels' oracles, and tests.  The
precision axis itself (which quantizer a GEMM uses, how weights are stored)
lives in ``repro.core.precision``; this module only provides the arithmetic.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

INT8_QMAX = 127.0
INT4_QMAX = 7.0    # symmetric int4: [-7, 7] (-8 unused, keeps dequant odd)
FP8_E4M3_MAX = 448.0


class Quantized(NamedTuple):
    q: jax.Array       # int8 (int8/int4 range) or float8_e4m3fn
    scale: jax.Array   # [..., 1] per-token (per-row) scale, fp32


def absmax(x: jax.Array) -> jax.Array:
    """Per-row absmax, clamped away from zero (Alg. 1 line 6).

    Public: tensor-parallel row-parallel projections pmax this over shards
    so quantization under sharding matches the unsharded semantics
    (``sharding.tp.reduce_max``, DESIGN.md §10).
    """
    a = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return jnp.maximum(a, 1e-8)


_absmax = absmax  # historical private alias


def quantize_int8(x: jax.Array,
                  absmax: jax.Array | None = None) -> Quantized:
    """Pass 1/2 of Alg. 1: per-row absmax scale, clamp, round-to-nearest.

    Uses the paper's reciprocal form (Alg. 1 line 7: r <- Qmax/a) so the
    Pallas kernel and this oracle share bit-identical arithmetic.
    ``absmax`` optionally overrides the locally computed per-row absmax.
    """
    a = _absmax(x) if absmax is None else absmax
    r = INT8_QMAX / a
    q = jnp.clip(jnp.round(x.astype(jnp.float32) * r), -INT8_QMAX, INT8_QMAX)
    # times the reciprocal: under jit XLA turns a division by a constant
    # into exactly this, so eager and jitted packing agree bit for bit
    return Quantized(q.astype(jnp.int8), a * (1.0 / INT8_QMAX))


def quantize_fp8(x: jax.Array,
                 absmax: jax.Array | None = None) -> Quantized:
    a = _absmax(x) if absmax is None else absmax
    scale = a / FP8_E4M3_MAX
    # clamp before the cast: e4m3 has no inf and XLA's float32->e4m3 cast
    # only saturates near the boundary (far-overflow becomes NaN); the
    # scale bounds |x|/scale at qmax up to 1 ulp, but keep the cast total
    q = jnp.clip(x.astype(jnp.float32) / scale,
                 -FP8_E4M3_MAX, FP8_E4M3_MAX).astype(jnp.float8_e4m3fn)
    return Quantized(q, scale)


def dequantize(qx: Quantized, dtype=jnp.float32) -> jax.Array:
    return (qx.q.astype(jnp.float32) * qx.scale).astype(dtype)


def quantize_weight_int8_rowwise(w: jax.Array) -> Quantized:
    """Per-output-channel symmetric int8 weight quantization (w8a8).

    w: [out, K]; scale: [out, 1].  Zeros stay exactly zero, so quantization
    commutes with the Z:L sparsity pattern and with Phi (pure permutation).
    """
    return quantize_int8(w)


def quantize_weight_int4_rowwise(w: jax.Array) -> Quantized:
    """Per-output-channel symmetric int4 weight quantization (the 'w4' axis).

    w: [out, K] -> q int8 in [-7, 7] (UNPACKED; ``packer.pack_nibbles``
    bit-packs two values per byte after Phi/compression), scale [out, 1].
    Zeros stay exactly zero — same pattern/Phi commutation as int8.
    """
    a = _absmax(w)
    r = INT4_QMAX / a
    q = jnp.clip(jnp.round(w.astype(jnp.float32) * r), -INT4_QMAX, INT4_QMAX)
    return Quantized(q.astype(jnp.int8), a * (1.0 / INT4_QMAX))


def matmul_dequant(qx: Quantized, qw: Quantized,
                   out_dtype=jnp.float32) -> jax.Array:
    """y = (q_x @ q_w^T) * s_x * s_w — the dense quantized GEMM semantics.

    Accumulates in int32 when both operands are integer-typed, else casts
    both losslessly to fp32 (the fp8 path).  The dequant epilogue applies
    the scales in the SAME order as the Pallas kernels ((acc * s_x) * s_w),
    so this dense reference is bit-comparable to the sparse pipeline.
    """
    ints = (jnp.issubdtype(qx.q.dtype, jnp.integer)
            and jnp.issubdtype(qw.q.dtype, jnp.integer))
    cdt = jnp.int32 if ints else jnp.float32
    acc = jnp.einsum("...k,mk->...m", qx.q.astype(cdt), qw.q.astype(cdt))
    y = acc.astype(jnp.float32) * qx.scale * jnp.squeeze(qw.scale, -1)
    return y.astype(out_dtype)


def int8_matmul_dequant(qx: Quantized, qw: Quantized,
                        out_dtype=jnp.float32) -> jax.Array:
    """Legacy name for the int8 instance of :func:`matmul_dequant`."""
    return matmul_dequant(qx, qw, out_dtype)
