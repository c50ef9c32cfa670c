"""Pure-jnp oracles for every Pallas kernel in this package.

These define the semantics; kernels must match them (tests sweep shapes and
dtypes with ``interpret=True`` and assert allclose).  They are also the
execution path on non-TPU backends and inside the multi-pod dry-run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import quant, slide, compressed as comp, packer, precision
from repro.core.patterns import SlideDecomposition


def epilogue(y: jax.Array, bias: jax.Array | None,
             activation: str | None) -> jax.Array:
    """Shared bias + nonlinearity semantics for every matmul oracle (fp32)."""
    from .fused_slide_matmul import apply_activation  # local: avoid cycle

    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return apply_activation(y, activation)


def fused_quant_slide(x: jax.Array, dec: SlideDecomposition,
                      fp8: bool = False,
                      absmax: jax.Array | None = None):
    """Paper Alg. 1: per-row dynamic quantization + activation lifting.

    x: [rows, K] -> (q_lifted int8|e4m3 [rows, gamma*K], scale fp32
    [rows, 1]).  Quantize-then-lift == lift-then-quantize (lifting only
    duplicates values, so the per-row absmax is unchanged).  ``absmax``
    optionally overrides the per-row absmax (tensor-parallel global
    quantization, DESIGN.md §10).
    """
    qx = (quant.quantize_fp8(x, absmax=absmax) if fp8
          else quant.quantize_int8(x, absmax=absmax))
    return slide.lift(qx.q, dec), qx.scale


def _quant_dot(q_x: jax.Array, q_w: jax.Array) -> jax.Array:
    """Shared accumulator rule: all-integer operands -> int32 dot; any fp8
    operand -> lossless fp32 casts + fp32 dot (DESIGN.md §10)."""
    ints = (jnp.issubdtype(q_x.dtype, jnp.integer)
            and jnp.issubdtype(q_w.dtype, jnp.integer))
    if ints:
        return jax.lax.dot_general(q_x, q_w, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.int32)
    return jax.lax.dot_general(
        q_x.astype(jnp.float32), q_w.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def quant_matmul(q_x: jax.Array, s_x: jax.Array, q_w: jax.Array,
                 s_w: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    """Quantized GEMM + dequant epilogue: (q_x @ q_w^T) * s_x * s_w.

    q_x: [rows, K] int8 or float8_e4m3fn; s_x: [rows, 1] fp32; q_w:
    [out, K] int8 (or e4m3); s_w: [out, 1] fp32.  Accumulator follows the
    operand dtypes (int32 for all-integer, else fp32).
    """
    acc = _quant_dot(q_x, q_w)
    return (acc.astype(jnp.float32) * s_x * s_w[:, 0][None, :]).astype(out_dtype)


def compressed_matmul_fp(x: jax.Array, c: comp.CompressedSlided,
                         out_dtype=None, bias: jax.Array | None = None,
                         activation: str | None = None) -> jax.Array:
    """Float path: decompress-to-original-layout weights, dense matmul.

    x: [rows, K]; returns [rows, out].  The TPU-adapted execution of
    DESIGN.md §2 — 1.0x dense FLOPs, compressed weight storage.  A stacked
    operand (``c.layer`` set) is indexed at its unit first.
    """
    out_dtype = out_dtype or x.dtype
    w_rec = comp.decompress_original(c.unstacked())  # [out, K]
    acc = jax.lax.dot_general(
        x.astype(jnp.float32), w_rec.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return epilogue(acc, bias, activation).astype(out_dtype)


def compressed_matmul_quant(x: jax.Array, c: comp.CompressedSlided,
                            s_w: jax.Array, recipe, out_dtype=None,
                            bias: jax.Array | None = None,
                            activation: str | None = None,
                            act_absmax: jax.Array | None = None
                            ) -> jax.Array:
    """Quantized path, recipe-polymorphic (DESIGN.md §10): per-token
    activation quantization (int8 or fp8-e4m3) + decompress-matmul over
    int8/int4 values + dequant epilogue.

    c.values hold rowwise-quantized weights (nibble-packed when
    ``c.packed``); s_w: [out, 1] fp32 row scales.  ``act_absmax``
    optionally overrides the per-token absmax (tensor-parallel global
    quantization).
    """
    rec = precision.resolve(recipe)
    out_dtype = out_dtype or x.dtype
    qx = rec.quantize_act(x, absmax=act_absmax)
    w_rec = comp.decompress_original(c.unstacked())  # int8-range [out, K]
    acc = _quant_dot(qx.q, w_rec)
    y = acc.astype(jnp.float32) * qx.scale * s_w[:, 0][None, :]
    return epilogue(y, bias, activation).astype(out_dtype)


def compressed_matmul_int8(x: jax.Array, c: comp.CompressedSlided,
                           s_w: jax.Array, out_dtype=None,
                           bias: jax.Array | None = None,
                           activation: str | None = None) -> jax.Array:
    """The int8 instance of :func:`compressed_matmul_quant` (w8a8)."""
    return compressed_matmul_quant(x, c, s_w, "int8", out_dtype,
                                   bias=bias, activation=activation)


def slided_matmul_quant(x: jax.Array, w_slided_q: jax.Array, s_w: jax.Array,
                        dec: SlideDecomposition, recipe, out_dtype=None,
                        bias: jax.Array | None = None,
                        activation: str | None = None,
                        act_absmax: jax.Array | None = None) -> jax.Array:
    """Paper-faithful GPU semantics end-to-end, recipe-polymorphic:

    y = (Psi(q_x) @ Phi(q_W)^T) * s_x * s_w   over the gamma*K contraction,
    with q_x int8 or fp8-e4m3 and Phi(q_W) int8 or nibble-packed int4.
    """
    rec = precision.resolve(recipe)
    out_dtype = out_dtype or x.dtype
    q_lift, s_x = fused_quant_slide(x, dec, fp8=rec.act == "fp8",
                                    absmax=act_absmax)
    if rec.packed_weights:
        w_slided_q = packer.unpack_nibbles(w_slided_q, q_lift.shape[-1])
    acc = _quant_dot(q_lift, w_slided_q)
    y = acc.astype(jnp.float32) * s_x * s_w[:, 0][None, :]
    return epilogue(y, bias, activation).astype(out_dtype)


def slided_matmul_int8(x: jax.Array, w_slided_q: jax.Array, s_w: jax.Array,
                       dec: SlideDecomposition, out_dtype=None,
                       bias: jax.Array | None = None,
                       activation: str | None = None) -> jax.Array:
    """The int8 instance of :func:`slided_matmul_quant`."""
    return slided_matmul_quant(x, w_slided_q, s_w, dec, "int8", out_dtype,
                               bias=bias, activation=activation)
