"""Pallas TPU kernel: dense w8a8 GEMM with per-token dequant epilogue.

The dense-quantized baseline (cuBLASLt INT8 analogue) that SlideSparse is
compared against in the paper's tables; shares the fused bias+activation
epilogue (DESIGN.md §2.3) so baseline-vs-sparse comparisons stay apples
to apples.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_slide_matmul import apply_activation, clamp_rows, prepare_bias


def _kernel(x_ref, w_ref, sx_ref, sw_ref, b_ref, o_ref, acc_ref, *,
            k_steps: int, has_bias: bool, activation: str | None):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x, w = x_ref[...], w_ref[...]
    if jnp.float8_e4m3fn in (x.dtype, w.dtype):
        # fp8 operands: lossless fp32 casts (the accumulator scratch is
        # fp32 in that case — see the wrapper)
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=acc_ref.dtype)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _epilogue():
        acc = acc_ref[...].astype(jnp.float32)
        out = acc * sx_ref[...] * sw_ref[...].reshape(1, -1)
        if has_bias:
            out = out + b_ref[...]
        o_ref[...] = apply_activation(out, activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret", "bm",
                                             "br", "bk", "activation"))
def quant_matmul_pallas(q_x, q_w, s_x, s_w, bias=None, *,
                        out_dtype=jnp.float32,
                        interpret: bool = False, bm: int = 256,
                        br: int = 256, bk: int = 512,
                        activation: str | None = None):
    """y[R, M] = act((q_x[R, K] @ q_w[M, K]^T) * s_x * s_w + bias).

    Dtype-polymorphic (DESIGN.md §10): all-integer operands accumulate in
    int32 (bit-exact vs the jnp oracle); any fp8-e4m3 operand is cast
    losslessly to fp32 and accumulates in fp32 (identical up to the
    K-blocked summation order).
    """
    rows, k = q_x.shape
    m = q_w.shape[0]
    br = clamp_rows(br, rows)
    pad_r, pad_k, pad_m = (-rows) % br, (-k) % bk, (-m) % bm
    has_bias, b = prepare_bias(bias, m)
    if pad_r or pad_k:
        q_x = jnp.pad(q_x, ((0, pad_r), (0, pad_k)))
    if pad_r:
        s_x = jnp.pad(s_x, ((0, pad_r), (0, 0)), constant_values=1.0)
    if pad_m or pad_k:
        q_w = jnp.pad(q_w, ((0, pad_m), (0, pad_k)))
    if pad_m:
        s_w = jnp.pad(s_w, ((0, pad_m), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_m)))
    rp, kp, mp = q_x.shape[0], q_x.shape[1], q_w.shape[0]
    k_steps = kp // bk
    grid = (rp // br, mp // bm, k_steps)
    ints = (jnp.issubdtype(q_x.dtype, jnp.integer)
            and jnp.issubdtype(q_w.dtype, jnp.integer))
    acc_dtype = jnp.int32 if ints else jnp.float32
    y = pl.pallas_call(
        functools.partial(_kernel, k_steps=k_steps, has_bias=has_bias,
                          activation=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bk), lambda r, m_, k_: (r, k_)),
            pl.BlockSpec((bm, bk), lambda r, m_, k_: (m_, k_)),
            pl.BlockSpec((br, 1), lambda r, m_, k_: (r, 0)),
            pl.BlockSpec((bm, 1), lambda r, m_, k_: (m_, 0)),
            pl.BlockSpec((1, bm), lambda r, m_, k_: (0, m_)),
        ],
        out_specs=pl.BlockSpec((br, bm), lambda r, m_, k_: (r, m_)),
        out_shape=jax.ShapeDtypeStruct((rp, mp), out_dtype),
        scratch_shapes=[pltpu.VMEM((br, bm), acc_dtype)],
        interpret=interpret,
    )(q_x, q_w, s_x, s_w, b)
    return y[:rows, :m]
