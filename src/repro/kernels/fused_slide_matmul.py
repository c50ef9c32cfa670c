"""Pallas TPU kernel: single-pass SlideSparse GEMM (quant + lift + matmul).

The paper's §4.2 memory-op argument says Activation Lifting is near-zero cost
*only* when Psi rides on the quantization store phase.  The two-kernel
pipeline (fused_quant_slide -> quant_matmul) still pays one HBM round-trip of
the lifted gamma*K activations (1.5x at 6:8).  This kernel removes it: the
per-token quantization + lifting run in the GEMM *prologue*, the lifted
int8/e4m3 rows live only in VMEM scratch, and the MXU consumes them directly
against Phi(W).  The precision axis is recipe-driven (DESIGN.md §10): the
prologue quantizer is int8 or fp8-e4m3 and 'w4' weights arrive nibble-packed
and are sign-extended in-kernel.  HBM traffic per call (DESIGN.md §2):

    two-kernel:  read X (4K) + write Psi(q) (gamma*K) + read Psi(q) (gamma*K)
                 + read Phi(W) + write Y
    single-pass: read X (4K) + read Phi(W) + write Y

Grid is (R/br, M/bm) with M innermost; the quant+lift prologue fires only at
m == 0, so each activation row-block is quantized exactly once per call and
reused from scratch for every output tile.  The dequant epilogue optionally
fuses a bias add and SiLU/GELU so MLP gate projections need no separate
elementwise pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.patterns import SlideDecomposition
from repro.core.packer import unpack_nibbles

from .fused_quant_slide import lift_pairs, quantize_rows

_QMAX = 127.0

ACTIVATIONS = {
    None: lambda v: v,
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
}


def apply_activation(v: jax.Array, activation: str | None) -> jax.Array:
    """Shared epilogue nonlinearity (kernels and jnp oracles use this one)."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"unsupported epilogue activation {activation!r};"
                         f" expected one of {sorted(ACTIVATIONS, key=str)}")
    return ACTIVATIONS[activation](v)


def prepare_bias(bias, m: int):
    """Shared bias-operand prep for the GEMM kernels: (has_bias, [1, m]
    fp32).  A zeros row stands in when there is no bias — the kernels
    specialize on the static has_bias flag and skip the add."""
    has_bias = bias is not None
    b = (bias if has_bias else jnp.zeros((m,), jnp.float32))
    return has_bias, b.astype(jnp.float32).reshape(1, m)


def clamp_rows(br: int, rows: int) -> int:
    """Don't over-tile tiny row counts: cap br at the next power of two.
    Shares autotune.rows_bucket so the cache keys and the clamp agree."""
    from . import autotune
    return min(br, autotune.rows_bucket(rows))


def _kernel(x_ref, w_ref, sw_ref, b_ref, o_ref, q_scr, sx_scr, *,
            n_fam: int, has_bias: bool, activation: str | None,
            fp8: bool, w4: bool):
    # Prologue (Alg. 1 fused into the GEMM): quantize + lift the row block
    # once per r, at the first m step; every later m step reuses the scratch.
    # The quantizer is recipe-selected (int8 round-to-nearest or e4m3
    # clamp-before-cast) and bit-identical to the quant.py oracles.
    @pl.when(pl.program_id(1) == 0)
    def _quant_lift():
        q8, scale = quantize_rows(x_ref[...].astype(jnp.float32), fp8)
        q_scr[...] = lift_pairs(q8, n_fam)
        sx_scr[...] = scale

    q, w = q_scr[...], w_ref[...]
    if w4:
        # 'w4' storage: two int4 nibbles per byte, sign-extended to int8 in
        # the prologue — half the weight HBM bytes of the int8 recipe
        w = unpack_nibbles(w)
    if fp8:
        # any e4m3 operand: lossless fp32 casts, fp32 accumulate — kernel
        # and jnp oracle run the identical dot
        q, w = q.astype(jnp.float32), w.astype(jnp.float32)
    acc = jax.lax.dot_general(
        q, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32 if fp8 else jnp.int32)
    out = acc.astype(jnp.float32) * sx_scr[...] * sw_ref[...].reshape(1, -1)
    if has_bias:
        out = out + b_ref[...]
    o_ref[...] = apply_activation(out, activation).astype(o_ref.dtype)


def default_tiles(m: int, k: int, gk: int,
                  vmem_budget: int = 12 * 1024 * 1024,
                  fp8: bool = False, w4: bool = False) -> tuple[int, int]:
    """(br, bm) heuristic: largest power-of-two tiles whose fp32 input,
    lifted scratch, weight tile and accumulator fit the budget.

    The footprint is recipe-aware (DESIGN.md §13): e4m3 operands are
    upcast to fp32 working copies for the MXU dot (both the lifted
    scratch and the weight tile — 4 extra bytes per element each), and
    'w4' weights unpack from nibbles to an int8 tile in the prologue.
    The earlier model ignored the fp8 upcast, so large-K fp8 shapes
    selected tiles whose real VMEM footprint overflowed the budget and
    collapsed the grid on hardware."""
    bm = 256 if m >= 256 else max(8, 1 << max(0, (m - 1)).bit_length())
    br = 256

    def need(br_, bm_):
        q_scr = br_ * gk * (5 if fp8 else 1)   # stored + fp32 upcast
        w_tile = bm_ * (gk // 2 if w4 else gk)  # nibble-packed at half width
        w_work = bm_ * gk * (4 if fp8 else (1 if w4 else 0))  # upcast/unpack
        return (br_ * k * 4 + q_scr + w_tile + w_work
                + br_ * bm_ * 4 + br_ * 8)
    while need(br, bm) > vmem_budget and br > 8:
        br //= 2
    while need(br, bm) > vmem_budget and bm > 8:
        bm //= 2  # huge gamma*K: the weight tile itself must shrink too
    return br, bm


@functools.partial(jax.jit, static_argnames=(
    "n_fam", "out_dtype", "interpret", "br", "bm", "activation", "act",
    "w4"))
def fused_slided_matmul_pallas(x, w_slided_q, s_w, bias=None, *, n_fam: int,
                               out_dtype=jnp.float32, interpret: bool = False,
                               br: int | None = None, bm: int | None = None,
                               activation: str | None = None,
                               act: str = "int8", w4: bool = False):
    """y[R, M] = act((Psi(q(x)) @ Phi(W)^T) * s_x * s_w + bias) — one kernel.

    x: [R, K] float; w_slided_q: [M, gamma*K] int8, or [M, gamma*K/2]
    nibble-packed bytes when ``w4``; s_w: [M, 1] fp32; bias: [M] fp32 or
    None.  ``act`` ('int8' | 'fp8') picks the prologue quantizer; the
    lifted activations never leave VMEM in either precision.
    """
    if act not in ("int8", "fp8"):
        raise ValueError(f"unsupported activation precision {act!r}")
    fp8 = act == "fp8"
    rows, k = x.shape
    if k % (2 * n_fam):
        raise ValueError(f"K={k} must be a multiple of 2N={2 * n_fam}")
    gk = (k // (2 * n_fam)) * (n_fam - 1) * 4
    gkw = gk // 2 if w4 else gk  # stored weight width (bytes when packed)
    m = w_slided_q.shape[0]
    if w_slided_q.shape[1] != gkw:
        raise ValueError(
            f"w_slided_q has contraction {w_slided_q.shape[1]}, expected"
            f" {'packed ' if w4 else ''}gamma*K = {gkw} for K={k}, N={n_fam}")
    dbr, dbm = default_tiles(m, k, gk, fp8=fp8, w4=w4)
    br, bm = br or dbr, bm or dbm
    br = clamp_rows(br, rows)

    pad_r, pad_m = (-rows) % br, (-m) % bm
    has_bias, b = prepare_bias(bias, m)
    if pad_r:
        x = jnp.pad(x, ((0, pad_r), (0, 0)))
    if pad_m:
        w_slided_q = jnp.pad(w_slided_q, ((0, pad_m), (0, 0)))
        s_w = jnp.pad(s_w, ((0, pad_m), (0, 0)), constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_m)))
    rp, mp = x.shape[0], w_slided_q.shape[0]

    grid = (rp // br, mp // bm)
    y = pl.pallas_call(
        functools.partial(_kernel, n_fam=n_fam, has_bias=has_bias,
                          activation=activation, fp8=fp8, w4=w4),
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, k), lambda r, m_: (r, 0)),
            pl.BlockSpec((bm, gkw), lambda r, m_: (m_, 0)),
            pl.BlockSpec((bm, 1), lambda r, m_: (m_, 0)),
            pl.BlockSpec((1, bm), lambda r, m_: (0, m_)),
        ],
        out_specs=pl.BlockSpec((br, bm), lambda r, m_: (r, m_)),
        out_shape=jax.ShapeDtypeStruct((rp, mp), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((br, gk),
                       jnp.float8_e4m3fn if fp8 else jnp.int8),
            pltpu.VMEM((br, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x, w_slided_q, s_w, b)
    return y[:rows, :m]


def fused_slided_matmul(x: jax.Array, w_slided_q: jax.Array, s_w: jax.Array,
                        dec: SlideDecomposition, bias=None,
                        out_dtype=jnp.float32, interpret: bool = False,
                        activation: str | None = None, recipe=None, **tiles):
    """Recipe-polymorphic wrapper: ``recipe`` (PrecisionRecipe or registry
    name; default 'int8') selects the prologue quantizer and whether the
    slided weight operand is nibble-packed."""
    n = dec.source.family_n
    if n is None or dec.hw.m != 2 or dec.hw.n != 4:
        raise ValueError("Pallas kernel supports the (2N-2):2N -> 2:4 family")
    from repro.core import precision  # deferred: core imports first

    rec = precision.resolve(recipe if recipe is not None else "int8")
    if not rec.quantized:
        raise ValueError(f"recipe {rec.name!r} has no quantized GEMM form")
    return fused_slided_matmul_pallas(
        x, w_slided_q, s_w, bias, n_fam=n, out_dtype=out_dtype,
        interpret=interpret, activation=activation, act=rec.act,
        w4=rec.packed_weights, **tiles)
