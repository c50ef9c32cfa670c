"""Public jit'd wrappers for the Pallas kernels, with jnp fallbacks.

Dispatch rule (DESIGN.md §6): Pallas lowers only on real TPU backends; the
multi-pod dry-run and CPU tests use the mathematically identical jnp paths
from ref.py.  ``use_pallas=None`` auto-selects; tests force
``use_pallas=True, interpret=True`` to execute kernel bodies on CPU.

Precision is recipe-driven (DESIGN.md §10): every quantized entry point
takes a :class:`repro.core.precision.PrecisionRecipe` (or registry name)
selecting the activation quantizer (int8 / fp8-e4m3), the weight storage
(int8 rowwise / nibble-packed int4 'w4') and the accumulator that follows
from them.  ``act_absmax`` lets tensor-parallel row-parallel projections
inject the pmax-global per-token absmax so sharded quantization matches the
unsharded semantics (DESIGN.md §9/§10).

Tile sizes flow through repro.kernels.autotune (DESIGN.md §2.4): every
wrapper consults the shape-keyed cache — keys include the act/weight dtypes
(``adt``/``wdt``) so an int8-tuned winner is never reused for fp8/w4
operands — and ``tune=True`` runs a one-shot search on the live operands
before caching the winner.  ``bias`` / ``activation`` select the fused
epilogue (DESIGN.md §2.3) on kernels that support it; the jnp fallbacks
apply the identical ref.epilogue semantics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import precision
from repro.core.compressed import CompressedSlided
from repro.core.patterns import SlideDecomposition

from . import ref
from . import autotune
from . import fused_quant_slide as _fqs
from . import fused_slide_matmul as _fsm
from . import slide_matmul as _smm
from . import quant_matmul as _qmm


def _auto(use_pallas: bool | None) -> bool:
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return use_pallas


def _flatten_rows(x: jax.Array):
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _flatten_absmax(act_absmax):
    """[..., 1] per-token absmax -> [rows, 1] aligned with _flatten_rows."""
    if act_absmax is None:
        return None
    return act_absmax.reshape(-1, 1)


def fused_quant_slide(x: jax.Array, dec: SlideDecomposition,
                      use_pallas: bool | None = None,
                      interpret: bool = False, tune: bool = False,
                      recipe=None):
    """Per-token quantization + SlideSparse lifting Psi (paper Alg. 1).

    x: [..., K] float -> (q [..., gamma*K] int8|e4m3, scale [..., 1] fp32)
    where gamma = wN/L is the (2N-2):2N family's lift expansion — each
    K/L source group becomes w windows of N slots.  ``recipe`` selects the
    quantizer (default: the int8 recipe).
    """
    rec = precision.resolve(recipe if recipe is not None else "int8")
    if not rec.quantized:
        raise ValueError(f"recipe {rec.name!r} has no activation quantizer"
                         " to fuse the lift into")
    fp8 = rec.act == "fp8"
    x2, lead = _flatten_rows(x)
    if _auto(use_pallas):
        tiles = autotune.tiles_for(
            "fused_quant_slide", rows=x2.shape[0], m=0, k=x2.shape[1],
            pattern=f"{dec.source.z}:{dec.source.l}",
            dtype=str(x2.dtype), adt=rec.act, interpret=interpret,
            tune=tune, operands=(x2,),
            run=lambda t: _fqs.fused_quant_slide(
                x2, dec, interpret=interpret, fp8=fp8,
                **t.kernel_kwargs("block_rows")))
        q, s = _fqs.fused_quant_slide(x2, dec, interpret=interpret, fp8=fp8,
                                      **tiles.kernel_kwargs("block_rows"))
    else:
        q, s = ref.fused_quant_slide(x2, dec, fp8=fp8)
    return q.reshape(lead + (q.shape[-1],)), s.reshape(lead + (1,))


def quant_matmul(q_x, s_x, q_w, s_w, out_dtype=jnp.float32,
                 use_pallas: bool | None = None, interpret: bool = False,
                 tune: bool = False):
    """Dense quantized GEMM + dequant epilogue (the quantized baseline).

    q_x: [..., K] int8 or fp8-e4m3 per-token-quantized activations; s_x:
    [..., 1] fp32 scales; q_w: [M, K] int8 (or e4m3) row-quantized
    weights; s_w: [M, 1] fp32 row scales.  Returns [..., M] in
    ``out_dtype``.  The accumulator follows the operand dtypes (int32 for
    all-integer, fp32 with any fp8 operand).
    """
    x2, lead = _flatten_rows(q_x)
    s2 = s_x.reshape(-1, 1)
    if _auto(use_pallas):
        tiles = autotune.tiles_for(
            "quant_matmul", rows=x2.shape[0], m=q_w.shape[0], k=x2.shape[1],
            adt=str(x2.dtype), wdt=str(q_w.dtype),
            interpret=interpret, tune=tune, operands=(x2, q_w),
            run=lambda t: _qmm.quant_matmul_pallas(
                x2, q_w, s2, s_w, out_dtype=out_dtype, interpret=interpret,
                **t.kernel_kwargs("bm", "br", "bk")))
        y = _qmm.quant_matmul_pallas(x2, q_w, s2, s_w, out_dtype=out_dtype,
                                     interpret=interpret,
                                     **tiles.kernel_kwargs("bm", "br", "bk"))
    else:
        y = ref.quant_matmul(x2, s2, q_w, s_w, out_dtype)
    return y.reshape(lead + (y.shape[-1],))


def compressed_matmul(x: jax.Array, c: CompressedSlided,
                      s_w: jax.Array | None = None,
                      recipe=None, act_quant: str | None = None,
                      out_dtype=None, use_pallas: bool | None = None,
                      interpret: bool = False,
                      bias: jax.Array | None = None,
                      activation: str | None = None, tune: bool = False,
                      act_absmax: jax.Array | None = None):
    """y = act(x @ decompress(c)^T + bias) — the TPU-adapted SlideSparse linear.

    Quantized recipes ('int8' | 'fp8' | 'w4' | 'fp8w4', or a
    PrecisionRecipe) require rowwise-quantized compressed values + s_w row
    scales and perform the fused per-token quantization on x; ``c.packed``
    must match the recipe's weight storage.  ``act_quant`` is the legacy
    spelling and maps onto the equivalent recipe.
    """
    rec = precision.resolve(recipe, act_quant)
    out_dtype = out_dtype or rec.out_dtype(x.dtype)
    x2, lead = _flatten_rows(x)
    if rec.quantized:
        if s_w is None:
            raise ValueError(f"recipe {rec.name!r} needs s_w row scales "
                             "(rowwise-quantized weights)")
        if rec.packed_weights != c.packed:
            raise ValueError(
                f"recipe {rec.name!r} expects "
                f"{'nibble-packed' if rec.packed_weights else 'per-slot'} "
                f"values but the operand has packed={c.packed}")
        aa = _flatten_absmax(act_absmax)
        if _auto(use_pallas):
            qx = rec.quantize_act(x2, absmax=aa)
            tiles = _compressed_tiles(qx.q, c, rec, tune, interpret,
                                      out_dtype, s_x=qx.scale, s_w=s_w,
                                      bias=bias, activation=activation)
            y = _smm.compressed_matmul(qx.q, c, s_x=qx.scale, s_w=s_w,
                                       bias=bias, out_dtype=out_dtype,
                                       interpret=interpret,
                                       activation=activation,
                                       **tiles.kernel_kwargs("bm", "br", "bk"))
        else:
            y = ref.compressed_matmul_quant(x2, c, s_w, rec, out_dtype,
                                            bias=bias, activation=activation,
                                            act_absmax=aa)
    else:
        if (jnp.issubdtype(x2.dtype, jnp.floating)
                and not jnp.issubdtype(c.values.dtype, jnp.floating)):
            raise TypeError(
                f"float activations ({x2.dtype}) against {c.values.dtype}"
                "-compressed weights: a silent cast would truncate the"
                " activations to integers. Pass a quantized recipe (e.g."
                " recipe='int8', with s_w row scales — act_quant='int8' is"
                " the legacy spelling) or compress float weights for the"
                " float path.")
        if _auto(use_pallas):
            x2c = x2.astype(c.values.dtype)
            tiles = _compressed_tiles(x2c, c, rec, tune, interpret,
                                      out_dtype, bias=bias,
                                      activation=activation)
            y = _smm.compressed_matmul(x2c, c, bias=bias, out_dtype=out_dtype,
                                       interpret=interpret,
                                       activation=activation,
                                       **tiles.kernel_kwargs("bm", "br", "bk"))
        else:
            y = ref.compressed_matmul_fp(x2, c, out_dtype, bias=bias,
                                         activation=activation)
    return y.reshape(lead + (y.shape[-1],))


def _compressed_tiles(x2, c, rec, tune, interpret, out_dtype, **call_kw):
    return autotune.tiles_for(
        "compressed_matmul", rows=x2.shape[0], m=c.out_features, k=c.k,
        pattern=f"{c.z}:{c.l}", adt=rec.act or str(x2.dtype),
        wdt=rec.weight or str(c.values.dtype), interpret=interpret,
        tune=tune, operands=(x2, c.values),
        run=lambda t: _smm.compressed_matmul(
            x2, c, out_dtype=out_dtype, interpret=interpret, **call_kw,
            **t.kernel_kwargs("bm", "br", "bk")))


def slided_matmul_quant(x: jax.Array, w_slided_q: jax.Array, s_w: jax.Array,
                        dec: SlideDecomposition, recipe="int8",
                        out_dtype=None, use_pallas: bool | None = None,
                        interpret: bool = False,
                        bias: jax.Array | None = None,
                        activation: str | None = None, tune: bool = False,
                        act_absmax: jax.Array | None = None):
    """Paper-faithful GPU-semantics path, executed as ONE kernel: per-token
    quantization + lifting run in the GEMM prologue (fused_slide_matmul.py),
    so the lifted gamma*K activations never touch HBM — vs. the old
    fused_quant_slide -> quant_matmul pair which round-tripped them.

    Recipe-polymorphic: int8 or fp8-e4m3 activations against int8 or
    nibble-packed int4 slided weights.  When ``act_absmax`` is given
    (tensor-parallel global quantization) the jnp oracle path runs — the
    in-kernel prologue computes its own absmax, and TP serving's hot path
    is the 'compressed' mode.
    """
    rec = precision.resolve(recipe)
    if not rec.quantized:
        raise ValueError(f"recipe {rec.name!r} has no quantized GEMM form")
    out_dtype = out_dtype or rec.out_dtype(x.dtype)
    x2, lead = _flatten_rows(x)
    aa = _flatten_absmax(act_absmax)
    if aa is not None or not _auto(use_pallas):
        y = ref.slided_matmul_quant(x2, w_slided_q, s_w, dec, rec, out_dtype,
                                    bias=bias, activation=activation,
                                    act_absmax=aa)
    else:
        tiles = autotune.tiles_for(
            "fused_slided_matmul", rows=x2.shape[0], m=w_slided_q.shape[0],
            k=x2.shape[1], pattern=f"{dec.source.z}:{dec.source.l}",
            dtype=str(x2.dtype), adt=rec.act, wdt=rec.weight,
            interpret=interpret, tune=tune,
            operands=(x2, w_slided_q),
            run=lambda t: _fsm.fused_slided_matmul(
                x2, w_slided_q, s_w, dec, bias=bias, out_dtype=out_dtype,
                interpret=interpret, activation=activation, recipe=rec,
                **t.kernel_kwargs("br", "bm")))
        y = _fsm.fused_slided_matmul(x2, w_slided_q, s_w, dec, bias=bias,
                                     out_dtype=out_dtype, interpret=interpret,
                                     activation=activation, recipe=rec,
                                     **tiles.kernel_kwargs("br", "bm"))
    return y.reshape(lead + (y.shape[-1],))


def slided_matmul_int8(x: jax.Array, w_slided_q: jax.Array, s_w: jax.Array,
                       dec: SlideDecomposition, out_dtype=None,
                       use_pallas: bool | None = None,
                       interpret: bool = False,
                       bias: jax.Array | None = None,
                       activation: str | None = None, tune: bool = False):
    """The int8 instance of :func:`slided_matmul_quant` (legacy name)."""
    return slided_matmul_quant(x, w_slided_q, s_w, dec, "int8", out_dtype,
                               use_pallas=use_pallas, interpret=interpret,
                               bias=bias, activation=activation, tune=tune)
