"""SparseLinear — SlideSparse as a first-class linear-layer feature.

One config object selects the execution path for every projection in the
model stack (mirrors the paper's single vLLM flag, §4.3):

  mode='dense'       plain dense matmul (baseline, cuBLASLt analogue)
  mode='masked'      training-time STE magnitude masking (sparse-aware train)
  mode='slided'      paper-faithful: Psi(x) @ Phi(W)^T over gamma*K
  mode='compressed'  TPU-adapted: compressed storage, decompress-to-original
                     matmul (Pallas kernel on TPU, jnp path elsewhere)

Precision composes with every mode through ``recipe`` (a
:class:`repro.core.precision.PrecisionRecipe` or registry name,
DESIGN.md §10): the activation quantizer (int8 / fp8-e4m3), the weight
storage (int8 rowwise / nibble-packed int4 'w4') and the accumulator are
one registry entry, not per-dtype branches — for 'slided' the activation
quantization is the fused quant+slide kernel of paper Alg. 1; for
'compressed' it is plain per-token quant (the unslide happens on the
weight side).  The legacy ``act_quant=None|'int8'`` field maps onto the
equivalent recipe (``precision.resolve`` is the only interpreter of it).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from .patterns import Pattern, SlideDecomposition, TWO_FOUR
from . import slide, packer, compressed as comp, quant, masks, precision
from .precision import PrecisionRecipe


@dataclasses.dataclass(frozen=True)
class SparsityConfig:
    pattern: tuple[int, int] | None = None  # (Z, L), e.g. (6, 8)
    mode: str = "dense"  # dense | masked | slided | compressed
    # legacy precision axis (None | 'int8'); resolved into ``recipe`` at
    # construction time — keep passing it from old call sites, but new code
    # should set ``recipe`` directly
    act_quant: str | None = None
    # precision recipe: PrecisionRecipe, registry name ('none' | 'int8' |
    # 'fp8' | 'w4' | 'fp8w4'), or None -> derived from act_quant
    recipe: PrecisionRecipe | str | None = None
    use_pallas: bool | None = None  # None -> auto (TPU backend only)
    # fuse the MLP nonlinearity (SiLU/GELU) + bias into the matmul epilogue
    # on kernel paths that support it (DESIGN.md §2.3); layers.swiglu checks
    # this knob to skip its separate elementwise pass
    fuse_epilogue: bool = False
    # one-shot tile-size autotuning per (op, shape) via kernels.autotune
    # (DESIGN.md §2.4); tuned tiles are cached in-process and on disk
    tune: bool = False
    # serve the paged KV steps through the fused flash-decode kernel
    # (kernels.paged_attention, DESIGN.md §16) instead of the
    # gather-then-SDPA oracle; argmax parity between the two is locked by
    # tests/test_paged_attention.py
    fused_attention: bool = False

    def __post_init__(self):
        # normalize once so every reader sees a PrecisionRecipe; the frozen
        # dataclass stays hashable (recipes are frozen dataclasses too)
        rec = precision.resolve(self.recipe, self.act_quant)
        if self.act_quant is not None and self.act_quant != rec.act:
            # an explicit legacy flag disagreeing with the carried recipe —
            # e.g. dataclasses.replace(cfg, act_quant='int8') on an
            # already-resolved config — must win, never silently drop
            rec = precision.resolve(None, self.act_quant)
        object.__setattr__(self, "recipe", rec)
        object.__setattr__(self, "act_quant", rec.act)

    def decomposition(self) -> SlideDecomposition | None:
        if self.pattern is None:
            return None
        return SlideDecomposition(Pattern(*self.pattern), TWO_FOUR)


DENSE = SparsityConfig()


def init(key: jax.Array, k_in: int, m_out: int, dtype=jnp.float32,
         scale: float | None = None) -> dict[str, Any]:
    """Dense master weights [out, in] (paper orientation W in R^{M x K})."""
    scale = scale if scale is not None else k_in ** -0.5
    w = jax.random.normal(key, (m_out, k_in), dtype=jnp.float32) * scale
    return {"w": w.astype(dtype)}


def prepare(params: dict[str, Any], cfg: SparsityConfig) -> dict[str, Any]:
    """Offline phase (§4.1) + load-time compression (§4.3).

    Prune master weights to the pattern, quantize per-row per the recipe's
    weight axis (zeros stay zero, so quantization commutes with the pattern
    and with Phi), run the packer, and emit the serving-side operand — for
    the 'w4' storage the values are additionally nibble-packed (two int4
    per byte) after Phi/compression.  'dense'/'masked' pass through
    unchanged.
    """
    dec = cfg.decomposition()
    if cfg.mode in ("dense", "masked") or dec is None:
        return dict(params)
    rec = cfg.recipe
    w = packer.prune_to_pattern(params["w"], dec.source)
    out = {k: v for k, v in params.items() if k != "w"}
    if rec.quantized:
        qw = rec.quantize_weight(w)
        w_store, out["s_w"] = qw.q, qw.scale
    else:
        w_store = w
    ws = slide.phi(w_store, dec)
    if cfg.mode == "slided":
        out["w_slided"] = (packer.pack_nibbles(ws) if rec.packed_weights
                           else ws)
    elif cfg.mode == "compressed":
        c = comp.compress(ws, dec, pack_values=rec.packed_weights)
        out["values"], out["indices"] = c.values, c.indices
        # K is recoverable from the (pack-agnostic) indices shape [P, K/L,
        # out]; storing it as a pytree leaf would get traced under jit
    else:
        raise ValueError(f"unknown mode {cfg.mode}")
    return out


def apply(params: dict[str, Any], x: jax.Array, cfg: SparsityConfig,
          activation: str | None = None, reduce_out: bool = False
          ) -> jax.Array:
    """y = act(x @ W^T) under the configured execution path. x: [..., K].

    ``activation`` (None | 'silu' | 'gelu') is fused into the kernel
    epilogue on the Pallas slided/compressed paths and applied as a
    separate elementwise op everywhere else — identical semantics either
    way (ref.epilogue is the shared oracle).

    ``reduce_out`` marks the projection as *row-parallel* under
    tensor-parallel serving (DESIGN.md §9): after the fused dequant
    epilogue the per-shard partial output is psum'd over the TP axis.
    ``activation`` is rejected in that case (a nonlinearity on partial
    sums would not commute with the psum).  With a quantized recipe the
    per-token scale of a row-parallel projection is the pmax-GLOBAL absmax
    (DESIGN.md §10), so sharded quantization emits the same quantized
    values as the unsharded run.  Outside an active TP trace context
    ``reduce_out`` is the identity, so training and single-device serving
    are unaffected.
    """
    from repro.kernels import ops as kops  # deferred: kernels import core
    from repro.sharding import tp

    dec = cfg.decomposition()
    rec = cfg.recipe
    out_dtype = x.dtype

    if reduce_out and activation is not None and tp.size() > 1:
        # act(partial_a) + act(partial_b) != act(partial_a + partial_b):
        # a nonlinearity cannot ride the fused epilogue of a row-parallel
        # projection — fuse it into the preceding column-parallel layer
        raise ValueError(
            f"activation={activation!r} cannot be fused into a "
            "row-parallel (reduce_out) projection under tensor "
            "parallelism: the epilogue would run on per-shard partial "
            "sums before the psum")

    # row-parallel + quantized recipe under an active TP context: quantize
    # with the global per-token absmax so every shard emits the same
    # quantized values as the unsharded run (one tiny pmax collective)
    act_absmax = None
    if reduce_out and rec.quantized and tp.size() > 1:
        act_absmax = tp.reduce_max(quant.absmax(x))

    def done(y):
        return tp.reduce(y) if reduce_out else y

    if cfg.mode == "dense" or dec is None:
        return done(_post_act(_plain(x, params["w"], cfg, out_dtype,
                                     act_absmax), activation))

    if cfg.mode == "masked":
        w = masks.ste_prune(params["w"], dec.source)
        return done(_post_act(_plain(x, w, cfg, out_dtype, act_absmax),
                              activation))

    params = params if _prepared(params, cfg) else prepare(params, cfg)

    if cfg.mode == "slided":
        ws = params["w_slided"]
        if rec.quantized:
            return done(kops.slided_matmul_quant(
                x, ws, params["s_w"], dec, recipe=rec, out_dtype=out_dtype,
                use_pallas=cfg.use_pallas, activation=activation,
                tune=cfg.tune, act_absmax=act_absmax))
        return done(_post_act(
            slide.slided_matmul(x, ws, dec).astype(out_dtype), activation))

    if cfg.mode == "compressed":
        k = params["indices"].shape[-2] * dec.source.l  # [P, K/L, out]
        # "layer": values/indices are a scanned unit stack, read in place
        # at that unit (models.transformer's unit scan)
        c = comp.CompressedSlided(
            params["values"], params["indices"], k,
            dec.source.z, dec.source.l, dec.hw.m, dec.hw.n,
            packed=rec.packed_weights, layer=params.get("layer"))
        return done(kops.compressed_matmul(
            x, c, s_w=params.get("s_w"), recipe=rec,
            out_dtype=out_dtype, use_pallas=cfg.use_pallas,
            activation=activation, tune=cfg.tune, act_absmax=act_absmax))

    raise ValueError(f"unknown mode {cfg.mode}")


def _post_act(y: jax.Array, activation: str | None) -> jax.Array:
    if activation is None:
        return y
    from repro.kernels.fused_slide_matmul import apply_activation

    return apply_activation(y, activation)


def _prepared(params: dict[str, Any], cfg: SparsityConfig) -> bool:
    return ("w_slided" in params) if cfg.mode == "slided" else ("values" in params)


def _plain(x, w, cfg: SparsityConfig, out_dtype, act_absmax=None):
    """Dense GEMM under the recipe — also the dense same-precision
    reference the sparse pipelines are parity-checked against."""
    rec = cfg.recipe
    if rec.quantized:
        qx = rec.quantize_act(x, absmax=act_absmax)
        qw = rec.quantize_weight(w)
        return quant.matmul_dequant(qx, qw, out_dtype)
    return jnp.einsum("...k,mk->...m", x, w.astype(x.dtype)).astype(out_dtype)
