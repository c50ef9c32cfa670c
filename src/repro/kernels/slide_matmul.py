"""Pallas TPU kernel: compressed-weight matmul with in-VMEM decompression.

The TPU adaptation of the paper's sparse GEMM (DESIGN.md §2): weights live in
HBM in the slided-compressed 2:4 format (values + in-window positions, one
per slot = exactly the (2N-2)/2N non-zero budget), stream HBM->VMEM at
*density* bytes, are decompressed to dense tiles by the VPU, and the MXU
consumes dense tiles at 1.0x dense FLOPs — the slide is undone during
decompression ("unslide fusion", our beyond-paper optimization).

Decompression is elementwise (no scatter, no lane reshape), which is what
the TPU compiler (Mosaic) lowers.  The operand is slot-planar
(``core.compressed``): plane ``p = j*M + t`` is a ``[G, out]`` array of
slot ``t`` of window ``j``.  Window ``j`` covers in-group source offsets
``s*j .. s*j+N-1``, so dense plane ``o`` (source offset ``o`` of every
L-group) is the sum over the slots of the windows covering ``o`` of
``where(pos == o - s*j, value, 0)``.  The packer guarantees each source
position receives at most one non-zero, so the sums never collide and the
result is exact in any dtype.  Arithmetic runs in int32 / float32 (the TPU
VPU has no int8 arithmetic) and the tile is stored back at the weight
dtype.  The dense tile is therefore offset-major — plane ``o`` row ``g``
is source position ``g*L + o`` — and the activations are permuted to the
same order once per call (``[L, rows, G]``, a small XLA transpose), so the
product is a sum of L plane matmuls ``x_o @ w_o``.

Grid order (DESIGN.md §2.3): ``(M/bm, R/br)`` with **R innermost**.  The
weight tile for output block m is decompressed exactly once — at r == 0,
chunk by chunk (``bk`` dense columns = ``bk/L`` groups per chunk) into a
persistent VMEM scratch — and every activation row-block then consumes the
cached dense tile.  Total decompressions per call are ``(M/bm) *
ceil(K/bk)`` regardless of R.  The dequant epilogue optionally fuses a
bias add and SiLU/GELU so the transformer MLP gate/up projections need no
separate elementwise pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.compressed import CompressedSlided

from .fused_slide_matmul import apply_activation, clamp_rows, prepare_bias

# Instrumentation (tests / benchmarks): counts runtime executions of one
# decompression chunk inside the kernel when instrument=True is passed.
_DECOMPRESS_COUNT = [0]

# tile footprint target and the scoped-VMEM ceiling handed to Mosaic
# (v5e has 128 MiB of VMEM; its default scoped limit is 16 MiB)
VMEM_BUDGET = 24 * 1024 * 1024
_VMEM_HEADROOM = 8 * 1024 * 1024
_VMEM_CAP = 100 * 1024 * 1024


def reset_decompress_count() -> None:
    _DECOMPRESS_COUNT[0] = 0


def decompress_count() -> int:
    return _DECOMPRESS_COUNT[0]


def _bump_decompress_count() -> None:
    _DECOMPRESS_COUNT[0] += 1


def vmem_limit(need: int) -> int:
    """Scoped-VMEM limit for a kernel whose blocks + scratch need ``need``
    bytes: headroom for Mosaic's own temporaries, never below the chip's
    default and never above the cap."""
    return int(min(max(need + _VMEM_HEADROOM, 16 * 1024 * 1024), _VMEM_CAP))


def _wide(a: jax.Array) -> jax.Array:
    """Widen to the dtype the VPU computes in (int32 / float32)."""
    return a.astype(jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer)
                    else jnp.float32)


def _unpack_pair(b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One nibble-packed byte plane -> its (low, high) int32 slot planes,
    sign-extended by arithmetic shifts."""
    b = b.astype(jnp.int32)
    return (b << 28) >> 28, b >> 4


def dense_planes(vals, idx, n_fam: int) -> list[jax.Array]:
    """Decompress one chunk: slot planes -> the L dense planes.

    ``vals``: P value planes (``[g, bm]`` each, any dtype) or, when
    ``len(vals) == P/2``, nibble-packed byte planes; ``idx``: P int8
    position planes.  Returns L widened (int32/float32) planes, plane ``o``
    holding source offset ``o`` of every group."""
    nw = n_fam - 1
    p_count = 2 * nw
    if len(vals) * 2 == p_count:
        vals = [v for b in vals for v in _unpack_pair(b)]
    vals = [_wide(v) for v in vals]
    pos = [i.astype(jnp.int32) for i in idx]
    planes = []
    for o in range(2 * n_fam):
        acc = None
        for j in range(max(0, (o - 2) // 2), min(nw, o // 2 + 1)):
            d = o - 2 * j  # offset within window j (stride s = 2)
            for t in range(2):
                p = 2 * j + t
                term = jnp.where(pos[p] == d, vals[p], jnp.zeros_like(vals[p]))
                acc = term if acc is None else acc + term
        planes.append(acc)
    return planes


def decompress_tile(vals: jax.Array, idx: jax.Array, n_fam: int) -> jax.Array:
    """[P, g, bm] slot planes (``[P/2, g, bm]`` bytes when nibble-packed) +
    [P, g, bm] positions -> [L, g, bm] dense offset-major planes at the
    weight dtype (int8 for packed values): ``out[o, g, m]`` is the weight
    of output row m at source position ``g*L + o``."""
    dt = jnp.int8 if vals.shape[0] != idx.shape[0] else vals.dtype
    planes = dense_planes([vals[p] for p in range(vals.shape[0])],
                          [idx[p] for p in range(idx.shape[0])], n_fam)
    return jnp.stack(planes).astype(dt)


def _mm_kernel(x_ref, v_ref, i_ref, sx_ref, sw_ref, b_ref, o_ref, w_scr,
               *, n_fam: int, gc: int, acc_dtype, quantized: bool,
               has_bias: bool, activation: str | None, instrument: bool):
    # Decompress the (:, m) weight tile once — at the first r step — into
    # the persistent VMEM scratch; all later r steps reuse it (R-innermost
    # grid).  'w4' byte planes are sign-extended inside dense_planes.
    g_total = w_scr.shape[1]

    def chunk(rows):
        planes = dense_planes(
            [v_ref[p, rows, :] for p in range(v_ref.shape[0])],
            [i_ref[p, rows, :] for p in range(i_ref.shape[0])], n_fam)
        for o, plane in enumerate(planes):
            w_scr[o, rows, :] = plane.astype(w_scr.dtype)
        if instrument:
            jax.debug.callback(_bump_decompress_count)

    @pl.when(pl.program_id(1) == 0)
    def _decompress():
        full = g_total // gc
        if full:
            def body(c, carry):
                chunk(pl.ds(pl.multiple_of(c * gc, gc), gc))
                return carry
            jax.lax.fori_loop(0, full, body, 0)
        if g_total % gc:
            chunk(pl.ds(full * gc, g_total - full * gc))

    acc = None
    for o in range(w_scr.shape[0]):
        x, w = x_ref[o], w_scr[o]
        if jnp.float8_e4m3fn in (x.dtype, w.dtype):
            # fp8 operands: lossless fp32 casts, fp32 accumulate — identical
            # arithmetic to the jnp oracle
            x, w = x.astype(jnp.float32), w.astype(jnp.float32)
        part = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                   preferred_element_type=acc_dtype)
        acc = part if acc is None else acc + part
    out = acc.astype(jnp.float32)
    if quantized:
        out = out * sx_ref[...] * sw_ref[...]
    if has_bias:
        out = out + b_ref[...]
    o_ref[...] = apply_activation(out, activation).astype(o_ref.dtype)


def choose_bk(l: int, groups: int = 32) -> int:
    """Dense width of one decompression chunk: ``groups`` L-groups, i.e.
    ``groups`` sublane rows of every plane (a multiple of 32 keeps int8
    planes on whole sublane tiles)."""
    return groups * l


def _bm_candidates(m: int, target: int = 512) -> list[int]:
    """Lane-legal output tiles, best first: multiples of 128 that divide M
    (no per-call padding), else M itself; then padded multiples of 128 as
    the fallback when those do not fit VMEM."""
    exact = [b for b in range(min(target, m) // 128 * 128, 0, -128)
             if m % b == 0] or [m]
    return exact + [b for b in (512, 256, 128) if b < m and b not in exact]


def tile_need(bm: int, br: int, k: int, kc: int, x_itemsize: int,
              w_itemsize: float, x_fp8: bool = False, l: int = 8) -> int:
    """VMEM bytes the kernel's blocks and scratch take at (bm, br): Pallas
    double-buffers every input and output block; the dense scratch holds a
    whole (K, bm) tile; decompression temporaries are one chunk of every
    plane at 32-bit."""
    up = (br * k + bm * k) * 4 if x_fp8 else 0  # fp32 upcast copies
    chunk = 3 * choose_bk(l) * bm * 4           # widened chunk planes
    return int(2 * br * k * x_itemsize          # x planes
               + 2 * bm * kc * (w_itemsize + 1)  # values + int8 positions
               + bm * k * max(w_itemsize, 1)     # dense decompressed scratch
               + up + chunk
               + 2 * br * bm * 4 + br * bm * 4)  # output blocks + accumulator


def default_tiles(m: int, k: int, kc: int, x_itemsize: int,
                  w_itemsize: float,
                  vmem_budget: int = VMEM_BUDGET,
                  x_fp8: bool = False) -> tuple[int, int]:
    """(bm, br) heuristic: the largest output tile that divides M and whose
    footprint (``tile_need``) fits the VMEM budget; the activation row
    block shrinks first.  ``x_fp8`` adds the fp32 working copies the kernel
    materializes for an e4m3 activation operand (DESIGN.md §13)."""
    bms = _bm_candidates(m)

    def need(bm_, br_):
        return tile_need(bm_, br_, k, kc, x_itemsize, w_itemsize, x_fp8)

    bm, br = bms[0], 256
    while need(bm, br) > vmem_budget and br > 8:
        br //= 2                              # x block shrinks fastest
    bm = next((b for b in bms if need(b, br) <= vmem_budget), bms[-1])
    while br < 256 and need(bm, 2 * br) <= vmem_budget:
        br *= 2
    return bm, br


@functools.partial(
    jax.jit,
    static_argnames=("n_fam", "quantized", "interpret", "bm", "br", "bk",
                     "out_dtype", "activation", "instrument"))
def compressed_matmul_pallas(x, values, indices, s_x, s_w, bias=None, *,
                             n_fam: int, quantized: bool,
                             out_dtype=jnp.float32, interpret: bool = False,
                             bm: int | None = None, br: int | None = None,
                             bk: int | None = None,
                             activation: str | None = None,
                             instrument: bool = False):
    """y[R, M] = act(x[R, K] @ decompress(values, indices)[M, K]^T
                     (+ dequant) (+ bias)).

    ``values``/``indices``: slot-planar ``[P, K/L, M]`` operands
    (``core.compressed``); values with ``P/2`` planes are nibble-packed
    int4 pairs (the 'w4' recipe), sign-extended in the decompress prologue.
    quantized=True: x int8 or float8_e4m3fn, integer values; int32
    accumulate for all-integer operands, fp32 (lossless casts) when any
    operand is fp8; epilogue * s_x * s_w.
    quantized=False: float path, fp32 accumulate (s_x/s_w ignored; pass
    ones).  bias: [M] fp32 or None; activation: None | 'silu' | 'gelu'
    (fused epilogue, applied after dequant/bias).  ``bk`` is the dense
    width of one decompression chunk; the full (K, bm) tile is cached in
    VMEM scratch.
    """
    rows, k = x.shape
    m = indices.shape[-1]
    l = 2 * n_fam
    g = k // l
    bk = bk or choose_bk(l)
    if bk % l:
        raise ValueError(f"bk={bk} must be a multiple of L={l} so chunk "
                         "boundaries align with window groups")
    kc = indices.shape[0] * g
    w_item = values.dtype.itemsize * values.shape[0] / indices.shape[0]
    x_fp8 = x.dtype == jnp.float8_e4m3fn
    dbm, dbr = default_tiles(m, k, kc, x.dtype.itemsize, w_item, x_fp8=x_fp8)
    bm, br = bm or dbm, br or dbr
    br = clamp_rows(br, rows)

    pad_r, pad_m = (-rows) % br, (-m) % bm
    has_bias, b = prepare_bias(bias, m, pad_m)
    # offset-major activations: xp[o, r, g] = x[r, g*L + o]
    xp = x.reshape(rows, g, l).transpose(2, 0, 1)
    if pad_r:
        xp = jnp.pad(xp, ((0, 0), (0, pad_r), (0, 0)))
        s_x = jnp.pad(s_x, ((0, pad_r), (0, 0)), constant_values=1.0)
    s_w = s_w.reshape(1, m)
    if pad_m:
        # no lane-legal tile divides M: pad the weight operand per call
        pm = ((0, 0), (0, 0), (0, pad_m))
        values, indices = jnp.pad(values, pm), jnp.pad(indices, pm)
        s_w = jnp.pad(s_w, ((0, 0), (0, pad_m)), constant_values=1.0)

    rp, mp = xp.shape[1], values.shape[-1]
    pv = values.shape[0]
    acc_dtype = (jnp.int32 if quantized and x.dtype == jnp.int8
                 else jnp.float32)
    wdt = jnp.int8 if pv != indices.shape[0] else values.dtype
    need = tile_need(bm, br, k, kc, x.dtype.itemsize, w_item, x_fp8, l)

    y = pl.pallas_call(
        functools.partial(_mm_kernel, n_fam=n_fam, gc=bk // l,
                          acc_dtype=acc_dtype, quantized=quantized,
                          has_bias=has_bias, activation=activation,
                          instrument=instrument),
        grid=(mp // bm, rp // br),  # R innermost: decompress once per m
        in_specs=[
            pl.BlockSpec((l, br, g), lambda m_, r: (0, r, 0)),
            pl.BlockSpec((pv, g, bm), lambda m_, r: (0, 0, m_)),
            pl.BlockSpec((indices.shape[0], g, bm), lambda m_, r: (0, 0, m_)),
            pl.BlockSpec((br, 1), lambda m_, r: (r, 0)),
            pl.BlockSpec((1, bm), lambda m_, r: (0, m_)),
            pl.BlockSpec((1, bm), lambda m_, r: (0, m_)),
        ],
        out_specs=pl.BlockSpec((br, bm), lambda m_, r: (r, m_)),
        out_shape=jax.ShapeDtypeStruct((rp, mp), out_dtype),
        scratch_shapes=[pltpu.VMEM((l, g, bm), wdt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(need)),
        interpret=interpret,
    )(xp, values, indices, s_x, s_w, b)
    return y[:rows, :m]


def compressed_matmul(x: jax.Array, c: CompressedSlided,
                      s_x: jax.Array | None = None,
                      s_w: jax.Array | None = None,
                      bias: jax.Array | None = None,
                      out_dtype=jnp.float32, interpret: bool = False,
                      activation: str | None = None, **tiles):
    """Dtype-polymorphic: the quantized path (dequant epilogue, integer or
    fp32 accumulation) is selected by the activation dtype — callers pass
    pre-quantized int8/e4m3 activations — and nibble-packing rides on
    ``c.packed`` (the 'w4' recipe)."""
    n = c.decomposition.source.family_n
    if n is None or c.m != 2 or c.n != 4:
        raise ValueError("Pallas kernel supports the (2N-2):2N -> 2:4 family")
    quantized = x.dtype in (jnp.int8, jnp.float8_e4m3fn)
    rows = x.shape[0]
    mout = c.out_features
    if s_x is None:
        s_x = jnp.ones((rows, 1), jnp.float32)
    if s_w is None:
        s_w = jnp.ones((mout, 1), jnp.float32)
    return compressed_matmul_pallas(
        x, c.values, c.indices, s_x, s_w, bias, n_fam=n, quantized=quantized,
        out_dtype=out_dtype, interpret=interpret, activation=activation,
        **tiles)
