"""Fused paged attention: flash-decode over the page table (DESIGN.md §16).

The serving engine's KV cache lives in a physical page pool
``[num_pages, page_size, KVH, hd]`` addressed through per-sequence page
tables (DESIGN.md §5).  The gather oracle
(``models.attention._pool_gather``) materializes the ENTIRE logical cache
``[B, maxp*P, KVH, hd]`` (plus int8 scale pages) in HBM every prefill
chunk / decode step / verify step and runs SDPA over the copy — per-step
attention traffic scales with pool *capacity*, not valid tokens.  This
module consumes the page table inside the kernel instead:

* Pallas path (TPU, or ``interpret=True`` on CPU): grid
  ``(B, splits, pages_per_split)``.  The page table and per-row KV
  lengths arrive as scalar-prefetch operands, so each grid step's
  BlockSpec index_map reads the table and fetches exactly the physical
  K/V (+ int8 scale) page it needs — all KV heads of it, a block whose
  minor dims are the pool's own ``(KVH, hd)`` — and the gathered copy
  never exists.  Each (batch, split) cell runs one online softmax per KV
  head — running max / sum-exp / unnormalized accumulator in VMEM
  scratch — over its pages and emits a partial ``(acc, m, l)``; the
  standard flash-decode
  ``(max, sum)`` merge combines splits outside the kernel:
  ``m* = max_s m_s;  l* = sum_s l_s * exp(m_s - m*);
  out = sum_s acc_s * exp(m_s - m*) / l*``.
* jnp path (CPU engines, same backend dispatch rule as ops.py): the same
  flash dataflow as a ``fori_loop`` over page blocks with a TRACED upper
  bound ``ceil(max(row_len) / tokens_per_block)`` — work proportional to
  valid tokens where the gather oracle pays capacity, which is what the
  long-context serve bench measures.

A ``lanes`` axis generalizes one kernel to all three paged step shapes:
decode (L=1), speculative verify (L=K+1, query row i at row length
``kv_len + i``), and chunked prefill (L=C with ``kv_len = start + 1`` for
row 0).  GQA stays native — queries are grouped per KV head (``rep =
H/KVH`` rows each) and K/V are never repeated.  int8 KV pages are
dequantized in-kernel from their scale pages immediately before each dot,
mirroring the oracle's op order, so fused-vs-gather parity holds at the
argmax level (online softmax reassociates the sum, so bitwise equality is
not the contract — see tests/test_paged_attention.py).

Parity contract: entries past a sequence's allocation point at physical
page 0 (``runtime.kv_cache.page_table_array``), and every position they
contribute is ``>= row_len`` where the kv_len mask kills it — identical
to the gather oracle's convention, so no index clamping is needed.  Rows
are never fully masked (position ``row_len - 1`` always survives both
the kv_len and sliding-window bounds), so the ``l == 0`` guard is only
reachable through the padded split tail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import autotune
from .slide_matmul import vmem_limit

NEG_INF = -1e30


def _auto(use_pallas: bool | None) -> bool:
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return use_pallas


def _default_splits(maxp: int) -> int:
    """S-split default: split only tables wide enough to amortize the
    (max, sum) merge of the extra partials."""
    return 1 if maxp <= 4 else min(4, maxp)


def _default_block_pages(maxp: int, page_size: int) -> int:
    """jnp-path block width: ~128 tokens per fori_loop iteration."""
    return max(1, min(maxp, max(1, 128 // page_size)))


# ------------------------------------------------------------- jnp mirror
def _flash_ref(q, pool, page_table, kv_len, window, block_pages):
    """Flash paged attention in pure jnp: fori_loop over page blocks with
    a traced upper bound, so HBM work tracks valid tokens (the fused
    economics) while staying jit/shard_map-compatible on every backend."""
    b, lanes, h, hd = q.shape
    page_size = pool["k"].shape[1]
    kvh = pool["k"].shape[2]
    rep = h // kvh
    maxp = page_table.shape[1]
    bp = max(1, min(block_pages, maxp))
    pad = (-maxp) % bp
    # pad with page 0: its positions are >= maxp*P >= every row_len, so the
    # kv_len mask drops them (same convention as unallocated table entries)
    pt = jnp.pad(page_table, ((0, 0), (0, pad))) if pad else page_table
    nblocks = (maxp + pad) // bp
    quant = pool["k"].dtype == jnp.int8
    tokens = bp * page_size

    q5 = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, lanes, kvh, rep, hd)
    row_len = kv_len.astype(jnp.int32)[:, None] \
        + jnp.arange(lanes, dtype=jnp.int32)[None, :]            # [B, L]
    needed = jnp.clip(
        (jnp.max(row_len) + tokens - 1) // tokens, 0, nblocks)

    m0 = jnp.full((b, kvh, rep, lanes), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, lanes), jnp.float32)
    a0 = jnp.zeros((b, kvh, rep, lanes, hd), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        ids = jax.lax.dynamic_slice_in_dim(pt, i * bp, bp, axis=1)  # [B, bp]
        kb, vb = pool["k"][ids], pool["v"][ids]    # [B, bp, P, KVH, hd]
        if quant:
            kb = kb.astype(jnp.float32) * pool["k_scale"][ids]
            vb = vb.astype(jnp.float32) * pool["v_scale"][ids]
        kb = kb.reshape(b, tokens, kvh, hd).astype(jnp.float32)
        vb = vb.reshape(b, tokens, kvh, hd).astype(jnp.float32)
        pos = i * tokens + jnp.arange(tokens, dtype=jnp.int32)
        ok = pos[None, None, :] < row_len[:, :, None]            # [B, L, T]
        if window is not None:
            ok &= pos[None, None, :] >= row_len[:, :, None] - window
        s = jnp.einsum("bqgrd,bkgd->bgrqk", q5, kb)
        s = jnp.where(ok[:, None, None, :, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        # the where guards the all-masked-block case: NEG_INF - NEG_INF
        # is 0.0 and exp(0) would smuggle weight-1 garbage into l/acc
        p = jnp.where(ok[:, None, None, :, :], jnp.exp(s - m_new[..., None]),
                      0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        upd = jnp.einsum("bgrqk,bkgd->bgrqd", p, vb)
        return m_new, l_new, acc * alpha[..., None] + upd

    _, l, acc = jax.lax.fori_loop(0, needed, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]   # [B, G, rep, L, hd]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, lanes, h, hd).astype(
        q.dtype)


# ------------------------------------------------------------ Pallas path
def _flash_kernel(pt_ref, kl_ref, q_ref, k_ref, v_ref, *refs,
                  page_size, rep, pps, window, quant):
    """One grid step: fold one physical page — all of its KV heads — into
    the per-head (m, l, acc) running softmax of this (batch, split) cell;
    flush the partials on the split's last page."""
    if quant:
        ks_ref, vs_ref, oacc_ref, m_ref, l_ref, acc_s, m_s, l_s = refs
    else:
        oacc_ref, m_ref, l_ref, acc_s, m_s, l_s = refs
    bb = pl.program_id(0)
    s_idx = pl.program_id(1)
    p_idx = pl.program_id(2)

    @pl.when(p_idx == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    kvh, lr = q_ref.shape[1], q_ref.shape[2]
    pos = (s_idx * pps + p_idx) * page_size \
        + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (lr, 1), 0) // rep
    row_len = kl_ref[bb] + lane                        # [Lr, 1]
    ok = pos < row_len
    if window is not None:
        ok &= pos >= row_len - window

    for g in range(kvh):  # GQA-native: head g's query rows vs its K/V
        qv = q_ref[0, g]                               # [Lr, hd] (pre-scaled)
        kb = k_ref[0, :, g, :].astype(jnp.float32)     # [P, hd]
        vb = v_ref[0, :, g, :].astype(jnp.float32)
        if quant:  # in-kernel dequant from the page's scale rows, oracle order
            kb = kb * ks_ref[0, :, g, :]
            vb = vb * vs_ref[0, :, g, :]
        sc = jnp.dot(qv, kb.T, preferred_element_type=jnp.float32)  # [Lr, P]
        sc = jnp.where(ok, sc, NEG_INF)
        m_prev = m_s[g]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(sc - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[g] = l_s[g] * alpha + p.sum(axis=-1, keepdims=True)
        acc_s[g] = acc_s[g] * alpha \
            + jnp.dot(p, vb, preferred_element_type=jnp.float32)
        m_s[g] = m_new

    @pl.when(p_idx == pps - 1)
    def _flush():
        oacc_ref[0, 0] = acc_s[...]
        m_ref[0, 0] = m_s[...]
        l_ref[0, 0] = l_s[...]


def _merge_splits(acc, m, l):
    """Standard flash-decode split merge (DESIGN.md §16):
    acc [B, NS, G, Lr, hd]; m/l [B, NS, G, Lr, 1] -> [B, G, Lr, hd]."""
    m_star = m.max(axis=1)
    w = jnp.exp(m - m_star[:, None])
    l_star = (l * w).sum(axis=1)
    out = (acc * w).sum(axis=1)
    return out / jnp.maximum(l_star, 1e-30)


def _flash_pallas(q, pool, page_table, kv_len, window, splits, interpret):
    b, lanes, h, hd = q.shape
    page_size = pool["k"].shape[1]
    kvh = pool["k"].shape[2]
    rep = h // kvh
    lr = lanes * rep
    maxp = page_table.shape[1]
    ns = max(1, min(splits, maxp))
    pps = -(-maxp // ns)
    pad = ns * pps - maxp
    pt = jnp.pad(page_table, ((0, 0), (0, pad))).astype(jnp.int32)
    kl = kv_len.astype(jnp.int32)
    quant = pool["k"].dtype == jnp.int8

    # [B, KVH, L*rep, hd], row = lane*rep + r (GQA-native grouping)
    qr = (q.astype(jnp.float32) * hd ** -0.5).reshape(
        b, lanes, kvh, rep, hd).transpose(0, 2, 1, 3, 4).reshape(
        b, kvh, lr, hd)

    def page_map(bi, s, p, pt_ref, kl_ref):
        # the scalar-prefetched table IS the gather: this grid step's
        # K/V block is the physical page the sequence's table names — the
        # whole page, all KV heads, so the block's minor dims (KVH, hd) are
        # the pool's own and the TPU tiling accepts them
        return (pt_ref[bi, s * pps + p], 0, 0, 0)

    page = (1, page_size, kvh, hd)
    in_specs = [
        pl.BlockSpec((1, kvh, lr, hd), lambda bi, s, p, *_: (bi, 0, 0, 0)),
        pl.BlockSpec(page, page_map),
        pl.BlockSpec(page, page_map),
    ]
    inputs = [qr, pool["k"], pool["v"]]
    kv_bytes = 2 * page_size * kvh * hd * pool["k"].dtype.itemsize
    if quant:
        in_specs += [pl.BlockSpec((1, page_size, kvh, 1), page_map)] * 2
        inputs += [pool["k_scale"], pool["v_scale"]]
        kv_bytes += 2 * page_size * kvh * 4

    part = lambda bi, s, p, *_: (bi, s, 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, ns, pps),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, kvh, lr, hd), part),
            pl.BlockSpec((1, 1, kvh, lr, 1), part),
            pl.BlockSpec((1, 1, kvh, lr, 1), part),
        ],
        scratch_shapes=[
            pltpu.VMEM((kvh, lr, hd), jnp.float32),
            pltpu.VMEM((kvh, lr, 1), jnp.float32),
            pltpu.VMEM((kvh, lr, 1), jnp.float32),
        ],
    )
    # q block + output blocks (double-buffered) + accumulator scratch, the
    # page blocks, and one head's [Lr, P] score temporaries
    rows_bytes = kvh * lr * (hd + 2) * 4
    need = 5 * rows_bytes + 2 * kv_bytes + 4 * lr * page_size * 4
    acc, m, l = pl.pallas_call(
        functools.partial(_flash_kernel, page_size=page_size, rep=rep,
                          pps=pps, window=window, quant=quant),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, ns, kvh, lr, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, ns, kvh, lr, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, ns, kvh, lr, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(need)),
        interpret=interpret,
    )(pt, kl, *inputs)
    out = _merge_splits(acc, m, l)                     # [B, KVH, Lr, hd]
    return out.reshape(b, kvh, lanes, rep, hd).transpose(
        0, 2, 1, 3, 4).reshape(b, lanes, h, hd).astype(q.dtype)


# ---------------------------------------------------------------- wrapper
def paged_attention(q, pool, page_table, kv_len, *,
                    sliding_window: int | None = None,
                    use_pallas: bool | None = None, interpret: bool = False,
                    tune: bool = False, splits: int | None = None,
                    block_pages: int | None = None):
    """Fused paged flash attention over the page pool.

    q: [B, L, H, hd] post-RoPE queries — query row (lane) i of sequence b
    attends causally over positions ``< kv_len[b] + i`` (and within
    ``sliding_window`` of its own position when set).  pool: page-pool
    dict {'k','v'[,'k_scale','v_scale']} as built by
    ``models.attention.make_paged_pool``; page_table: [B, maxp] int32
    physical page ids (unallocated entries 0, per
    ``runtime.kv_cache.page_table_array``); kv_len: [B] row-0 logical KV
    lengths — the ``_decode_sdpa`` convention where callers pass the
    post-write length of the first query row.

    Dispatch follows ops.py: Pallas on TPU backends (or when forced with
    ``use_pallas=True``, typically with ``interpret=True`` on CPU), the
    jnp flash mirror otherwise.  ``splits`` (Pallas S-splits) and
    ``block_pages`` (jnp-path pages per loop block) come from the
    autotune cache when not given (keyed with ``adt=`` KV dtype; br =
    splits, bk = block_pages).  Returns [B, L, H, hd] in q.dtype.
    """
    b, lanes, h, hd = q.shape
    page_size = pool["k"].shape[1]
    kvh = pool["k"].shape[2]
    if h % kvh:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kvh}")
    maxp = page_table.shape[1]
    window = int(sliding_window) if sliding_window is not None else None

    def run(t: autotune.TileConfig):
        return _dispatch(q, pool, page_table, kv_len, window, use_pallas,
                         interpret, t.br, t.bk)

    tiles = autotune.tiles_for(
        "paged_attention", rows=b * lanes, m=kvh * hd, k=maxp * page_size,
        adt=str(pool["k"].dtype), lanes=lanes, kvh=kvh, hd=hd, qh=h,
        window=window or 0, interpret=interpret, tune=tune,
        operands=(q, pool["k"], page_table, kv_len), run=run)
    return _dispatch(q, pool, page_table, kv_len, window, use_pallas,
                     interpret, splits or tiles.br, block_pages or tiles.bk)


def _dispatch(q, pool, page_table, kv_len, window, use_pallas, interpret,
              splits, block_pages):
    maxp = page_table.shape[1]
    page_size = pool["k"].shape[1]
    if _auto(use_pallas):
        return _flash_pallas(q, pool, page_table, kv_len, window,
                             splits or _default_splits(maxp), interpret)
    return _flash_ref(q, pool, page_table, kv_len, window,
                      block_pages or _default_block_pages(maxp, page_size))
