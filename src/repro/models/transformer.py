"""Decoder-only LM stack: dense / SWA / local:global / MoE / SSM / hybrid.

Layers are grouped into the config's repeating *unit* (e.g. gemma3's
5 local : 1 global, jamba's 7 mamba : 1 attn) and scanned with stacked
parameters — one traced unit regardless of depth, which keeps 80-layer
compiles tractable and gives the sharding rules a single leading 'unit'
axis.  ``jax.checkpoint`` wraps the unit for training (remat).

Every projection routes through ``linear.apply`` on ``cfg.sparsity``, so
both axes of the paper's technique — the (2N-2):2N pattern AND the
precision recipe (int8 / fp8 / w4 operands, DESIGN.md §10) — apply
model-wide without any per-layer branching here."""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import linear as sl
from repro.configs.base import ModelConfig
from repro.sharding import ctx as shard_ctx
from repro.sharding import tp
from . import layers, attention, moe, ssm


def _sp(x, cfg):
    """Sequence parallelism (Megatron-SP): at unit boundaries the residual
    stream is sharded over ('model' on S) so the per-unit activations saved
    for backward shrink by the TP degree; GSPMD turns the boundary
    collectives into all-gather/reduce-scatter pairs.  No-op without a mesh,
    when S doesn't divide (decode steps), or when the config disables it
    (measured: on some stacks GSPMD answers with collective-permute churn —
    see EXPERIMENTS.md §Perf)."""
    if not cfg.sequence_parallel:
        return x
    return shard_ctx.constrain(x, "dp", "model", None)


def _remat_split(u: int) -> tuple[int, int]:
    """Factor u = s1 * s2 with s1 + s2 minimal (2-level remat segments)."""
    best = (1, u)
    d = 1
    while d * d <= u:
        if u % d == 0 and d + u // d < sum(best):
            best = (d, u // d)
        d += 1
    return best


# ----------------------------------------------------------------- specs
def attn_spec(cfg: ModelConfig, kind: str) -> attention.AttnSpec:
    """Attention spec; inside a tensor-parallel trace (sharding.tp ctx,
    DESIGN.md §9) the spec describes the LOCAL shard: heads and KV heads
    shrink by the TP degree (head-parallel attention + head-parallel paged
    KV pool), head_dim and the GQA ratio are preserved."""
    shards = tp.size()
    return attention.AttnSpec(
        d_model=cfg.d_model,
        num_heads=cfg.num_heads // shards,
        num_kv_heads=cfg.num_kv_heads // shards,
        head_dim=cfg.resolved_head_dim,
        rope_theta=cfg.rope_theta,
        causal=True,
        sliding_window=cfg.sliding_window if kind == "swa" else None,
        m_rope=cfg.m_rope,
        tile_skip=cfg.swa_tile_skip,
    )


def ssm_spec(cfg: ModelConfig) -> ssm.SSMSpec:
    """SSM spec; under tensor parallelism the SSD heads shard over the TP
    axis (spec.shards), shrinking d_inner/num_heads to the local shard."""
    return ssm.SSMSpec(d_model=cfg.d_model, d_state=cfg.ssm_state,
                       d_conv=cfg.ssm_conv, expand=cfg.ssm_expand,
                       head_dim=cfg.ssm_head_dim, chunk=cfg.ssm_chunk,
                       shards=tp.size())


def moe_spec(cfg: ModelConfig) -> moe.MoESpec:
    return moe.MoESpec(d_model=cfg.d_model, d_ff=cfg.d_ff,
                       num_experts=cfg.moe_num_experts,
                       top_k=cfg.moe_top_k,
                       capacity_factor=cfg.moe_capacity_factor,
                       expert_padding=cfg.moe_expert_padding)


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def _ffn(lp, cfg: ModelConfig, h, sp, is_moe: bool):
    """The unit's FFN: MoE experts or dense SwiGLU.  The dense path honors
    sp.fuse_epilogue (SiLU fused into the gate projection's Pallas epilogue,
    DESIGN.md §2.3); the MoE expert MLP uses raw einsums and ignores the
    knob — threading fusion through moe.apply is an open item."""
    if is_moe:
        return moe.apply(lp["ffn"], moe_spec(cfg), h, sp)
    return layers.swiglu(lp["ffn"], h, sp)


# ------------------------------------------------------------------ init
def _unit_init(cfg: ModelConfig, key) -> dict[str, Any]:
    unit = {}
    for i, (kind, is_moe) in enumerate(zip(cfg.unit_pattern, cfg.moe_pattern)):
        key, k1, k2 = jax.random.split(key, 3)
        lp = {"pre_norm": layers.rmsnorm_init(cfg.d_model)}
        if kind == "ssm":
            lp["mixer"] = ssm.init(k1, ssm_spec(cfg), _dtype(cfg))
        else:
            lp["mixer"] = attention.init(k1, attn_spec(cfg, kind), _dtype(cfg))
        if cfg.d_ff > 0:
            lp["ffn_norm"] = layers.rmsnorm_init(cfg.d_model)
            if is_moe:
                lp["ffn"] = moe.init(k2, moe_spec(cfg), _dtype(cfg))
            else:
                lp["ffn"] = layers.swiglu_init(k2, cfg.d_model, cfg.d_ff,
                                               _dtype(cfg))
        unit[f"layer_{i}"] = lp
    return unit


def init_keys(cfg: ModelConfig, key):
    """The key split ``init`` uses: (embed key, lm_head key, [U] unit keys).
    ``init_units(cfg, unit_keys[u:u+1])`` rebuilds unit ``u`` alone."""
    ke, kh, ku = jax.random.split(key, 3)
    return ke, kh, jax.random.split(ku, cfg.num_units)


def init_units(cfg: ModelConfig, unit_keys) -> dict[str, Any]:
    """Stacked [len(unit_keys), ...] parameters of the scanned units."""
    return jax.vmap(lambda k: _unit_init(cfg, k))(unit_keys)


def init_outer(cfg: ModelConfig, ke, kh) -> dict[str, Any]:
    """Everything outside the scanned units: embedding, final norm, head."""
    return {
        "embed": layers.embed_init(ke, cfg.vocab_size, cfg.d_model,
                                   _dtype(cfg)),
        "final_norm": layers.rmsnorm_init(cfg.d_model),
        "lm_head": sl.init(kh, cfg.d_model, cfg.vocab_size, _dtype(cfg)),
    }


def init(cfg: ModelConfig, key) -> dict[str, Any]:
    ke, kh, unit_keys = init_keys(cfg, key)
    return {**init_outer(cfg, ke, kh), "units": init_units(cfg, unit_keys)}


# --------------------------------------------------------------- forward
def _apply_unit(cfg: ModelConfig, unit_params, x, positions, cache=None,
                kv_len=None):
    """One unit (len(unit_pattern) layers). Returns (x, new_unit_cache).

    In training (cache is None) each *layer* is checkpointed: multi-layer
    units (jamba's 8) would otherwise hold every layer's FFN/SSD
    intermediates live at once during the unit's backward — ~10x the
    residual-stream footprint at d_ff=24576.
    """
    sp = cfg.sparsity
    new_cache = {}
    for i, (kind, is_moe) in enumerate(zip(cfg.unit_pattern, cfg.moe_pattern)):
        def layer_body(xx, lp, lcache, kind=kind, is_moe=is_moe):
            lc = {}
            h = layers.rmsnorm(lp["pre_norm"], xx, cfg.norm_eps)
            if kind == "ssm":
                y, nc = ssm.apply(lp["mixer"], ssm_spec(cfg), h, sp,
                                  cache=lcache)
            else:
                y, nc = attention.apply(lp["mixer"], attn_spec(cfg, kind), h,
                                        positions, sp, cache=lcache,
                                        kv_len=kv_len)
            xx = xx + y
            if cfg.d_ff > 0:
                h = layers.rmsnorm(lp["ffn_norm"], xx, cfg.norm_eps)
                xx = xx + _ffn(lp, cfg, h, sp, is_moe)
            return xx, nc

        # NOTE: an additional per-layer jax.checkpoint here was measured and
        # REFUTED on jamba train (EXPERIMENTS §Perf extras): +15% FLOPs,
        # +12% collectives, memory flat — the unit-level checkpoint already
        # bounds the backward working set
        lcache = None if cache is None else cache[f"layer_{i}"]
        x, nc = layer_body(x, unit_params[f"layer_{i}"], lcache)
        if nc is not None:
            new_cache[f"layer_{i}"] = nc
    return x, (new_cache or None)


def backbone(params, cfg: ModelConfig, x, positions):
    """Embedded inputs [B, S, D] -> final hidden [B, S, D] (no cache).

    Two-level rematerialized scan over units: with U units split s1 x s2,
    backward-saved residual-stream carries drop from U to ~(s1 + s2) —
    e.g. mixtral's 56 units save 15 x [B,S,D] instead of 56 (the dominant
    training temp at 4k sequence length).
    """
    def unit_fn(carry, unit_params):
        out, _ = _apply_unit(cfg, unit_params, carry, positions)
        return _sp(out, cfg), None

    if cfg.remat:
        unit_fn = jax.checkpoint(unit_fn)
    s1, s2 = (_remat_split(cfg.num_units)
              if cfg.remat and cfg.remat_2level else (1, cfg.num_units))
    x = _sp(x, cfg)
    if s1 == 1:
        x, _ = jax.lax.scan(unit_fn, x, params["units"])
    else:
        seg_params = jax.tree_util.tree_map(
            lambda a: a.reshape((s1, s2) + a.shape[1:]), params["units"])

        def seg_fn(carry, seg):
            out, _ = jax.lax.scan(unit_fn, carry, seg)
            return out, None

        x, _ = jax.lax.scan(jax.checkpoint(seg_fn), x, seg_params)
    return layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)


def embed_tokens(params, cfg: ModelConfig, tokens, extra_embeds=None):
    """Token ids [B, S] (+ optional stub modality embeddings) -> [B, S, D]."""
    x = layers.embed(params["embed"], tokens).astype(_dtype(cfg))
    if extra_embeds is not None:
        # modality frontend stub: precomputed embeddings are summed into the
        # reserved prefix positions (vision/audio tokens)
        n = extra_embeds.shape[1]
        x = x.at[:, :n].add(extra_embeds.astype(_dtype(cfg)))
    return x


def logits_fn(params, cfg: ModelConfig, hidden):
    return layers.unembed(params["lm_head"], hidden, cfg.sparsity)


def chunked_xent(lm_head, cfg: ModelConfig, h, labels):
    """Sequence-chunked LM head + next-token cross entropy.

    Caps the [*, chunk, V] logits transient — with gemma3's 262k vocab a
    full-sequence fp32 logits tensor would dominate peak memory.
    labels < 0 are masked out.
    """
    b, s, _ = h.shape
    chunk = min(cfg.logits_chunk, s)
    pad = (-s) % chunk
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nch = (s + pad) // chunk
    hs = h.reshape(b, nch, chunk, -1).transpose(1, 0, 2, 3)
    ls = labels.reshape(b, nch, chunk).transpose(1, 0, 2)

    def chunk_loss(carry, xs):
        hc, lc = xs
        logits = layers.unembed(lm_head, hc, cfg.sparsity).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        valid = (lc >= 0).astype(jnp.float32)
        nll = (lse - picked) * valid
        return (carry[0] + nll.sum(), carry[1] + valid.sum()), None

    # remat: without this the scan saves per-chunk logits for the backward
    # pass (~[S/chunk, B, chunk, V] fp32 — dominates peak memory at 262k
    # vocab); recomputing them is a few % of step FLOPs
    chunk_loss = jax.checkpoint(chunk_loss)
    (total, count), _ = jax.lax.scan(
        chunk_loss, (jnp.float32(0), jnp.float32(0)), (hs, ls))
    return total / jnp.maximum(count, 1.0)


def lm_loss(params, cfg: ModelConfig, tokens, labels, extra_embeds=None):
    """Next-token cross entropy, sequence-chunked LM head (peak-memory cap)."""
    x = embed_tokens(params, cfg, tokens, extra_embeds)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    h = backbone(params, cfg, x, positions)
    return chunked_xent(params["lm_head"], cfg, h, labels)


# ------------------------------------------------------------- inference
def make_cache(cfg: ModelConfig, batch: int, max_len: int):
    """Stacked [U, ...] cache pytree matching the unit scan."""
    kv_dtype = jnp.dtype(cfg.kv_cache_dtype)

    def one_unit(_):
        c = {}
        for i, kind in enumerate(cfg.unit_pattern):
            if kind == "ssm":
                c[f"layer_{i}"] = ssm.make_cache(ssm_spec(cfg), batch)
            else:
                c[f"layer_{i}"] = attention.make_cache(
                    attn_spec(cfg, kind), batch, max_len, kv_dtype)
        return c

    return jax.vmap(one_unit)(jnp.arange(cfg.num_units))


def prefill(params, cfg: ModelConfig, tokens, max_len: int | None = None,
            extra_embeds=None):
    """Full-prompt forward; returns (logits_last [B, V], cache, kv_len)."""
    b, s = tokens.shape
    max_len = max_len or s
    x = embed_tokens(params, cfg, tokens, extra_embeds)
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    sp = cfg.sparsity

    def unit_fn(carry, unit_params):
        h, = carry
        new_cache = {}
        xx = h
        for i, (kind, is_moe) in enumerate(
                zip(cfg.unit_pattern, cfg.moe_pattern)):
            lp = unit_params[f"layer_{i}"]
            hh = layers.rmsnorm(lp["pre_norm"], xx, cfg.norm_eps)
            if kind == "ssm":
                spec = ssm_spec(cfg)
                y, cache_i = ssm.apply(lp["mixer"], spec, hh, sp)
            else:
                spec = attn_spec(cfg, kind)
                y, _ = attention.apply(lp["mixer"], spec, hh, positions, sp)
                cache_i = attention.build_prefill_cache(
                    lp["mixer"], spec, hh, positions, sp, max_len,
                    jnp.dtype(cfg.kv_cache_dtype))
            new_cache[f"layer_{i}"] = cache_i
            xx = xx + y
            if cfg.d_ff > 0:
                hh = layers.rmsnorm(lp["ffn_norm"], xx, cfg.norm_eps)
                xx = xx + _ffn(lp, cfg, hh, sp, is_moe)
        return (_sp(xx, cfg),), new_cache

    (h,), cache = jax.lax.scan(unit_fn, (_sp(x, cfg),), params["units"])
    h = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = logits_fn(params, cfg, h[:, -1:, :])[:, 0]
    kv_len = jnp.full((b,), s, jnp.int32)
    return logits, cache, kv_len


# ------------------------------------------------------- paged inference
# All three paged step shapes (prefill chunk, decode, verify) attend
# through attention.pool_attend, which dispatches between the KV-gather
# oracle and the fused flash-decode kernel on cfg.sparsity.fused_attention
# (DESIGN.md §16) — nothing in this module branches on the choice.
def make_paged_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                     max_batch: int):
    """Stacked [U, ...] paged cache: attention layers hold a physical page
    pool [num_pages, page_size, KVH, hd] (one logical page id addresses the
    same slot in every layer, vLLM-style); SSM layers hold O(1) per-slot
    recurrent state [max_batch, ...]."""
    kv_dtype = jnp.dtype(cfg.kv_cache_dtype)

    def one_unit(_):
        c = {}
        for i, kind in enumerate(cfg.unit_pattern):
            if kind == "ssm":
                c[f"layer_{i}"] = ssm.make_cache(ssm_spec(cfg), max_batch)
            else:
                c[f"layer_{i}"] = attention.make_paged_pool(
                    attn_spec(cfg, kind), num_pages, page_size, kv_dtype)
        return c

    return jax.vmap(one_unit)(jnp.arange(cfg.num_units))


def paged_copy_pages(cfg: ModelConfig, cache, src_ids, dst_ids):
    """Copy-on-write step (DESIGN.md §11): duplicate physical pages
    ``src_ids[i] -> dst_ids[i]`` in every attention layer's pool (one
    logical page id addresses the same slot in every layer, so one host
    decision copies the whole stack).  SSM layers hold per-slot state, not
    pages — they pass through untouched (prefix caching is attention-only).
    ``dst == num_pages`` entries are padding no-ops."""
    out = {}
    for i, kind in enumerate(cfg.unit_pattern):
        lc = cache[f"layer_{i}"]
        out[f"layer_{i}"] = (lc if kind == "ssm"
                             else attention.pool_copy_pages(lc, src_ids,
                                                            dst_ids))
    return out


def paged_prefill_chunk(params, cfg: ModelConfig, tokens, cache, page_table,
                        start, real_len, slot, reset, page_size: int):
    """One prompt chunk of one sequence through the paged cache.

    tokens: [1, C] (rows >= real_len are right-padding); page_table:
    [1, max_pages]; start/real_len/slot: i32 scalars; reset: bool scalar —
    True on a sequence's first chunk, zeroing the slot's stale SSM state.
    Returns (logits [1, V] at the last real token, new_cache).
    """
    b, c = tokens.shape
    x = layers.embed(params["embed"], tokens).astype(_dtype(cfg))
    positions = start + jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[None],
                                         (b, c))
    sp = cfg.sparsity
    vlen = jnp.full((b,), real_len, jnp.int32)

    def unit_fn(carry, xs):
        unit_params, unit_cache = xs
        xx = carry
        new_cache = {}
        for i, (kind, is_moe) in enumerate(
                zip(cfg.unit_pattern, cfg.moe_pattern)):
            lp = unit_params[f"layer_{i}"]
            lc = unit_cache[f"layer_{i}"]
            hh = layers.rmsnorm(lp["pre_norm"], xx, cfg.norm_eps)
            if kind == "ssm":
                st = jax.tree_util.tree_map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, 0), lc)
                st = jax.tree_util.tree_map(
                    lambda a: jnp.where(reset, jnp.zeros_like(a), a), st)
                y, new_st = ssm.apply(lp["mixer"], ssm_spec(cfg), hh, sp,
                                      cache=st, chunked=True, valid_len=vlen)
                nc = jax.tree_util.tree_map(
                    lambda full, upd: jax.lax.dynamic_update_slice_in_dim(
                        full, upd.astype(full.dtype), slot, 0), lc, new_st)
            else:
                y, nc = attention.paged_prefill_chunk(
                    lp["mixer"], attn_spec(cfg, kind), hh, positions, sp,
                    lc, page_table, start, real_len, page_size)
            xx = xx + y
            if cfg.d_ff > 0:
                hh = layers.rmsnorm(lp["ffn_norm"], xx, cfg.norm_eps)
                xx = xx + _ffn(lp, cfg, hh, sp, is_moe)
            new_cache[f"layer_{i}"] = nc
        return xx, new_cache

    x, new_cache = jax.lax.scan(unit_fn, x, (params["units"], cache))
    h = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    last = jnp.clip(real_len - 1, 0, c - 1)
    h_last = jax.lax.dynamic_slice_in_dim(h, last, 1, axis=1)
    logits = logits_fn(params, cfg, h_last)[:, 0]
    return logits, new_cache


def paged_decode_step(params, cfg: ModelConfig, token, cache, page_table,
                      kv_len, active, page_size: int):
    """One decode token for every slot at once.  token: [B] i32; kv_len:
    [B] context lengths already written; active: [B] bool (inactive slots
    compute garbage the engine ignores; their pool writes are dropped).
    Returns (logits [B, V], new_cache)."""
    b = token.shape[0]
    x = layers.embed(params["embed"], token[:, None]).astype(_dtype(cfg))
    positions = kv_len[:, None]
    sp = cfg.sparsity

    def unit_fn(carry, xs):
        unit_params, unit_cache = xs
        xx = carry
        new_cache = {}
        for i, (kind, is_moe) in enumerate(
                zip(cfg.unit_pattern, cfg.moe_pattern)):
            lp = unit_params[f"layer_{i}"]
            lc = unit_cache[f"layer_{i}"]
            hh = layers.rmsnorm(lp["pre_norm"], xx, cfg.norm_eps)
            if kind == "ssm":
                y, nc = ssm.apply(lp["mixer"], ssm_spec(cfg), hh, sp,
                                  cache=lc)
                # inactive slots (incl. mid-chunked-prefill ones) must keep
                # their state: the garbage decode input would otherwise
                # clobber the SSD/conv state between two prefill chunks
                nc = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(
                        active.reshape((b,) + (1,) * (new.ndim - 1)),
                        new, old.astype(new.dtype)), nc, lc)
            else:
                y, nc = attention.paged_decode_step(
                    lp["mixer"], attn_spec(cfg, kind), hh, sp, lc,
                    page_table, kv_len, active, page_size)
            xx = xx + y
            if cfg.d_ff > 0:
                hh = layers.rmsnorm(lp["ffn_norm"], xx, cfg.norm_eps)
                xx = xx + _ffn(lp, cfg, hh, sp, is_moe)
            new_cache[f"layer_{i}"] = nc
        return xx, new_cache

    x, new_cache = jax.lax.scan(unit_fn, x, (params["units"], cache))
    h = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, h)[:, 0]
    return logits, new_cache


def paged_verify_step(params, cfg: ModelConfig, tokens, cache, page_table,
                      kv_len, real_len, active, page_size: int):
    """Speculative verify step (DESIGN.md §14): score C = K+1 tokens per
    slot in one batched pass.  tokens: [B, C] — per slot the last emitted
    token followed by its draft tokens, right-padded; kv_len: [B] pre-step
    *written* lengths (== seq.kv_len - 1, the decode convention);
    real_len: [B] real lane counts (1 + n_draft); active: [B] bool.
    Returns (logits [B, C, V], new_cache) — logits[:, i] predicts the
    token following lane i; the host accepts the longest agreeing prefix.

    SSM stacks are rejected at engine construction (the recurrent state
    advances in place and cannot roll back a rejected suffix), so every
    mixer here is paged attention."""
    b, c = tokens.shape
    x = layers.embed(params["embed"], tokens).astype(_dtype(cfg))
    sp = cfg.sparsity

    def unit_fn(carry, xs):
        unit_params, unit_cache = xs
        xx = carry
        new_cache = {}
        for i, (kind, is_moe) in enumerate(
                zip(cfg.unit_pattern, cfg.moe_pattern)):
            if kind == "ssm":
                raise ValueError(
                    "speculative verify_step does not support SSM layers "
                    "(recurrent state cannot roll back rejected drafts)")
            lp = unit_params[f"layer_{i}"]
            lc = unit_cache[f"layer_{i}"]
            hh = layers.rmsnorm(lp["pre_norm"], xx, cfg.norm_eps)
            y, nc = attention.paged_verify_step(
                lp["mixer"], attn_spec(cfg, kind), hh, sp, lc,
                page_table, kv_len, real_len, active, page_size)
            xx = xx + y
            if cfg.d_ff > 0:
                hh = layers.rmsnorm(lp["ffn_norm"], xx, cfg.norm_eps)
                xx = xx + _ffn(lp, cfg, hh, sp, is_moe)
            new_cache[f"layer_{i}"] = nc
        return xx, new_cache

    x, new_cache = jax.lax.scan(unit_fn, x, (params["units"], cache))
    h = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, h)
    return logits, new_cache


def serve_step(params, cfg: ModelConfig, token, cache, kv_len):
    """One-token decode. token: [B] int32; cache: stacked unit cache;
    kv_len: [B] current lengths. Returns (logits [B, V], cache, kv_len+1)."""
    b = token.shape[0]
    x = layers.embed(params["embed"], token[:, None]).astype(_dtype(cfg))
    positions = kv_len[:, None]

    def unit_fn(carry, xs):
        h = carry
        unit_params, unit_cache = xs
        out, new_cache = _apply_unit(cfg, unit_params, h, positions,
                                     cache=unit_cache, kv_len=kv_len)
        return out, new_cache

    x, new_cache = jax.lax.scan(unit_fn, x, (params["units"], cache))
    h = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = logits_fn(params, cfg, h)[:, 0]
    return logits, new_cache, kv_len + 1
