"""Tensor-parallel serving context + partitioning rules (DESIGN.md §9).

The serving engine runs its two jitted step functions under
``jax.shard_map`` over a 1-D ``('tp',)`` mesh.  This module
owns everything TP-specific:

* :class:`TPContext` / :func:`activate` — a thread-local marker that the
  surrounding code is being traced *per shard*.  Model code stays
  mesh-agnostic: :func:`reduce` (the row-parallel psum) and the
  shard-aware spec helpers are no-ops / trivial without an active context.
* :func:`serve_param_specs` / :func:`serve_cache_specs` — Megatron-style
  partitioning of the parameter tree and of the paged KV/SSM cache:

  ==================  =========================================  =========
  role                parameters                                 sharded dim
  ==================  =========================================  =========
  column-parallel     wq wk wv wx wz wdt w_gate w_up lm_head     out
  row-parallel        wo w_down                                  in (K)
  replicated          embed router wB wC norms(d_model) biases   —
  head-sharded        conv_w A_log dt_bias D mixer-norm g        heads/dI
  ==================  =========================================  =========

  Compressed operands (``values``/``indices``, the packed (2N-2):2N
  blocks) shard on the same logical dims as their dense ``w``: the
  compressed layout is slot-planar ``[P, K/L, out]``, so a row-parallel
  K-slice is a contiguous slice of the group axis, a column-parallel slice
  one of the trailing ``out`` axis, and every device holds *only its
  shard* of the packed blocks — see ``compressed.split_k``/``split_out``.
  Nibble-packed 'w4' values (DESIGN.md §10) pair slot planes within a
  byte, so they shard on exactly the same dims.  Quantized recipes stay
  parity with the unsharded engine because row-parallel activation
  quantization uses the :func:`reduce_max` global absmax (see
  ``linear.apply``).
* :func:`validate` — fail-fast divisibility checks (heads, d_ff, vocab,
  SSM heads, and pattern-group alignment of row-parallel K shards).
* :func:`rmsnorm` — TP-aware gated-RMSNorm for activations sharded on
  their feature axis (the SSM d_inner): mean-of-squares via psum.

The column→row pairing keeps each block's interior collective-free; the
single psum per mixer/FFN happens *after* the fused epilogue
(dequant + bias + activation) via ``linear.apply(..., reduce_out=True)``,
so the row-parallel reduction runs on the fused output (DESIGN.md §9).

The paged KV pool shards on its KV-head axis (``serve_cache_specs``), so
the fused paged-attention kernel (DESIGN.md §16) composes for free: each
shard runs ``kernels.paged_attention`` over its own KV-head slice of the
pool with the replicated page table, exactly like the gather oracle, and
the per-shard attention outputs feed the row-parallel ``wo`` whose psum
is already the block's one collective — fused vs gather adds no
communication either way.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "tp"

_STATE = threading.local()

# parent-dict names -> role (see module docstring table)
_COL_PARALLEL = {"wq", "wk", "wv", "wx", "wz", "wdt", "w_gate", "w_up",
                 "lm_head"}
_ROW_PARALLEL = {"wo", "w_down"}
_REPLICATED = {"embed", "router", "wB", "wC"}
# per-head / per-feature 1-D-ish leaves sharded on their trailing dim
_HEAD_SHARDED_LEAVES = {"conv_w", "A_log", "dt_bias", "D"}


@dataclasses.dataclass(frozen=True)
class TPContext:
    """Active tensor-parallel trace context (inside shard_map)."""
    axis: str = AXIS
    size: int = 1


def current() -> TPContext | None:
    return getattr(_STATE, "ctx", None)


def size() -> int:
    """TP degree of the active context (1 when not inside shard_map)."""
    ctx = current()
    return ctx.size if ctx is not None else 1


@contextlib.contextmanager
def activate(tp: int, axis: str = AXIS):
    """Mark the dynamic extent as per-shard code of a ``tp``-way mesh."""
    prev = current()
    _STATE.ctx = TPContext(axis=axis, size=tp)
    try:
        yield
    finally:
        _STATE.ctx = prev


def reduce(x: jax.Array) -> jax.Array:
    """Row-parallel all-reduce: psum over the TP axis; identity without an
    active context (single device, training, unit tests)."""
    ctx = current()
    if ctx is None or ctx.size == 1:
        return x
    return jax.lax.psum(x, ctx.axis)


def reduce_max(x: jax.Array) -> jax.Array:
    """Elementwise max over the TP axis; identity without an active context.

    Used by ``linear.apply`` to turn a row-parallel projection's per-shard
    per-token absmax into the GLOBAL absmax before quantizing (DESIGN.md
    §10): every shard then emits the same quantized values and the same
    dequant scale as the unsharded run, so quantized recipes stay
    argmax-parity with the single-device engine (the residual difference
    is only the fp32 reassociation of the post-epilogue psum)."""
    ctx = current()
    if ctx is None or ctx.size == 1:
        return x
    return jax.lax.pmax(x, ctx.axis)


def argmax_tokens(logits: jax.Array) -> jax.Array:
    """Greedy token ids from (possibly vocab-sharded) logits, on device.

    ``lm_head`` is column-parallel, so inside shard_map each shard holds a
    contiguous ``[..., V/tp]`` vocab slice of the logits.  The global
    argmax is two local reductions plus one all-gather of scalars per
    lane: per-shard argmax/max, then an argmax across the gathered shard
    axis.  Tie-breaking matches ``jnp.argmax`` on the unsharded logits
    exactly — first occurrence, i.e. the *lowest global vocab index*:
    within a shard the local argmax already picks the lowest local index,
    and across shards ``all_gather`` stacks shards in axis-index order so
    the outer argmax picks the lowest shard among equal maxima.  The
    returned ids are replicated across shards (out-spec ``P()``), which is
    what lets the engine fetch a ``[B]`` int32 array — or feed it straight
    back into the next step — instead of ``[B, V]`` float32 logits
    (DESIGN.md §15).  Works for any leading shape: ``[B, V/tp]`` decode
    logits and ``[B, K+1, V/tp]`` verify logits alike."""
    ctx = current()
    if ctx is None or ctx.size == 1:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    vloc = logits.shape[-1]
    loc = jnp.argmax(logits, axis=-1)
    best = jnp.take_along_axis(logits, loc[..., None], axis=-1)[..., 0]
    gidx = (loc + jax.lax.axis_index(ctx.axis) * vloc).astype(jnp.int32)
    allv = jax.lax.all_gather(best, ctx.axis)   # [tp, ...] shard maxima
    alli = jax.lax.all_gather(gidx, ctx.axis)   # [tp, ...] global indices
    shard = jnp.argmax(allv, axis=0)            # ties -> lowest shard
    return jnp.take_along_axis(alli, shard[None], axis=0)[0]


def rmsnorm(params, x, eps: float = 1e-6):
    """RMSNorm over a feature axis that is *sharded* across TP shards
    (the SSM gated norm over d_inner): the mean of squares is the global
    psum of local sums, so sharded == unsharded up to reassociation.
    Falls back to plain local RMSNorm without an active context."""
    ctx = current()
    dt = x.dtype
    xf = x.astype(jnp.float32)
    if ctx is None or ctx.size == 1:
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    else:
        ss = jax.lax.psum(jnp.sum(xf * xf, axis=-1, keepdims=True), ctx.axis)
        ms = ss / (x.shape[-1] * ctx.size)
    xf = xf * jax.lax.rsqrt(ms + eps)
    return (xf * params["g"]).astype(dt)


# ------------------------------------------------------------------ mesh
def make_serve_mesh(tp: int) -> Mesh:
    """1-D ('tp',) mesh over the first ``tp`` local devices."""
    devs = jax.devices()
    if tp > len(devs):
        raise ValueError(
            f"tp={tp} exceeds {len(devs)} available device(s); on CPU run "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count={tp}")
    return Mesh(np.asarray(devs[:tp]), (AXIS,))


# ------------------------------------------------------------ param specs
def _names(path) -> list[str]:
    return [str(getattr(k, "key", getattr(k, "name", k))) for k in path]


def _p(spec) -> P:
    """P(...) with trailing Nones trimmed: shard_map emits outputs with
    normalized specs, and a jit cache key must not distinguish
    P(None, 'tp', None) from P(None, 'tp') or the second step call
    retraces on its own output's sharding."""
    spec = list(spec)
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def _proj_spec(leaf_name: str, nd: int, role: str) -> P:
    """Spec for one leaf of a projection dict ({'w'} | {'values','indices'}
    | {'w_slided'} [+ 's_w']).  Dense and slided layouts are [..., out,
    K-like]; compressed operands are slot-planar [..., P, K/L, out].
    Column parallelism shards ``out``, row parallelism the K-like dim (for
    compressed operands the group axis K/L, which slices congruently with
    K)."""
    spec: list = [None] * nd
    out_dim, k_dim = ((nd - 1, nd - 2) if leaf_name in ("values", "indices")
                      else (nd - 2, nd - 1))
    if leaf_name == "s_w":  # [..., out, 1] row scales
        if role == "col":
            spec[nd - 2] = AXIS
        return _p(spec)
    if role == "col":
        spec[out_dim] = AXIS
    elif role == "row":
        spec[k_dim] = AXIS
    return _p(spec)


def _leaf_spec(path, leaf) -> P:
    names = _names(path)
    last = names[-1]
    nd = leaf.ndim
    if any(n in _REPLICATED for n in names):
        return P()
    if last in _HEAD_SHARDED_LEAVES:
        spec = [None] * nd
        spec[nd - 1] = AXIS          # [U, H] / [U, K, dI]: trailing dim
        return _p(spec)
    if last == "g":
        # mixer-internal gated norm spans the sharded d_inner; every other
        # norm spans the replicated d_model residual stream
        if "mixer" in names and "norm" in names:
            return _p([None] * (nd - 1) + [AXIS])
        return P()
    parent = names[-2] if len(names) >= 2 else ""
    if parent in _COL_PARALLEL:
        return _proj_spec(last, nd, "col")
    if parent in _ROW_PARALLEL:
        return _proj_spec(last, nd, "row")
    return P()


def serve_param_specs(params, tp: int):
    """PartitionSpec pytree for the serving parameter tree (packed or
    dense).  Raises ValueError on any leaf whose sharded dim does not
    divide ``tp`` — TP serving has no silent replication fallback, because
    the in-model psum placement assumes the table above."""
    def spec(path, leaf):
        s = _leaf_spec(path, leaf)
        for dim, ax in enumerate(s):
            if ax is not None and leaf.shape[dim] % tp:
                raise ValueError(
                    f"TP={tp} cannot shard {'/'.join(_names(path))} "
                    f"shape {leaf.shape} on dim {dim}")
        return s

    return jax.tree_util.tree_map_with_path(spec, params)


def serve_cache_specs(cache):
    """PartitionSpec pytree for the paged cache (DESIGN.md §9):

    * attention pools ``k``/``v`` [U, pages, P, KVH, hd] and scale pages
      [U, pages, P, KVH, 1] shard the KV-head dim — each shard owns the
      full page *structure* but only its heads' bytes;
    * SSM ``conv`` [U, B, K-1, dI] shards d_inner, ``ssd`` [U, B, H, P, N]
      shards heads;
    * anything else (none today) stays replicated.

    Because the page *structure* replicates, the host-side page table,
    refcounts, and prefix-cache hash index (DESIGN.md §11) are shared
    across shards unchanged: a copy-on-write page copy is per-shard
    elementwise on these same specs (replicated src/dst id vectors), so
    a tp=N engine reuses prefixes and copies pages identically to tp=1.
    """
    def spec(path, leaf):
        last = _names(path)[-1]
        nd = leaf.ndim
        s: list = [None] * nd
        if last in ("k", "v", "k_scale", "v_scale") and nd == 5:
            s[3] = AXIS
        elif last == "conv" and nd == 4:
            s[3] = AXIS
        elif last == "ssd" and nd == 5:
            s[2] = AXIS
        return _p(s)

    return jax.tree_util.tree_map_with_path(spec, cache)


def named_shardings(specs, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------ validation
def validate(cfg, tp: int) -> None:
    """Fail fast on configs the TP partitioning cannot express.

    Checks (cfg is a ``configs.base.ModelConfig``): attention heads and KV
    heads divide tp (head-parallel KV pool), d_ff and vocab divide tp,
    SSM heads divide tp, and — when serving a packed ``compressed`` /
    ``slided`` model — each row-parallel K shard stays aligned to the
    pattern's L-group so packed blocks never straddle shards.
    """
    if tp <= 1:
        return
    errs = []
    if cfg.num_heads % tp:
        errs.append(f"num_heads={cfg.num_heads}")
    if cfg.num_kv_heads % tp:
        errs.append(f"num_kv_heads={cfg.num_kv_heads}")
    if cfg.d_ff and cfg.d_ff % tp:
        errs.append(f"d_ff={cfg.d_ff}")
    if cfg.vocab_size % tp:
        errs.append(f"vocab_size={cfg.vocab_size}")
    if "ssm" in cfg.unit_pattern:
        d_inner = cfg.ssm_expand * cfg.d_model
        n_heads = d_inner // cfg.ssm_head_dim
        if n_heads % tp:
            errs.append(f"ssm heads={n_heads}")
    sp = cfg.sparsity
    if sp.pattern is not None and sp.mode in ("slided", "compressed"):
        l = sp.pattern[1]
        qdim = cfg.num_heads * cfg.resolved_head_dim
        row_ks = [("attn wo", qdim), ("w_down", cfg.d_ff)]
        if "ssm" in cfg.unit_pattern:
            row_ks.append(("ssm wo", cfg.ssm_expand * cfg.d_model))
        for name, k in row_ks:
            # a layer is only packed when L divides its K (pack_params)
            if k and k % l == 0 and (k // tp) % l:
                errs.append(f"{name}: K/tp={k // tp} not a multiple of "
                            f"L={l} (pattern group would straddle shards)")
    if errs:
        raise ValueError(f"config incompatible with tp={tp}: "
                         + "; ".join(errs))
