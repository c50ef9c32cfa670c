"""Serving: one-shot prefill+decode reference AND the continuous-batching
paged-KV engine (DESIGN.md §5), optionally tensor-parallel (§9).

Mirrors the paper's three phases (§4): the offline packer output is applied
at load time via ``pack_params`` (prune -> quantize -> Phi -> compress),
then per-request execution runs the fused-kernel linears.

``generate`` is the dense-cache one-shot path (also the parity oracle for
the engine tests).  :class:`ServeEngine` is the step-driven serving engine:
requests join mid-flight, prefill chunks interleave with decode steps,
finished sequences retire and free their KV pages.  With
``EngineConfig.tp > 1`` both jitted steps run under ``shard_map`` over a
1-D ``('tp',)`` device mesh: weights are column-/row-parallel, the paged
KV pool is head-parallel, and greedy decode stays argmax-identical to the
single-device engine (``tests/test_tp_serve.py``).  Quantized precision
recipes (int8 / fp8 / w4, DESIGN.md §10) ride along: row-parallel layers
quantize with the pmax-GLOBAL per-token absmax, so sharded quantization
emits the same quantized values as the unsharded run and parity holds up
to fp32 reassociation of the post-epilogue psum (DESIGN.md §9/§10).
"""
from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import linear as sl
from repro.models import model as M
from repro.runtime import draft as draft_mod
from repro.runtime import faults as fl
from repro.runtime.kv_cache import KVCacheManager, PagedKVConfig
from repro.runtime import scheduler as sch
from repro.runtime import spans
from repro.runtime.scheduler import (DecodeBatch, PrefillChunk, Request,
                                     Scheduler, VerifyBatch, make_policy)
from repro.sharding import tp as tpmod


@dataclasses.dataclass
class ServeStats:
    """Wall-clock accounting of one ``generate`` call (one-shot path)."""
    prefill_s: float
    decode_s: float
    tokens_generated: int

    @property
    def decode_tok_s(self) -> float:
        return self.tokens_generated / max(self.decode_s, 1e-9)


def pack_params(params: dict[str, Any], cfg: ModelConfig) -> dict[str, Any]:
    """Load-time compression (§4.3): walk the tree and run linear.prepare on
    every SparseLinear leaf-dict (a dict holding only a weight matrix 'w',
    possibly with leading stack axes — the scanned unit projections are
    [U, out, K] and ``jax.lax.scan`` strips the unit axis before
    ``linear.apply`` sees them).

    Packing at load time (not lazily inside the jitted step) matters for
    quantized recipes under tensor parallelism (DESIGN.md §10): the rowwise
    weight scales are computed over the FULL contraction dim here, then the
    packed blocks + scales are sharded — a lazy in-trace prepare would
    quantize each shard's local K-slice with its own scale and break parity
    with the unsharded engine."""
    sp = cfg.sparsity
    if sp.mode in ("dense", "masked") or sp.pattern is None:
        return params

    def walk(node, name=""):
        if isinstance(node, dict):
            if name in ("embed", "router"):
                return node  # lookup tables / routers are not GEMMs
            if "router" in node:
                # MoE block: the [E, F, D] expert stacks run the grouped
                # einsum path (moe._expert_weights), not SparseLinear
                return node
            if set(node) == {"w"} and node["w"].ndim >= 2 \
                    and node["w"].shape[-1] % sp.pattern[1] == 0:
                return sl.prepare(node, sp)
            return {k: walk(v, k) for k, v in node.items()}
        return node

    return walk(params)


def init_packed(cfg: ModelConfig, key) -> dict[str, Any]:
    """``pack_params(M.init(cfg, key), cfg)`` — the same arrays, bit for
    bit — built one scanned unit at a time, so it fits where the dense and
    the packed model do not fit together (a 4B model at 6:8 on one 16 GB
    chip: 7.9 GB dense bf16 + 8.9 GB packed).

    Each unit is initialised from its own key of ``transformer.init_keys``,
    packed, and written into a preallocated stacked packed tree whose
    buffers are donated to every write; its dense weights and the
    compression temporaries are freed before the next unit.  Peak device
    memory is the packed model plus one unit's dense weights and
    temporaries.  Stacks that ``pack_params`` leaves dense get the same
    one-unit-at-a-time initialisation (a bounded init peak); encoder-
    decoder models are initialised and packed whole."""
    if cfg.is_encoder_decoder:
        return pack_params(M.init(cfg, key), cfg)
    from repro.models import transformer as T

    ke, kh, unit_keys = T.init_keys(cfg, key)
    # initialised op by op exactly as M.init does (jit may round the
    # random draws differently); packing is jitted, which keeps its
    # temporaries fused
    pack = jax.jit(lambda p: pack_params(p, cfg))

    def pack_units(k):
        return pack(T.init_units(cfg, k))

    shapes = jax.eval_shape(pack_units, unit_keys[:1])
    units = jax.tree_util.tree_map(
        lambda s: jnp.zeros((cfg.num_units,) + s.shape[1:], s.dtype), shapes)
    put = jax.jit(lambda stack, one, u: jax.tree_util.tree_map(
        lambda a, b: jax.lax.dynamic_update_slice_in_dim(a, b, u, 0),
        stack, one), donate_argnums=0)
    for u in range(cfg.num_units):
        units = put(units, pack_units(unit_keys[u:u + 1]), u)
    return {**pack(T.init_outer(cfg, ke, kh)), "units": units}


def generate(params, cfg: ModelConfig, batch, max_new_tokens: int,
             greedy: bool = True, key=None):
    """Prefill the prompt batch then decode ``max_new_tokens`` steps.
    Returns (tokens [B, max_new_tokens], ServeStats)."""
    b, s = batch["tokens"].shape
    max_len = s + max_new_tokens

    t0 = time.time()
    logits, cache, kv_len = jax.block_until_ready(
        M.prefill(params, cfg, batch, max_len=max_len))[0], None, None
    logits, cache, kv_len = M.prefill(params, cfg, batch, max_len=max_len)
    logits = jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    step = jax.jit(lambda p, tok, c, kl: M.serve_step(p, cfg, tok, c, kl))
    outs = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    t1 = time.time()
    for i in range(max_new_tokens):
        outs.append(tok)
        logits, cache, kv_len = step(params, tok, cache, kv_len)
        if greedy or key is None:
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits).astype(jnp.int32)
    jax.block_until_ready(tok)
    t_decode = time.time() - t1
    return jnp.stack(outs, 1), ServeStats(t_prefill, t_decode,
                                          int(b * max_new_tokens))


# ----------------------------------------------------------------- engine
@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Sizing knobs for the paged serving engine.

    ``tp`` is the tensor-parallel degree (DESIGN.md §9): the engine runs
    its two jitted steps under shard_map over a 1-D ``('tp',)`` mesh of
    the first ``tp`` devices.  Page counts are per *shard-replicated*
    table: every shard holds the same ``num_pages`` page structure, each
    page carrying only its KVH/tp heads' bytes.

    ``prefix_cache`` turns on radix-prefix reuse over ref-counted
    copy-on-write pages (DESIGN.md §11): admissions that share a full-page
    prompt prefix with earlier traffic fork the cached pages and prefill
    only the uncached suffix.  ``policy`` names the admission/eviction
    policy (``fcfs`` | ``priority`` — ``scheduler.POLICIES``).

    ``speculate=K > 0`` turns on self-speculative decoding (DESIGN.md
    §14): the ``draft_source`` (``runtime.draft.DRAFT_SOURCES``) proposes
    up to K tokens per running sequence and a fourth fixed-shape jitted
    step — verify, ``[max_batch, K+1]`` — scores every draft in one
    batched pass; the longest agreeing prefix is accepted, so greedy
    streams are argmax-identical to ``speculate=0``.

    ``device_sample`` (DESIGN.md §15) fetches the on-device argmax ids
    the jitted steps now return — ``[B]`` / ``[B, K+1]`` int32 — instead
    of the ``[B, vocab]`` float32 logits; False selects the host-side
    logits fallback (the pre-§15 transfer, with batched host argmax).
    Either way the steps compute and return both outputs, so the flag
    never changes what compiles — only what the host fetches.

    ``async_loop`` turns on the overlapped engine loop (DESIGN.md §15):
    decode dispatch is decoupled from result application, so the host
    applies step N's tokens while the device runs step N+1, and — on the
    lookahead fast path — step N's device-resident token array feeds
    step N+1's dispatch with no host round-trip.  Greedy streams, traces
    and terminal statuses stay identical to ``async_loop=False``.
    """
    max_batch: int = 4        # decode slots
    page_size: int = 8        # tokens per KV page
    num_pages: int = 64       # physical pages per attention layer
    max_seq_len: int = 128    # prompt + generated cap per sequence
    prefill_chunk: int = 16   # prompt tokens per engine step (token budget)
    tp: int = 1               # tensor-parallel degree (devices in the mesh)
    prefix_cache: bool = False  # radix prefix cache + COW pages (§11)
    policy: str = "fcfs"      # scheduler policy name (fcfs | priority)
    speculate: int = 0        # max draft tokens per verify step (0 = off)
    draft_source: str = "ngram"  # draft source name (ngram | random)
    # overlapped host/device loop (DESIGN.md §15)
    device_sample: bool = True  # fetch on-device argmax ids, not logits
    async_loop: bool = False    # overlap host scheduling with device steps
    # request-lifecycle robustness (DESIGN.md §12)
    max_queue: int | None = None  # bounded admission queue; None = unbounded
    watchdog: bool = False    # assert kv invariants after every decision
    step_retries: int = 2     # transient step-error retries before FAILED
    retry_backoff_s: float = 0.0  # backoff base between step retries
    faults: "fl.FaultPlan | None" = None  # deterministic fault injection

    def kv_config(self) -> PagedKVConfig:
        return PagedKVConfig(page_size=self.page_size,
                             num_pages=self.num_pages,
                             max_batch=self.max_batch,
                             max_seq_len=self.max_seq_len,
                             tp=self.tp)


@dataclasses.dataclass
class Completion:
    """A finished request: generated token ids (greedy stream, including
    tokens emitted before any recompute-preemption), eviction count, and
    the terminal lifecycle status (DESIGN.md §12).

    ``status`` is one of ``OK | TIMEOUT | CANCELLED | REJECTED | FAILED``;
    non-OK completions carry a typed ``reason`` from the scheduler's
    failure taxonomy and keep whatever tokens were generated before the
    exit (a TIMEOUT/CANCELLED stream is a prefix of the fault-free one).

    ``timing`` holds the request's lifecycle stamps on the scheduler's
    clock (:class:`~repro.runtime.scheduler.Timing`: submitted, admitted,
    first prefill chunk decided, first token appended)."""
    rid: int
    prompt: list[int]
    tokens: list[int]
    evictions: int = 0
    status: str = sch.OK
    reason: str | None = None
    timing: sch.Timing = dataclasses.field(default_factory=sch.Timing,
                                           compare=False)

    @property
    def ok(self) -> bool:
        return self.status == sch.OK


@dataclasses.dataclass
class EngineStats:
    """Engine-level counters accumulated over a ``run``: step/token
    accounting, eviction count, mean decode-batch occupancy, the
    tensor-parallel degree, the precision recipe the run executed at,
    and the prefix-cache economics (DESIGN.md §11).

    ``prefill_tokens`` counts *first-pass* prompt tokens only;
    ``recompute_tokens`` separates the re-prefills that recompute-
    preemption forces (they were previously double-counted as new prompt
    tokens, which inflated prompt-throughput and corrupted hit-rate
    denominators)."""
    steps: int = 0
    wall_s: float = 0.0
    warmup_s: float = 0.0     # jit compile + first-exec time paid in warmup()
    decode_tokens: int = 0
    decode_steps: int = 0
    prefill_tokens: int = 0
    recompute_tokens: int = 0  # eviction re-prefills (not new prompt work)
    evictions: int = 0
    mean_occupancy: float = 0.0
    tp: int = 1               # tensor-parallel degree of the run
    precision: str = "none"   # precision-recipe name (DESIGN.md §10)
    # speculative decoding (DESIGN.md §14)
    verify_steps: int = 0     # VerifyBatch steps executed
    draft_tokens: int = 0     # draft tokens proposed
    accepted_tokens: int = 0  # draft tokens accepted (bonus tokens excluded)
    # prefix cache (DESIGN.md §11)
    prefix_cache: bool = False
    prefix_hit_tokens: int = 0       # prompt tokens served from cached pages
    prefill_chunks_skipped: int = 0  # prefill steps avoided by hits
    cow_copies: int = 0              # device page copies (copy-on-write)
    cached_page_evictions: int = 0   # LRU reclaims of refcount-0 pages
    # request lifecycle (DESIGN.md §12) — terminal statuses + fault economics
    completed_ok: int = 0
    cancelled: int = 0
    timeouts: int = 0
    rejected: int = 0                # typed backpressure/capacity refusals
    failed: int = 0
    quarantined: int = 0             # watchdog invariant quarantines
    admission_deferrals: int = 0     # admissions deferred by alloc failure
    step_errors: int = 0             # transient step-dispatch faults seen
    step_retries: int = 0            # retries that recovered a step
    faults_injected: int = 0         # injector-fired faults (all sites)
    goodput_tokens: int = 0          # decode tokens of OK completions only
    p95_queue_wait_steps: float = 0.0
    # overlapped loop instrumentation (DESIGN.md §15)
    host_gap_s: float = 0.0     # device-idle time: step ready -> next dispatch
    overlap_frac: float = 0.0   # 1 - host_gap_s/wall_s (device-busy fraction)
    d2h_bytes: int = 0          # step-output bytes fetched device -> host
    lookahead_steps: int = 0    # decode steps dispatched via the fast path

    @property
    def decode_tok_s(self) -> float:
        return self.decode_tokens / max(self.wall_s, 1e-9)

    @property
    def acceptance_rate(self) -> float:
        """Accepted fraction of proposed draft tokens (0 when no drafts)."""
        return self.accepted_tokens / max(self.draft_tokens, 1)

    @property
    def goodput_tok_s(self) -> float:
        """Decode throughput counting only tokens delivered in OK
        completions — the overload-bench headline (DESIGN.md §12)."""
        return self.goodput_tokens / max(self.wall_s, 1e-9)

    @property
    def prefix_hit_rate(self) -> float:
        """Cached fraction of all prompt tokens that needed KV."""
        total = (self.prefix_hit_tokens + self.prefill_tokens
                 + self.recompute_tokens)
        return self.prefix_hit_tokens / max(total, 1)

    @property
    def decode_tok_s_per_device(self) -> float:
        """Aggregate decode throughput normalized by the TP mesh size —
        the per-chip number the paper's multi-GPU tables report."""
        return self.decode_tok_s / max(self.tp, 1)


def _tag(sp, decision) -> None:
    """Write what a step executes into its ``engine.step`` span."""
    if decision is None:
        sp.set_metadata(kind="none", lanes=0)
    elif isinstance(decision, PrefillChunk):
        sp.set_metadata(kind="prefill", lanes=1, rid=decision.seq.rid)
    else:
        kind = "verify" if isinstance(decision, VerifyBatch) else "decode"
        sp.set_metadata(kind=kind, lanes=len(decision.seqs))


class ServeEngine:
    """Continuous-batching engine over the fused SlideSparse pipeline.

    All linears (q/k/v/o, FFN, lm_head) still route through
    ``linear.apply`` — dense, masked, or the PR-1 fused slided/compressed
    kernels, per ``cfg.sparsity`` — so the engine is the serving scenario
    wrapped around the same GEMM path the paper benchmarks.

    Fixed-shape jitted step functions (no shape-polymorphic retraces): a
    [1, prefill_chunk] prompt-chunk step, a [max_batch] decode step, a
    [_cow_lanes] copy-on-write page-copy step, and — with
    ``ecfg.speculate=K > 0`` — a [max_batch, K+1] speculative verify step
    (DESIGN.md §14).  Scheduling, drafting, accept/reject, and page
    accounting stay on host.

    With ``ecfg.tp > 1`` (DESIGN.md §9) both steps run under shard_map on
    a 1-D ``('tp',)`` mesh: attention/FFN/lm_head weights are Megatron
    column-/row-parallel (packed compressed blocks slice along whole
    L-groups), SSD heads shard, and the paged KV pool is head-parallel —
    each shard scatters/gathers only its KVH/tp heads through the shared
    host page table.  Row-parallel projections psum AFTER their fused
    dequant epilogue (``linear.apply(reduce_out=True)``; nonlinearities
    fuse into the column-parallel layers, never into a row-parallel one);
    lm_head is column-parallel over vocab, so per-shard logits concatenate
    and greedy argmax needs no further collective.  Scheduling, page
    accounting, and sampling are unchanged — TP is invisible above the
    two step functions.  Argmax-parity with the single-device engine
    holds for dense / compressed / int8-KV stacks and for the quantized
    precision recipes (int8 / fp8 / w4): row-parallel projections
    quantize with the pmax-global per-token absmax (``tp.reduce_max``),
    so every shard emits the unsharded quantized values (DESIGN.md §10).

    With ``ecfg.prefix_cache`` (DESIGN.md §11) the engine hashes each
    prompt's full token pages at enqueue, forks cached pages in at
    admission (ref-counted sharing), prefills only the uncached suffix,
    and copy-on-writes any shared page before a step writes into it via
    a third fixed-shape jitted copy step.  Because paged K/V writes are
    token-local and both cache modes run the same fixed step shapes,
    cache-on greedy decode is argmax-identical to cache-off.  All prefix
    decisions are host-side, so a tp=N engine reuses prefixes identically
    to tp=1.
    """

    def __init__(self, params, cfg: ModelConfig,
                 ecfg: EngineConfig | None = None):
        self.ecfg = ecfg or EngineConfig()
        if cfg.is_encoder_decoder:
            raise NotImplementedError("paged engine is decoder-only")
        if self.ecfg.prefix_cache and "ssm" in cfg.unit_pattern:
            raise ValueError(
                "prefix_cache requires an attention-only stack: SSM layers "
                "carry per-slot recurrent state that cached pages cannot "
                "restore at the resume point (DESIGN.md §11)")
        if self.ecfg.speculate > 0 and "ssm" in cfg.unit_pattern:
            raise ValueError(
                "speculate requires an attention-only stack: SSM layers "
                "advance per-slot recurrent state in place, so a rejected "
                "draft suffix cannot be rolled back (DESIGN.md §14)")
        if self.ecfg.speculate < 0:
            raise ValueError(f"speculate={self.ecfg.speculate} must be >= 0")
        self.params, self.cfg = params, cfg
        # hash namespace: cache entries are keyed to the exact serving
        # recipe — model, precision, KV dtype, mesh degree, page size —
        # so recipes never cross-pollinate (DESIGN.md §11)
        namespace = (f"{cfg.name}|{cfg.sparsity.recipe.name}"
                     f"|kv={cfg.kv_cache_dtype}|tp={self.ecfg.tp}"
                     f"|ps={self.ecfg.page_size}")
        self.injector = (fl.FaultInjector(self.ecfg.faults)
                         if self.ecfg.faults is not None else None)
        self.kv = KVCacheManager(self.ecfg.kv_config(), namespace=namespace,
                                 injector=self.injector)
        # draft sources are pure host-side functions of the token context
        # (runtime.draft): the scheduler proposes, the engine verifies
        self.draft_source = None
        if self.ecfg.speculate > 0:
            kw = ({"vocab_size": cfg.vocab_size}
                  if self.ecfg.draft_source == "random" else {})
            self.draft_source = draft_mod.make_draft_source(
                self.ecfg.draft_source, **kw)
        self.sched = Scheduler(self.kv, self.ecfg.prefill_chunk,
                               policy=make_policy(self.ecfg.policy),
                               prefix_cache=self.ecfg.prefix_cache,
                               max_queue=self.ecfg.max_queue,
                               watchdog=self.ecfg.watchdog,
                               speculate=self.ecfg.speculate,
                               draft_source=self.draft_source)
        self.cache = M.make_paged_cache(cfg, self.ecfg.num_pages,
                                        self.ecfg.page_size,
                                        self.ecfg.max_batch)
        ps = self.ecfg.page_size
        ntp = self.ecfg.tp
        # one fixed-shape COW copy call: enough lanes for a decode batch
        # (<= 1 write page per slot) or a prefill chunk's page span
        self._cow_lanes = max(self.ecfg.max_batch,
                              -(-self.ecfg.prefill_chunk // ps) + 1)

        # every model-evaluating step returns (ids, logits, cache): the
        # greedy argmax runs ON DEVICE (tp.argmax_tokens — TP-global with
        # jnp.argmax tie-breaking), so the host may fetch a few int32 ids
        # instead of [B, vocab] float32 logits, or thread the device-
        # resident ids straight into the next decode dispatch (DESIGN.md
        # §15).  Both outputs always exist — ``device_sample`` only picks
        # which one the host fetches, so the flag never retraces.  The
        # closures' names are spans.PREFILL_STEP, DECODE_STEP, COPY_STEP
        # and VERIFY_STEP: a trace finds each compiled step by them.
        def prefill_step(p, tok, c, pt, start, rlen, slot, reset):
            with tpmod.activate(ntp):
                logits, c = M.paged_prefill_chunk(p, cfg, tok, c, pt, start,
                                                  rlen, slot, reset, ps)
                return tpmod.argmax_tokens(logits), logits, c

        def decode_step(p, tok, c, pt, kvl, act):
            with tpmod.activate(ntp):
                logits, c = M.paged_decode_step(p, cfg, tok, c, pt, kvl,
                                                act, ps)
                return tpmod.argmax_tokens(logits), logits, c

        def copy_step(c, src, dst):
            with tpmod.activate(ntp):
                return M.paged_copy_pages(cfg, c, src, dst)

        # verify lanes: the last emitted token + up to `speculate` drafts
        self._verify_lanes = self.ecfg.speculate + 1

        def verify_step(p, tok, c, pt, kvl, rlen, act):
            with tpmod.activate(ntp):
                logits, c = M.paged_verify_step(p, cfg, tok, c, pt, kvl,
                                                rlen, act, ps)
                return tpmod.argmax_tokens(logits), logits, c

        if ntp > 1:
            tpmod.validate(cfg, ntp)
            self.mesh = tpmod.make_serve_mesh(ntp)
            pspecs = tpmod.serve_param_specs(params, ntp)
            cspecs = tpmod.serve_cache_specs(self.cache)
            # each device holds ONLY its weight/KV shard from here on
            self.params = jax.device_put(
                params, tpmod.named_shardings(pspecs, self.mesh))
            self.cache = jax.device_put(
                self.cache, tpmod.named_shardings(cspecs, self.mesh))
            rep = P()
            logits_spec = P(None, "tp")  # lm_head column-parallel on vocab
            # sampled ids are replicated (argmax_tokens all-gathers the
            # per-shard winners), so their out-spec is P() like any scalar
            self._prefill_fn = jax.jit(jax.shard_map(
                prefill_step, mesh=self.mesh,
                in_specs=(pspecs, rep, cspecs, rep, rep, rep, rep, rep),
                out_specs=(rep, logits_spec, cspecs), check_vma=False))
            self._decode_fn = jax.jit(jax.shard_map(
                decode_step, mesh=self.mesh,
                in_specs=(pspecs, rep, cspecs, rep, rep, rep),
                out_specs=(rep, logits_spec, cspecs), check_vma=False))
            if self.ecfg.speculate > 0:
                # verify logits are [B, K+1, V]: vocab still column-
                # parallel, one extra replicated lane axis in the middle
                self._verify_fn = jax.jit(jax.shard_map(
                    verify_step, mesh=self.mesh,
                    in_specs=(pspecs, rep, cspecs, rep, rep, rep, rep),
                    out_specs=(rep, P(None, None, "tp"), cspecs),
                    check_vma=False))
            # COW page copies are per-shard elementwise on the head-sharded
            # pools; the host-decided (src, dst) pairs replicate, so every
            # shard copies the same page structure (DESIGN.md §11)
            self._cow_fn = jax.jit(jax.shard_map(
                copy_step, mesh=self.mesh, in_specs=(cspecs, rep, rep),
                out_specs=cspecs, check_vma=False))
        else:
            self._prefill_fn = jax.jit(prefill_step)
            self._decode_fn = jax.jit(decode_step)
            self._cow_fn = jax.jit(copy_step)
            if self.ecfg.speculate > 0:
                self._verify_fn = jax.jit(verify_step)
            # commit params + cache to the device up front: committedness
            # is part of the jit cache key and it propagates — once the
            # async loop feeds a committed token array (see _put_tok), the
            # step outputs turn committed, and an uncommitted initial
            # cache would make the NEXT prefill/decode a second trace
            self.params = jax.device_put(self.params, jax.devices()[0])
            self.cache = jax.device_put(self.cache, jax.devices()[0])
        self.completions: dict[int, Completion] = {}
        self._prompts: dict[int, list[int]] = {}
        self.stats = EngineStats(tp=ntp, precision=cfg.sparsity.recipe.name)
        # overlapped-loop state (DESIGN.md §15): the dispatched-but-not-
        # applied decode step (decision + device-resident sampled ids),
        # the instant the last fetched step output became ready (host-gap
        # accounting), and the backoff occurrence counter (jitter)
        self._pending: tuple[DecodeBatch, jax.Array] | None = None
        self._t_ready: float | None = None
        self._backoff_n = 0
        # decode token inputs are committed to the sharding the step
        # OUTPUTS its sampled ids with (replicated under tp): the jit
        # cache keys on input shardings, so an uncommitted numpy token
        # array and a threaded device-resident id array would otherwise
        # be two cache entries — breaking the compile-once contract the
        # moment the fast path fires
        mesh = getattr(self, "mesh", None)
        self._tok_sharding = (jax.sharding.NamedSharding(mesh, P())
                              if mesh is not None else jax.devices()[0])

    def _put_tok(self, arr: np.ndarray) -> jax.Array:
        return jax.device_put(arr, self._tok_sharding)

    # ------------------------------------------------------------ warmup
    def warmup(self) -> float:
        """Compile + first-execute the engine's fixed-shape jitted steps
        (prefill, decode, COW copy — plus verify when speculating)
        outside any measured window.

        The step functions are per-engine closures, so every new engine
        pays jit compilation on its first real step — and ``run`` bills
        that into ``wall_s``, which silently corrupted decode-throughput
        comparisons (a cache-on vs cache-off serve bench measured mostly
        compile time; DESIGN.md §13).  Dummy inputs run each function
        once and every output is DISCARDED: the jitted steps are purely
        functional and nothing is donated, so ``self.cache``, the page
        accounting and the stats are untouched.  After the dummy passes
        each live step function is asserted to hold exactly ONE compiled
        entry — the compile-once contract the fixed shapes exist for
        (DESIGN.md §15); a second trace here means a shape or sharding
        leaked into the cache key.  Returns the elapsed seconds (also
        recorded as ``stats.warmup_s``)."""
        ec = self.ecfg
        t0 = time.time()
        ptab = self.kv.page_table_array()
        jax.block_until_ready(self._prefill_fn(
            self.params, np.zeros((1, ec.prefill_chunk), np.int32),
            self.cache, ptab[:1], np.int32(0), np.int32(ec.prefill_chunk),
            np.int32(0), np.bool_(True)))
        jax.block_until_ready(self._decode_fn(*self._dummy_decode_args()))
        n = self._cow_lanes
        # all lanes carry the out-of-bounds dst id: every write is dropped
        jax.block_until_ready(self._cow_fn(
            self.cache, np.zeros((n,), np.int32),
            np.full((n,), ec.num_pages, np.int32)))
        if ec.speculate > 0:
            # inactive slots drop every write, so the dummy pass is pure
            jax.block_until_ready(self._verify_fn(
                self.params,
                np.zeros((ec.max_batch, self._verify_lanes), np.int32),
                self.cache, ptab, np.zeros((ec.max_batch,), np.int32),
                np.ones((ec.max_batch,), np.int32),
                np.zeros((ec.max_batch,), bool)))
        for name, fn in (("prefill", self._prefill_fn),
                         ("decode", self._decode_fn),
                         ("cow", self._cow_fn),
                         ("verify", getattr(self, "_verify_fn", None))):
            assert fn is None or fn._cache_size() == 1, \
                f"{name} step compiled {fn._cache_size()} times in warmup"
        self.stats.warmup_s = time.time() - t0
        return self.stats.warmup_s

    def _dummy_decode_args(self):
        """Decode-step inputs with every slot inactive (no write lands)."""
        b = self.ecfg.max_batch
        return (self.params, self._put_tok(np.zeros((b,), np.int32)),
                self.cache, self.kv.page_table_array(),
                np.zeros((b,), np.int32), np.zeros((b,), bool))

    def decode_hlo(self) -> str:
        """Optimized HLO text of the compiled decode step — what runs on the
        device, e.g. to check that its kernels are Pallas custom calls.
        Compiles the step again unless a persistent compilation cache
        holds it."""
        return self._decode_fn.lower(
            *self._dummy_decode_args()).compile().as_text()

    def prefill_logits(self, prompt: list[int]) -> np.ndarray:
        """Logits ``[V]`` at the last token of ``prompt`` (at most one
        prefill chunk), computed by the compiled prefill step as a fresh
        sequence over pages ``0..``; the cache it writes is discarded and
        no state changes, as in ``warmup``.  For comparing engines that
        serve the same model under different kernels or recipes."""
        ec = self.ecfg
        if not 0 < len(prompt) <= ec.prefill_chunk:
            raise ValueError(f"prompt of {len(prompt)} tokens does not fit "
                             f"one prefill chunk of {ec.prefill_chunk}")
        chunk = np.zeros((1, ec.prefill_chunk), np.int32)
        chunk[0, :len(prompt)] = prompt
        maxp = self.kv.page_table_array().shape[1]
        pages = (np.arange(maxp, dtype=np.int32) % ec.num_pages)[None]
        _, logits, _ = self._prefill_fn(
            self.params, chunk, self.cache, pages, np.int32(0),
            np.int32(len(prompt)), np.int32(0), np.bool_(True))
        return np.asarray(logits[0], np.float32)

    # ------------------------------------------------------------ intake
    def submit(self, prompt: list[int], max_new_tokens: int,
               rid: int | None = None, arrival: int = 0,
               eos_id: int | None = None, priority: int = 0,
               deadline_steps: int | None = None,
               deadline_s: float | None = None) -> int:
        """Enqueue a request.  Admission is *typed*, never an exception:
        an oversized prompt or a full bounded queue produces a REJECTED
        completion (reason ``prompt_exceeds_capacity`` / ``queue_full`` /
        ``shed_by_policy``) visible immediately in ``self.completions``.

        ``deadline_steps`` caps scheduler steps after arrival (a
        deterministic budget usable in tests); ``deadline_s`` is a
        wall-clock deadline.  Both are checked at decision boundaries
        only, so the fixed-shape jitted steps are untouched."""
        rid = rid if rid is not None else len(self._prompts)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not prompt:
            raise ValueError("prompt must be non-empty")
        with spans.span(spans.SUBMIT, rid=rid):
            self._prompts[rid] = list(prompt)
            # block hashing at enqueue (DESIGN.md §11): the chained full-
            # page hashes ride the request so admission can probe the
            # prefix index
            hashes = (self.kv.hashes_for(prompt)
                      if self.ecfg.prefix_cache else None)
            dstep = (arrival + deadline_steps
                     if deadline_steps is not None else None)
            dt = (time.monotonic() + deadline_s
                  if deadline_s is not None else None)
            self.sched.submit(Request(rid=rid, prompt=list(prompt),
                                      max_new_tokens=max_new_tokens,
                                      arrival=arrival, eos_id=eos_id,
                                      priority=priority, block_hashes=hashes,
                                      deadline_step=dstep, deadline_t=dt))
            self._drain_finished()  # surface immediate rejection/shed
        return rid

    def cancel(self, rid: int) -> bool:
        """Client-initiated cancellation: drop the request whether it is
        waiting or mid-flight (pages/COW refcounts released) and emit a
        CANCELLED completion carrying tokens generated so far.  Returns
        False when ``rid`` is unknown or already terminal.

        With ``async_loop`` a dispatched-but-unapplied decode step may be
        in flight; its tokens are applied FIRST, so cancellation keeps
        exactly the step-boundary semantics of the synchronous loop (the
        cancelled stream includes the token the device already computed,
        and a sequence the in-flight step finished retires as OK rather
        than CANCELLED — DESIGN.md §15 voiding rules)."""
        self._apply_pending()
        self.sched.retire_finished()
        hit = self.sched.cancel(rid)
        self._drain_finished()
        return hit

    # -------------------------------------------------------------- step
    def _sample(self, logits_row: np.ndarray) -> int:
        return int(np.argmax(logits_row))  # greedy (parity with generate)

    def _fetch(self, x) -> np.ndarray:
        """Materialize one step output on host — the engine's ONLY
        device->host synchronization point.  Accounts the payload in
        ``stats.d2h_bytes`` (the §15 decode fast path moves ``[B]`` int32
        per step; the logits fallback moves ``[B, vocab]`` float32) and
        stamps ``_t_ready``: the fetch returning means the device has
        drained its queue, so host time from here to the next dispatch is
        device-idle gap (``stats.host_gap_s``)."""
        with spans.span(spans.FETCH, bytes=x.nbytes):
            arr = np.asarray(x)
        self.stats.d2h_bytes += arr.nbytes
        self._t_ready = time.time()
        return arr

    def _note_dispatch(self) -> None:
        """Called immediately before handing the device new step work:
        closes the host-gap window opened by the last ``_fetch``."""
        if self._t_ready is not None:
            self.stats.host_gap_s += max(0.0, time.time() - self._t_ready)
            self._t_ready = None

    def _apply_pending(self) -> None:
        """Land the in-flight decode step (async loop): fetch its sampled
        ids — blocking until the device finishes it — and append them via
        ``Scheduler.completed_decode``, which skips lanes whose sequence
        left ``running`` between dispatch and apply (§15 voiding)."""
        if self._pending is None:
            return
        batch, ids_dev = self._pending
        self._pending = None
        ids = self._fetch(ids_dev)
        self.sched.completed_decode(
            batch, [int(ids[s.slot]) for s in batch.seqs])

    def _drain_finished(self) -> list[Completion]:
        """Convert the scheduler's terminal :class:`~repro.runtime.
        scheduler.Finished` records (any status) into Completions."""
        out = []
        for fin in self.sched.take_finished():
            comp = Completion(fin.rid, self._prompts.get(fin.rid, []),
                              list(fin.tokens), fin.evictions,
                              status=fin.status, reason=fin.reason,
                              timing=fin.timing)
            self.completions[fin.rid] = comp
            out.append(comp)
        return out

    def _backoff_wait(self, attempt: int) -> None:
        """Backoff between step retries: exponential base with
        deterministic jitter, non-blocking for the overlapped loop.

        Jitter (0.5x–1.5x, blake2b of the fault seed and the backoff
        occurrence number) decorrelates retry storms without breaking
        fault-schedule replay — the delay is a pure function of run
        config, never of wall clock.  Non-blocking: any deferred decode
        apply is drained FIRST (host work the engine would otherwise do
        after the sleep), and only the remainder of the delay is slept;
        the device keeps draining already-dispatched work throughout
        either way, because JAX dispatch is asynchronous and nothing
        here blocks on device results."""
        base = self.ecfg.retry_backoff_s * (2 ** attempt)
        seed = self.ecfg.faults.seed if self.ecfg.faults is not None else 0
        h = hashlib.blake2b(f"backoff|{seed}|{self._backoff_n}".encode(),
                            digest_size=8).digest()
        self._backoff_n += 1
        delay = base * (0.5 + int.from_bytes(h, "big") / 2.0 ** 64)
        t0 = time.time()
        self._apply_pending()
        remaining = delay - (time.time() - t0)
        if remaining > 0:
            time.sleep(remaining)

    def _dispatch(self, fn, *args):
        """Run a jitted step through the fault injector's ``step`` site
        with bounded retry/backoff: a :class:`~repro.runtime.faults.
        TransientStepError` fires *before* the device function runs, so
        retrying is always safe.  Exhausting ``step_retries`` re-raises
        for the caller to fail the decision's requests."""
        if self.injector is None:
            return self._call(fn, *args)
        attempts = self.ecfg.step_retries + 1
        for attempt in range(attempts):
            if self.injector.fire("step"):
                self.stats.step_errors += 1
                if attempt + 1 >= attempts:
                    raise fl.TransientStepError(
                        f"injected step failure persisted through "
                        f"{self.ecfg.step_retries} retries")
                self.stats.step_retries += 1
                if self.ecfg.retry_backoff_s:
                    self._backoff_wait(attempt)
                continue
            return self._call(fn, *args)

    def _call(self, fn, *args):
        """Hand the device one model step: the jitted call returns once
        the step is enqueued and its numpy inputs are on the device."""
        with spans.span(spans.DISPATCH):
            self._note_dispatch()
            return fn(*args)

    def _run_cow(self, pairs) -> None:
        """Execute host-decided copy-on-write page copies on device before
        the step that writes into the (now exclusive) dst pages.  Fixed
        [_cow_lanes] shape — unused lanes carry the out-of-bounds dst id
        ``num_pages`` (dropped writes), so the copy fn compiles once."""
        if not pairs:
            return
        self._note_dispatch()
        n = self._cow_lanes
        for i in range(0, len(pairs), n):
            src = np.zeros((n,), np.int32)
            dst = np.full((n,), self.ecfg.num_pages, np.int32)
            for j, (s, d) in enumerate(pairs[i:i + n]):
                src[j], dst[j] = s, d
            self.cache = self._cow_fn(self.cache, src, dst)
        self.stats.cow_copies += len(pairs)

    def step(self) -> list[Completion]:
        """Execute one scheduler decision; returns newly finished requests
        (any terminal status — OK completions and failures alike).

        With ``async_loop`` (DESIGN.md §15) a decode step may still be in
        flight from the previous call.  The fast path asks the scheduler
        for a *lookahead* decode decision — provably the same batch
        regardless of what the in-flight step sampled — and dispatches it
        immediately, threading the device-resident sampled ids of step N
        in as step N+1's token input (no host round-trip); only then does
        the host land step N's tokens, overlapped with the device running
        step N+1.  When no safe lookahead exists (membership could
        change, deadlines, speculation, faults, page pressure) the
        pending step is applied first and the decision falls through to
        the synchronous path below, which then observes exactly the state
        the synchronous loop would have — that equivalence is what keeps
        async-on traces bitwise identical to async-off.  Fault injection
        disables the fast path outright (``injector`` is not None): the
        lookahead's allocation calls would otherwise shift the
        deterministic per-site fault schedule.

        The step runs inside the ``engine.step`` span and its phases
        inside ``engine.schedule`` / ``prepare`` / ``dispatch`` /
        ``apply`` (``runtime.spans``)."""
        with spans.span(spans.STEP, step=self.stats.steps) as sp:
            self.stats.steps += 1
            if self.ecfg.async_loop and self._pending is not None:
                with spans.span(spans.SCHEDULE):
                    la = (self.sched.lookahead_decode(self._pending[0])
                          if self.injector is None else None)
                if la is not None:
                    _tag(sp, la)
                    return self._threaded_decode(la)
                # slow path: land the in-flight tokens first so
                # next_decision sees the post-step state (retire what the
                # step finished)
                with spans.span(spans.APPLY):
                    self._apply_pending()
                    self.sched.retire_finished()
            return self._sync_step(sp)

    def _threaded_decode(self, la: DecodeBatch) -> list[Completion]:
        """Fast-path decode dispatch (DESIGN.md §15): step N+1 starts from
        step N's on-device token array before step N's results ever reach
        the host."""
        batch, ids_dev = self._pending
        with spans.span(spans.PREPARE):
            self._run_cow(la.cow)  # provably empty on this path (lookahead
            #                        write pages are already exclusive)
            bmax = self.ecfg.max_batch
            kvl = np.zeros((bmax,), np.int32)
            active = np.zeros((bmax,), bool)
            for seq in la.seqs:
                # tokens are not applied yet, so seq.kv_len is the PRE-
                # apply length == post-apply kv_len - 1, the context-
                # written count the decode step wants; inactive lanes of
                # ids_dev carry whatever lane garbage step N computed —
                # rows are batch-independent and masked writes drop them,
                # same as the zero padding the synchronous path feeds
                kvl[seq.slot] = seq.kv_len
                active[seq.slot] = True
            ptab = self.kv.page_table_array()
        ids2, _logits, self.cache = self._call(
            self._decode_fn, self.params, ids_dev, self.cache, ptab, kvl,
            active)
        self.stats.lookahead_steps += 1
        # overlap window: the device is running step N+1 while the host
        # fetches and applies step N here
        with spans.span(spans.APPLY):
            self._apply_pending()
            self._t_ready = None  # device holds queued work — not idle
            self._pending = (la, ids2)
            self.sched.retire_finished()  # no-op by lookahead precondition
            return self._drain_finished()

    def _sync_step(self, sp) -> list[Completion]:
        with spans.span(spans.SCHEDULE):
            decision = self.sched.next_decision()
        _tag(sp, decision)
        if decision is None:
            # no executable work this tick (future arrivals, a voided
            # decision, or a deferred admission); clock has advanced
            return self._drain_finished()

        if (isinstance(decision, PrefillChunk) and self.injector is not None
                and self.injector.poisoned(decision.seq.rid)):
            # poisoned request: fail at dispatch, before the device step
            # runs or the COW copies execute (its dst pages are freed
            # unread, so skipping the copies is safe — the pairs all
            # belong to this one sequence)
            self.sched.fail(decision.seq, sch.REASON_POISONED)
            return self._drain_finished()

        with spans.span(spans.PREPARE):
            self._run_cow(decision.cow)
            fn, args = self._inputs(decision)
        try:
            ids, logits, self.cache = self._dispatch(fn, *args)
        except fl.TransientStepError:
            # retries exhausted: the device function never ran (injection
            # precedes dispatch), so page state is consistent — fail the
            # decision's requests and keep serving everyone else
            ids = None
            doomed = ([decision.seq] if isinstance(decision, PrefillChunk)
                      else list(decision.seqs))
            for seq in doomed:
                self.sched.fail(seq, sch.REASON_STEP_ERROR)
        if ids is not None and self.ecfg.async_loop and isinstance(
                decision, DecodeBatch):
            # defer the apply: tokens land at the next step() / cancel()
            # boundary, overlapped with host scheduling (and possibly a
            # threaded next dispatch) — §15
            self._pending = (decision, ids)
            return self._drain_finished()
        with spans.span(spans.APPLY):
            if ids is not None:
                self._land(decision, ids, logits)
            self.sched.retire_finished()
            return self._drain_finished()

    def _inputs(self, decision):
        """The jitted step that executes ``decision`` and its arguments,
        built on the host from the scheduler's state."""
        bmax = self.ecfg.max_batch
        if isinstance(decision, PrefillChunk):
            seq, start, length = decision.seq, decision.start, decision.length
            chunk = seq.prompt[start:start + length]
            chunk = chunk + [0] * (self.ecfg.prefill_chunk - length)
            pt = self.kv.page_table_array()[seq.slot:seq.slot + 1]
            return self._prefill_fn, (
                self.params, np.asarray([chunk], np.int32), self.cache, pt,
                np.int32(start), np.int32(length), np.int32(seq.slot),
                np.bool_(start == seq.resume_pos))
        kvl = np.zeros((bmax,), np.int32)
        active = np.zeros((bmax,), bool)
        if isinstance(decision, VerifyBatch):
            lanes = self._verify_lanes
            token = np.zeros((bmax, lanes), np.int32)
            rlen = np.ones((bmax,), np.int32)
            for seq, drft in zip(decision.seqs, decision.drafts):
                token[seq.slot, 0] = seq.out_tokens[-1]
                token[seq.slot, 1:1 + len(drft)] = drft
                kvl[seq.slot] = seq.kv_len - 1  # context written
                rlen[seq.slot] = 1 + len(drft)
                active[seq.slot] = True
            return self._verify_fn, (
                self.params, token, self.cache, self.kv.page_table_array(),
                kvl, rlen, active)
        assert isinstance(decision, DecodeBatch)
        token = np.zeros((bmax,), np.int32)
        for seq in decision.seqs:
            token[seq.slot] = seq.out_tokens[-1]
            kvl[seq.slot] = seq.kv_len - 1  # context written
            active[seq.slot] = True
        return self._decode_fn, (
            self.params, self._put_tok(token), self.cache,
            self.kv.page_table_array(), kvl, active)

    def _land(self, decision, ids, logits) -> None:
        """Fetch what the host needs of an executed step's outputs and
        hand the scheduler its tokens."""
        if isinstance(decision, PrefillChunk):
            seq = decision.seq
            self.sched.completed_prefill(decision)
            if not seq.prefilling:  # prompt done -> first token
                # mid-prompt chunks fetch NOTHING (pure dispatch); the
                # final chunk fetches [1] int32 — or the logits row on
                # the fallback path
                if self.ecfg.device_sample:
                    tok = int(self._fetch(ids)[0])
                else:
                    tok = self._sample(self._fetch(logits[0]))
                self.sched.append_token(seq, tok)
        elif isinstance(decision, VerifyBatch):
            if self.ecfg.device_sample:
                argmax_all = self._fetch(ids)     # [B, K+1] int32
            else:
                # logits fallback: one batched argmax over the whole
                # [B, K+1, V] block (the former per-lane Python loop,
                # vectorized — same first-occurrence tie-breaking)
                argmax_all = np.argmax(self._fetch(logits), axis=-1)
            results = []
            for seq, drft in zip(decision.seqs, decision.drafts):
                # lane i's logits predict the token after lane i; lanes
                # past real_len are padding — never consulted
                argmax = [int(t) for t in
                          argmax_all[seq.slot, :1 + len(drft)]]
                n_acc, emitted = draft_mod.accept_drafts(drft, argmax)
                eos = seq.req.eos_id
                if eos is not None and eos in emitted:
                    # tokens after eos were never really generated; if
                    # the cut drops the bonus token, every emitted token
                    # is an accepted draft
                    emitted = emitted[:emitted.index(eos) + 1]
                    n_acc = min(n_acc, len(emitted))
                results.append((n_acc, emitted))
            # appends tokens, counts accept stats, truncates rejected-
            # suffix pages (KV rollback, DESIGN.md §14)
            self.sched.completed_verify(decision, results)
        else:
            if self.ecfg.device_sample:
                toks = self._fetch(ids)           # [B] int32
            else:
                toks = np.argmax(self._fetch(logits), axis=-1)
            for seq in decision.seqs:
                self.sched.append_token(seq, int(toks[seq.slot]))

    def run(self, on_step=None) -> dict[int, Completion]:
        """Drive until every submitted request reaches a terminal status.

        ``on_step(engine, step_index)``, when given, runs after every
        engine step — the hook chaos tests and demos use to submit or
        cancel mid-flight on a deterministic schedule."""
        t0 = time.time()
        while self.sched.has_work:
            self.step()
            if on_step is not None:
                on_step(self, self.stats.steps)
        self._apply_pending()  # async: nothing may stay in flight past run
        self.sched.retire_finished()
        self._drain_finished()
        jax.block_until_ready(self.cache)
        s, ss = self.stats, self.sched.stats
        s.wall_s = time.time() - t0
        s.overlap_frac = max(0.0, min(1.0, 1.0 - s.host_gap_s
                                      / max(s.wall_s, 1e-9)))
        s.decode_tokens, s.decode_steps = ss.decode_tokens, ss.decode_steps
        s.prefill_tokens, s.evictions = ss.prefill_tokens, ss.evicted
        s.recompute_tokens = ss.recompute_tokens
        s.mean_occupancy = ss.mean_occupancy
        s.verify_steps = ss.verify_steps
        s.draft_tokens = ss.draft_tokens
        s.accepted_tokens = ss.accepted_tokens
        s.prefix_cache = self.ecfg.prefix_cache
        s.prefix_hit_tokens = ss.prefix_hit_tokens
        s.prefill_chunks_skipped = ss.prefill_chunks_skipped
        s.cached_page_evictions = self.kv.pool.cached_evictions
        # request lifecycle (DESIGN.md §12)
        s.cancelled, s.timeouts = ss.cancelled, ss.timeouts
        s.rejected, s.failed = ss.rejected, ss.failed
        s.quarantined = ss.quarantined
        s.admission_deferrals = ss.admission_deferrals
        s.p95_queue_wait_steps = ss.queue_wait_pct(95.0)
        s.completed_ok = sum(1 for c in self.completions.values() if c.ok)
        s.goodput_tokens = sum(len(c.tokens)
                               for c in self.completions.values() if c.ok)
        if self.injector is not None:
            s.faults_injected = self.injector.total_injected
        return dict(self.completions)
