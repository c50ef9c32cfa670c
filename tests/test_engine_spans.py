"""The engine's host spans and request lifecycle stamps (DESIGN.md §15).

* a tiny engine traced by the profiler on the CPU: every ``engine.step``
  holds its phases, nested within it, in the synchronous loop and the
  overlapped one;
* the stamps under an injected clock keep ``t_submit <= t_admit <=
  t_first_chunk <= t_first_token``, and a request evicted and requeued
  keeps its first stamps;
* the compiled step programs carry the names ``runtime.spans`` gives.
"""
import dataclasses
import glob
import itertools
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import registry
from repro.models import model as M
from repro.runtime import scheduler, serve_loop, spans
from repro.runtime.kv_cache import KVCacheManager, PagedKVConfig


@pytest.fixture(scope="module")
def tiny():
    base = registry.smoke_config("h2o-danube-3-4b")
    cfg = dataclasses.replace(base, d_model=48, num_heads=4, num_kv_heads=2,
                              head_dim=12, num_layers=2)
    return cfg, M.init(cfg, jax.random.PRNGKey(0))


def _engine(tiny, **over):
    cfg, params = tiny
    ecfg = dataclasses.replace(serve_loop.EngineConfig(
        max_batch=3, page_size=4, num_pages=32, max_seq_len=32,
        prefill_chunk=6), **over)
    eng = serve_loop.ServeEngine(params, cfg, ecfg)
    eng.warmup()
    return eng


def _events(trace_dir):
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(spans.PREFIX):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                                {k: v for k, v in ev.stats}))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _within(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("async_loop", [False, True])
def test_every_step_holds_its_phases(tiny, tmp_path, async_loop):
    eng = _engine(tiny, async_loop=async_loop)
    rng = np.random.default_rng(0)
    # prompts of 5, 8 and 11 tokens: one, two and two chunks of 6
    lens = (5, 8, 11)
    jax.profiler.start_trace(str(tmp_path))
    for rid, n in enumerate(lens):
        eng.submit(rng.integers(0, tiny[0].vocab_size, n).tolist(), 6,
                   rid=rid)
    n_steps = 0
    while eng.sched.has_work:
        eng.step()
        n_steps += 1
    jax.profiler.stop_trace()
    assert len(eng.completions) == 3
    # the overlapped loop took its fast path (threaded decode) too
    assert (eng.stats.lookahead_steps > 0) == async_loop
    assert all(c.ok for c in eng.completions.values())

    evs = _events(str(tmp_path))
    steps = [e for e in evs if e[0] == spans.STEP]
    assert [e[3]["step"] for e in steps] == list(range(n_steps))
    kinds = [e[3]["kind"] for e in steps]
    ss = eng.sched.stats
    assert kinds.count("prefill") == ss.prefill_chunks == 5
    assert kinds.count("decode") == ss.decode_steps
    assert [e[3]["rid"] for e in steps if e[3]["kind"] == "prefill"] \
        == [0, 1, 1, 2, 2]
    assert all(e[3]["lanes"] >= 1 for e in steps if e[3]["kind"] != "none")

    nested = set()
    for step in steps:
        kids = [i for i, c in enumerate(evs)
                if c[0] != spans.STEP and _within(c, step)]
        nested.update(kids)
        names = {evs[i][0] for i in kids}
        if step[3]["kind"] == "none":
            continue
        assert {spans.SCHEDULE, spans.PREPARE, spans.DISPATCH} <= names
        if not async_loop and step[3]["kind"] == "decode":
            assert {spans.FETCH, spans.APPLY} <= names
        # a fetch lands a step's results: always inside engine.apply
        for f in (evs[i] for i in kids if evs[i][0] == spans.FETCH):
            assert any(_within(f, evs[i]) for i in kids
                       if evs[i][0] == spans.APPLY)
    loose = [e for i, e in enumerate(evs)
             if e[0] != spans.STEP and i not in nested]
    # outside any step: the submits alone (a decode step left in flight
    # keeps its lanes running, so a later step lands it)
    assert {e[0] for e in loose} == {spans.SUBMIT}
    assert [e[3]["rid"] for e in evs if e[0] == spans.SUBMIT] == [0, 1, 2]
    # each step's output is fetched once: a decode step's ids, and the
    # first token of each prompt's final chunk
    fetches = [e for e in evs if e[0] == spans.FETCH]
    assert len(fetches) == ss.decode_steps + len(lens)
    assert sum(e[3]["bytes"] for e in fetches) == eng.stats.d2h_bytes


def _check_order(t):
    assert None not in (t.t_submit, t.t_admit, t.t_first_chunk,
                        t.t_first_token)
    assert t.t_submit <= t.t_admit <= t.t_first_chunk <= t.t_first_token


@pytest.mark.parametrize("async_loop", [False, True])
def test_lifecycle_stamps_survive_eviction(tiny, async_loop):
    """Under page pressure a request is evicted and requeued; its
    completion carries the stamps of its first admission, first chunk and
    first token."""
    eng = _engine(tiny, num_pages=7, max_seq_len=28, prefill_chunk=8,
                  async_loop=async_loop)
    eng.sched.time_fn = itertools.count().__next__   # a tick per stamp
    rng = np.random.default_rng(7)
    for rid, n in enumerate((9, 13, 11)):
        eng.submit(rng.integers(0, tiny[0].vocab_size, n).tolist(), 8,
                   rid=rid)
    first: dict[int, dict] = {}

    def snapshot(e, _step):
        reqs = [s.req for s in e.sched.running] + list(e.sched.waiting)
        for r in reqs:
            seen = first.setdefault(r.rid, {})
            for k, v in dataclasses.asdict(r.timing).items():
                if v is not None:
                    seen.setdefault(k, v)

    out = eng.run(on_step=snapshot)
    assert eng.stats.evictions > 0, "test needs page pressure"
    evicted = [c for c in out.values() if c.evictions]
    assert evicted
    for c in out.values():
        assert c.ok
        _check_order(c.timing)
        for k, v in first[c.rid].items():
            assert getattr(c.timing, k) == v
    # the evicted request was admitted again after its first stamps
    assert eng.sched.stats.admitted > len(out)


def test_scheduler_stamps_each_event_once():
    """Driven without an engine: each stamp reads the clock once, when its
    event first happens, and a REJECTED request is stamped at submit."""
    ticks = itertools.count(100)
    cfg = PagedKVConfig(page_size=4, num_pages=6, max_batch=3,
                        max_seq_len=24)
    sched = scheduler.Scheduler(KVCacheManager(cfg), prefill_chunk=8,
                                time_fn=ticks.__next__)
    reqs = [scheduler.Request(rid=i, prompt=[0] * 8, max_new_tokens=8)
            for i in range(3)]
    big = scheduler.Request(rid=9, prompt=[0] * 30, max_new_tokens=1)
    for r in reqs + [big]:
        assert r.timing == scheduler.Timing()
        sched.submit(r)
    assert [r.timing.t_submit for r in reqs + [big]] == [100, 101, 102, 103]
    seen = {}
    while sched.has_work:
        d = sched.next_decision()
        if d is None:
            continue
        if isinstance(d, scheduler.PrefillChunk):
            sched.completed_prefill(d)
            if not d.seq.prefilling:
                sched.append_token(d.seq, 1)
        else:
            sched.completed_decode(d, [1] * len(d.seqs))
        for r in reqs:     # a stamp, once taken, never moves
            for k, v in dataclasses.asdict(r.timing).items():
                if v is not None:
                    assert seen.setdefault((r.rid, k), v) == v
        sched.retire_finished()
    assert sched.stats.evicted > 0, "test needs page pressure"
    fins = {f.rid: f for f in sched.take_finished()}
    assert fins[9].status == scheduler.REJECTED
    assert fins[9].timing.t_submit == 103 and fins[9].timing.t_admit is None
    stamps = []
    for r in reqs:
        t = fins[r.rid].timing
        _check_order(t)
        assert t is r.timing
        stamps += [t.t_admit, t.t_first_chunk, t.t_first_token]
    # every stamp its own tick: nothing was stamped twice
    assert len(set(stamps)) == len(stamps)
    assert max(stamps) < next(ticks)


def test_step_programs_carry_the_span_module_names(tiny):
    """A trace finds each compiled step by ``jit_<name>``; renaming a step
    closure has to change ``runtime.spans`` with it."""
    eng = _engine(tiny, speculate=1)
    ec = eng.ecfg
    ptab = eng.kv.page_table_array()
    b, n = ec.max_batch, eng._cow_lanes
    lowered = {
        spans.PREFILL_STEP: eng._prefill_fn.lower(
            eng.params, np.zeros((1, ec.prefill_chunk), np.int32),
            eng.cache, ptab[:1], np.int32(0), np.int32(1), np.int32(0),
            np.bool_(True)),
        spans.DECODE_STEP: eng._decode_fn.lower(*eng._dummy_decode_args()),
        spans.COPY_STEP: eng._cow_fn.lower(
            eng.cache, np.zeros((n,), np.int32), np.zeros((n,), np.int32)),
        spans.VERIFY_STEP: eng._verify_fn.lower(
            eng.params, np.zeros((b, 2), np.int32), eng.cache, ptab,
            np.zeros((b,), np.int32), np.ones((b,), np.int32),
            np.zeros((b,), bool)),
    }
    for step, low in lowered.items():
        assert f"module @{spans.program(step)} " in low.as_text()


def test_span_names():
    assert len(set(spans.NAMES)) == len(spans.NAMES) == 7
    assert all(n.startswith(spans.PREFIX) for n in spans.NAMES)
