"""What the program's spans and request stamps give (``bench/engine_trace.py``):
worked by hand on a constructed window, on the committed trace that holds
no program span, and on a tiny cell driven on the CPU."""
import glob
import os

import pytest

from bench import engine_trace as et
from bench import model, trace
from bench.driver import Compiles, ReqRecord, Window, WindowRecord

from repro.runtime import scheduler, spans

MS = 1_000_000  # ns
OLD = os.path.join(os.path.dirname(__file__), "testdata",
                   "danube4b-68-bf16.layer1.xplane.pb")


def _window():
    """Window 0-100 ms; the device runs 10-30 and 60-90.  Two whole steps
    (A: 6-44, B: 51-94) and one that runs past the window's end (C)."""
    ops = [trace.Op("a", 10 * MS, 30 * MS), trace.Op("b", 60 * MS, 90 * MS)]
    host = [("bench.window", 0, 100 * MS, {}),
            ("bench.step", 5 * MS, 45 * MS, {"step": 7}),
            ("bench.record", 45 * MS, 50 * MS, {}),
            ("bench.step", 50 * MS, 95 * MS, {"step": 8}),
            ("bench.step", 95 * MS, 130 * MS, {"step": 9})]
    program = [(n, s * MS, e * MS, {}) for n, s, e in (
        ("engine.step", 6, 44), ("engine.schedule", 6, 8),
        ("engine.prepare", 8, 9), ("engine.dispatch", 9, 10),
        ("engine.apply", 28, 43), ("engine.fetch", 28, 40),
        ("engine.step", 51, 94), ("engine.schedule", 51, 55),
        ("engine.prepare", 55, 58), ("engine.dispatch", 58, 61),
        ("engine.apply", 89, 93), ("engine.fetch", 89, 92),
        ("engine.step", 96, 130))]
    return trace.Summary((0, 100 * MS), ops, [], host), program


def test_step_host_ms_by_hand():
    s, program = _window()
    # A: 38 ms less its 12 ms fetch; B: 43 less 3; C is not whole
    assert et.step_host_ms(s.window, program) == pytest.approx(33.0)
    assert et.step_host_ms(s.window, []) is None


def test_innermost_by_hand():
    spans_ = [("bench.step", 0, 10), ("engine.step", 2, 9),
              ("engine.fetch", 3, 4), ("engine.apply", 3, 6),
              ("bench.record", 10, 12), ("engine.x", 12, 12)]
    # the fetch opened inside the apply at the same instant: it is shorter
    assert et.innermost(spans_) == [
        (0, 2, "bench.step"), (2, 3, "engine.step"), (3, 4, "engine.fetch"),
        (4, 6, "engine.apply"), (6, 9, "engine.step"), (9, 10, "bench.step"),
        (10, 12, "bench.record")]


def test_idle_gaps_program_by_hand():
    s, program = _window()
    # gap 0-10: none 0-5, bench.step 5-6, schedule 6-8, prepare 8-9,
    # dispatch 9-10; gap 30-60: fetch 30-40, apply 40-43, engine.step
    # 43-44, bench.step 44-45, record 45-50, bench.step 50-51, schedule
    # 51-55, prepare 55-58, dispatch 58-60; gap 90-100: fetch 90-92,
    # apply 92-93, engine.step 93-94, bench.step 94-96, engine.step 96-100
    got = et.idle_gaps_program(s, program)
    assert got == pytest.approx({
        "none": 0.005, "bench.step": 0.005, "bench.record": 0.005,
        "engine.schedule": 0.006, "engine.prepare": 0.004,
        "engine.dispatch": 0.003, "engine.fetch": 0.012,
        "engine.apply": 0.004, "engine.step": 0.006})
    assert sum(got.values()) == pytest.approx(s.window_s - s.busy_s)
    # the benchmark's own table puts every gap under bench.step
    assert s.idle_by_span() == pytest.approx({"bench.step": 0.05})
    # without program spans, the stretches go to the benchmark's spans
    assert et.idle_gaps_program(s, []) == pytest.approx({
        "none": 0.005, "bench.step": 0.040, "bench.record": 0.005})


def test_prefill_waits_by_hand():
    reqs = {0: ReqRecord(0, 0, 1.0, [1], 4), 1: ReqRecord(1, 1, 2.0, [1], 4),
            2: ReqRecord(2, 2, 11.0, [1], 4)}
    rec = WindowRecord(0.0, 10.0, reqs, [])
    timing = {0: scheduler.Timing(100.0, 100.1, 100.5, 101.0),
              1: scheduler.Timing(100.2, 100.3, None, None),   # no chunk yet
              2: scheduler.Timing(110.0, 110.0, 110.0, 110.0)}  # not due
    # waits 0.5 s and 3.0 s (1's until the close at 103.2)
    assert et.prefill_wait_p90_ms(rec, timing, 103.2) == pytest.approx(
        (0.5 + 0.9 * 2.5) * 1e3)
    assert et.prefill_service_p90_ms(rec, timing) == pytest.approx(500.0)
    assert et.prefill_wait_p90_ms(rec, {}, 103.2) is None
    assert et.prefill_service_p90_ms(rec, {}) is None


def test_old_trace_holds_no_program_span():
    """The trace recorded before the program had spans: nothing to read,
    and the reduction's numbers as ``test_recorded_trace`` pins them."""
    data = trace.load(OLD)
    assert et.collect(data) == []
    s = trace.summarize(data)
    assert et.step_host_ms(s.window, []) is None
    got = et.idle_gaps_program(s, [])
    assert sum(got.values()) == pytest.approx(
        (81435698 - 43793009 - 6103853) * 1e-9)
    assert set(got) <= {"bench.step", "bench.record", "none"}


NEW = os.path.join(os.path.dirname(__file__), "testdata",
                   "danube4b-68-bf16.layer1.engine.xplane.pb")


def test_recorded_trace_with_program_spans():
    """The same one-layer cut recorded with the engine's spans
    (``bench/record_trace.py`` on a v5e): two one-chunk prefills and four
    decode steps.  By hand from the file: each ``bench.step`` holds one
    ``engine.step`` with its five phases, the fetch inside the apply; the
    existing reduction reads it as before."""
    data = trace.load(NEW)
    s = trace.summarize(data)
    assert s.window == (47635549, 86002348)
    assert s.steps == set(range(6))
    assert s.busy_s == pytest.approx(6096148e-9)
    assert s.module_seconds("jit_prefill_step") == pytest.approx(
        [1132426e-9, 1131802e-9])
    assert s.idle_by_span() == pytest.approx(
        {"bench.step": 0.010719413, "bench.record": 0.021551238})

    program = et.collect(data)
    assert len(program) == 36
    steps = [p for p in program if p[0] == spans.STEP]
    assert [(st["step"], st["kind"], st["lanes"], st.get("rid"))
            for _, _, _, st in steps] == [
        (0, "prefill", 1, 0), (1, "decode", 1, None),
        (2, "prefill", 1, 1), (3, "decode", 2, None),
        (4, "decode", 2, None), (5, "decode", 1, None)]
    bench_steps = [h for h in s.host if h[0] == "bench.step"]
    phases = [spans.SCHEDULE, spans.PREPARE, spans.DISPATCH, spans.APPLY,
              spans.FETCH]
    for (_, bs, be, bst), (_, es, ee, est) in zip(bench_steps, steps):
        assert bst["step"] == est["step"] and bs <= es and ee <= be
        kids = [p for p in program if p[0] != spans.STEP
                and es <= p[1] and p[2] <= ee]
        assert [p[0] for p in kids] == phases
        (apply,), (fetch,) = kids[3:4], kids[4:]
        assert apply[1] <= fetch[1] and fetch[2] <= apply[2]
        assert fetch[3]["bytes"] == (4 if est["kind"] == "prefill" else 16)
    # steps 0 and 1 by hand: 4448020 ns less a fetch of 2286040, and
    # 3680639 less 1886129
    fetches = [p for p in program if p[0] == spans.FETCH]
    assert [e - b for _, b, e, _ in steps[:2]] == [4448020, 3680639]
    assert [e - b for _, b, e, _ in fetches[:2]] == [2286040, 1886129]
    assert et.step_host_ms(s.window, program) == pytest.approx(
        1.7638551666666666)
    assert et.idle_gaps_program(s, program) == pytest.approx({
        "engine.fetch": 0.012091268, "engine.dispatch": 0.001536021,
        "engine.prepare": 0.001208545, "engine.schedule": 0.000725219,
        "engine.apply": 0.00055453, "engine.step": 0.000462668,
        "bench.record": 0.01547133, "none": 0.0001656,
        "bench.step": 0.00005547})


def test_names_are_the_programs():
    """Readers find spans and programs by name: a rename in the program
    has to fail here, not leave a metric silently missing."""
    from bench.metrics import prefill_chunk_ms

    assert et.PREFIX == spans.PREFIX
    assert (et.STEP, et.FETCH) == (spans.STEP, spans.FETCH)
    assert prefill_chunk_ms.MODULE == spans.program(spans.PREFILL_STEP)


def test_a_tiny_cell_on_the_cpu(tmp_path):
    """The window driven on the CPU with a trace: every request due in it
    has its stamps, and the program's spans partition the idle window
    (no device plane: all of it is idle)."""
    from bench import traffic
    from bench.test_bench_run import MIX, TINY

    cfg = model.program_config(TINY)
    eng = model.make_engine(model.init_params(cfg, 5), cfg, MIX["engine"])
    eng.warmup()
    plan = traffic.Plan(MIX, TINY["vocab_size"], 5)
    rec = Window(eng, plan, Compiles()).run(1.5, trace_dir=str(tmp_path))
    close = eng.sched.time_fn()
    timing = et.stamps(eng)
    assert set(rec.requests) <= set(timing)
    assert et.prefill_wait_p90_ms(rec, timing, close) > 0
    assert et.prefill_service_p90_ms(rec, timing) > 0

    data = trace.load(glob.glob(os.path.join(str(tmp_path), "**",
                                             "*.xplane.pb"),
                                recursive=True)[0])
    program = et.collect(data)
    host = sorted(((ev.name, int(ev.start_ns), int(ev.end_ns),
                    dict(ev.stats))
                   for plane in data.planes if plane.name.startswith("/host:")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("bench.")), key=lambda h: h[1])
    window = next((s, e) for n, s, e, _ in host if n == "bench.window")
    s = trace.Summary(window, [], [], host)
    out = et.trace_report(s, program, rec)
    assert et.request_report(rec, timing, close)["ttft_p90_ms"] > 0
    assert out["step_host_ms"] > 0
    assert out["spans_in_window"]["engine.step"] == len(s.steps)
    names = {k for k, _ in out["idle_gaps_program"]}
    assert names & set(spans.NAMES) and names <= set(spans.NAMES) | {
        "bench.step", "bench.record", "bench.submit", "none"}
    assert sum(v for _, v in out["idle_gaps_program"]) == pytest.approx(
        s.window_s)
    assert out["engine_share_of_step_idle"] > 50
