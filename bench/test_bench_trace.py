"""The trace reduction: worked by hand on a constructed window, and on a
small trace recorded on the chip (``testdata/``, made by
``bench/record_trace.py``)."""
import os

import pytest

from bench import trace

MS = 1_000_000  # ns


def _summary():
    # window 0..100 ms; ops overlap (10-30, 20-40), a loop 60-90 holds a
    # kernel 60-70, and one op straddles the window's end (95-120)
    ops = [trace.Op("compressed_matmul_pallas", 10 * MS, 30 * MS),
           trace.Op("b", 20 * MS, 40 * MS),
           trace.Op("while", 60 * MS, 90 * MS),
           trace.Op("compressed_matmul_pallas", 60 * MS, 70 * MS),
           trace.Op("c", 95 * MS, 120 * MS)]
    host = [("bench.window", 0, 100 * MS, {}),
            ("bench.step", 5 * MS, 45 * MS, {"step": 7}),
            ("bench.record", 45 * MS, 58 * MS, {}),
            ("bench.step", 58 * MS, 99 * MS, {"step": 8}),
            ("bench.step", 99 * MS, 130 * MS, {"step": 9})]
    mods = [("jit_prefill_step(3)", 10 * MS, 40 * MS),
            ("jit_decode_step(4)", 60 * MS, 70 * MS),
            ("jit_prefill_step(3)", 101 * MS, 120 * MS)]
    return trace.Summary((0, 100 * MS), ops, mods, host)


def test_busy_gaps_and_owners_by_hand():
    s = _summary()
    assert s.busy_intervals() == [(10 * MS, 40 * MS), (60 * MS, 90 * MS),
                                  (95 * MS, 100 * MS)]
    assert s.busy_s == pytest.approx(0.065)
    assert s.window_s == pytest.approx(0.1)
    assert s.gaps() == [(0, 10 * MS), (40 * MS, 60 * MS), (90 * MS, 95 * MS)]
    # 0-10: step 7 covers 5 ms; 40-60: record covers 13 ms of 20;
    # 90-95: step 8 covers all of it
    assert s.idle_by_span() == pytest.approx(
        {"bench.step": 0.015, "bench.record": 0.020})
    assert s.steps == {7, 8}
    assert s.kernel_seconds(trace.GEMM_KERNEL) == pytest.approx(0.030)
    assert s.module_seconds("jit_prefill_step") == pytest.approx([0.030])
    # the loop's own time is what its kernel leaves of it
    assert s.op_seconds() == pytest.approx({
        "compressed_matmul_pallas": 0.030, "b": 0.020, "while": 0.020,
        "c": 0.005})
    b = s.breakdown()
    assert b["device_ops"][0] == ["compressed_matmul_pallas",
                                  pytest.approx(0.030)]
    assert [k for k, _ in b["idle_gaps"]] == ["bench.record", "bench.step"]


def test_op_names():
    assert trace.op_name("%compressed_matmul_pallas.68 = bf16[16,3840] "
                         "custom-call(...)") == "compressed_matmul_pallas"
    assert trace.op_name("%copy-start.12 = (s32[1,128]) copy-start(s)") \
        == "copy-start"
    assert trace.op_name("%fusion = f32[2] fusion(...)") == "fusion"


RECORDED = os.path.join(os.path.dirname(__file__), "testdata",
                        "danube4b-68-bf16.layer1.xplane.pb")


def test_recorded_trace():
    """A one-layer cut of danube4b-68-bf16 on a v5e: two prefill chunks
    and four decode steps inside ``bench.window``.  By hand from the
    file: the window runs 43793009-81435698 ns; the 790 ops of line
    ``XLA Ops`` never overlap and sum to 6103853 ns; 48 of them are the
    compressed GEMM, 5670050 ns; the modules ran 1134563 and 1133788 ns
    (prefill) and 961414, 959779, 959727, 959345 ns (decode)."""
    s = trace.reduce(RECORDED)
    assert s.window == (43793009, 81435698)
    assert s.busy_s == pytest.approx(6103853e-9)
    assert s.kernel_seconds(trace.GEMM_KERNEL) == pytest.approx(5670050e-9)
    assert s.module_seconds("jit_prefill_step") == pytest.approx(
        [1134563e-9, 1133788e-9])
    assert s.module_seconds("jit_decode_step") == pytest.approx(
        [961414e-9, 959779e-9, 959727e-9, 959345e-9])
    assert s.steps == set(range(6))
    assert sum(s.op_seconds().values()) == pytest.approx(6103853e-9)
    idle = s.idle_by_span()
    assert sum(idle.values()) == pytest.approx(
        (81435698 - 43793009 - 6103853) * 1e-9)
    assert set(idle) <= {"bench.step", "bench.record"}


def test_prefill_chunk_ms_reads_the_prefill_program():
    from bench.metrics import prefill_chunk_ms

    s = trace.reduce(RECORDED)
    assert prefill_chunk_ms.read(None, s) == pytest.approx(
        (1134563 + 1133788) / 2 * 1e-6)
