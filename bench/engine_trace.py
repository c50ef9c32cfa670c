"""What the program's own spans and request stamps say about a cell.

The engine writes named host spans into the profiler's trace
(``engine.step`` and its phases, ``repro.runtime.spans``) and stamps each
request when it is submitted, admitted, given its first prefill chunk and
its first token (``Completion.timing``).  From them:

- ``step_host_ms``: the mean, over the ``engine.step`` spans wholly in the
  traced window, of the span's length less the time its ``engine.fetch``
  spans cover: the host's own work per step;
- ``idle_gaps_program``: the window's device idle seconds by the span
  the host was in, each stretch of a gap going to the innermost
  ``engine.*`` span over it, else to the innermost benchmark span
  (``bench.window`` aside), else to ``none``;
- ``prefill_wait_p90_ms``: p90 over the requests due in the window of
  ``t_first_chunk - t_submit``, the wait for a prefill turn (a request
  with no chunk by the close counts its wait until then);
- ``prefill_service_p90_ms``: p90 over those with a first token of
  ``t_first_token - t_first_chunk``, the prefill with the decode steps
  interleaved between its chunks.

A program without the spans or stamps gives each of them nothing: None,
or an empty table.  On the chip, the command below runs a cell's window
with the trace that ``bench.run --trace 1`` records and prints one JSON
line: these numbers beside the trace's existing breakdown, the host's
mean step time inside and outside the traced part, and what a span
costs while nothing records.  Starting and stopping the trace stalls the
loop for a moment, which the requests waiting then feel; ``--trace 0``
records no trace and prints the stamps' split alone.  It checks no
output; ``bench.run`` does.

    python3 -m bench.engine_trace --workload phi3m-68-int8.prefill --seed 7 --seconds 51 [--trace 0]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time
import types

import numpy as np

# the program's span names (repro.runtime.spans)
PREFIX = "engine."
STEP = "engine.step"
FETCH = "engine.fetch"
WINDOW = "bench.window"


def collect(data) -> list[tuple[str, int, int, dict]]:
    """The ``engine.*`` host events of a trace (``ProfileData``) as
    ``(name, start_ns, end_ns, stats)``, by start."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name, int(ev.start_ns), int(ev.end_ns),
                                {k: v for k, v in ev.stats}))
    out.sort(key=lambda h: (h[1], -h[2]))
    return out


def step_host_ms(window: tuple[int, int], program) -> float | None:
    """Mean host time per step in ``window`` (ns, trace clock), fetches
    excluded (ms).  The loop runs on one thread, so fetches never
    overlap."""
    w0, w1 = window
    steps = [(s, e) for n, s, e, _ in program
             if n == STEP and w0 <= s and e <= w1]
    if not steps:
        return None
    fetches = [(s, e) for n, s, e, _ in program if n == FETCH]
    own = [(e - s) - sum(max(0, min(e, fe) - max(s, fs))
                         for fs, fe in fetches)
           for s, e in steps]
    return float(np.mean(own)) * 1e-6


def innermost(spans) -> list[tuple[int, int, str]]:
    """``(start, end, name)`` stretches, each under one innermost span of
    ``spans`` (``(name, start, end)``): an ``engine.*`` span before a
    benchmark span, then the one opened last."""
    spans = [sp for sp in spans if sp[2] > sp[1]]
    opens: dict[int, list] = {}
    closes: dict[int, list] = {}
    for i, (_, s, e) in enumerate(spans):
        opens.setdefault(s, []).append(i)
        closes.setdefault(e, []).append(i)
    active: set[int] = set()
    out = []
    edges = sorted(set(opens) | set(closes))
    for a, b in zip(edges, edges[1:]):
        active.difference_update(closes.get(a, ()))
        active.update(opens.get(a, ()))
        if active:
            i = max(active, key=lambda j: (spans[j][0].startswith(PREFIX),
                                           spans[j][1], -spans[j][2]))
            if out and out[-1][2] == spans[i][0] and out[-1][1] == a:
                out[-1] = (out[-1][0], b, spans[i][0])
            else:
                out.append((a, b, spans[i][0]))
    return out


def idle_gaps_program(summary, program) -> dict[str, float]:
    """Idle seconds of ``summary``'s window by the innermost span the host
    was in (``innermost``); ``none`` where no span was open."""
    spans = [(n, s, e) for n, s, e, _ in program]
    spans += [(n, s, e) for n, s, e, _ in summary.host if n != WINDOW]
    segs = innermost(spans)
    out: dict[str, float] = {}
    k = 0
    for gs, ge in summary.gaps():
        covered = 0
        while k < len(segs) and segs[k][1] <= gs:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < ge:
            s, e, name = segs[j]
            c = min(e, ge) - max(s, gs)
            if c > 0:
                out[name] = out.get(name, 0.0) + c * 1e-9
                covered += c
            j += 1
        if ge - gs > covered:
            out["none"] = out.get("none", 0.0) + (ge - gs - covered) * 1e-9
    return out


def stamps(engine) -> dict[int, object]:
    """Every request's lifecycle stamps, from the engine's completions
    and the requests its scheduler still holds; empty where the program
    keeps none."""
    reqs = [(rid, c) for rid, c in engine.completions.items()]
    reqs += [(s.rid, s.req) for s in engine.sched.running]
    reqs += [(r.rid, r) for r in engine.sched.waiting]
    return {rid: x.timing for rid, x in reqs if hasattr(x, "timing")}


def _due(window):
    return [r for r in window.requests.values()
            if window.t0 <= r.due < window.t1]


def prefill_wait_p90_ms(window, timing, close: float) -> float | None:
    """``close`` is the run's end on the stamps' clock."""
    lat = [((t.t_first_chunk if t.t_first_chunk is not None else close)
            - t.t_submit)
           for t in (timing.get(r.rid) for r in _due(window))
           if t is not None and t.t_submit is not None]
    return float(np.percentile(lat, 90) * 1e3) if lat else None


def prefill_service_p90_ms(window, timing) -> float | None:
    lat = [t.t_first_token - t.t_first_chunk
           for t in (timing.get(r.rid) for r in _due(window))
           if t is not None and t.t_first_token is not None]
    return float(np.percentile(lat, 90) * 1e3) if lat else None


def step_times(rec) -> dict:
    """Mean host seconds of ``step()`` by the step's kind, inside the
    traced part of the window and outside it (the tracer's cost)."""
    a, b = rec.trace_span
    out: dict[str, dict[str, float]] = {}
    for part, inside in (("traced", True), ("untraced", False)):
        by: dict[str, list[float]] = {}
        for s in rec.steps:
            if rec.t0 <= s.t0 < rec.t1 and (a <= s.t0 and s.t1 <= b) \
                    == inside:
                by.setdefault(s.kind, []).append(s.t1 - s.t0)
        out[part] = {k: float(np.mean(v)) for k, v in sorted(by.items())}
        out[part + "_steps"] = {k: len(v) for k, v in sorted(by.items())}
    return out


def span_cost_us(n: int = 100_000) -> dict[str, float]:
    """Host microseconds one span costs while no trace records: without
    stats and with one."""
    import jax

    out = {}
    for label, stats in (("plain", {}), ("one_stat", {"step": 1})):
        t = time.perf_counter()
        for _ in range(n):
            with jax.profiler.TraceAnnotation("engine.cost", **stats):
                pass
        out[label] = (time.perf_counter() - t) / n * 1e6
    return out


def request_report(rec, timing, close: float) -> dict:
    """The stamps' split of time to first token, beside it."""
    from bench.metrics import ttft_p90_ms

    return {
        "prefill_wait_p90_ms": prefill_wait_p90_ms(rec, timing, close),
        "prefill_service_p90_ms": prefill_service_p90_ms(rec, timing),
        "ttft_p90_ms": ttft_p90_ms.read(types.SimpleNamespace(window=rec),
                                        None),
    }


def trace_report(summary, program, rec) -> dict:
    """What the spans say, beside the trace's existing breakdown."""
    old = summary.idle_by_span()
    new = idle_gaps_program(summary, program)
    step_idle = old.get("bench.step", 0.0)
    counts: dict[str, int] = {}
    for n, s, e, _ in program:
        if summary.window[0] <= s and e <= summary.window[1]:
            counts[n] = counts.get(n, 0) + 1
    engine_idle = sum(v for k, v in new.items() if k.startswith(PREFIX))
    return {
        "step_host_ms": step_host_ms(summary.window, program),
        "device_idle_share": 100.0 * (1.0 - summary.busy_s
                                      / summary.window_s),
        "idle_gaps": sorted(([k, v] for k, v in old.items()),
                            key=lambda kv: -kv[1]),
        "idle_gaps_program": sorted(([k, v] for k, v in new.items()),
                                    key=lambda kv: -kv[1]),
        "engine_share_of_step_idle": (100.0 * engine_idle / step_idle
                                      if step_idle and program else None),
        "spans_in_window": counts,
        "step_s": step_times(rec),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1,
                    help="0: no trace, the stamps alone (starting and "
                         "stopping a trace stalls the loop)")
    args = ap.parse_args(argv)

    from bench import cells, chip

    cell = cells.load_cell(args.workload)
    dev = chip.require(cell.chips)
    from bench import driver, model, run, trace, traffic

    run.log(f"{cell.name} on {dev.device_kind}, seed {args.seed}")
    run.enable_cache()
    out = {"workload": cell.name, "seed": args.seed, "kind": dev.device_kind,
           "span_inactive_us": span_cost_us()}
    cfg = model.program_config(cell.config)
    engine = model.make_engine(model.init_params(cfg, args.seed), cfg,
                               cell.traffic["engine"], cell.chips)
    engine.warmup()
    plan = traffic.Plan(cell.traffic, cell.config["vocab_size"], args.seed)
    tmp = tempfile.mkdtemp(prefix="engine_trace_") if args.trace else None
    try:
        rec = driver.Window(engine, plan, driver.Compiles()).run(
            args.seconds, trace_dir=tmp)
        out.update(request_report(rec, stamps(engine),
                                  engine.sched.time_fn()))
        if tmp is not None:
            data = trace.load(glob.glob(os.path.join(tmp, "**",
                                                     "*.xplane.pb"),
                                        recursive=True)[0])
            out.update(trace_report(trace.summarize(data), collect(data),
                                    rec))
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
