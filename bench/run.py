"""Run one cell of the benchmark on the chip.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (process start, model init and packing from the seed, engine
warm-up) is timed as ``setup_s``; then the window runs for ``--seconds``
with nothing compiling in it.  With ``--trace 0`` the result reports the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of part of the window.  Once the window has
closed, the served tokens of a sample of requests are compared with the
plain reference (``bench/check.py``), which decides ``correct``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
with ``--trace 1``), then ``checks``: each number compared, with its
limit.  On anything but the TPUs the cell asks for it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from bench import cells, chip  # noqa: E402


@dataclasses.dataclass
class Run:
    """What a metric reader reads: the cell, the window's record and the
    set-up split."""
    cell: cells.Cell
    window: object                 # driver.WindowRecord
    setup: dict[str, float]
    peaks: object                  # peaks.Peaks
    steps_work: dict               # step index -> yardstick.Work

    @property
    def int8(self) -> bool:
        return self.cell.config["sparsity"]["recipe"] == "int8"


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def enable_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), every
    program cached, so a second run of a cell compiles nothing."""
    import jax

    from bench import model  # noqa: F401  (puts the program on sys.path)
    from repro.launch.serve import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks, limit: float | None = None,
             control: bool = False) -> dict:
    """Set up, measure, check; returns the result line's object.
    ``control`` also reads the control's number (``check.control_gap``)
    into ``checks``; the benchmark's own runs never do."""
    from bench import check, driver, model, traffic, yardstick

    setup = {"process_s": time.time() - t_start}
    log(f"compilation cache {enable_cache()}")
    cfg = model.program_config(cell.config)
    t = time.time()
    params = model.init_params(cfg, seed)
    setup["init_pack_s"] = time.time() - t
    t = time.time()
    engine = model.make_engine(params, cfg, cell.traffic["engine"],
                               cell.chips)
    engine.warmup()
    setup["warmup_s"] = time.time() - t
    plan = traffic.Plan(cell.traffic, cell.config["vocab_size"], seed)
    compiles = driver.Compiles()
    setup["setup_s"] = time.time() - t_start
    log("set-up: " + ", ".join(f"{k} {v:.3f}" for k, v in setup.items()))

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        rec = driver.Window(engine, plan, compiles).run(
            seconds, trace_dir=trace_dir)
        device = chip.record(cell.chips)
        del engine, params
        gc.collect()
        log(f"window {rec.seconds:.3f} s, {len(rec.steps)} steps, "
            f"{len(rec.requests)} requests, {rec.compiles} compiles")

        t = time.time()
        n_check = cell.traffic.get("check_requests", check.SAMPLE)
        sample = check.sample(rec, seed, n_check)
        ctrl = None
        if not sample:      # nothing finished: nothing to compare
            gap = None
        elif control:
            quant = check.CONTROL[cell.config["sparsity"]["recipe"]]
            gap, ctrl = check.control_gap(cell.config, seed,
                                          check.rows(sample, n_check), quant)
        else:
            gap = check.widest_gap(cell.config, seed,
                                   check.rows(sample, n_check))
        log(f"reference over {len(sample)} requests, "
            f"{sum(len(r.tokens) for r in sample)} served tokens: "
            f"{time.time() - t:.1f} s")

        work = {s.idx: (yardstick.decode_work(cell.config, list(s.lanes))
                        if s.kind == "decode" else
                        yardstick.prefill_work(cell.config, *s.lanes))
                for s in rec.steps if s.kind != "none"}
        run = Run(cell, rec, setup, peaks, work)
        summary = None
        if trace:
            from bench import trace as tr

            path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            summary = tr.reduce(path)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cells.reader(m["name"])(run, summary)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    arrived = [r for r in rec.requests.values() if rec.t0 <= r.due < rec.t1]
    failed = sum(1 for r in arrived
                 if not r.times or r.status not in (None, "OK"))
    limit = cell.config["correct"]["max_logit_gap"] if limit is None \
        else limit
    checks = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "compiles_in_window": {"value": rec.compiles, "limit": 0},
    }
    if ctrl is not None:
        checks["control_max_logit_gap"] = {"value": ctrl, "limit": limit}
    correct = (limit is not None and gap is not None and gap <= limit
               and rec.compiles == 0)
    out = {"correct": bool(correct), "attempted": len(arrived),
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = summary.breakdown()
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = cells.load_cell(args.workload)
    dev = chip.require(cell.chips)
    from bench import peaks

    log(f"{cell.name} on {dev.device_kind}, seed {args.seed}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START,
                   peaks.for_kind(dev.device_kind))
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
