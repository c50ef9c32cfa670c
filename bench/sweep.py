"""Find the highest arrival rate an open-loop cell sustains: one set of
weights, then a window at each rate on a fresh engine.  A rate is
sustained when the waiting queue does not grow over its window (the
mean queue length of the window's last third is no longer than that of
its first third plus one request).

    python3 -m bench.sweep --workload danube4b-68-bf16.chat --rates 1,1.5,2 --seconds 51
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    from bench import cells, chip, run

    cell = cells.load_cell(args.workload)
    chip.require(cell.chips)
    run.enable_cache()
    from bench import driver, model, traffic

    cfg = model.program_config(cell.config)
    params = model.init_params(cfg, args.seed)
    compiles = driver.Compiles()
    for rate in (float(r) for r in args.rates.split(",")):
        # a fresh engine per rate: request ids restart with each plan
        engine = model.make_engine(params, cfg, cell.traffic["engine"],
                                   cell.chips)
        engine.warmup()
        queue: list[tuple[float, int]] = []

        def counted_step(step=engine.step, engine=engine, queue=queue):
            out = step()
            queue.append((time.perf_counter(), len(engine.sched.waiting)))
            return out

        engine.step = counted_step
        mix = dict(cell.traffic, rate_per_s=rate)
        plan = traffic.Plan(mix, cell.config["vocab_size"], args.seed)
        rec = driver.Window(engine, plan, compiles).run(args.seconds)
        run_ = run.Run(dataclasses.replace(cell, traffic=mix), rec, {},
                       None, {})
        q = [(t, n) for t, n in queue if rec.t0 <= t < rec.t1]
        third = (rec.t1 - rec.t0) / 3
        first = [n for t, n in q if t < rec.t0 + third]
        last = [n for t, n in q if t >= rec.t1 - third]
        grow = float(np.mean(last)) - float(np.mean(first))
        out = {"rate": rate, "arrived": sum(
            1 for r in rec.requests.values() if rec.t0 <= r.due < rec.t1),
            "queue_first_third": float(np.mean(first)),
            "queue_last_third": float(np.mean(last)),
            "sustained": grow <= 1.0, "compiles": rec.compiles}
        for m in ("output_tok_s", "ttft_p90_ms", "itl_p95_ms"):
            out[m] = cells.reader(m)(run_, None)
        print(json.dumps(out), flush=True)
        del engine, counted_step
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
