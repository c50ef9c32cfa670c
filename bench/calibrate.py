"""Readings that set the limit of a cell's comparison: on each seed, a
run of the cell at its own size and load, then the program's widest gap
and the control's (the reference in the nearest precision below the
configuration's) over the same served requests.  All seeds run in one
process; each prints its result line.

    python3 -m bench.calibrate --workload <cell> --seeds 1,2,3 --seconds 30
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import cells, chip, peaks, run

    cell = cells.load_cell(args.workload)
    dev = chip.require(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(cell, seed, args.seconds, False, time.time(),
                           peaks.for_kind(dev.device_kind), control=True)
        c = out["checks"]
        print(json.dumps({"seed": seed,
                          "program": c["max_logit_gap"]["value"],
                          "control": c["control_max_logit_gap"]["value"],
                          "metrics": {k: v["value"]
                                      for k, v in out["metrics"].items()},
                          "failed": out["failed"],
                          "attempted": out["attempted"],
                          "memory_peak_bytes":
                              out["device"]["memory_peak_bytes"]}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
