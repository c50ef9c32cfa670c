"""Record a small profiler trace on the chip, for the trace reduction's
test data and for reading a trace's layout by hand.

Serves a one-layer cut of a configuration (its published widths, one
layer) through the program's engine: one prefill chunk, then decode
steps, each inside the benchmark's host spans, traced.  Writes the
``.xplane.pb`` to ``--out`` and prints every plane, line and the first
events of each, with their stats.

    python3 -m bench.record_trace --config danube4b-68-bf16 --out chiprun_out/trace
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--layers", type=int, default=1)
    ap.add_argument("--decode-steps", type=int, default=3)
    args = ap.parse_args(argv)

    from bench import cells, chip, spans

    dev = chip.require(1)
    import jax
    import numpy as np

    from bench import model

    c = dict(cells.load_config(args.config), num_hidden_layers=args.layers)
    cfg = model.program_config(c)
    eng_cfg = {"max_batch": 4, "page_size": 16, "num_pages": 64,
               "max_seq_len": 256, "prefill_chunk": 128}
    eng = model.make_engine(model.init_params(cfg, 0), cfg, eng_cfg)
    eng.warmup()
    rng = np.random.default_rng(0)
    for rid in range(2):
        eng.submit(rng.integers(0, c["vocab_size"], 100).tolist(),
                   args.decode_steps + 1, rid=rid)
    tmp = os.path.join(args.out, "raw")
    shutil.rmtree(tmp, ignore_errors=True)
    jax.profiler.start_trace(tmp)
    with spans.span(spans.WINDOW):
        idx = 0
        while eng.sched.has_work:
            with spans.span(spans.STEP, step=idx):
                eng.step()
            idx += 1
            with spans.span(spans.RECORD):
                time.sleep(0.002)
        jax.block_until_ready(eng.cache)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(args.out, f"{args.config}.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(tmp)
    print(f"trace {dst} {os.path.getsize(dst)} B on {dev.device_kind}")
    print(json.dumps(describe(dst), indent=1))
    return 0


def describe(path: str, per_line: int = 6) -> list:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "events": len(evs), "first": [
                [e.name, e.start_ns, e.duration_ns,
                 {k: str(v)[:80] for k, v in e.stats}]
                for e in evs[:per_line]]})
        out.append({"plane": plane.name, "lines": lines})
    return out


if __name__ == "__main__":
    sys.exit(main())
