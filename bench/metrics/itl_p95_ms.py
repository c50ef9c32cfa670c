"""95th percentile of every gap between consecutive tokens of one
request, over all requests and all gaps inside the window (ms)."""
import numpy as np


def read(run, trace):
    w = run.window
    gaps = [b - a for r in w.requests.values()
            for a, b in zip(r.times, r.times[1:]) if w.t0 <= a and b < w.t1]
    return float(np.percentile(gaps, 95) * 1e3) if gaps else None
