"""90th percentile of time to first token over every request that
arrived in the window, from its due time (open loop) or its submission
(closed loop) to the step that returned its first token (ms).  A
request still without a first token when the run stopped counts with
the time it had waited by then."""
import numpy as np


def read(run, trace):
    w = run.window
    end = max((s.t1 for s in w.steps), default=w.t1)
    lat = [(r.times[0] if r.times else end) - r.due
           for r in w.requests.values() if w.t0 <= r.due < w.t1]
    return float(np.percentile(lat, 90) * 1e3) if lat else None
