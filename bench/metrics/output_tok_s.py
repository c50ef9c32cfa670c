"""Output tokens per second: every token emitted inside the window,
divided by the window."""


def read(run, trace):
    w = run.window
    n = sum(1 for r in w.requests.values() for t in r.times
            if w.t0 <= t < w.t1)
    return n / w.seconds
