"""Mean share of the decode slots (``max_batch``) that each decode step
of the window served, read from the scheduler's decision log (%)."""


def read(run, trace):
    w = run.window
    lanes = [len(s.lanes) for s in w.steps
             if s.kind == "decode" and w.t0 <= s.t0 < w.t1]
    if not lanes:
        return None
    return 100.0 * sum(lanes) / len(lanes) / run.cell.traffic["engine"][
        "max_batch"]
