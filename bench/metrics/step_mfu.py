"""The whole step's share of the chip's peak: over the steps of the
traced window, the least time the yardstick allows each (its operations
over the peak rate or its bytes over the peak bandwidth, whichever is
larger), summed and divided by the traced window (%)."""


def read(run, trace):
    steps = [i for i in trace.steps if i in run.steps_work]
    if not steps:
        return None
    least = sum(run.steps_work[i].step_s(run.peaks, run.int8) for i in steps)
    return 100.0 * least / trace.window_s
