"""Device time of one execution of the prefill step program, averaged
over its executions in the traced window (ms)."""

MODULE = "jit_prefill_step"


def read(run, trace):
    d = trace.module_seconds(MODULE)
    return 1e3 * sum(d) / len(d) if d else None
