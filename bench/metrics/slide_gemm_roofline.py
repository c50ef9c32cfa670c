"""The compressed GEMM's share of its roofline: the least time the
yardstick allows the GEMM work of the traced window's steps, divided by
the summed device time of the compressed-GEMM kernel's events (%)."""

from bench.trace import GEMM_KERNEL


def read(run, trace):
    steps = [i for i in trace.steps if i in run.steps_work]
    spent = trace.kernel_seconds(GEMM_KERNEL)
    if not steps or spent <= 0:
        return None
    least = sum(run.steps_work[i].gemm_s(run.peaks, run.int8) for i in steps)
    return 100.0 * least / spent
