"""The benchmark's own host spans: named intervals around its calls into
the program, written into the profiler's trace when one is recording (a
``TraceAnnotation`` costs about a microsecond when none is)."""
from __future__ import annotations

import jax

# every span the benchmark records; the trace reduction tags each device
# idle gap with the one that covers it
STEP = "bench.step"          # ServeEngine.step()
SUBMIT = "bench.submit"      # ServeEngine.submit()
RECORD = "bench.record"      # reading token counts after a step
WAIT = "bench.wait"          # open loop: nothing due, sleeping
WINDOW = "bench.window"      # the traced part of the measured window


def span(name: str, **stats):
    """``stats`` are written into the trace event (e.g. a step's index)."""
    return jax.profiler.TraceAnnotation(name, **stats)
