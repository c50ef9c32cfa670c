"""Named host spans of the serving engine's loop (DESIGN.md §15).

Each span is a ``jax.profiler.TraceAnnotation``.  While a profiler trace
records (``jax.profiler.start_trace``), a span lands as a host event in
the same ``.xplane.pb`` as the device's operations, on the same clock, so
a stretch in which the device idles can be laid against what the host
was doing.  While none records, a span costs about a microsecond, so the
spans are always in the code.

Readers of a trace find the spans and the compiled step programs by the
names below; renaming one is a change to every reader.

===================  =====================================================
``engine.step``      all of ``ServeEngine.step()``; stats ``step`` (engine
                     step index), ``kind`` (prefill | decode | verify |
                     none), ``lanes`` (sequences served), ``rid`` (prefill)
``engine.schedule``  the scheduler's decision: deadlines, admission, page
                     allocation, eviction (``next_decision`` or
                     ``lookahead_decode``)
``engine.prepare``   the step's inputs: token, length and active arrays,
                     the page table, copy-on-write page copies
``engine.dispatch``  the jitted call until it returns: enqueue and the
                     host-to-device transfer of its numpy inputs
``engine.fetch``     the blocking device-to-host read of a step's output,
                     i.e. the wait for the device; stat ``bytes``
``engine.apply``     landing a step's results: tokens appended, finished
                     sequences retired, completions drained; holds the
                     ``engine.fetch`` of the step it lands
``engine.submit``    ``ServeEngine.submit()``: prefix hashing, enqueue;
                     stat ``rid``
===================  =====================================================
"""
from __future__ import annotations

import jax

STEP = "engine.step"
SCHEDULE = "engine.schedule"
PREPARE = "engine.prepare"
DISPATCH = "engine.dispatch"
FETCH = "engine.fetch"
APPLY = "engine.apply"
SUBMIT = "engine.submit"
NAMES = (STEP, SCHEDULE, PREPARE, DISPATCH, FETCH, APPLY, SUBMIT)
PREFIX = "engine."

# the engine's jitted step functions (``ServeEngine.__init__``): XLA names
# the compiled program ``jit_<name>``, which is how the trace's module
# line, and so a reader of one step's device time, finds it
PREFILL_STEP = "prefill_step"
DECODE_STEP = "decode_step"
COPY_STEP = "copy_step"
VERIFY_STEP = "verify_step"


def program(step: str) -> str:
    """The compiled program's name of the jitted step function ``step``."""
    return f"jit_{step}"


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """``stats`` are written into the trace event; more can be added once
    the span is open with ``set_metadata``."""
    return jax.profiler.TraceAnnotation(name, **stats)
