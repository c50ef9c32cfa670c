"""One reader per metric: ``read(run, trace)`` returns the metric's value,
or None where the run gives it nothing to read (the harness then leaves
the metric out of the line).  ``run`` is ``bench.run.Run``; ``trace`` is
the reduced profiler trace (``bench.trace.Summary``) of a ``--trace 1``
run, else None."""
