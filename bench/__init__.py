"""On-chip serving benchmark: cells, traffic, reference and trace reduction.

Everything that belongs to one configuration, model family, traffic mix
or metric is a file of its own under ``configs/``, ``families/``,
``traffic/`` and ``metrics/``, found by the name ``BENCHMARK.json`` or
the configuration file gives it.  Run a cell with::

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
