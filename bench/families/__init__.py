"""One module per model family, found by name: a configuration file
names its family with ``"family": "<name>"`` (``dense`` where the key is
absent) and the harness loads ``families/<name>.py``
(``bench.cells.family``).  A family defines:

- ``program_config(c) -> ModelConfig``: the program's configuration of
  the model the file describes (keys as in the published
  ``config.json``); it imports the program inside the function.
- ``forward(c, seed, tokens, cands, quant=None) -> (gap, top)``: the
  plain float32 ``HIGHEST`` reference (``bench.reference.forward``),
  with the program's weights drawn from the seed and pruned as the
  file's ``sparsity`` says; it imports nothing of the program.
- ``step_work(c, lanes, head_rows) -> bench.yardstick.Work``: the work
  one serving step requires, counted from the configuration and the
  rows served, never from the stored format
  (``bench.yardstick.decode_work`` / ``prefill_work``).
- ``check(c, cfg) -> None``: raises where ``cfg``, the program's
  configuration, is not the model the file describes; the tests call
  it on every configuration file, so each family says how its keys map.

The pieces a family shares live where they are: weight draws, pruning,
quantization, norms, RoPE and attention in ``bench.reference``; ``Work``,
GEMM and attention counts in ``bench.yardstick``."""
