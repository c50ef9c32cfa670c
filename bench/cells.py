"""Everything found by name: a cell of ``BENCHMARK.json``, the
configuration file and traffic mix it names, and the reader of each
metric it reports (``bench/metrics/<metric>.py``).  Adding a cell,
configuration, mix or metric adds files and entries; nothing here
changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple[dict, ...]   # the entries this cell reports
    per_layer: tuple[dict, ...]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str, bench_dir: str = BENCH) -> dict:
    return load_json(os.path.join(bench_dir, "configs", f"{name}.json"))


def load_traffic(name: str, bench_dir: str = BENCH) -> dict:
    return load_json(os.path.join(bench_dir, "traffic", f"{name}.json"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: str | None = None,
              bench_dir: str = BENCH) -> Cell:
    spec = load_json(spec_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(os.path.join(os.path.dirname(bench_dir),
                                    configs[w["config"]]["file"]))
    return Cell(name, config, load_traffic(w["traffic"], bench_dir),
                int(w["chips"]),
                tuple(m for m in spec["end_to_end"] if _reports(m, name)),
                tuple(m for m in spec["per_layer"] if _reports(m, name)))


def reader(metric: str, bench_dir: str = BENCH):
    """The ``read(run, trace)`` function of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
