"""Everything found by name: a cell of ``BENCHMARK.json``, the
configuration file and traffic mix it names, the model family of that
configuration (``bench/families/<family>.py``, from the file's
``"family"`` key, ``dense`` where it has none) and the reader of each
metric it reports (``bench/metrics/<metric>.py``).  Adding a cell,
configuration, family, mix or metric adds files and entries; nothing
here changes."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# set on a configuration as it is loaded, never in its file: the
# directory its family module is found in
FAMILIES = "_families"


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


def _config(path: str, bench_dir: str) -> dict:
    return {**load_json(path), FAMILIES: os.path.join(bench_dir, "families")}


def load_config(name: str, bench_dir: str = BENCH) -> dict:
    return _config(os.path.join(bench_dir, "configs", f"{name}.json"),
                   bench_dir)


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
    config = _config(os.path.join(os.path.dirname(bench_dir),
                                  configs[w["config"]]["file"]), bench_dir)
    return Cell(name, config, load_traffic(w["traffic"], bench_dir),
                int(w["chips"]),
                tuple(m for m in spec["end_to_end"] if _reports(m, name)),
                tuple(m for m in spec["per_layer"] if _reports(m, name)))


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench_dir: str = BENCH):
    """The ``read(run, trace)`` function of ``metrics/<metric>.py``."""
    return _module(os.path.join(bench_dir, "metrics", f"{metric}.py"),
                   f"bench_metric_{metric.replace('.', '_')}").read


@functools.cache
def _family(path: str):
    # once per process, so that the family's jitted functions keep
    # their compiled programs from call to call
    name = os.path.basename(path)[:-3]
    return _module(path, f"bench_family_{name.replace('.', '_')}")


def family(c: dict):
    """The module ``families/<family>.py`` of a configuration (its
    ``"family"``, ``dense`` where it has none), from the bench directory
    it was loaded from: it defines ``program_config``, ``forward``,
    ``step_work`` and ``check`` (``bench/families/__init__.py``)."""
    where = c.get(FAMILIES, os.path.join(BENCH, "families"))
    return _family(os.path.join(where, f"{c.get('family', 'dense')}.py"))
