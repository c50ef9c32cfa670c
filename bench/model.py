"""The system under test: a configuration file turned into the program's
``ModelConfig`` by its family (``bench/families/<family>.py``, found by
the file's ``"family"``, ``dense`` where it has none), weights from the
seed and a ``ServeEngine`` sized by the traffic mix's ``engine`` object,
whose every key names an ``EngineConfig`` field.  Only the deployment
is set here; every other field of ``ModelConfig``, ``SparsityConfig``
and ``EngineConfig`` keeps the program's default."""
from __future__ import annotations

import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402
from repro.runtime import serve_loop  # noqa: E402

from bench import cells  # noqa: E402
from bench.seeds import model_key  # noqa: E402


def program_config(c: dict) -> ModelConfig:
    """The program's configuration of the model a configuration file
    describes (keys as in the published ``config.json``)."""
    return cells.family(c).program_config(c)


def engine_config(engine: dict, chips: int = 1) -> serve_loop.EngineConfig:
    """The mix's ``engine`` object as the program's ``EngineConfig``:
    every key a field, none unknown, and ``tp`` no more than the cell's
    ``chips``."""
    fields = {f.name for f in dataclasses.fields(serve_loop.EngineConfig)}
    unknown = sorted(set(engine) - fields)
    if unknown:
        raise ValueError(f"engine keys {unknown} name no EngineConfig field")
    if engine.get("tp", 1) > chips:
        raise ValueError(f"tp {engine['tp']} needs more than the cell's "
                         f"{chips} chips")
    return serve_loop.EngineConfig(**engine)


def init_params(cfg: ModelConfig, seed: int):
    """Packed weights from the seed (dense ones as initialised), built on
    the device by the program's loading path (``init_packed``: one
    scanned unit at a time)."""
    return jax.block_until_ready(serve_loop.init_packed(cfg, model_key(seed)))


def make_engine(params, cfg: ModelConfig, engine: dict, chips: int = 1):
    return serve_loop.ServeEngine(params, cfg, engine_config(engine, chips))
