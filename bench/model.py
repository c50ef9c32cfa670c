"""The system under test: a configuration file turned into the program's
``ModelConfig``, packed weights from the seed and a ``ServeEngine`` sized
by the traffic mix.  Only the deployment is set here (model, 6:8
pattern, precision recipe, KV dtype, engine sizing); every other field
of ``ModelConfig``, ``SparsityConfig`` and ``EngineConfig`` keeps the
program's default."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from repro.configs.base import ModelConfig  # noqa: E402
from repro.core.linear import SparsityConfig  # noqa: E402
from repro.runtime import serve_loop  # noqa: E402

from bench.seeds import model_key  # noqa: E402


def program_config(c: dict) -> ModelConfig:
    """The program's configuration of the model a configuration file
    describes (keys as in the published ``config.json``)."""
    window = c.get("sliding_window")
    return ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        head_dim=c["head_dim"],
        unit_pattern=("swa",) if window else ("attn",),
        sliding_window=window or ModelConfig.sliding_window,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c["torch_dtype"], kv_cache_dtype=c["kv_cache_dtype"],
        sparsity=SparsityConfig(pattern=tuple(c["sparsity"]["pattern"]),
                                mode="compressed",
                                recipe=c["sparsity"]["recipe"]))


def engine_config(engine: dict) -> serve_loop.EngineConfig:
    return serve_loop.EngineConfig(
        max_batch=engine["max_batch"], page_size=engine["page_size"],
        num_pages=engine["num_pages"], max_seq_len=engine["max_seq_len"],
        prefill_chunk=engine["prefill_chunk"])


def init_params(cfg: ModelConfig, seed: int):
    """Packed weights from the seed, built on the device by the program's
    loading path (``init_packed``: one scanned unit at a time)."""
    return jax.block_until_ready(serve_loop.init_packed(cfg, model_key(seed)))


def make_engine(params, cfg: ModelConfig, engine: dict):
    return serve_loop.ServeEngine(params, cfg, engine_config(engine))
