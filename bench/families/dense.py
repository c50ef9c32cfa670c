"""The dense decoder family: every layer GQA attention with RoPE (half
split) and a SwiGLU MLP, RMSNorm before each, an untied head; all layers
sliding-window when the file gives ``sliding_window``, else all full.

``sparsity.pattern`` ``[z, l]`` serves the weights pruned z:l and
compressed; ``null`` serves the dense model."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import yardstick as Y


def program_config(c: dict):
    """The program's ``ModelConfig`` of the model the file describes."""
    from repro.configs.base import ModelConfig
    from repro.core.linear import SparsityConfig

    window = c.get("sliding_window")
    pattern = c["sparsity"]["pattern"]
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
        sparsity=SparsityConfig(
            pattern=tuple(pattern) if pattern else None,
            mode="compressed" if pattern else "dense",
            recipe=c["sparsity"]["recipe"]))


def check(c: dict, cfg) -> None:
    """Raise where ``cfg`` is not the model the file describes: every
    width, the layers and the window (all full where the file has
    none)."""
    window = c.get("sliding_window")
    want = (c["num_hidden_layers"], c["hidden_size"],
            c["intermediate_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["vocab_size"],
            float(c["rms_norm_eps"]), ("swa",) if window else ("attn",))
    got = (cfg.num_layers, cfg.d_model, cfg.d_ff, cfg.num_heads,
           cfg.num_kv_heads, cfg.resolved_head_dim, cfg.vocab_size,
           cfg.norm_eps, cfg.unit_pattern)
    if got != want or (window and cfg.sliding_window != window):
        raise ValueError(f"{c['name']}: the program's configuration {got} "
                         f"(window {cfg.sliding_window}) is not the "
                         f"file's {want} (window {window})")


@functools.partial(jax.jit, static_argnames=("shape", "quant"))
def _layer(x, w, shape, quant):
    h_, kvh, hd, theta, window, eps = shape
    n, s, _ = x.shape
    hn = R._rms(x, eps)
    q = R._rope(R._lin(hn, w["wq"], quant).reshape(n, s, h_, hd), theta)
    k = R._rope(R._lin(hn, w["wk"], quant).reshape(n, s, kvh, hd), theta)
    v = R._lin(hn, w["wv"], quant).reshape(n, s, kvh, hd)
    a = jax.lax.map(lambda qkv: R._attend(*qkv, window), (q, k, v))
    x = x + R._lin(a.reshape(n, s, h_ * hd), w["wo"], quant)
    hn = R._rms(x, eps)
    g, u = R._lin(hn, w["w_gate"], quant), R._lin(hn, w["w_up"], quant)
    return x + R._lin(jax.nn.silu(g) * u, w["w_down"], quant)


def forward(c: dict, seed: int, tokens: np.ndarray, cands: np.ndarray,
            quant: str | None = None):
    """The reference, layer by layer (``bench.reference.forward``)."""
    ke, kh, unit_keys = R.model_keys(c, seed)
    window = c.get("sliding_window") or tokens.shape[1]
    shape = (c["num_attention_heads"], c["num_key_value_heads"],
             c["head_dim"], float(c["rope_theta"]), int(window),
             float(c["rms_norm_eps"]))
    x = jnp.take(R.embedding(c, ke), jnp.asarray(tokens), axis=0
                 ).astype(jnp.float32)
    for u in range(c["num_hidden_layers"]):
        w = {k: R._prep(v, c, quant)
             for k, v in R.layer_weights(c, unit_keys[u]).items()}
        x = _layer(x, w, shape, quant)
        del w
    gap, top = R._head(x, R._prep(R.head_weights(c, kh), c, quant),
                       jnp.asarray(cands), float(c["rms_norm_eps"]), quant)
    return np.asarray(gap), np.asarray(top)


def step_work(c: dict, lanes: list[tuple[int, int]],
              head_rows: int) -> Y.Work:
    """Every layer's projections at the rows served, the head at
    ``head_rows``, and every lane's attention."""
    rows = sum(r for r, _ in lanes)
    w = Y.Work()
    for k_in, m_out, count in Y.linears(c):
        g = Y._gemm(c, k_in, m_out, rows)
        w += Y.Work(g.gemm_ops * count, g.gemm_bytes * count)
    w += Y._gemm(c, c["hidden_size"], c["vocab_size"], head_rows)
    w += Y._attention(c, lanes)
    return w
