"""The plain reference: a float32 forward of the model a configuration
file describes, pruned as its ``sparsity`` says, built from the file and
the seed alone.

It imports nothing of the program and takes nothing the program made.
Its weights are drawn from the seed by the same random draws the
program's initialisation makes (so both sides hold the same model), then
pruned here: in every group of ``l`` along the input dimension the ``z``
largest magnitudes stay, ties going to the earlier position (a
``pattern`` of ``null`` keeps every weight).  Every matrix product runs
at ``HIGHEST`` precision, layer by layer, so that it fits one chip beside
nothing else.  The layers are the configuration's family's
(``bench/families/<family>.py``); the pieces here are shared by all.

``quant`` computes the same forward in a lower precision, for the
control of the comparison that decides ``correct``: ``"int8"`` quantizes
every linear's weights per output row and its input per token to int8;
``"w4"`` quantizes weights to int4 (per output row) and inputs to int8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import cells
from bench.seeds import model_key

HI = jax.lax.Precision.HIGHEST
QMAX = {"int8": (127.0, 127.0), "w4": (7.0, 127.0)}  # (weight, input)
ATTN_CHUNK = 256


def _normal(key, shape, scale, dtype):
    # the program's initialisation, op for op
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale
            ).astype(dtype)


def _linear_init(key, k_in, m_out, dtype):
    return _normal(key, (m_out, k_in), k_in ** -0.5, dtype)


def layer_weights(c: dict, unit_key) -> dict:
    """Dense weights ``[out, in]`` of one layer, as the seed gives them."""
    d, f = c["hidden_size"], c["intermediate_size"]
    hd = c["head_dim"]
    qd, kvd = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    dt = jnp.dtype(c["torch_dtype"])
    _, k1, k2 = jax.random.split(unit_key, 3)
    kq, kk, kv, ko = jax.random.split(k1, 4)
    kg, ku, kd = jax.random.split(k2, 3)
    return {"wq": _linear_init(kq, d, qd, dt), "wk": _linear_init(kk, d, kvd, dt),
            "wv": _linear_init(kv, d, kvd, dt), "wo": _linear_init(ko, qd, d, dt),
            "w_gate": _linear_init(kg, d, f, dt), "w_up": _linear_init(ku, d, f, dt),
            "w_down": _linear_init(kd, f, d, dt)}


def model_keys(c: dict, seed: int):
    ke, kh, ku = jax.random.split(model_key(seed), 3)
    return ke, kh, jax.random.split(ku, c["num_hidden_layers"])


def embedding(c: dict, ke):
    return _normal(ke, (c["vocab_size"], c["hidden_size"]), 0.02,
                   jnp.dtype(c["torch_dtype"]))


def head_weights(c: dict, kh):
    return _linear_init(kh, c["hidden_size"], c["vocab_size"],
                        jnp.dtype(c["torch_dtype"]))


@functools.partial(jax.jit, static_argnames=("z", "l"))
def prune(w, z: int, l: int):
    """Keep the ``z`` largest ``|w|`` of every ``l`` along the last axis
    (ties to the earlier position); float32 out."""
    w = w.astype(jnp.float32)
    g = jnp.abs(w).reshape(w.shape[:-1] + (w.shape[-1] // l, l))
    a, b = g[..., :, None], g[..., None, :]
    pos = jnp.arange(l)
    earlier = pos[None, :] < pos[:, None]
    rank = jnp.sum((b > a) | ((b == a) & earlier), axis=-1)
    return jnp.where((rank < z).reshape(w.shape), w, 0.0)


def _quant(x, qmax):
    a = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8)
    return jnp.clip(jnp.round(x * (qmax / a)), -qmax, qmax) * (a / qmax)


def _prep(w, c, quant):
    pattern = c["sparsity"]["pattern"]
    w = prune(w, *pattern) if pattern else w.astype(jnp.float32)
    return w if quant is None else _quant(w, QMAX[quant][0])


def _lin(x, w, quant):
    if quant is not None:
        x = _quant(x, QMAX[quant][1])
    return jnp.einsum("nsk,mk->nsm", x, w, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v, window):
    """Causal (windowed) GQA attention of one sequence, queries in
    chunks.  q [S, H, hd]; k, v [S, KVH, hd]."""
    s, h, hd = q.shape
    kvh = k.shape[1]
    qc = q.reshape(s // ATTN_CHUNK, ATTN_CHUNK, kvh, h // kvh, hd)
    kpos = jnp.arange(s)

    def chunk(args):
        qi, i = args
        sc = jnp.einsum("cgrd,kgd->grck", qi, k, precision=HI) * hd ** -0.5
        d = (i * ATTN_CHUNK + jnp.arange(ATTN_CHUNK))[:, None] - kpos[None]
        sc = jnp.where((d >= 0) & (d < window), sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("grck,kgd->cgrd", p, v, precision=HI)

    out = jax.lax.map(chunk, (qc, jnp.arange(s // ATTN_CHUNK)))
    return out.reshape(s, h, hd)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, w, cands, eps, quant):
    def one(args):
        xi, ci = args
        logits = _lin(_rms(xi, eps)[None], w, quant)[0]
        best = jnp.max(logits, axis=-1, keepdims=True)
        pick = jnp.take_along_axis(logits, ci, axis=-1)
        return best - pick, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return jax.lax.map(one, (x, cands))


def forward(c: dict, seed: int, tokens: np.ndarray, cands: np.ndarray,
            quant: str | None = None):
    """Run the reference over ``tokens`` ``[N, S]`` (``S`` a multiple of
    ATTN_CHUNK; padding at the end of a row is never attended by the
    positions before it).  Returns the gaps ``[N, S, K]`` by which the
    logits of ``cands[n, p, :]`` lie below the best logit at position
    ``p``, and the argmax ``[N, S]``."""
    return cells.family(c).forward(c, seed, tokens, cands, quant)
