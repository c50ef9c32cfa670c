"""The plain reference holds the program's model: the same weights from
the seed, bit for bit, and the same logits as the program's masked
float32 forward of the pruned weights."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, model, reference
from bench.seeds import model_key

SMALL = dict(cells.load_config("phi3m-68-int8"), hidden_size=64,
             intermediate_size=96, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             vocab_size=128, sliding_window=20)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 7, 2 ** 40 + 3])
def test_weights_are_the_programs(seed):
    from repro.models import model as M

    p = M.init(model.program_config(SMALL), model_key(seed))
    ke, kh, units = reference.model_keys(SMALL, seed)
    assert np.array_equal(reference.embedding(SMALL, ke), p["embed"]["w"])
    assert np.array_equal(reference.head_weights(SMALL, kh),
                          p["lm_head"]["w"])
    names = {"wq": ("mixer", "wq"), "wk": ("mixer", "wk"),
             "wv": ("mixer", "wv"), "wo": ("mixer", "wo"),
             "w_gate": ("ffn", "w_gate"), "w_up": ("ffn", "w_up"),
             "w_down": ("ffn", "w_down")}
    for u in range(SMALL["num_hidden_layers"]):
        w = reference.layer_weights(SMALL, units[u])
        for k, (blk, name) in names.items():
            assert np.array_equal(
                w[k], p["units"]["layer_0"][blk][name]["w"][u]), (u, k)


def test_seeds_give_different_models():
    a = reference.layer_weights(SMALL, reference.model_keys(SMALL, 1)[2][0])
    b = reference.layer_weights(SMALL, reference.model_keys(SMALL, 2)[2][0])
    assert not np.array_equal(a["wq"], b["wq"])


def test_prune_keeps_six_of_eight():
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 64)).astype(jnp.bfloat16)
    w = w.at[0, :8].set(1.0)   # ties: the earlier positions stay
    p = np.asarray(reference.prune(w, 6, 8))
    assert ((p.reshape(-1, 8) != 0).sum(-1) == 6).all()
    assert (p[0, :6] == 1).all() and (p[0, 6:8] == 0).all()
    kept = np.abs(np.asarray(w, np.float32)).reshape(-1, 8)
    dropped = np.where(p.reshape(-1, 8) == 0, kept, -1).max(-1)
    stayed = np.where(p.reshape(-1, 8) != 0, kept, np.inf).min(-1)
    assert (dropped <= stayed).all()


def test_logits_match_the_programs_masked_float32_forward():
    from repro.core.linear import SparsityConfig
    from repro.models import model as M

    c = dict(SMALL, torch_dtype="float32", sparsity={"pattern": [6, 8],
                                                     "recipe": "none"})
    cfg = dataclasses.replace(model.program_config(c), sparsity=SparsityConfig(
        pattern=(6, 8), mode="masked"))
    seed = 11
    toks = np.random.default_rng(0).integers(0, 128, (2, 40), np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(M.prefill(M.init(cfg, model_key(seed)), cfg,
                                    {"tokens": jnp.asarray(toks)})[0])
    rows = np.zeros((4, 256), np.int32)
    rows[:2, :40] = toks
    top = want.argmax(-1)
    cands = np.zeros((4, 256, 1), np.int32)
    cands[:2, 39, 0] = top
    gap, arg = reference.forward(c, seed, rows, cands)
    assert (arg[:2, 39] == top).all()
    assert np.abs(gap[:2, 39, 0]).max() < 1e-4
