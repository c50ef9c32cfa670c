"""A configuration's model family is found by name
(``bench/families/<family>.py``), the dense family computes what the
harness computed before families existed, a dense configuration is the
unpruned model on every side, and the mix's ``engine`` keys reach the
program's ``EngineConfig`` by name."""
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cells, model, reference, yardstick
from bench.seeds import model_key

SPEC = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))

# Pinned from the harness before the dense code moved into
# ``families/dense.py``: the yardstick's (gemm_ops, gemm_bytes, attn_ops,
# attn_bytes) at full width, exactly.
WORK = {
    "danube4b-68-bf16": {
        "decode_3": (17274470400.0, 6067952640.0, 815800320.0, 205056000.0),
        "decode_16": (92130508800.0, 6110817280.0, 2397634560.0,
                      605306880.0),
        "prefill_0": (2853988761600.0, 7709639680.0, 48412753920.0,
                      235929600.0),
        "prefill_1500": (2853988761600.0, 7709639680.0, 331528273920.0,
                         374169600.0)},
    "phi3m-68-int8": {
        "decode_3": (16074178560.0, 2965266560.0, 453222400.0, 113920000.0),
        "decode_16": (85728952320.0, 2986467584.0, 1332019200.0,
                      336281600.0),
        "prefill_0": (2617491947520.0, 3759982464.0, 26895974400.0,
                      131072000.0),
        "prefill_1500": (2617491947520.0, 3759982464.0, 184182374400.0,
                         207872000.0)},
}
LANES = {"decode_3": ("decode", [10, 700, 1500]),
         "decode_16": ("decode", [128 + 37 * i for i in range(16)]),
         "prefill_0": ("prefill", (0, 512)),
         "prefill_1500": ("prefill", (1500, 512))}
# ... and the reference's gaps at smoke widths: the argmax's sha256
# exactly, the widest gap and the sum of gaps to float32 rounding (the
# order of XLA's CPU sums follows the cores it may use, so the last bits
# of a gap do: seen 5e-7 on the widest, 3e-5 on the sum)
REF = {
    ("danube4b-68-bf16", None): ("efe5a74bab14d348", 6.890228271484375,
                                 2631.438769519329),
    ("danube4b-68-bf16", "int8"): ("ad2bb1717685d2ab", 6.950616359710693,
                                   2634.0366708040237),
    ("danube4b-68-bf16", "w4"): ("713e64acd5d24b82", 7.102021217346191,
                                 2643.6328751444817),
    ("phi3m-68-int8", None): ("ece02f5aa0848cbe", 6.801466464996338,
                              2629.846008837223),
    ("phi3m-68-int8", "int8"): ("6f5bfd2d3069d9e3", 6.830676078796387,
                                2633.0485537052155),
    ("phi3m-68-int8", "w4"): ("859a28b67d02034d", 7.015444755554199,
                              2642.4203206300735),
}


def smoke(name, **kw):
    return dict(cells.load_config(name), hidden_size=64,
                intermediate_size=96, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                vocab_size=128, sliding_window=20, **kw)


@pytest.mark.parametrize("config", sorted(WORK))
@pytest.mark.parametrize("case", sorted(LANES))
def test_nothing_moved_in_the_yardstick(config, case):
    c = cells.load_config(config)
    kind, lanes = LANES[case]
    w = (yardstick.decode_work(c, lanes) if kind == "decode"
         else yardstick.prefill_work(c, *lanes))
    assert (w.gemm_ops, w.gemm_bytes, w.attn_ops, w.attn_bytes) == \
        WORK[config][case]


@pytest.mark.parametrize("config,quant", sorted(REF, key=str))
def test_nothing_moved_in_the_reference(config, quant):
    rng = np.random.default_rng(5)
    toks = rng.integers(0, 128, (2, 256), np.int32)
    cands = rng.integers(0, 128, (2, 256, 2), np.int32)
    gap, top = reference.forward(smoke(config), 2 ** 33 + 9, toks, cands,
                                 quant=quant)
    top_sha, widest, total = REF[(config, quant)]
    assert hashlib.sha256(top.tobytes()).hexdigest()[:16] == top_sha
    assert float(gap.max()) == pytest.approx(widest, abs=1e-5)
    assert float(gap.astype(np.float64).sum()) == pytest.approx(total,
                                                                rel=1e-6)


FIXTURE_FAMILY = '''
from bench import yardstick


def program_config(c):
    return ("fixture", c["hidden_size"])


def forward(c, seed, tokens, cands, quant=None):
    return ("gap", seed, quant), "top"


def step_work(c, lanes, head_rows):
    return yardstick.Work(gemm_ops=float(sum(r for r, _ in lanes)),
                          attn_ops=float(head_rows))


def check(c, cfg):
    if cfg != ("fixture", c["hidden_size"]):
        raise ValueError(cfg)
'''


def test_a_family_from_one_file(tmp_path):
    """A later change adds a family as one file and a configuration that
    names it; the harness's entry points find it with no edit."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "families"):
        (bench / d).mkdir(parents=True)
    (bench / "families" / "fixture.py").write_text(FIXTURE_FAMILY)
    conf = dict(cells.load_json(os.path.join(
        cells.BENCH, "configs", "danube4b-68-bf16.json")),
        name="fixture-model", family="fixture")
    (bench / "configs" / "fixture-model.json").write_text(json.dumps(conf))
    (bench / "traffic" / "fixture_mix.json").write_text(
        json.dumps(cells.load_traffic("decode_b16")))
    spec = dict(SPEC, configs=[{
        "name": "fixture-model", "source": "x",
        "file": "bench/configs/fixture-model.json", "reduced": [],
        "why": "fixture"}], workloads=[{
            "name": "fixture-model.mix", "config": "fixture-model",
            "traffic": "fixture_mix", "chips": 1, "why": "fixture"}])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    c = cells.load_cell("fixture-model.mix", str(path), str(bench)).config
    assert model.program_config(c) == ("fixture", 3840)
    cells.family(c).check(c, model.program_config(c))
    assert reference.forward(c, 5, None, None, "int8") == (
        ("gap", 5, "int8"), "top")
    assert yardstick.decode_work(c, [3, 4]) == yardstick.Work(
        gemm_ops=2.0, attn_ops=2.0)
    assert yardstick.prefill_work(c, 0, 7) == yardstick.Work(
        gemm_ops=7.0, attn_ops=1.0)
    # the same name from the repository's own directory: no such file
    with pytest.raises(FileNotFoundError):
        model.program_config(cells.load_config("danube4b-68-bf16")
                             | {"family": "fixture"})


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(cells.BENCH, "configs"))))
def test_every_file_finds_its_family(name):
    """``families/<family>.py``, and ``dense`` where the file names none
    (as the files of the first benchmark do)."""
    c = cells.load_config(name)
    assert cells.family(c).__file__ == os.path.join(
        cells.BENCH, "families", f"{c.get('family', 'dense')}.py")
    assert cells.family(c) is cells.family(dict(c))   # loaded once
    for first in ("danube4b-68-bf16", "phi3m-68-int8"):
        assert "family" not in cells.load_json(
            os.path.join(cells.BENCH, "configs", f"{first}.json"))


# --------------------------------------------------------------- dense

def test_dense_configuration_maps_to_the_dense_program():
    cfg = model.program_config(cells.load_config("danube4b-dense-bf16"))
    assert cfg.sparsity.mode == "dense" and cfg.sparsity.pattern is None
    assert cfg.sparsity.recipe.name == "none"
    sparse = model.program_config(cells.load_config("danube4b-68-bf16"))
    assert dataclasses.replace(cfg, name=sparse.name,
                               sparsity=sparse.sparsity) == sparse


def test_dense_reference_matches_the_programs_float32_dense_forward():
    from repro.models import model as M

    c = smoke("danube4b-dense-bf16", torch_dtype="float32")
    cfg = model.program_config(c)
    assert cfg.sparsity.mode == "dense"
    seed = 13
    toks = np.random.default_rng(1).integers(0, 128, (2, 40), np.int32)
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
    # the same file at 6:8 is another model: pruning moves the logits
    every = np.random.default_rng(2).integers(0, 128, (4, 256, 1), np.int32)
    dense, _ = reference.forward(c, seed, rows, every)
    pruned, _ = reference.forward(dict(c, sparsity={"pattern": [6, 8],
                                                    "recipe": "none"}),
                                  seed, rows, every)
    assert np.abs(pruned - dense)[:2, :40].max() > 1e-2


def test_dense_weights_are_loaded_unpacked():
    from repro.models import model as M
    from repro.runtime import serve_loop

    cfg = model.program_config(smoke("danube4b-dense-bf16"))
    got = model.init_params(cfg, 21)
    want = M.init(cfg, model_key(21))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    keys = {jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_leaves_with_path(got)}
    assert not any("values" in k or "indices" in k for k in keys)
    assert serve_loop.pack_params(want, cfg) is want


def test_dense_decode_step_by_hand():
    # as test_bench_yardstick's smoke model (one layer): 30720 layer
    # weights and a 128 x 64 head, every one kept, bf16, no positions
    c = dict(smoke("danube4b-dense-bf16"), num_hidden_layers=1,
             sliding_window=4096)
    w = yardstick.decode_work(c, [10, 20])
    assert w.gemm_ops == 2 * (30720 + 8192) * 2
    act = 2 * 2 * (128 + 96 + 96 + 128 + 160 + 160 + 160)
    head_act = 2 * 2 * (64 + 128)
    assert w.gemm_bytes == 2 * (30720 + 8192) + act + head_act
    assert w.attn_ops == 4 * 64 * (11 + 21)
    assert w.attn_bytes == 2 * 2 * 16 * 2 * (10 + 20 + 2) + 2 * 2 * 64 * 2
    # against 6:8: three quarters of the operations, and 2.25 B a weight
    # where pruned values and positions cost 6 * 2 / 8 + 5 / 64
    sparse = yardstick.decode_work(
        dict(c, sparsity={"pattern": [6, 8], "recipe": "none"}), [10, 20])
    assert sparse.gemm_ops == 0.75 * w.gemm_ops
    assert w.gemm_bytes - sparse.gemm_bytes == pytest.approx(
        (30720 + 8192) * (2 - 6 * 2 / 8 - 5 / 64))


def test_dense_int8_prefill_by_hand():
    c = dict(smoke("danube4b-dense-bf16"), num_hidden_layers=1,
             sliding_window=4096, sparsity={"pattern": None,
                                            "recipe": "int8"})
    w = yardstick.prefill_work(c, 4, 3)
    assert w.gemm_ops == 2 * (30720 * 3 + 8192)
    scales = 4 * (64 + 32 + 32 + 64 + 96 + 96 + 64 + 128)
    act = 3 * (1 * (64 * 5 + 64 + 96) + 2 * (64 + 32 + 32 + 64 + 96 + 96 + 64))
    head_act = 1 * 64 + 2 * 128
    assert w.gemm_bytes == (30720 + 8192) * 1 + scales + act + head_act


# ---------------------------------------------------------- engine keys

@pytest.mark.parametrize("mix", sorted(
    f[:-5] for f in os.listdir(os.path.join(cells.BENCH, "traffic"))))
def test_every_mix_gives_the_engine_it_gave(mix):
    from repro.runtime.serve_loop import EngineConfig

    e = cells.load_traffic(mix)["engine"]
    assert model.engine_config(e) == EngineConfig(
        max_batch=e["max_batch"], page_size=e["page_size"],
        num_pages=e["num_pages"], max_seq_len=e["max_seq_len"],
        prefill_chunk=e["prefill_chunk"])


def test_engine_keys_reach_the_engine_by_name():
    e = dict(cells.load_traffic("decode_b16")["engine"], tp=4,
             prefix_cache=True, async_loop=True)
    got = model.engine_config(e, chips=4)
    assert (got.tp, got.prefix_cache, got.async_loop) == (4, True, True)
    assert got.max_batch == e["max_batch"]


@pytest.mark.parametrize("engine,chips,message", [
    ({"max_batch": 4, "prefix_cahce": True}, 1, "prefix_cahce"),
    ({"max_batch": 4, "tp": 4}, 1, "tp 4"),
    ({"max_batch": 4, "tp": 2}, 1, "tp 2"),
])
def test_engine_keys_refused(engine, chips, message):
    with pytest.raises(ValueError, match=message):
        model.engine_config(engine, chips)
