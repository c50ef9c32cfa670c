"""A whole run of the harness at a tiny size on the CPU (the look for a
chip skipped): the result line's keys, the refusal of a CPU, and the
faults of the timed path that the comparison has to catch."""
import json
import time

import jax
import jax.numpy as jnp
import pytest

from bench import cells, model, peaks, run

TINY = dict(cells.load_config("danube4b-68-bf16"), hidden_size=256,
            intermediate_size=512, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, head_dim=64, vocab_size=512,
            sliding_window=100)
MIX = {"loop": "closed", "clients": 3,
       "prompt": {"dist": "uniform", "min": 8, "max": 40},
       "output": {"dist": "uniform", "min": 40, "max": 80},
       "first_output": {"dist": "uniform", "min": 1, "max": 80},
       "engine": {"max_batch": 4, "page_size": 8, "prefill_chunk": 16,
                  "max_seq_len": 120, "num_pages": 64}}
E2E = tuple({"name": n, "unit": u} for n, u in (
    ("setup_s", "s"), ("output_tok_s", "tokens/s"), ("itl_p95_ms", "ms"),
    ("ttft_p90_ms", "ms")))
# at this size the widest gap of sound runs is 0.018-0.026 (bf16 activations
# against the float32 reference) and the control 0.058-0.17 on six seeds;
# every fault below reads far above both
LIMIT = 0.045
SEED = 2 ** 35 + 3
# the same model served dense: sound runs 0.0026-0.0416 and the control
# 0.066-0.166 on eleven seeds (0.0227 and 0.110 on SEED)
TINY_DENSE = dict(TINY, sparsity={"pattern": None, "recipe": "none"})
DENSE_LIMIT = 0.05


@pytest.fixture(autouse=True)
def no_disk_cache(monkeypatch):
    monkeypatch.setattr(run, "enable_cache", lambda: "off")


def tiny_run(seed=SEED, control=False, config=TINY, limit=LIMIT):
    cell = cells.Cell("tiny", config, MIX, 1, E2E, ())
    return run.run_cell(cell, seed, 1.5, False, time.time(),
                        peaks.for_kind("TPU v5 lite"), limit=limit,
                        control=control)


def test_result_line():
    out = tiny_run()
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in E2E}
    assert set(out["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    assert out["checks"]["compiles_in_window"]["value"] == 0
    json.dumps(out)


def test_refuses_a_cpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "danube4b-68-bf16.decode", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


def _faulty(kind):
    """The engine with its decode step broken where the tokens are made."""
    make = model.make_engine

    def make_engine(params, cfg, engine, chips=1):
        eng = make(params, cfg, engine, chips)
        orig = eng._decode_fn

        def step(p, tok, c, pt, kvl, act):
            ids, logits, c2 = orig(p, tok, c, pt, kvl, act)
            if kind == "state_unchanged":      # the KV cache not written
                return ids, logits, c
            if kind == "half_batch":
                # half the lanes left out of each step (which half turns
                # with the context length): an uncomputed lane reads 0
                return jnp.where(kvl % 2 == 1, 0, ids), logits, c2
            return (ids + 1) % cfg.vocab_size, logits, c2   # token altered

        eng._decode_fn = jax.jit(step)
        return eng

    return make_engine


MODELS = pytest.mark.parametrize("config,limit", [
    (TINY, LIMIT), (TINY_DENSE, DENSE_LIMIT)], ids=["compressed", "dense"])


@MODELS
@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "token_altered"])
def test_a_broken_timed_path_is_not_correct(kind, config, limit,
                                            monkeypatch):
    monkeypatch.setattr(model, "make_engine", _faulty(kind))
    out = tiny_run(config=config, limit=limit)
    assert out["checks"]["compiles_in_window"]["value"] == 0
    assert out["checks"]["max_logit_gap"]["value"] > limit
    assert out["correct"] is False


@MODELS
def test_the_control_is_not_correct(config, limit):
    """The reference one precision below the configuration's, in the
    program's place (the unpruned reference at int8 for a dense
    configuration), reads above the limit that sound runs keep."""
    out = tiny_run(control=True, config=config, limit=limit)
    c = out["checks"]
    assert out["correct"] is True
    assert c["max_logit_gap"]["value"] <= limit
    assert c["control_max_logit_gap"]["value"] > limit


@pytest.mark.parametrize("n", [4, 8])
def test_check_compares_the_requests_the_mix_asks_for(n):
    """``check_requests`` sets how many finished requests are compared:
    the longest, and the rest drawn from the seed."""
    from bench import check
    from bench.driver import ReqRecord, WindowRecord

    reqs = {i: ReqRecord(i, 0, 0.0, [1] * 5, 9, tokens=[2] * (i + 1),
                         status="OK") for i in range(12)}
    rec = WindowRecord(0.0, 1.0, reqs, [])
    picked = check.sample(rec, SEED, n)
    assert len(picked) == n and picked[0].rid == 11
    assert len({r.rid for r in picked}) == n
    rows = check.rows(picked, n)
    assert rows.tokens.shape == (n, check.BUCKET)
    assert rows.count == sum(len(r.tokens) for r in picked)
