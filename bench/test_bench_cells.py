"""Cells, configurations, mixes and metrics are found by name, and a new
cell and metric can be added from files alone."""
import json
import os
import re
import shutil

import pytest

from bench import cells, model

SPEC = cells.load_json(os.path.join(cells.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_json_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and c["file"].startswith("bench/")
        assert cells.load_json(os.path.join(cells.ROOT, c["file"]))[
            "reduced"] == c["reduced"]
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        reports = {m["name"] for m in SPEC["end_to_end"]
                   if w["name"] in m.get("workloads", [w["name"]])}
        assert "setup_s" in reports and len(reports) >= 2
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])


def _maps(c: dict):
    """What every family's configuration file maps to; its family's
    ``check`` says how the rest of its keys do."""
    cfg = model.program_config(c)
    assert cfg.d_model == c["hidden_size"]
    assert cfg.vocab_size == c["vocab_size"]
    assert cfg.sparsity.mode == (
        "compressed" if c["sparsity"]["pattern"] else "dense")
    assert cfg.sparsity.recipe.name == c["sparsity"]["recipe"]
    cells.family(c).check(c, cfg)
    assert set(c["reduced"]) <= set(c.get("published", {}))


def _loads(c: cells.Cell, bench_dir: str = cells.BENCH):
    engine = c.traffic["engine"]
    assert c.chips in (1, 4) and c.end_to_end and c.per_layer
    assert model.engine_config(engine, c.chips).tp == engine.get("tp", 1)
    _maps(c.config)
    for m in c.end_to_end + c.per_layer:
        assert callable(cells.reader(m["name"], bench_dir))


def _bench_dir(tmp_path):
    """A bench directory as a checkout has it, without configurations
    and mixes."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic"):
        (bench / d).mkdir(parents=True)
    for d in ("families", "metrics"):
        shutil.copytree(os.path.join(cells.BENCH, d), bench / d)
    return bench


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_by_name(cell):
    _loads(cells.load_cell(cell))


def test_a_four_chip_tp_cell_from_files_alone(tmp_path):
    """A cell that shards its model over four chips is a mix whose
    ``engine`` sets ``tp`` and a cell entry with ``chips`` 4."""
    bench = _bench_dir(tmp_path)
    shutil.copy(os.path.join(cells.BENCH, "configs", "danube4b-68-bf16.json"),
                bench / "configs")
    mix = cells.load_traffic("decode_b16")
    mix = dict(mix, engine=dict(mix["engine"], tp=4))
    (bench / "traffic" / "decode_tp4.json").write_text(json.dumps(mix))
    every = {k: [{f: v for f, v in m.items() if f != "workloads"}
                 for m in SPEC[k]] for k in ("end_to_end", "per_layer")}
    spec = dict(SPEC, **every, configs=[
        c for c in SPEC["configs"] if c["name"] == "danube4b-68-bf16"],
        workloads=[{"name": "danube4b-68-bf16.tp4",
                    "config": "danube4b-68-bf16", "traffic": "decode_tp4",
                    "chips": 4, "why": "fixture"}])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    cell = cells.load_cell("danube4b-68-bf16.tp4", str(path), str(bench))
    _loads(cell, str(bench))
    assert cell.chips == 4
    assert model.engine_config(cell.traffic["engine"], cell.chips).tp == 4


def test_cell_config_and_metric_from_files_alone(tmp_path):
    """A later change adds a configuration, a mix, a metric and a cell as
    files and entries; the loader needs no edit."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics"):
        (bench / d).mkdir(parents=True)
    conf = cells.load_config("danube4b-68-bf16")
    conf = dict(conf, name="fixture-model", num_hidden_layers=2)
    (bench / "configs" / "fixture-model.json").write_text(json.dumps(conf))
    mix = dict(cells.load_traffic("decode_b16"), clients=3)
    (bench / "traffic" / "fixture_mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "fixture_metric.py").write_text(
        "def read(run, trace):\n    return 2.0 * run\n")
    spec = dict(SPEC, configs=[{
        "name": "fixture-model", "source": "x",
        "file": "bench/configs/fixture-model.json", "reduced": [],
        "why": "fixture"}], workloads=[{
            "name": "fixture-model.mix", "config": "fixture-model",
            "traffic": "fixture_mix", "chips": 1, "why": "fixture"}],
        per_layer=[{"name": "fixture_metric", "unit": "%",
                    "better": "higher", "source": "program_counter",
                    "layer": "engine loop", "moves": "output_tok_s"}])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(spec))
    cell = cells.load_cell("fixture-model.mix", str(path), str(bench))
    assert cell.config["num_hidden_layers"] == 2
    assert cell.traffic["clients"] == 3
    assert [m["name"] for m in cell.per_layer] == ["fixture_metric"]
    assert cells.reader("fixture_metric", str(bench))(21.0, None) == 42.0
    with pytest.raises(KeyError):
        cells.load_cell("danube4b-68-bf16.decode", str(path), str(bench))


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(cells.BENCH, "configs"))))
def test_every_configuration_file_maps_to_the_program(name):
    _maps(cells.load_config(name))


def test_a_full_attention_file_maps_to_the_program(tmp_path):
    """A dense file with no ``sliding_window`` is the model with every
    layer full: the program's units, and the yardstick's keys."""
    from bench import yardstick

    bench = _bench_dir(tmp_path)
    conf = cells.load_json(os.path.join(cells.BENCH, "configs",
                                        "danube4b-dense-bf16.json"))
    del conf["sliding_window"]
    conf["name"] = "full-attention"
    (bench / "configs" / "full-attention.json").write_text(json.dumps(conf))
    c = cells.load_config("full-attention", str(bench))
    _maps(c)
    cfg = model.program_config(c)
    assert cfg.unit_pattern == ("attn",)
    with pytest.raises(ValueError, match="window 1024"):
        cells.family(c).check(dict(c, sliding_window=1024), cfg)
    swa = dict(c, sliding_window=4096)
    assert yardstick.decode_work(c, [6000]).attn_ops > \
        yardstick.decode_work(swa, [6000]).attn_ops
