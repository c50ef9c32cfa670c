"""Shape-keyed tile autotuner: cache semantics + search (DESIGN.md §2.4)."""
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.patterns import Pattern, SlideDecomposition, TWO_FOUR
from repro.core import packer, quant
from repro.kernels import autotune, ops


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    autotune.clear()
    yield
    autotune.clear()


def test_tileconfig_kernel_kwargs_filters_none_and_names():
    t = autotune.TileConfig(bm=128, br=None, bk=256, block_rows=64)
    assert t.kernel_kwargs() == {"bm": 128, "bk": 256, "block_rows": 64}
    assert t.kernel_kwargs("bm", "br") == {"bm": 128}


def test_rows_bucket_powers_of_two():
    assert autotune.rows_bucket(1) == 8
    assert autotune.rows_bucket(8) == 8
    assert autotune.rows_bucket(9) == 16
    assert autotune.rows_bucket(333) == 512


def test_lookup_miss_returns_default_tiles():
    assert autotune.tiles_for("op", rows=8, m=8, k=8) == autotune.DEFAULT


def test_record_and_lookup_roundtrip_in_process():
    key = autotune.make_key("op", rows=8, m=16, k=32)
    autotune.record(key, autotune.TileConfig(bm=128, br=64), 12.5)
    got = autotune.lookup(key)
    assert got == autotune.TileConfig(bm=128, br=64)


def test_disk_cache_survives_process_state_reset(tmp_path):
    key = autotune.make_key("op", rows=8, m=16, k=32)
    autotune.record(key, autotune.TileConfig(bk=512), 3.0)
    path = autotune.cache_path()
    with open(path) as f:
        disk = json.load(f)
    assert disk[key]["tiles"]["bk"] == 512
    # simulate a fresh process: drop memory, force disk re-read
    autotune.clear()
    autotune._DISK_LOADED = False
    assert autotune.lookup(key) == autotune.TileConfig(bk=512)


@pytest.mark.parametrize("payload", [
    '{"half": {"tiles": {"bm": 64',       # truncated (interrupted writer)
    "[1, 2, 3]",                          # parses, but root is not a dict
    '{"key": 5}',                         # record is not an object
    "\x00\xff garbage",                   # not JSON at all
])
def test_corrupt_disk_cache_quarantined_not_fatal(payload):
    """A corrupt/truncated on-disk cache must never crash the kernels: it
    is moved to ``.bak`` with a warning and tuning restarts empty."""
    path = autotune.cache_path()
    with open(path, "w") as f:
        f.write(payload)
    autotune.clear()
    autotune._DISK_LOADED = False
    with pytest.warns(RuntimeWarning, match="corrupt"):
        assert autotune.lookup("whatever") is None  # triggers _load_disk
    import os
    assert not os.path.exists(path)        # bad file moved aside...
    with open(path + ".bak") as f:
        assert f.read() == payload         # ...preserved for post-mortem
    # the cache is fully functional again: record writes a fresh file
    key = autotune.make_key("op", rows=8, m=16, k=32)
    autotune.record(key, autotune.TileConfig(bm=128), 1.0)
    autotune.clear()
    autotune._DISK_LOADED = False
    assert autotune.lookup(key) == autotune.TileConfig(bm=128)


def test_cache_disabled_with_empty_env(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "")
    assert autotune.cache_path() is None
    # record must not raise without a disk path
    autotune.record(autotune.make_key("op", rows=1, m=1, k=1),
                    autotune.DEFAULT, 1.0)


def test_cache_path_unset_means_no_disk_cache(monkeypatch, tmp_path):
    """Served tiles come from committed code: with REPRO_AUTOTUNE_CACHE
    unset nothing is read from or written to any file (no ~/.cache)."""
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE")
    monkeypatch.setenv("HOME", str(tmp_path))
    assert autotune.cache_path() is None
    autotune.record(autotune.make_key("op", rows=1, m=1, k=1),
                    autotune.TileConfig(bm=128), 1.0)
    assert list(tmp_path.iterdir()) == []


def test_autotune_picks_fastest_candidate():
    slow = autotune.TileConfig(bm=128)
    fast = autotune.TileConfig(bm=256)

    def run(tiles):
        if tiles == slow:
            time.sleep(0.01)
        return np.zeros(())

    best = autotune.autotune("op", run, [slow, fast],
                             key=autotune.make_key("op", rows=1, m=1, k=1))
    assert best == fast
    assert autotune.lookup(autotune.make_key("op", rows=1, m=1, k=1)) == fast


def test_autotune_skips_crashing_candidates():
    bad = autotune.TileConfig(bm=7)

    def run(tiles):
        if tiles == bad:
            raise ValueError("invalid tile")
        return np.zeros(())

    assert autotune.autotune("op", run, [bad, autotune.DEFAULT]) \
        == autotune.DEFAULT


def test_tune_skipped_under_jit_tracing():
    """Tuning inside jit would time TRACING (block_until_ready is a no-op
    on tracers) and cache a noise-derived winner — it must be skipped."""
    import jax

    dec = SlideDecomposition(Pattern(6, 8), TWO_FOUR)
    rng = np.random.default_rng(1)
    k, m, rows = 4 * dec.source.l, 16, 8
    w = packer.prune_to_pattern(
        jnp.asarray(rng.standard_normal((m, k)), jnp.float32), dec.source)
    qw = quant.quantize_weight_int8_rowwise(w)
    ws_q = packer.pack_slided(qw.q, dec)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)

    @jax.jit
    def f(a):
        return ops.slided_matmul_int8(a, ws_q, qw.scale, dec,
                                      use_pallas=True, interpret=True,
                                      tune=True)

    jax.block_until_ready(f(x))
    key = autotune.make_key("fused_slided_matmul",
                            rows=autotune.rows_bucket(rows), m=m, k=k,
                            pattern="6:8", dtype="float32", adt="int8",
                            wdt="int8", interpret=True)
    assert autotune.lookup(key) is None  # nothing recorded under trace


def test_ops_tune_records_and_reuses(monkeypatch):
    dec = SlideDecomposition(Pattern(6, 8), TWO_FOUR)
    rng = np.random.default_rng(0)
    k, m, rows = 4 * dec.source.l, 16, 8
    w = packer.prune_to_pattern(
        jnp.asarray(rng.standard_normal((m, k)), jnp.float32), dec.source)
    qw = quant.quantize_weight_int8_rowwise(w)
    ws_q = packer.pack_slided(qw.q, dec)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    y = ops.slided_matmul_int8(x, ws_q, qw.scale, dec, use_pallas=True,
                               interpret=True, tune=True)
    key = autotune.make_key("fused_slided_matmul",
                            rows=autotune.rows_bucket(rows), m=m, k=k,
                            pattern="6:8", dtype="float32", adt="int8",
                            wdt="int8", interpret=True)
    assert autotune.lookup(key) is not None
    # second call must hit the cache, not re-search
    calls = []
    monkeypatch.setattr(autotune, "autotune",
                        lambda *a, **kw: calls.append(1) or autotune.DEFAULT)
    y2 = ops.slided_matmul_int8(x, ws_q, qw.scale, dec, use_pallas=True,
                                interpret=True, tune=True)
    assert not calls
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-6)
