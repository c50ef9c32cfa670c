"""PrecisionRecipe axis (DESIGN.md §10): registry/shim, w4 packing,
recipe-polymorphic kernels, and the dense same-precision references."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
# runs under real hypothesis when installed, else the seeded fallback sweep
from proptest import given, settings, strategies as st

from repro.core.patterns import Pattern, SlideDecomposition, TWO_FOUR
from repro.core import (compressed as comp, linear, packer, precision,
                        quant)
from repro.core.linear import SparsityConfig
from repro.core.precision import RECIPES, PrecisionRecipe
from repro.kernels import ops, ref


def _dec(n):
    return SlideDecomposition(Pattern(2 * n - 2, 2 * n), TWO_FOUR)


def _weights(rng, m, k, pat):
    w = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    return packer.prune_to_pattern(w, pat)


# ------------------------------------------------------------ registry/shim
def test_recipe_registry_axes():
    assert RECIPES["none"].quantized is False
    assert RECIPES["int8"].acc_dtype == jnp.int32
    assert RECIPES["fp8"].acc_dtype == jnp.float32
    assert RECIPES["fp8"].act_dtype == jnp.float8_e4m3fn
    assert RECIPES["w4"].packed_weights and RECIPES["w4"].act == "int8"
    assert RECIPES["fp8w4"].packed_weights and RECIPES["fp8w4"].act == "fp8"


def test_recipe_rejects_inconsistent_axes():
    with pytest.raises(ValueError, match="both quantized or both float"):
        PrecisionRecipe("bad", act=None, weight="int8")
    with pytest.raises(ValueError, match="both quantized or both float"):
        PrecisionRecipe("bad", act="int8", weight=None)
    with pytest.raises(ValueError, match="unknown activation"):
        PrecisionRecipe("bad", act="fp4", weight="int8")
    with pytest.raises(ValueError, match="unknown weight"):
        PrecisionRecipe("bad", act="int8", weight="w2")


def test_act_quant_shim_maps_onto_recipes():
    """Back-compat pin: the legacy act_quant strings map onto the registry
    entries, and precision.resolve is the only interpreter of them."""
    assert precision.resolve(None, act_quant=None) is RECIPES["none"]
    assert precision.resolve(None, act_quant="int8") is RECIPES["int8"]
    assert SparsityConfig().recipe is RECIPES["none"]
    assert SparsityConfig(act_quant="int8").recipe is RECIPES["int8"]
    assert SparsityConfig(recipe="fp8").recipe is RECIPES["fp8"]
    # explicit recipe wins; act_quant mirrors its activation axis after init
    cfg = SparsityConfig(recipe="w4")
    assert cfg.act_quant == "int8"
    assert dataclasses.replace(cfg, tune=True).recipe is RECIPES["w4"]
    # the legacy axis is exactly None | 'int8' — 'fp8' must NOT sneak in
    with pytest.raises(ValueError, match="unknown act_quant"):
        SparsityConfig(act_quant="fp8")
    with pytest.raises(ValueError, match="unknown act_quant"):
        SparsityConfig(act_quant="int4")
    with pytest.raises(ValueError, match="unknown precision recipe"):
        SparsityConfig(recipe="fp16")


def test_act_quant_replace_on_resolved_config_is_not_dropped():
    """Regression: dataclasses.replace(cfg, act_quant='int8') on an
    already-resolved config must flip the recipe, not silently keep the
    carried one (__post_init__ sees a resolved recipe AND the explicit
    flag; the explicit flag wins on disagreement)."""
    cfg = dataclasses.replace(SparsityConfig(), act_quant="int8")
    assert cfg.recipe is RECIPES["int8"] and cfg.act_quant == "int8"
    cfg2 = dataclasses.replace(SparsityConfig(recipe="fp8"),
                               act_quant="int8")
    assert cfg2.recipe is RECIPES["int8"]
    # and a no-op replace keeps the recipe (mirrored act_quant matches)
    cfg3 = dataclasses.replace(SparsityConfig(recipe="fp8w4"), tune=True)
    assert cfg3.recipe is RECIPES["fp8w4"]


def test_recipe_hashable_as_jit_constant():
    cfg = SparsityConfig(pattern=(6, 8), mode="compressed", recipe="fp8")
    assert hash(cfg) == hash(dataclasses.replace(cfg))
    assert cfg == dataclasses.replace(cfg)


# ------------------------------------------------------------- w4 packing
@settings(max_examples=25, deadline=None)
@given(st.integers(1, 8), st.integers(1, 32), st.integers(0, 2**31 - 1))
def test_nibble_pack_roundtrip(rows, half_cols, seed):
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.integers(-8, 8, size=(rows, 2 * half_cols)),
                    jnp.int8)
    p = packer.pack_nibbles(v)
    assert p.dtype == jnp.int8 and p.shape == (rows, half_cols)
    np.testing.assert_array_equal(np.asarray(packer.unpack_nibbles(p)),
                                  np.asarray(v))


def test_nibble_pack_rejects_odd_width():
    with pytest.raises(ValueError, match="odd trailing"):
        packer.pack_nibbles(jnp.zeros((2, 3), jnp.int8))


def test_int4_weight_quant_range_and_zeros():
    w = jnp.asarray([[0.0, 1.0, -2.0, 0.0, 0.5, 0.0, 0.0, 3.0]])
    qw = quant.quantize_weight_int4_rowwise(w)
    q = np.asarray(qw.q)
    assert q.dtype == np.int8
    assert np.abs(q).max() <= 7
    assert (q[np.asarray(w) == 0] == 0).all()  # commutes with the pattern
    np.testing.assert_allclose(np.asarray(qw.scale[:, 0]), [3.0 / 7.0],
                               rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from([2, 3, 4]), st.sampled_from([2, 4]),
       st.integers(0, 2**31 - 1))
def test_w4_packed_compression_and_shard_mirrors(n, shards, seed):
    """Nibble-packed compression is lossless, and split_k/split_out of
    packed blocks decompress to exactly the K-/out-slices of the unsharded
    reference (byte slices congruent with slot slices)."""
    dec = _dec(n)
    rng = np.random.default_rng(seed)
    out, k = 4 * shards, dec.source.l * 2 * shards
    w = _weights(rng, out, k, dec.source)
    q4 = quant.quantize_weight_int4_rowwise(w)
    c = comp.compress(packer.pack_slided(q4.q, dec), dec, pack_values=True)
    assert c.packed and c.values.shape[-3] * 2 == c.indices.shape[-3]
    full = np.asarray(comp.decompress_original(c))
    np.testing.assert_array_equal(full, np.asarray(q4.q))
    for i, sh in enumerate(comp.split_k(c, shards)):
        assert sh.packed and sh.k == k // shards
        np.testing.assert_array_equal(
            np.asarray(comp.decompress_original(sh)),
            full[:, i * k // shards:(i + 1) * k // shards])
    for i, sh in enumerate(comp.split_out(c, shards)):
        np.testing.assert_array_equal(
            np.asarray(comp.decompress_original(sh)),
            full[i * out // shards:(i + 1) * out // shards])


# ----------------------------------------------- recipe-polymorphic kernels
@pytest.mark.parametrize("recipe", ["fp8", "w4", "fp8w4"])
@pytest.mark.parametrize("n_fam", [2, 3, 4])
def test_compressed_matmul_recipe_kernel_matches_oracle(recipe, n_fam):
    dec = _dec(n_fam)
    k, m, rows = 8 * dec.source.l, 40, 13
    rng = np.random.default_rng(n_fam)
    rec = RECIPES[recipe]
    w = _weights(rng, m, k, dec.source)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    qw = rec.quantize_weight(w)
    c = comp.compress(packer.pack_slided(qw.q, dec), dec,
                      pack_values=rec.packed_weights)
    y_ref = ref.compressed_matmul_quant(x, c, qw.scale, rec, jnp.float32)
    y_k = ops.compressed_matmul(x, c, s_w=qw.scale, recipe=rec,
                                out_dtype=jnp.float32, use_pallas=True,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)
    # ... and the oracle equals the dense same-precision reference exactly
    # reconstructed weights == rowwise-quantized pruned weights
    y_dense = quant.matmul_dequant(rec.quantize_act(x), qw, jnp.float32)
    np.testing.assert_array_equal(np.asarray(y_ref), np.asarray(y_dense))


@pytest.mark.parametrize("recipe", ["fp8", "w4", "fp8w4"])
@pytest.mark.parametrize("rows", [1, 8, 333])
def test_fused_slided_matmul_recipe_matches_ref(recipe, rows):
    """The single-pass kernel (quant+lift prologue, w4 nibble unpack,
    dtype-selected accumulator) tracks the jnp oracle for every recipe."""
    dec = _dec(3)
    k, m = 8 * dec.source.l, 40
    rng = np.random.default_rng(rows)
    rec = RECIPES[recipe]
    w = _weights(rng, m, k, dec.source)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    qw = rec.quantize_weight(w)
    ws = packer.pack_slided(qw.q, dec)
    if rec.packed_weights:
        ws = packer.pack_nibbles(ws)
    y_ref = ref.slided_matmul_quant(x, ws, qw.scale, dec, rec, jnp.float32)
    y_k = ops.slided_matmul_quant(x, ws, qw.scale, dec, rec,
                                  out_dtype=jnp.float32, use_pallas=True,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


def test_quant_matmul_fp8_operands():
    """The dense quantized baseline accepts e4m3 activations (fp32 accum)."""
    rng = np.random.default_rng(3)
    rows, m, k = 16, 24, 128
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    qx = quant.quantize_fp8(x)
    qw = quant.quantize_weight_int8_rowwise(w)
    y_ref = ref.quant_matmul(qx.q, qx.scale, qw.q, qw.scale)
    y_k = ops.quant_matmul(qx.q, qx.scale, qw.q, qw.scale, use_pallas=True,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)
    # close to the fp matmul (fp8 is ~2-3% relative on gaussian data)
    y_fp = np.asarray(x) @ np.asarray(w).T
    rel = np.abs(np.asarray(y_k) - y_fp) / (np.abs(y_fp) + 1.0)
    assert rel.mean() < 0.05


def test_fused_quant_slide_recipe_dispatch():
    """ops.fused_quant_slide(recipe=...) selects the e4m3 quantizer and is
    bit-identical to the quantize_fp8-based oracle (divide-by-scale form)."""
    dec = _dec(4)
    x = jnp.asarray(np.random.default_rng(7).standard_normal((19, 48)) * 3,
                    jnp.float32)
    q_ref, s_ref = ref.fused_quant_slide(x, dec, fp8=True)
    q_k, s_k = ops.fused_quant_slide(x, dec, use_pallas=True, interpret=True,
                                     recipe="fp8")
    assert q_k.dtype == jnp.float8_e4m3fn
    np.testing.assert_array_equal(np.asarray(q_k, np.float32),
                                  np.asarray(q_ref, np.float32))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), rtol=1e-6)
    with pytest.raises(ValueError, match="no activation quantizer"):
        ops.fused_quant_slide(x, dec, recipe="none")


def test_compressed_matmul_recipe_operand_mismatch():
    """A recipe whose weight storage disagrees with the operand's packing
    fails fast instead of silently misinterpreting the bytes."""
    dec = _dec(3)
    rng = np.random.default_rng(5)
    w = _weights(rng, 16, 4 * dec.source.l, dec.source)
    q4 = quant.quantize_weight_int4_rowwise(w)
    c = comp.compress(packer.pack_slided(q4.q, dec), dec, pack_values=True)
    x = jnp.asarray(rng.standard_normal((4, 4 * dec.source.l)), jnp.float32)
    with pytest.raises(ValueError, match="packed"):
        ops.compressed_matmul(x, c, s_w=q4.scale, recipe="int8",
                              use_pallas=False)
    with pytest.raises(ValueError, match="s_w"):
        ops.compressed_matmul(x, c, recipe="w4", use_pallas=False)


# -------------------------------------------------- linear.apply dispatch
@pytest.mark.parametrize("recipe", ["int8", "fp8", "w4", "fp8w4"])
@pytest.mark.parametrize("mode", ["compressed", "slided"])
def test_linear_recipe_paths_match_dense_same_precision(recipe, mode):
    """Sparse execution under every recipe equals the dense same-precision
    reference (masked mode + same recipe) — the end-state parity the
    engine tests extend to full decoding."""
    params = linear.init(jax.random.PRNGKey(0), 48, 24)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 48), jnp.float32)
    cfg = SparsityConfig(pattern=(6, 8), mode=mode, recipe=recipe,
                         use_pallas=False)
    ref_cfg = SparsityConfig(pattern=(6, 8), mode="masked", recipe=recipe)
    y = linear.apply(params, x, cfg)
    y_ref = linear.apply(params, x, ref_cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    # prepared == lazy, and the master weights are dropped at serving time
    prepared = linear.prepare(params, cfg)
    assert "w" not in prepared and "s_w" in prepared
    np.testing.assert_array_equal(
        np.asarray(linear.apply(prepared, x, cfg)), np.asarray(y))


def test_prepare_w4_emits_packed_values():
    params = linear.init(jax.random.PRNGKey(0), 48, 24)
    cfg = SparsityConfig(pattern=(6, 8), mode="compressed", recipe="w4")
    prepared = linear.prepare(params, cfg)
    assert prepared["values"].shape[-3] * 2 == prepared["indices"].shape[-3]
    int8_cfg = SparsityConfig(pattern=(6, 8), mode="compressed",
                              recipe="int8")
    int8_prep = linear.prepare(params, int8_cfg)
    assert prepared["values"].nbytes * 2 == int8_prep["values"].nbytes


# --------------------------------------------------------- autotune keys
def test_autotune_keys_distinguish_precisions(monkeypatch, tmp_path):
    """Regression (ISSUE 4 satellite): an int8-tuned tile winner must not
    be reused for fp8 or w4 operands of the same logical shape — the
    adt/wdt key components keep the cache entries apart."""
    from repro.kernels import autotune

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    base = dict(rows=8, m=16, k=32, pattern="6:8", interpret=True)
    keys = {name: autotune.make_key("compressed_matmul", adt=r.act,
                                    wdt=r.weight, **base)
            for name, r in RECIPES.items() if r.quantized}
    assert len(set(keys.values())) == len(keys)
    autotune.clear()
    autotune.record(keys["int8"], autotune.TileConfig(bm=128), 1.0)
    assert autotune.lookup(keys["int8"]) == autotune.TileConfig(bm=128)
    for name in ("fp8", "w4", "fp8w4"):
        assert autotune.lookup(keys[name]) is None, \
            f"int8 winner leaked into the {name} key"
    autotune.clear()
