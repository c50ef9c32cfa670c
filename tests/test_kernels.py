"""Per-kernel allclose vs the ref.py jnp oracles, interpret=True on CPU.

Sweeps shapes (including non-divisible tails), dtypes and sparsity patterns,
per the deliverable (c) requirement.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.patterns import Pattern, SlideDecomposition, TWO_FOUR
from repro.core import packer, compressed as comp, quant
from repro.kernels import ops, ref
from repro.kernels.fused_quant_slide import fused_quant_slide_pallas, lift_pairs
from repro.kernels.slide_matmul import compressed_matmul_pallas, decompress_tile
from repro.kernels.quant_matmul import quant_matmul_pallas
from repro.core import slide

PATTERNS = [(4, 6), (6, 8), (8, 10), (14, 16)]


def _dec(p):
    return SlideDecomposition(Pattern(*p), TWO_FOUR)


def _weights(rng, m, k, pat, dtype=jnp.float32):
    w = jnp.asarray(rng.standard_normal((m, k)), dtype)
    return packer.prune_to_pattern(w, pat)


# ---------------------------------------------------------------- kernel 1
@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("rows,k_groups", [(1, 2), (7, 4), (64, 16), (130, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_quant_slide_matches_ref(pattern, rows, k_groups, dtype):
    dec = _dec(pattern)
    k = k_groups * dec.source.l
    rng = np.random.default_rng(hash((pattern, rows, k)) % 2**32)
    x = jnp.asarray(rng.standard_normal((rows, k)) * 3, dtype)
    q_ref, s_ref = ref.fused_quant_slide(x, dec)
    q_k, s_k = ops.fused_quant_slide(x, dec, use_pallas=True, interpret=True)
    # allow <=1 quantum on round-to-nearest ties (XLA fusion-order dependent)
    diff = np.abs(np.asarray(q_k, np.int32) - np.asarray(q_ref, np.int32))
    assert diff.max() <= 1 and (diff != 0).mean() < 0.01
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), rtol=1e-6)


def test_lift_pairs_equals_index_map():
    """The kernel's slice-based Psi == the gather-based Psi for all N."""
    for n in (3, 4, 5, 8):
        dec = _dec((2 * n - 2, 2 * n))
        k = 4 * dec.source.l
        x = jnp.arange(6 * k, dtype=jnp.float32).reshape(6, k)
        np.testing.assert_array_equal(
            np.asarray(lift_pairs(x, n)), np.asarray(slide.lift(x, dec)))


@pytest.mark.parametrize("pattern", [(4, 6), (6, 8)])
def test_fused_quant_slide_fp8(pattern):
    """FP8 (e4m3) variant of Alg. 1 — the paper's FP8 columns."""
    dec = _dec(pattern)
    k = 8 * dec.source.l
    x = jnp.asarray(np.random.default_rng(5).standard_normal((24, k)) * 2,
                    jnp.float32)
    q_ref, s_ref = ref.fused_quant_slide(x, dec, fp8=True)
    q_k, s_k = fused_quant_slide_pallas(x, n_fam=dec.source.family_n,
                                        interpret=True, fp8=True)
    assert q_k.dtype == jnp.float8_e4m3fn
    np.testing.assert_allclose(np.asarray(q_k, np.float32),
                               np.asarray(q_ref, np.float32),
                               rtol=0.07, atol=0.05)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), rtol=1e-6)
    # dequantized roundtrip error bounded by e4m3 relative precision
    rec = np.asarray(q_k, np.float32) * np.asarray(s_k)
    lifted = np.asarray(x)[:, np.asarray(
        __import__('repro.core.slide', fromlist=['lift_index_map'])
        .lift_index_map(k, *pattern, 2, 4))]
    rel = np.abs(rec - lifted) / (np.abs(lifted) + 1e-3)
    assert rel.mean() < 0.05


@pytest.mark.parametrize("pattern", [(4, 6), (6, 8), (8, 10)])
@pytest.mark.parametrize("rows", [1, 24, 130])
def test_fused_quant_slide_fp8_scale_correctness(pattern, rows):
    """fp8 branch: the per-row scale is exactly absmax/448 (clamped), for
    adversarial rows — huge outliers, tiny denormal-range rows, zero rows."""
    dec = _dec(pattern)
    k = 4 * dec.source.l
    rng = np.random.default_rng(hash((pattern, rows)) % 2**32)
    x = np.asarray(rng.standard_normal((rows, k)), np.float32)
    x[0, 0] = 3e4           # outlier row
    if rows > 2:
        x[1, :] = 0.0       # all-zero row -> absmax clamps to 1e-8
        x[2, :] *= 1e-9     # sub-clamp magnitudes
    x = jnp.asarray(x)
    q, s = fused_quant_slide_pallas(x, n_fam=dec.source.family_n,
                                    interpret=True, fp8=True)
    assert q.dtype == jnp.float8_e4m3fn
    expected = np.maximum(np.abs(np.asarray(x)).max(-1, keepdims=True), 1e-8)
    expected = expected / 448.0
    np.testing.assert_allclose(np.asarray(s), expected, rtol=1e-6)


def test_fused_quant_slide_fp8_saturating_cast():
    """e4m3 has no inf: the store path must saturate at +-448 and the
    quantized magnitudes can never exceed the fp8 max.  Note XLA's raw
    float32->e4m3 cast only saturates NEAR the boundary — far-overflow
    becomes NaN — which is why the kernel clamps before casting."""
    big = jnp.asarray([1e4, 448.0, 460.0], jnp.float32)
    cast = np.asarray(big.astype(jnp.float8_e4m3fn), np.float32)
    assert np.isnan(cast[0])            # raw cast is NOT total...
    np.testing.assert_array_equal(cast[1:], [448.0, 448.0])
    clamped = jnp.clip(big, -448.0, 448.0).astype(jnp.float8_e4m3fn)
    np.testing.assert_array_equal(     # ...the kernel's clamp+cast is
        np.asarray(clamped, np.float32), [448.0, 448.0, 448.0])

    dec = _dec((6, 8))
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((16, 4 * dec.source.l)) * 1e6,
                    jnp.float32)
    q, s = fused_quant_slide_pallas(x, n_fam=4, interpret=True, fp8=True)
    qf = np.asarray(q, np.float32)
    assert np.isfinite(qf).all()
    assert np.abs(qf).max() <= 448.0
    # each row's absmax element lands on the fp8 max exactly
    assert (np.abs(qf).max(axis=-1) == 448.0).all()


@pytest.mark.parametrize("pattern", [(4, 6), (6, 8)])
def test_fused_quant_slide_fp8_roundtrip_vs_float_reference(pattern):
    """Dequantized fp8 output reconstructs the LIFTED float input to within
    e4m3 relative precision (2^-3 mantissa ~ 6% worst case)."""
    dec = _dec(pattern)
    k = 8 * dec.source.l
    x = jnp.asarray(np.random.default_rng(6).standard_normal((32, k)) * 5,
                    jnp.float32)
    q, s = fused_quant_slide_pallas(x, n_fam=dec.source.family_n,
                                    interpret=True, fp8=True, block_rows=8)
    rec = np.asarray(q, np.float32) * np.asarray(s)
    lifted = np.asarray(slide.lift(x, dec))
    rel = np.abs(rec - lifted) / (np.abs(lifted) + 1e-6)
    assert rel.mean() < 0.04
    np.testing.assert_allclose(rec, lifted, rtol=0.07, atol=1e-3)


def test_fused_quant_slide_fp8_matches_jnp_oracle():
    """The fp8 kernel branch tracks ref.fused_quant_slide(fp8=True) through
    the ops dispatch padding path (rows not a multiple of block_rows)."""
    dec = _dec((6, 8))
    k = 6 * dec.source.l
    x = jnp.asarray(np.random.default_rng(8).standard_normal((37, k)) * 2,
                    jnp.float32)
    q_ref, s_ref = ref.fused_quant_slide(x, dec, fp8=True)
    q_k, s_k = fused_quant_slide_pallas(x, n_fam=4, interpret=True, fp8=True,
                                        block_rows=16)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(q_k, np.float32),
                               np.asarray(q_ref, np.float32),
                               rtol=0.07, atol=0.05)


def test_fused_quant_slide_small_block_rows():
    dec = _dec((6, 8))
    x = jnp.asarray(np.random.default_rng(0).standard_normal((33, 48)),
                    jnp.float32)
    q1, s1 = fused_quant_slide_pallas(x, n_fam=4, interpret=True, block_rows=8)
    q2, s2 = ref.fused_quant_slide(x, dec)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


# ---------------------------------------------------------------- kernel 2
def test_decompress_tile_matches_decompress_original():
    for n in (3, 4, 5):
        dec = _dec((2 * n - 2, 2 * n))
        rng = np.random.default_rng(n)
        w = _weights(rng, 8, 8 * dec.source.l, dec.source)
        c = comp.compress(packer.pack_slided(w, dec), dec)
        out = decompress_tile(c.values, c.indices, n)  # [L, G, out]
        dense = np.asarray(comp.decompress_original(c))  # [out, G*L]
        np.testing.assert_array_equal(
            np.asarray(out), dense.reshape(8, -1, 2 * n).transpose(2, 1, 0))


@pytest.mark.parametrize("pattern", PATTERNS)
@pytest.mark.parametrize("rows,m,k_groups", [(4, 16, 8), (64, 96, 32), (130, 50, 17)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_compressed_matmul_fp_matches_ref(pattern, rows, m, k_groups, dtype):
    dec = _dec(pattern)
    k = k_groups * dec.source.l
    rng = np.random.default_rng(hash((pattern, rows, m, k)) % 2**32)
    w = _weights(rng, m, k, dec.source, dtype)
    x = jnp.asarray(rng.standard_normal((rows, k)), dtype)
    c = comp.compress(packer.pack_slided(w, dec), dec)
    y_ref = ref.compressed_matmul_fp(x, c, jnp.float32)
    y_k = ops.compressed_matmul(x, c, out_dtype=jnp.float32,
                                use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("pattern", [(4, 6), (6, 8), (8, 10)])
@pytest.mark.parametrize("rows,m,k_groups", [(3, 24, 4), (64, 128, 64), (257, 40, 33)])
def test_compressed_matmul_int8_matches_ref(pattern, rows, m, k_groups):
    dec = _dec(pattern)
    k = k_groups * dec.source.l
    rng = np.random.default_rng(hash((pattern, rows, m)) % 2**32)
    w = _weights(rng, m, k, dec.source)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    qw = quant.quantize_weight_int8_rowwise(w)
    c = comp.compress(packer.pack_slided(qw.q, dec), dec)
    y_ref = ref.compressed_matmul_int8(x, c, qw.scale, jnp.float32)
    y_k = ops.compressed_matmul(x, c, s_w=qw.scale, act_quant="int8",
                                out_dtype=jnp.float32, use_pallas=True,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------- kernel 3
@pytest.mark.parametrize("rows,m,k", [(1, 8, 128), (64, 256, 512), (100, 300, 640)])
def test_quant_matmul_matches_ref(rows, m, k):
    rng = np.random.default_rng(hash((rows, m, k)) % 2**32)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    qx, qw = quant.quantize_int8(x), quant.quantize_weight_int8_rowwise(w)
    y_ref = ref.quant_matmul(qx.q, qx.scale, qw.q, qw.scale)
    y_k = quant_matmul_pallas(qx.q, qw.q, qx.scale, qw.scale, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-6, atol=1e-5)


# -------------------------------------------------- paper-faithful pipeline
@pytest.mark.parametrize("pattern", [(6, 8), (4, 6)])
def test_slided_int8_pipeline_matches_ref_and_dense(pattern):
    dec = _dec(pattern)
    k, m, rows = 32 * dec.source.l, 64, 48
    rng = np.random.default_rng(0)
    w = _weights(rng, m, k, dec.source)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    qw = quant.quantize_weight_int8_rowwise(w)
    ws_q = packer.pack_slided(qw.q, dec)
    y_ref = ref.slided_matmul_int8(x, ws_q, qw.scale, dec, jnp.float32)
    y_k = ops.slided_matmul_int8(x, ws_q, qw.scale, dec,
                                 out_dtype=jnp.float32, use_pallas=True,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)
    # and the whole quantized-sparse pipeline approximates the fp matmul
    y_fp = np.asarray(x) @ np.asarray(w).T
    rel = np.abs(np.asarray(y_k) - y_fp) / (np.abs(y_fp) + 1.0)
    assert rel.mean() < 0.03


# ------------------------------------------------------- GPU/TPU semantics
@pytest.mark.parametrize("pattern", PATTERNS)
def test_slided_and_compressed_paths_agree(pattern):
    """Paper-faithful (gamma*K) and TPU-adapted (K) execution agree exactly
    in integer arithmetic — the two sides of DESIGN.md §2."""
    dec = _dec(pattern)
    k, m, rows = 8 * dec.source.l, 24, 16
    rng = np.random.default_rng(1)
    w = _weights(rng, m, k, dec.source)
    qw = quant.quantize_weight_int8_rowwise(w)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    qx = quant.quantize_int8(x)
    ws_q = packer.pack_slided(qw.q, dec)
    c = comp.compress(ws_q, dec)
    # integer accumulators, identical scales -> bit-equal results
    acc_slided = np.asarray(slide.lift(qx.q, dec)).astype(np.int64) @ \
        np.asarray(ws_q).astype(np.int64).T
    acc_orig = np.asarray(qx.q).astype(np.int64) @ \
        np.asarray(comp.decompress_original(c)).astype(np.int64).T
    np.testing.assert_array_equal(acc_slided, acc_orig)
