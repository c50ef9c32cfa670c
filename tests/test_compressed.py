"""Compressed 2:4 representation: round-trips + storage accounting (§4.3)."""
import numpy as np
import jax.numpy as jnp
# runs under real hypothesis when installed, else the seeded fallback sweep
from proptest import given, settings, strategies as st

from repro.core.patterns import Pattern, SlideDecomposition, TWO_FOUR
from repro.core import packer, compressed as comp


family = st.integers(3, 8)


def _pattern_weights(rng, rows, groups, pat):
    w = rng.standard_normal((rows, groups * pat.l)).astype(np.float32)
    return packer.prune_to_pattern(jnp.asarray(w), pat)


@settings(max_examples=40, deadline=None)
@given(family, st.integers(1, 4), st.integers(0, 2**31 - 1))
def test_compress_roundtrips(n, groups, seed):
    rng = np.random.default_rng(seed)
    pat = Pattern.from_family(n)
    dec = SlideDecomposition(pat, TWO_FOUR)
    w = _pattern_weights(rng, 5, groups, pat)
    ws = packer.pack_slided(w, dec)
    c = comp.compress(ws, dec)
    # slided round-trip
    np.testing.assert_array_equal(np.asarray(comp.decompress_slided(c)),
                                  np.asarray(ws))
    # original-layout decompression == unslide (the TPU weight path)
    np.testing.assert_array_equal(np.asarray(comp.decompress_original(c)),
                                  np.asarray(w))


@settings(max_examples=25, deadline=None)
@given(family, st.integers(1, 3), st.integers(0, 2**31 - 1))
def test_zero_storage_overhead(n, groups, seed):
    """§4.3: compressed size == source non-zero budget (density * K)."""
    rng = np.random.default_rng(seed)
    pat = Pattern.from_family(n)
    dec = SlideDecomposition(pat, TWO_FOUR)
    w = _pattern_weights(rng, 3, groups, pat)
    c = comp.compress(packer.pack_slided(w, dec), dec)
    k = w.shape[-1]
    assert c.slots == dec.compressed_len(k)
    assert c.values.size == w.shape[0] * dec.compressed_len(k)
    assert dec.compressed_len(k) == int(k * pat.density)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 200), st.integers(0, 2**31 - 1))
def test_meta_bitpack_roundtrip(count, seed):
    rng = np.random.default_rng(seed)
    idx = jnp.asarray(rng.integers(0, 4, size=(3, count)), jnp.int8)
    words = comp.pack_meta(idx)
    rec = comp.unpack_meta(words, count)
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(idx))
    # 2 bits per index, 16 per int32 word
    assert words.shape[-1] == (count + 15) // 16


def test_compressed_pytree():
    import jax
    dec = SlideDecomposition(Pattern(6, 8), TWO_FOUR)
    w = _pattern_weights(np.random.default_rng(0), 4, 2, dec.source)
    c = comp.compress(packer.pack_slided(w, dec), dec)
    leaves, treedef = jax.tree_util.tree_flatten(c)
    assert len(leaves) == 2
    c2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert c2.k == c.k and c2.l == c.l
    np.testing.assert_array_equal(np.asarray(c2.values), np.asarray(c.values))
