"""Compressed linears inside the serving unit scan reach the Pallas GEMM as
the whole ``[U, ...]`` weight stacks plus the unit index, read in place
(``kernels.slide_matmul``, ``models.transformer._scan_units``).  On the
CPU the kernel runs in interpret mode; the AOT guard that no per-unit
weight copy is left lives in ``tests/test_tpu_compile.py``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import registry
from repro.core import compressed as comp, packer, precision
from repro.core.linear import SparsityConfig
from repro.core.patterns import SlideDecomposition, Pattern, TWO_FOUR
from repro.kernels import ops, slide_matmul as smm
from repro.models import model as M
from repro.runtime import serve_loop

DEC = SlideDecomposition(Pattern(6, 8), TWO_FOUR)
U = 3


def _stack(recipe: str, m: int, k: int, seed: int = 0):
    """[U, ...] compressed stacks of ``recipe``'s weight storage, their
    [U, m, 1] row scales, and the kernel's activation operand."""
    rec = precision.resolve(recipe)
    rng = np.random.default_rng(seed)
    cs, scales = [], []
    for _ in range(U):
        w = packer.prune_to_pattern(
            jnp.asarray(rng.standard_normal((m, k)), jnp.float32), DEC.source)
        if rec.quantized:
            qw = rec.quantize_weight(w)
            w, s_w = qw.q, qw.scale
        else:
            w, s_w = w.astype(jnp.bfloat16), jnp.ones((m, 1), jnp.float32)
        cs.append(comp.compress(packer.pack_slided(w, DEC), DEC,
                                pack_values=rec.packed_weights))
        scales.append(s_w)
    x = jnp.asarray(rng.standard_normal((5, k)), jnp.float32)
    if rec.quantized:
        qx = rec.quantize_act(x)
        x, s_x = qx.q, qx.scale
    else:
        x, s_x = x.astype(jnp.bfloat16), jnp.ones((5, 1), jnp.float32)
    values = jnp.stack([c.values for c in cs])
    indices = jnp.stack([c.indices for c in cs])
    return x, s_x, values, indices, jnp.stack(scales), rec.quantized


@pytest.mark.parametrize("recipe", ["none", "int8", "w4"])
@pytest.mark.parametrize("m,bm", [(256, None), (192, 128)])
def test_stacked_operand_matches_unit_slice(recipe, m, bm):
    """The kernel on the stack at a traced unit index equals the kernel on
    that unit's slice, bit for bit.  At (192, 128) the tile does not
    divide the width and both run a partial last output block."""
    x, s_x, values, indices, s_w, quantized = _stack(recipe, m, 4 * 64)
    kw = dict(n_fam=4, quantized=quantized, interpret=True, bm=bm)

    def body(_, i):
        y = smm.compressed_matmul_pallas(x, values, indices, s_x, s_w[i],
                                         layer=i, **kw)
        return None, y

    units = jnp.array([0, U - 1], jnp.int32)
    _, got = jax.lax.scan(body, None, units)
    for n, i in enumerate((0, U - 1)):
        want = smm.compressed_matmul_pallas(x, values[i], indices[i], s_x,
                                            s_w[i], **kw)
        np.testing.assert_array_equal(np.asarray(got[n]), np.asarray(want))


@pytest.mark.parametrize("stacked", [True, False])
@pytest.mark.parametrize("recipe", ["none", "int8", "w4"])
def test_partial_last_block_matches_reference(recipe, stacked):
    """A width the output tile does not divide (192 at bm 128) runs a
    partial last output block, nothing padded, and equals the dense
    product of the unit's decompressed weights, on the stack and on the
    unit's own operand."""
    x, s_x, values, indices, s_w, quantized = _stack(recipe, 192, 4 * 64)
    i = U - 1
    operand = ((values, indices, jnp.int32(i)) if stacked
               else (values[i], indices[i], None))
    got = smm.compressed_matmul_pallas(x, *operand[:2], s_x, s_w[i],
                                       layer=operand[2], n_fam=4,
                                       quantized=quantized, interpret=True,
                                       bm=128)
    c = comp.CompressedSlided(values[i], indices[i], 4 * 64, 6, 8, 2, 4,
                              packed=precision.resolve(recipe).packed_weights)
    w = comp.decompress_original(c).astype(jnp.float32)  # [192, K]
    acc = jnp.dot(x.astype(jnp.float32), w.T,
                  precision=jax.lax.Precision.HIGHEST)
    want = acc * s_x * s_w[i][:, 0] if quantized else acc
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("use_pallas", [True, False])
def test_ops_dispatch_indexes_the_stack(use_pallas):
    """ops.compressed_matmul on a stacked operand equals the call on the
    unit's own operand, on the kernel and on the jnp reference path."""
    _, _, values, indices, s_w, _ = _stack("int8", 128, 4 * 32, seed=1)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((4, 128)),
                    jnp.float32)
    for i in range(U):
        stacked = comp.CompressedSlided(values, indices, 128, 6, 8, 2, 4,
                                        layer=jnp.int32(i))
        got, want = (ops.compressed_matmul(
            x, c, s_w=s_w[i], recipe="int8", use_pallas=use_pallas,
            interpret=True) for c in (stacked, stacked.unstacked()))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _tiny(use_pallas: bool):
    base = registry.smoke_config("h2o-danube-3-4b")
    base = dataclasses.replace(base, d_model=64, num_heads=4, num_kv_heads=2,
                               head_dim=16, d_ff=128)
    cfg = dataclasses.replace(base, sparsity=SparsityConfig(
        pattern=(6, 8), mode="compressed", use_pallas=use_pallas))
    params = serve_loop.pack_params(M.init(base, jax.random.PRNGKey(0)), cfg)
    return cfg, params


@pytest.mark.parametrize("use_pallas", [True, False])
def test_unit_scan_matches_sliced_scan(monkeypatch, use_pallas):
    """The serving scan over in-place stacks gives the logits of the plain
    scan over sliced units, on the kernel (TPU interpreter) and on the jnp
    reference path."""
    from repro.models import transformer as T

    cfg, params = _tiny(use_pallas)
    cache = M.make_paged_cache(cfg, 8, 4, 2)
    tok = jnp.array([3, 7], jnp.int32)
    table = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    args = (cfg, tok, cache, table, jnp.array([2, 5], jnp.int32),
            jnp.array([True, True]), 4)
    with pltpu.force_tpu_interpret_mode():
        got, _ = T.paged_decode_step(params, *args)
        monkeypatch.setattr(T, "_scan_units", lambda unit_fn, x, units,
                            cache: jax.lax.scan(unit_fn, x, (units, cache)))
        want, _ = T.paged_decode_step(params, *args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
