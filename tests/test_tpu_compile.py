"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

The TPU compiler (Mosaic) accepts less than interpret mode does: block
shapes must fit the (8, 128) tiling, the VPU has no int8 arithmetic, lane
dims cannot be reshaped freely.  These tests compile the compressed GEMM
and the fused paged attention at the full h2o-danube-3-4b widths for one
chip of a described ``v5e:2x2`` topology (nothing is executed), so a kernel
change that the chip would refuse fails here without a chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and every test worker imports every test file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.compressed import CompressedSlided
from repro.core.patterns import Pattern
from repro.kernels import ops
from repro.kernels import paged_attention as pa

D_MODEL, D_FF, HEADS, KV_HEADS, HEAD_DIM = 3840, 10240, 32, 8, 120
Z, L = 6, 8
PREFILL_ROWS = 128  # chip_smoke.py's prefill chunk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile here would be written to a persistent cache it can never
    # read back without a chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("recipe", ["none", "int8"])
@pytest.mark.parametrize("rows", [8, PREFILL_ROWS])
@pytest.mark.parametrize("name,m,k", [("w_up", D_FF, D_MODEL),
                                      ("w_down", D_MODEL, D_FF),
                                      ("wq", HEADS * HEAD_DIM, D_MODEL),
                                      ("wk", KV_HEADS * HEAD_DIM, D_MODEL)])
def test_compressed_matmul_compiles(one_chip, name, m, k, rows, recipe):
    """The weights reach the kernel as stored: no pad or copy of a
    ``[P, G, *]`` weight array, also at wk's 960, a width no lane-legal
    tile that fits divides (a partial last output block).  XLA may still
    prefetch a small operand whole into VMEM (slices of the same width)."""
    pat = Pattern(Z, L)
    planes, groups = pat.family_n * 2 - 2, k // L
    wdt = jnp.int8 if recipe == "int8" else jnp.bfloat16
    args = [_spec((rows, k), jnp.bfloat16, one_chip),
            _spec((planes, groups, m), wdt, one_chip),
            _spec((planes, groups, m), jnp.int8, one_chip),
            _spec((m, 1), jnp.float32, one_chip)]

    def gemm(x, values, indices, s_w):
        c = CompressedSlided(values, indices, k, Z, L, 2, 4)
        return ops.compressed_matmul(
            x, c, s_w=s_w if recipe == "int8" else None, recipe=recipe,
            use_pallas=True)

    compiled = _compile(gemm, *args)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
    text = compiled.as_text()
    made = [ln for ln in text[text.index("\nENTRY"):].splitlines()
            if (w := re.search(rf"= \S+\[{planes},{groups},(\d+)\]", ln))
            and (int(w[1]) != m or re.search(r" (pad|copy|copy-start)\(", ln))]
    assert not made, made[:1]


@pytest.mark.parametrize("recipe", ["fp8", "w4", "fp8w4"])
def test_other_recipes_compile(one_chip, recipe):
    """fp8 activations and nibble-packed int4 weights: compiled (the chip
    smoke run does not execute them)."""
    from repro.core import precision

    rec = precision.resolve(recipe)
    m, k, rows = D_FF, D_MODEL, 8
    planes = 3 if rec.packed_weights else 6
    args = [_spec((rows, k), jnp.bfloat16, one_chip),
            _spec((planes, k // L, m), jnp.int8, one_chip),
            _spec((6, k // L, m), jnp.int8, one_chip),
            _spec((m, 1), jnp.float32, one_chip)]

    def gemm(x, values, indices, s_w):
        c = CompressedSlided(values, indices, k, Z, L, 2, 4,
                             packed=rec.packed_weights)
        return ops.compressed_matmul(x, c, s_w=s_w, recipe=rec,
                                     use_pallas=True)

    _compile(gemm, *args)


@pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
@pytest.mark.parametrize("batch,lanes", [(8, 1), (8, 4), (1, PREFILL_ROWS)])
def test_paged_attention_compiles(one_chip, batch, lanes, kv_dtype):
    pages, page_size, max_pages = 512, 16, 40
    pool_shape = (pages, page_size, KV_HEADS, HEAD_DIM)
    pool = {"k": _spec(pool_shape, kv_dtype, one_chip),
            "v": _spec(pool_shape, kv_dtype, one_chip)}
    if kv_dtype == jnp.int8:
        sshape = (pages, page_size, KV_HEADS, 1)
        pool["k_scale"] = _spec(sshape, jnp.float32, one_chip)
        pool["v_scale"] = _spec(sshape, jnp.float32, one_chip)
    q = _spec((batch, lanes, HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    table = _spec((batch, max_pages), jnp.int32, one_chip)
    kv_len = _spec((batch,), jnp.int32, one_chip)

    def attend(q, pool, table, kv_len):
        return pa.paged_attention(q, pool, table, kv_len,
                                  sliding_window=4096, use_pallas=True)

    _compile(attend, q, pool, table, kv_len)


def _shapes(line: str) -> list[tuple[int, ...]]:
    """Every array shape written in one HLO line, e.g. ``bf16[2,6,480]``."""
    return [tuple(int(d) for d in dims.split(","))
            for dims in re.findall(r"\b[a-z]+\d*\[([\d,]+)\]", line)]


@pytest.mark.parametrize("recipe", ["none", "int8"])
def test_unit_scan_reads_weight_stacks_in_place(one_chip, recipe):
    """A 2-unit decode step at danube widths: every compressed GEMM in the
    unit scan takes the whole [2, 6, G, M] stacks, and no dynamic-slice
    fusion copies one unit's [6, G, M] weight slice out of them."""
    from repro.configs import registry
    from repro.core.linear import SparsityConfig
    from repro.models import model as M
    from repro.runtime.serve_loop import pack_params

    units, page_size, batch = 2, 16, 8
    cfg = dataclasses.replace(
        registry.get("h2o-danube-3-4b"), num_layers=units,
        sparsity=SparsityConfig(pattern=(Z, L), mode="compressed",
                                recipe=recipe, use_pallas=True))
    params = jax.eval_shape(lambda k: pack_params(M.init(cfg, k), cfg),
                            jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: M.make_paged_cache(cfg, 64, page_size,
                                                      batch))
    place = lambda tree: jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, one_chip), tree)
    stacks = {a.shape for a in jax.tree_util.tree_leaves(params["units"])
              if a.ndim == 4}
    assert len(stacks) == 4 and all(s[:2] == (units, 6) for s in stacks)
    args = (place(params), _spec((batch,), jnp.int32, one_chip),
            place(cache), _spec((batch, 8), jnp.int32, one_chip),
            _spec((batch,), jnp.int32, one_chip),
            _spec((batch,), jnp.bool_, one_chip))
    hlo = _compile(lambda p, t, c, pt, kl, act: M.paged_decode_step(
        p, cfg, t, c, pt, kl, act, page_size), *args).as_text()

    slices = [ln for ln in hlo.splitlines()
              if re.match(r"\s*%\S*dynamic-slice\S*fusion\S* = ", ln)]
    unit_slices = {s[1:] for s in stacks}
    copied = [ln for ln in slices if _shapes(ln.split(" = ", 1)[1])[:1]
              and _shapes(ln.split(" = ", 1)[1])[0] in unit_slices]
    assert not copied, copied[:2]

    calls = [ln for ln in hlo.splitlines()
             if re.match(r"\s*%compressed_matmul_pallas\S* = ", ln)]
    stacked = [ln for ln in calls if stacks & set(_shapes(ln))]
    # every call but the LM head's (outside the scan) reads the stacks
    assert len(stacked) >= 7 and len(calls) - len(stacked) == 1
