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
import os

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
                                      ("wq", HEADS * HEAD_DIM, D_MODEL)])
def test_compressed_matmul_compiles(one_chip, name, m, k, rows, recipe):
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
