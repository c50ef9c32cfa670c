"""Compile each cell's decode and prefill step programs at their real
widths for a described TPU v5e (no chip needed) and print the compiler's
memory analysis: the bytes each program holds on the device.

    JAX_PLATFORMS=cpu python3 -m bench.aot_memory [cell ...]

The steps are the engine's own (``paged_decode_step`` /
``paged_prefill_chunk`` with the on-device argmax), with the Pallas
kernels on as on the chip, over abstract packed weights and the cell's
KV pool.  Nothing runs, so this says nothing about time.
"""
from __future__ import annotations

import dataclasses
import os
import sys


def analyse(cell, topo) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from bench import model
    from repro.models import model as M
    from repro.runtime import serve_loop
    from repro.sharding import tp as tpmod

    cfg = model.program_config(cell.config)
    cfg = dataclasses.replace(cfg, sparsity=dataclasses.replace(
        cfg.sparsity, use_pallas=True))
    e = cell.traffic["engine"]
    one = SingleDeviceSharding(topo.devices[0])

    def put(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    params = put(jax.eval_shape(
        lambda k: serve_loop.pack_params(M.init(cfg, k), cfg),
        jax.random.PRNGKey(0)))
    cache = put(jax.eval_shape(lambda: M.make_paged_cache(
        cfg, e["num_pages"], e["page_size"], e["max_batch"])))
    maxp = -(-e["max_seq_len"] // e["page_size"])
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa
    b = e["max_batch"]
    ps = e["page_size"]

    def decode(p, tok, c, pt, kvl, act):
        with tpmod.activate(1):
            logits, c = M.paged_decode_step(p, cfg, tok, c, pt, kvl, act, ps)
            return tpmod.argmax_tokens(logits), logits, c

    def prefill(p, tok, c, pt, start, rlen, slot, reset):
        with tpmod.activate(1):
            logits, c = M.paged_prefill_chunk(p, cfg, tok, c, pt, start,
                                              rlen, slot, reset, ps)
            return tpmod.argmax_tokens(logits), logits, c

    out = {"weights": sum(int(np.prod(x.shape)) * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(params)),
           "pool": sum(int(np.prod(x.shape)) * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(cache))}
    bool_b = jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one)
    progs = {
        "decode": jax.jit(decode).lower(params, i32(b), cache, i32(b, maxp),
                                        i32(b), bool_b),
        "prefill": jax.jit(prefill).lower(
            params, i32(1, e["prefill_chunk"]), cache, i32(1, maxp), i32(),
            i32(), i32(), jax.ShapeDtypeStruct((), jnp.bool_, sharding=one)),
    }
    for name, lowered in progs.items():
        ma = lowered.compile().memory_analysis()
        out[name] = {k: int(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")}
    return out


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from bench import cells

    jax.config.update("jax_enable_compilation_cache", False)
    names = (argv if argv is not None else sys.argv[1:]) or [
        w["name"] for w in cells.load_json(
            os.path.join(cells.ROOT, "BENCHMARK.json"))["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        r = analyse(cells.load_cell(name), topo)
        peak = max(r[p]["argument_size_in_bytes"] + r[p]["output_size_in_bytes"]
                   + r[p]["temp_size_in_bytes"] - r[p]["alias_size_in_bytes"]
                   for p in ("decode", "prefill"))
        print(name, r, "peak_bytes", peak, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
