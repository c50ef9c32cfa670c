#!/usr/bin/env python3
"""Smoke run of the SlideSparse serving path on a TPU.

Serves h2o-danube-3-4b at its published widths (24 layers, d_model 3840,
32/8 heads of 120, d_ff 10240, vocab 32000, bf16) with random weights made
from a seed, pruned to 6:8, through the entry points a user calls —
``serve_loop.init_packed`` (``pack_params`` one scanned unit at a time)
and ``ServeEngine`` — and checks what comes out.  Phases, one process, one
after the other (each frees the previous phase's model first):

  reference   the masked 6:8 engine: plain XLA GEMMs over the same pruned
              weights, gather attention; and the one-shot masked int8
              forward, the reference of the int8 phase
  compressed  6:8 compressed weights, bf16: the Pallas compressed GEMM and
              the fused paged-attention kernel
  int8        the same with the int8 precision recipe

Every phase serves TRAFFIC (4 requests of 512 prompt tokens and 32 new
tokens, max_batch 4, prefill chunks of 128), asserts that every
completion is OK, that the compiled decode step calls Pallas kernels
(``tpu_custom_call``; a kernel that fell back to its jnp mirror or to
interpret mode fails), and that the logits of the first prefill chunk of
every prompt agree with the reference within REL_TOL.

``--tp 4`` runs only the tensor-parallel path: the compressed bf16 engine
on a 4-chip mesh against the 1-chip engine it must agree with, and checks
that each chip holds its quarter of the sharded weights.

Needs a TPU: on any other platform it exits non-zero before any phase.
The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
a failed phase raises, and the script exits non-zero without that line.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # four chips
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "h2o-danube-3-4b"
PATTERN = (6, 8)
SEED = 0
PAGE_SIZE = 16


@dataclasses.dataclass(frozen=True)
class Traffic:
    """The requests every phase serves (max_batch = requests)."""
    requests: int = 4
    prompt_len: int = 512
    new_tokens: int = 32
    prefill_chunk: int = 128


TRAFFIC = Traffic()
# ||logits - reference|| / ||reference|| over the first prefill chunk of
# every prompt.  bf16 GEMMs and a different attention kernel reassociate
# the sums; a wrong decompression or page lookup is off by O(1).
REL_TOL = {"none": 0.05, "int8": 0.10}
HBM_BYTES = 16e9  # one v5e chip


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def model_config(recipe: str, mode: str, fused: bool, smoke: bool = False,
                 interpret: bool = False):
    """The served configuration.  ``smoke``/``interpret`` shrink it to
    ``smoke_config`` and force the Pallas kernels on (tests run them under
    the TPU interpreter on a CPU)."""
    from repro.configs import registry
    from repro.core.linear import SparsityConfig

    cfg = registry.smoke_config(ARCH) if smoke else registry.get(ARCH)
    return dataclasses.replace(cfg, sparsity=SparsityConfig(
        pattern=PATTERN, mode=mode, recipe=recipe, fused_attention=fused,
        use_pallas=True if interpret else None))


def engine_config(traffic: Traffic, tp: int = 1):
    from repro.runtime.serve_loop import EngineConfig

    seq = traffic.prompt_len + traffic.new_tokens
    return EngineConfig(max_batch=traffic.requests, page_size=PAGE_SIZE,
                        num_pages=traffic.requests * -(-seq // PAGE_SIZE) + 8,
                        max_seq_len=seq, prefill_chunk=traffic.prefill_chunk,
                        tp=tp)


def make_prompts(vocab: int, traffic: Traffic) -> np.ndarray:
    rng = np.random.default_rng(SEED)
    return rng.integers(0, vocab, size=(traffic.requests, traffic.prompt_len),
                        dtype=np.int32)


def nbytes(tree) -> int:
    import jax

    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def serve(eng, prompts: np.ndarray, new_tokens: int):
    """Submit every prompt, run to completion, check every stream."""
    for i, p in enumerate(prompts):
        eng.submit(p.tolist(), new_tokens, rid=i)
    out = eng.run()
    status = {rid: c.status for rid, c in out.items()}
    assert len(out) == len(prompts) and all(c.ok for c in out.values()), \
        f"not every completion is OK: {status}"
    assert all(len(c.tokens) == new_tokens for c in out.values())
    return out


def chunk_logits(eng, prompts: np.ndarray) -> np.ndarray:
    chunk = eng.ecfg.prefill_chunk
    return np.stack([eng.prefill_logits(p[:chunk].tolist()) for p in prompts])


def compare(name: str, got: np.ndarray, ref: np.ndarray, tol: float) -> float:
    assert got.shape == ref.shape, (got.shape, ref.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite logits"
    diff = got - ref
    rel = float(np.linalg.norm(diff) / np.linalg.norm(ref))
    worst = float(np.abs(diff).max())
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    log(f"{name}: max |logit diff| {worst:.6g} (reference max |logit| "
        f"{float(np.abs(ref).max()):.6g}); relative L2 {rel:.6g} "
        f"(tolerance {tol}); argmax agreement {agree:.3f}")
    assert rel <= tol, f"{name}: logits differ from the reference by {rel}"
    return worst


def run_engine(name: str, params, cfg, prompts, traffic: Traffic, *,
               kernels: bool, tp: int = 1):
    """Serve ``prompts`` on a fresh engine; returns (chunk logits, engine)."""
    from repro.runtime.serve_loop import ServeEngine

    eng = ServeEngine(params, cfg, engine_config(traffic, tp))
    warm = eng.warmup()
    if kernels:
        assert "tpu_custom_call" in eng.decode_hlo(), \
            f"{name}: the compiled decode step calls no Pallas kernel"
    logits = chunk_logits(eng, prompts)
    out = serve(eng, prompts, traffic.new_tokens)
    log(f"{name}: weights {nbytes(eng.params)} B; warm-up (compile) "
        f"{warm:.1f} s; {len(out)} completions OK; peak_bytes_in_use "
        f"{peak_bytes()}")
    return logits, eng


def phase_reference(prompts, traffic=TRAFFIC, *, smoke=False,
                    interpret=False):
    """Masked 6:8 engine (bf16 reference) and the one-shot masked int8
    forward (int8 reference), over the same dense weights."""
    import jax

    from repro.models import model as M
    from repro.runtime import serve_loop

    cfg = model_config("none", "masked", fused=False, smoke=smoke,
                       interpret=interpret)
    # masked serving keeps the dense weights: init_packed only bounds the
    # initialisation peak (one unit at a time), the arrays are M.init's
    params = serve_loop.init_packed(cfg, jax.random.PRNGKey(SEED))
    ref, eng = run_engine("reference masked bf16", params, cfg, prompts,
                          traffic, kernels=False)
    del eng
    cfg8 = model_config("int8", "masked", fused=False, smoke=smoke,
                        interpret=interpret)
    fwd = jax.jit(lambda p, t: M.prefill(p, cfg8, {"tokens": t})[0])
    ref8 = np.asarray(fwd(params, prompts[:, :traffic.prefill_chunk]),
                      np.float32)
    log(f"reference masked int8 (one-shot forward): logits {ref8.shape}")
    return ref, ref8


def phase_compressed(recipe: str, ref: np.ndarray, prompts, traffic=TRAFFIC,
                     *, smoke=False, interpret=False):
    """Compressed 6:8 engine under ``recipe`` vs its masked reference."""
    import jax

    from repro.runtime import serve_loop

    name = f"compressed {recipe if recipe != 'none' else 'bf16'}"
    cfg = model_config(recipe, "compressed", fused=True, smoke=smoke,
                       interpret=interpret)
    t0 = time.time()
    params = serve_loop.init_packed(cfg, jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    log(f"{name}: init + pack {time.time() - t0:.1f} s")
    logits, eng = run_engine(name, params, cfg, prompts, traffic,
                             kernels=not interpret)
    del params, eng
    compare(name, logits, ref, REL_TOL[recipe])
    return logits


def phase_tp(prompts, tp: int, traffic=TRAFFIC, *, smoke=False,
             interpret=False):
    """Compressed bf16 at ``tp`` chips against the 1-chip engine."""
    import jax

    from repro.runtime import serve_loop
    from repro.sharding import tp as tpmod

    cfg = model_config("none", "compressed", fused=True, smoke=smoke,
                       interpret=interpret)
    params = serve_loop.init_packed(cfg, jax.random.PRNGKey(SEED))
    ref, eng = run_engine("compressed bf16 tp=1", params, cfg, prompts,
                          traffic, kernels=not interpret)
    del eng
    gc.collect()  # the tp=1 engine's arrays leave the device before tp=4
    logits, eng = run_engine(f"compressed bf16 tp={tp}", params, cfg,
                             prompts, traffic, kernels=not interpret, tp=tp)
    del params
    gc.collect()
    # each chip holds a 1/tp slice of every sharded leaf and a whole copy
    # of the replicated ones (embedding, norms)
    specs = tpmod.serve_param_specs(eng.params, tp)
    expect = sum(x.nbytes // (tp if any(a is not None for a in s) else 1)
                 for x, s in zip(jax.tree_util.tree_leaves(eng.params),
                                 jax.tree_util.tree_leaves(
                                     specs, is_leaf=lambda v: isinstance(
                                         v, jax.sharding.PartitionSpec))))
    held: dict = {}
    for x in jax.tree_util.tree_leaves(eng.params):
        for shard in x.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + shard.data.nbytes
    total = nbytes(eng.params)
    log(f"tp={tp}: weight bytes per chip {sorted(held.values())} of {total}"
        f" (expected {expect} each)")
    assert len(held) == tp and all(v == expect for v in held.values()), held
    assert expect <= 0.3 * total, f"{expect} of {total} is not a quarter"
    del eng
    compare(f"compressed bf16 tp={tp} vs tp=1", logits, ref, REL_TOL["none"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel check on this many "
                         "chips (4 on a v5e 2x2 host)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(jax.devices()) < args.tp:
        print(f"chip_smoke: --tp {args.tp} needs {args.tp} chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2
    from repro.launch.serve import enable_compile_cache

    log(f"device {dev.device_kind} x{len(jax.devices())}; compilation "
        f"cache {enable_compile_cache()}")
    t0 = time.time()
    prompts = make_prompts(model_config("none", "masked", False).vocab_size,
                           TRAFFIC)
    if args.tp > 1:
        phase_tp(prompts, args.tp)
    else:
        # collect between phases: any cycle still holding a phase's arrays
        # must let go of the device before the next model is built
        ref, ref8 = phase_reference(prompts)
        gc.collect()
        phase_compressed("none", ref, prompts)
        gc.collect()
        phase_compressed("int8", ref8, prompts)
    peak = peak_bytes()
    log(f"peak_bytes_in_use {peak}; total {time.time() - t0:.1f} s")
    assert peak is None or peak < HBM_BYTES, peak
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
