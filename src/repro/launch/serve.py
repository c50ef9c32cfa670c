"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Loads (or initializes) weights, runs the SlideSparse offline packer +
load-time compression (paper §4 phases 1-2), then serves batched requests
through prefill + decode.
"""
import argparse
import dataclasses
import os
import pathlib

import jax

from repro.configs import registry
from repro.core.linear import SparsityConfig
from repro.runtime import serve_loop


# fixed, so that every run from this checkout finds the previous run's
# executables (the directory is part of the cache's key); ignored by git
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the serving entry points keep JAX's persistent compilation
    cache: ``JAX_COMPILATION_CACHE_DIR`` when it is set, else
    ``CHECKOUT_CACHE``."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on at ``compile_cache_dir()``."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sparse", nargs=2, type=int, metavar=("Z", "L"))
    ap.add_argument("--act-quant", choices=["int8"], default=None,
                    help="legacy precision flag; maps onto --precision int8")
    ap.add_argument("--precision", default=None,
                    choices=["none", "int8", "fp8", "w4", "fp8w4"],
                    help="precision recipe (DESIGN.md §10): activation "
                         "quantizer x weight storage; overrides --act-quant")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--engine", action="store_true",
                    help="serve through the continuous-batching paged-KV "
                         "engine (staggered arrivals) instead of the "
                         "one-shot prefill+decode loop")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree for --engine (DESIGN.md "
                         "§9); needs >= N devices — on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=256)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache over ref-counted copy-on-"
                         "write pages for --engine (DESIGN.md §11): "
                         "shared prompt prefixes skip re-prefill")
    ap.add_argument("--policy", default="fcfs",
                    choices=["fcfs", "priority"],
                    help="--engine scheduler admission/eviction policy")
    ap.add_argument("--inject-faults", type=int, default=None,
                    metavar="SEED",
                    help="--engine: arm the deterministic fault injector "
                         "(DESIGN.md §12) — seeded allocation failures + "
                         "transient step errors; the engine must degrade "
                         "per-request, never crash")
    ap.add_argument("--watchdog", action="store_true",
                    help="--engine: assert KV accounting invariants after "
                         "every scheduler decision; violations quarantine "
                         "the offending request instead of killing the "
                         "loop")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="--engine: bounded admission queue — beyond this "
                         "depth, backpressure rejects (fcfs) or sheds the "
                         "lowest-priority queued request (priority)")
    ap.add_argument("--deadline-steps", type=int, default=None,
                    help="--engine: per-request step budget; requests "
                         "exceeding it finish as TIMEOUT with their "
                         "partial stream")
    ap.add_argument("--speculate", type=int, default=0, metavar="K",
                    help="--engine: self-speculative decoding — draft up "
                         "to K tokens per sequence, score them in one "
                         "fixed-shape [B, K+1] verify step (DESIGN.md "
                         "§14); streams are argmax-identical to K=0")
    ap.add_argument("--draft", default="ngram",
                    help="--engine: draft source for --speculate "
                         "(registered: ngram, random)")
    ap.add_argument("--async", dest="async_loop", action="store_true",
                    help="--engine: overlapped host/device loop (DESIGN.md "
                         "§15) — on-device sampling + token threading + "
                         "lookahead scheduling; argmax-identical streams")
    args = ap.parse_args(argv)
    if args.tp > 1 and not args.engine:
        raise SystemExit("--tp requires --engine (the one-shot loop is "
                         "single-device; DESIGN.md §9)")

    cfg = registry.smoke_config(args.arch) if args.smoke \
        else registry.get(args.arch)
    if args.sparse:
        cfg = dataclasses.replace(cfg, sparsity=SparsityConfig(
            pattern=tuple(args.sparse), mode="compressed",
            recipe=args.precision, act_quant=args.act_quant))

    enable_compile_cache()
    # init + pack one scanned unit at a time: the dense and the packed
    # model never sit on the device together
    params = serve_loop.init_packed(cfg, jax.random.PRNGKey(0))

    batch = {"tokens": jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
        cfg.vocab_size)}
    if cfg.frontend == "audio":
        batch["audio_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.max_source_positions, cfg.d_model))

    if args.engine:
        from repro.runtime import faults as fl
        plan = (fl.FaultPlan(seed=args.inject_faults, alloc_fail_rate=0.08,
                             step_error_rate=0.04)
                if args.inject_faults is not None else None)
        ecfg = serve_loop.EngineConfig(
            max_batch=args.batch, page_size=args.page_size,
            num_pages=args.num_pages,
            max_seq_len=args.prompt_len + args.new_tokens,
            prefill_chunk=args.prefill_chunk, tp=args.tp,
            prefix_cache=args.prefix_cache, policy=args.policy,
            max_queue=args.max_queue, watchdog=args.watchdog, faults=plan,
            speculate=args.speculate, draft_source=args.draft,
            async_loop=args.async_loop)
        eng = serve_loop.ServeEngine(params, cfg, ecfg)
        for i in range(args.batch):
            eng.submit(batch["tokens"][i].tolist(), args.new_tokens,
                       rid=i, arrival=i,  # staggered joins
                       deadline_steps=args.deadline_steps)
        out = eng.run()
        s = eng.stats
        print(f"[launch.serve] engine(tp={s.tp}, precision={s.precision}, "
              f"policy={ecfg.policy}): {len(out)} requests; "
              f"decode {s.decode_tok_s:.1f} tok/s "
              f"({s.decode_tok_s_per_device:.1f}/device); occupancy "
              f"{s.mean_occupancy:.2f}; evictions {s.evictions}; "
              f"sample: {out[0].tokens[:8]}")
        if args.prefix_cache:
            print(f"[launch.serve] prefix cache: hit_rate "
                  f"{s.prefix_hit_rate:.2f}; {s.prefill_chunks_skipped} "
                  f"chunks skipped; {s.cow_copies} COW copies")
        if args.speculate > 0:
            print(f"[launch.serve] speculative: K={args.speculate} "
                  f"source={args.draft}; {s.verify_steps} verify steps; "
                  f"accepted {s.accepted_tokens}/{s.draft_tokens} "
                  f"(rate {s.acceptance_rate:.2f})")
        if args.async_loop:
            print(f"[launch.serve] async loop: {s.lookahead_steps} "
                  f"lookahead dispatches; host gap {s.host_gap_s * 1e3:.1f}"
                  f"ms; overlap {s.overlap_frac:.2f}; d2h {s.d2h_bytes}B")
        if plan is not None or args.watchdog or args.max_queue is not None \
                or args.deadline_steps is not None:
            eng.kv.check()  # robustness run: prove pages balanced
            print(f"[launch.serve] lifecycle: ok={s.completed_ok} "
                  f"cancelled={s.cancelled} timeouts={s.timeouts} "
                  f"rejected={s.rejected} failed={s.failed} "
                  f"quarantined={s.quarantined}; goodput "
                  f"{s.goodput_tok_s:.1f} tok/s; faults_injected="
                  f"{s.faults_injected}; p95_queue_wait="
                  f"{s.p95_queue_wait_steps:.0f} steps; kv invariants OK")
        return

    toks, stats = serve_loop.generate(params, cfg, batch, args.new_tokens)
    print(f"[launch.serve] prefill {stats.prefill_s:.2f}s; decode "
          f"{stats.decode_tok_s:.1f} tok/s; sample: {toks[0][:8].tolist()}")


if __name__ == "__main__":
    main()
