"""Shape-keyed tile-size autotuner for the SlideSparse Pallas kernels.

The kernels expose tile knobs (bm, br, bk, block_rows) whose best values
depend on the operand shapes, dtypes and backend.  This module picks them
(DESIGN.md §2.4):

* ``lookup`` — two-level cache: an in-process dict, backed by an on-disk
  JSON file so tuned configurations survive across processes (serving
  restarts, benchmark runs).  The file is used only when
  ``REPRO_AUTOTUNE_CACHE`` names it; unset or empty, nothing persists.
* ``autotune`` — times each candidate config with the caller-supplied
  runner (warmup + best-of-reps wall clock, like the benchmark harness)
  and records the winner.
* ``tiles_for`` — the ops.py entry point: cached -> cached value; ``tune``
  requested -> search; otherwise empty config (kernel-side heuristics).

Cache file format (DESIGN.md §2.4): ``{key: {"tiles": {bm, br, bk,
block_rows}, "us": best_us, "backend": ...}}`` where ``key`` is
``op|param=value|...`` over the shape/dtype parameters, sorted by name.
Null tile entries mean "kernel default".

Roofline feedback (DESIGN.md §13): before timing, candidates whose modeled
HBM traffic (``roofline.tile_traffic``) exceeds ``PRUNE_RATIO`` x the best
candidate's are skipped — a tile that re-streams operands that many times
cannot reach the bandwidth bound, so timing it is wasted work.  Tuned
entries additionally record ``roofline_us`` (the analytic bound for the
shape), ``efficiency`` (bound / achieved) and a human-readable ``why``
explaining how the winner won.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import time
import warnings
from typing import Any, Callable, Iterable

import jax

from . import roofline

_FIELDS = ("bm", "br", "bk", "block_rows")

# candidates whose modeled HBM traffic exceeds this multiple of the best
# candidate's cannot reach the bandwidth bound — skip timing them
PRUNE_RATIO = 2.0


@dataclasses.dataclass(frozen=True)
class TileConfig:
    """Tile sizes for one kernel launch; None -> use the kernel's default."""

    bm: int | None = None
    br: int | None = None
    bk: int | None = None
    block_rows: int | None = None

    def kernel_kwargs(self, *names: str) -> dict[str, int]:
        """Non-None tiles restricted to the knobs a kernel accepts."""
        return {f: getattr(self, f) for f in (names or _FIELDS)
                if getattr(self, f) is not None}


DEFAULT = TileConfig()

_MEM: dict[str, dict[str, Any]] = {}
_DIRTY: set[str] = set()  # keys recorded by THIS process (merge-on-write set)
_DISK_LOADED = False


def cache_path() -> str | None:
    """The on-disk cache file: ``REPRO_AUTOTUNE_CACHE`` when it names one,
    else None — no persistence, so served tiles come from the code (the
    kernels' heuristics) unless a cache file is explicitly given."""
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or None


def _quarantine_cache(path: str, err: Exception) -> None:
    """A corrupt/truncated cache file (interrupted pre-flock writer, hand
    edit, disk fault) must not take the kernels down — or silently poison
    tuning.  Move it aside to ``<path>.bak`` for post-mortem, warn once,
    and continue with an empty cache that will be re-tuned and rewritten
    atomically."""
    bak = path + ".bak"
    try:
        os.replace(path, bak)
        where = f"quarantined to {bak}"
    except OSError:
        where = "could not be quarantined (left in place, ignored)"
    warnings.warn(f"autotune cache {path} is corrupt ({err}); {where}; "
                  "continuing with an empty cache", RuntimeWarning,
                  stacklevel=3)


def _parse_cache(raw: str) -> dict[str, Any]:
    """Strict parse of the on-disk cache: a JSON object whose values are
    record objects.  Anything else raises ValueError — a cache that
    *parses* but has the wrong shape would otherwise crash ``lookup``
    far from the cause."""
    disk = json.loads(raw)  # JSONDecodeError is a ValueError
    if not isinstance(disk, dict):
        raise ValueError(f"cache root is {type(disk).__name__}, not object")
    for key, rec in disk.items():
        if not isinstance(rec, dict):
            raise ValueError(f"record {key!r} is {type(rec).__name__}, "
                             "not object")
    return disk


def _load_disk() -> None:
    global _DISK_LOADED
    if _DISK_LOADED:
        return
    _DISK_LOADED = True
    path = cache_path()
    if not path or not os.path.exists(path):
        return
    try:
        with open(path) as f:
            raw = f.read()
    except OSError:
        return  # unreadable (permissions/races): run uncached
    try:
        disk = _parse_cache(raw)
    except (ValueError, UnicodeDecodeError) as e:
        _quarantine_cache(path, e)
        return
    for key, rec in disk.items():
        _MEM.setdefault(key, rec)


def _save_disk() -> None:
    """Atomic merge-on-write persistence.

    Two concurrent tuning processes (parallel bench runs) must neither
    tear the JSON nor clobber each other's keys: re-read the file, merge
    our in-process entries over it, dump to a temp file in the same
    directory and ``os.replace`` — readers always see a complete old or
    new file, and a concurrent writer's disjoint keys survive.
    """
    path = cache_path()
    if not path:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        # the read-merge-replace must be mutually exclusive or two writers
        # both read the old file and the second replace drops the first
        # writer's keys (lost update); flock a sidecar so the data file
        # itself can still be atomically os.replace'd under the lock
        with open(path + ".lock", "w") as lock:
            try:
                import fcntl
                fcntl.flock(lock, fcntl.LOCK_EX)
            except (ImportError, OSError):
                # non-POSIX or flock-less filesystem (NFS without lockd):
                # keep the atomic replace, lose only the merge guard —
                # persistence must not regress to nothing here
                pass
            merged: dict[str, Any] = {}
            try:
                with open(path) as f:
                    merged = _parse_cache(f.read())
            except (OSError, ValueError, UnicodeDecodeError):
                merged = {}  # absent, torn, or corrupt: start fresh
            # merge ONLY keys this process tuned: _MEM also holds entries
            # loaded from disk at startup, and writing those back would
            # revert a concurrent writer's newer tuning for the same key
            merged.update({k: _MEM[k] for k in _DIRTY if k in _MEM})
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                       suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                json.dump(merged, f, indent=1, sort_keys=True)
            os.replace(tmp, path)  # readers never see a torn file
    except OSError:
        pass  # read-only filesystems must not break the kernels


def clear(memory_only: bool = True) -> None:
    """Drop the in-process cache (tests); optionally the disk file too."""
    global _DISK_LOADED
    _MEM.clear()
    _DIRTY.clear()
    _DISK_LOADED = memory_only  # memory_only: don't re-read stale disk state
    if not memory_only:
        path = cache_path()
        if path and os.path.exists(path):
            os.remove(path)


def make_key(op: str, **params: Any) -> str:
    """Cache key over shape/dtype params + backend + TP shard count.

    The ``shards=`` component keeps single-device and tensor-parallel
    tunings apart: under TP the kernel sees *local* operand shards whose
    best tiles need not match a same-shaped single-device call (different
    VMEM pressure from the collective epilogue), and the rows bucket of a
    sharded call must never overwrite the unsharded winner.

    The ops.py callers likewise pass ``adt=``/``wdt=`` (activation /
    weight precision of the recipe, DESIGN.md §10) through ``params``: an
    int8-tuned tile winner must never be silently reused for fp8 or
    nibble-packed w4 operands, whose VMEM footprints and accumulator
    dtypes differ at identical logical shapes.
    """
    from repro.sharding import tp  # deferred: kernels must import cleanly

    parts = [op] + [f"{k}={params[k]}" for k in sorted(params)]
    parts.append(f"backend={jax.default_backend()}")
    shards = tp.size()
    if shards > 1:
        parts.append(f"shards={shards}")
    return "|".join(parts)


def lookup(key: str) -> TileConfig | None:
    _load_disk()
    rec = _MEM.get(key)
    if rec is None:
        return None
    tiles = rec.get("tiles", {})
    return TileConfig(**{f: tiles.get(f) for f in _FIELDS})


def record(key: str, tiles: TileConfig, us: float, *,
           roofline_us: float | None = None,
           why: str | None = None) -> None:
    """Cache ``tiles`` as the winner for ``key`` (in-process + disk).

    ``roofline_us`` is the analytic bound for the tuned shape (DESIGN.md
    §13); when given, the entry also records ``efficiency`` (bound /
    achieved) and ``why`` — so a cache inspection explains each winner
    instead of just asserting it."""
    _load_disk()
    rec: dict[str, Any] = {"tiles": {f: getattr(tiles, f) for f in _FIELDS},
                           "us": us, "backend": jax.default_backend()}
    if roofline_us is not None:
        rec["roofline_us"] = roofline_us
        rec["efficiency"] = roofline_us / us if us > 0 else 0.0
    if why is not None:
        rec["why"] = why
    _MEM[key] = rec
    _DIRTY.add(key)
    _save_disk()


def _time(run: Callable[[TileConfig], Any], tiles: TileConfig,
          reps: int = 3) -> float:
    jax.block_until_ready(run(tiles))  # compile + warmup
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(run(tiles))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def candidates(op: str, rows: int, m: int, k: int) -> list[TileConfig]:
    """Small per-op search spaces (kept tiny: tuning runs the real kernel).

    Sized against the ROWS BUCKET, not the live row count: the winner is
    cached per bucket, so every tile that is valid anywhere in the bucket
    must be in the running.
    """
    rows = rows_bucket(rows)
    if op == "fused_quant_slide":
        return [TileConfig(block_rows=b) for b in (32, 64, 128, 256)
                if b <= max(8, rows)] or [DEFAULT]
    if op == "paged_attention":
        # br = Pallas S-splits, bk = jnp-path pages per loop block
        # (kernels.paged_attention); both dispatch paths read their knob
        # from the same cache entry
        return [DEFAULT] + [TileConfig(br=s, bk=bp)
                            for s in (1, 2, 4) for bp in (1, 4, 8)]
    row_opts = [b for b in (64, 128, 256) if b <= max(64, rows)]
    out_opts = [b for b in (128, 256) if b <= max(128, m)]
    cands = [DEFAULT]
    for br in row_opts:
        for bm in out_opts:
            cands.append(TileConfig(bm=bm, br=br))
    return cands


def autotune(op: str, run: Callable[[TileConfig], Any],
             cands: Iterable[TileConfig] | None = None, *,
             key: str | None = None, rows: int = 0, m: int = 0,
             k: int = 0, params: dict[str, Any] | None = None) -> TileConfig:
    """Time every candidate with ``run`` and cache the fastest under ``key``.

    ``params`` carries the cache-key components (pattern/adt/wdt) so the
    roofline traffic model can price each candidate: tiles whose modeled
    HBM traffic exceeds ``PRUNE_RATIO`` x the best candidate's are pruned
    without timing (they cannot reach the bandwidth bound)."""
    cand_list = list(cands if cands is not None
                     else candidates(op, rows, m, k))
    traffic = {i: roofline.tile_traffic(op, rows=rows, m=m, k=k,
                                        br=t.br, bm=t.bm, **(params or {}))
               for i, t in enumerate(cand_list)}
    known = [v for v in traffic.values() if v is not None]
    floor = min(known) if known else None
    best_tiles, best_us = DEFAULT, float("inf")
    pruned = timed = 0
    for i, tiles in enumerate(cand_list):
        tr = traffic[i]
        if (floor is not None and tr is not None
                and tr > PRUNE_RATIO * floor):
            pruned += 1
            continue
        try:
            us = _time(run, tiles)
        except Exception:
            continue  # candidate invalid for this shape (VMEM, divisibility)
        timed += 1
        if us < best_us:
            best_tiles, best_us = tiles, us
    if key is not None and best_us != float("inf"):
        cost = roofline.op_cost(op, rows=rows, m=m, k=k, **(params or {}))
        bound = roofline.roofline_us(cost) if cost is not None else None
        why = (f"best of {timed} timed / {len(cand_list)} candidates"
               f" ({pruned} roofline-pruned)")
        if bound is not None:
            why += (f"; achieved {best_us:.1f}us vs {bound:.1f}us bound"
                    f" ({bound / best_us:.1%})")
        record(key, best_tiles, best_us, roofline_us=bound, why=why)
    return best_tiles


def rows_bucket(rows: int) -> int:
    """Round the (dynamic, batch-dependent) row count up to a power of two so
    serving batch jitter doesn't fragment the cache."""
    return max(8, 1 << max(0, rows - 1).bit_length())


def tracing(*operands: Any) -> bool:
    """True when any operand is an abstract tracer (inside jit/scan/vmap).

    Tuning must not run under trace: ``block_until_ready`` is a no-op on
    tracers, so _time would measure Python TRACING speed and persist a
    noise-derived winner to the cache."""
    return any(isinstance(a, jax.core.Tracer) for a in operands)


def tiles_for(op: str, *, rows: int, m: int, k: int, tune: bool = False,
              run: Callable[[TileConfig], Any] | None = None,
              operands: tuple = (), **key_params: Any) -> TileConfig:
    """Cached tiles for (op, shape); optionally search when ``tune``.

    ``operands``: the live arrays the runner closes over — tuning is
    silently skipped when they are tracers (see ``tracing``); the cached
    entry (from an eager tune) still applies inside jit.
    """
    key = make_key(op, rows=rows_bucket(rows), m=m, k=k, **key_params)
    cached = lookup(key)
    if cached is not None:
        return cached
    if tune and run is not None and not tracing(*operands):
        return autotune(op, run, key=key, rows=rows, m=m, k=k,
                        params=key_params)
    return DEFAULT
