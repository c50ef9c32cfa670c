"""The one traffic generator: a mix is a JSON file of parameters under
``bench/traffic/``, read here and expanded from ``--seed``.

Every length and every gap between arrivals is drawn from a fixed,
stratified pool (the distribution's quantiles at ``(i + 0.5) / POOL``)
that the seed only shuffles, so every seed serves the same set of sizes
and arrivals in another order.  Prompt token ids are drawn from the seed.
A window serves only a part of the pool; where a mix sets ``block``, the
pool is made of blocks of that many requests, each block the
distribution's ``block`` quantiles in an order drawn from the seed, so
that every stretch of whole blocks serves the same sizes and gaps.

A mix has:

- ``loop``: ``closed`` (``clients`` callers that each send their next
  request when the previous one completes) or ``open`` (Poisson arrivals
  at ``rate_per_s``, sent when due whatever the state of the server);
- ``prompt`` and ``output`` lengths: ``{"dist": "uniform", "min", "max"}``
  or ``{"dist": "lognormal", "median", "sigma", "min", "max"}``;
- ``first_output`` (closed loop): the length distribution of each
  client's first request, so that streams start out of step;
- ``block`` (optional): the stratified blocks above; without it the
  whole pool of ``POOL`` is shuffled at once;
- ``size_seed`` (optional): lengths and gaps are drawn from this seed and
  not from ``--seed``, so every seed serves the same requests in the same
  order, with its own token ids and weights: for a closed loop whose work
  depends on the order in which the sizes come;
- ``check_requests`` (optional): how many finished requests the check
  of ``correct`` compares (``bench/check.py``), where 4 would hold too
  few served tokens;
- ``engine``: the engine sizing the mix needs (``max_batch``,
  ``page_size``, ``num_pages``, ``max_seq_len``, ``prefill_chunk``).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

POOL = 4096


@dataclasses.dataclass(frozen=True)
class Request:
    idx: int            # position in the generator's order
    client: int         # closed loop: the caller; open loop: -1
    due: float | None   # open loop: seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _quantiles(spec: dict, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        x = lo + u * (hi - lo + 1)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(v) for v in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _shuffled(values, block: int | None,
              rng: np.random.Generator) -> np.ndarray:
    """``values(POOL)`` shuffled, or with ``block`` whole blocks of
    ``values(block)``, each shuffled on its own, ``POOL`` or a few more."""
    if not block:
        return rng.permutation(values(POOL))
    q = values(block)
    return np.concatenate([rng.permutation(q)
                           for _ in range(-(-POOL // block))])


def _pool(spec: dict, block: int | None,
          rng: np.random.Generator) -> np.ndarray:
    return _shuffled(lambda n: _quantiles(spec, n), block, rng)


class Plan:
    """The requests of one run, expanded from a mix and a seed."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, vocab, seed
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop {self.loop!r}")
        rng = np.random.default_rng([mix.get("size_seed", seed), 0])
        block = mix.get("block")
        self._prompt = _pool(mix["prompt"], block, rng)
        self._output = _pool(mix["output"], block, rng)
        if self.loop == "closed":
            self.clients = int(mix["clients"])
            self._first = _pool(mix.get("first_output", mix["output"]),
                                block, rng)
        else:
            self.clients = 0
            gaps = _shuffled(  # Exp(1) quantiles
                lambda n: -np.log1p(-(np.arange(n) + 0.5) / n), block, rng)
            self._arrivals = np.cumsum(gaps) / float(mix["rate_per_s"])

    def _tokens(self, idx: int, n: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, 1, idx])
        return rng.integers(0, self.vocab, size=n, dtype=np.int32)

    def closed_request(self, client: int, k: int) -> Request:
        """The ``k``-th request of closed-loop ``client``."""
        idx = k * self.clients + client
        i = idx % len(self._prompt)   # every pool has this length
        out = (self._first if k == 0 else self._output)[i]
        n = int(self._prompt[i])
        return Request(idx, client, None, self._tokens(idx, n), int(out))

    def open_requests(self, seconds: float) -> list[Request]:
        """Every open-loop request due in the first ``seconds``."""
        due = self._arrivals[self._arrivals < seconds]
        if len(due) == len(self._arrivals):
            raise ValueError(f"{len(due)} arrivals do not cover {seconds} s")
        return [Request(i, -1, float(t),
                        self._tokens(i, int(self._prompt[i])),
                        int(self._output[i])) for i, t in enumerate(due)]

    def max_seq_len(self) -> int:
        return int(self.mix["prompt"]["max"] + self.mix["output"]["max"])

