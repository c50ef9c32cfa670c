"""What decides ``correct``: the logits the reference gives the tokens
the timed path served.

Once the window has closed and the program's state is freed, a sample
of the requests the engine finished (the one with the most served
tokens, and others drawn from the seed) goes through the reference,
each prompt with its served tokens.  Every served token was the
program's greedy choice at its position, the first one at the end of
prefill and the rest in decode steps through the paged cache; the
number compared is the widest gap by which such a token's reference
logit lies below the reference's best logit at that position.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import reference
from bench.driver import ReqRecord, WindowRecord

SAMPLE = 4          # requests compared per run, unless the mix says
                    # otherwise (``check_requests``)
# the control: the reference in the nearest precision below the
# configuration's recipe (int8 below bf16; int4 weights below int8)
CONTROL = {"none": "int8", "int8": "w4"}
BUCKET = 512        # row length rounds up to this, so few shapes compile


@dataclasses.dataclass
class Rows:
    tokens: np.ndarray   # [N, S] prompt + served tokens, zero padded
    cands: np.ndarray    # [N, S] the served token each position predicts
    served: np.ndarray   # [N, S] bool: the position predicts a served token
    count: int           # served tokens compared


def sample(rec: WindowRecord, seed: int,
           n: int = SAMPLE) -> list[ReqRecord]:
    done = sorted((r for r in rec.requests.values()
                   if r.status == "OK" and r.tokens), key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)),
                      replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def rows(reqs: list[ReqRecord], n: int = SAMPLE) -> Rows:
    """``n`` rows (at least ``len(reqs)``), so that every run of a cell
    compiles one reference shape per length bucket."""
    n = max(n, len(reqs))
    s = -(-max(len(r.prompt) + len(r.tokens) for r in reqs) // BUCKET) \
        * BUCKET
    tokens = np.zeros((n, s), np.int32)
    cands = np.zeros((n, s), np.int32)
    served = np.zeros((n, s), bool)
    for i, r in enumerate(reqs):
        p, seq = len(r.prompt), r.prompt + r.tokens
        tokens[i, :len(seq)] = seq
        cands[i, :len(seq) - 1] = seq[1:]
        served[i, p - 1:len(seq) - 1] = True
    return Rows(tokens, cands, served, int(served.sum()))


def widest_gap(c: dict, seed: int, r: Rows) -> float:
    """The program's number: widest reference-logit gap of a served token."""
    gap, _ = reference.forward(c, seed, r.tokens, r.cands[..., None])
    return float(gap[..., 0][r.served].max())


def control_gap(c: dict, seed: int, r: Rows, quant: str) -> tuple[float, float]:
    """(program's widest gap, control's widest gap): the control is the
    reference computed at ``quant``; at each position its top token is
    read against the reference's best."""
    _, top = reference.forward(c, seed, r.tokens, r.cands[..., None],
                               quant=quant)
    gap, _ = reference.forward(c, seed, r.tokens,
                               np.stack([r.cands, top], axis=-1))
    return (float(gap[..., 0][r.served].max()),
            float(gap[..., 1][r.served].max()))
