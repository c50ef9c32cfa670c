"""The measured window: the benchmark drives ``ServeEngine.step()``
itself, submits requests on the wall clock (open loop) or as each
client's previous request completes (closed loop), and stamps every
output token with the host time at which the step that produced it
returned.

Token counts are read from the engine's running sequences
(``sched.running[*].out_tokens``) and its completions; each step's work
(which lanes decoded, which prompt chunk prefilled) is read from the
scheduler's decision log (``sched.trace``).
"""
from __future__ import annotations

import dataclasses
import re
import time

import jax

from bench import spans
from bench.traffic import Plan, Request

DRAIN_S = 60.0      # after the close: wait this long at most for first tokens
_PREFILL = re.compile(r"prefill r(\d+)\[(\d+):(\d+)\]")


@dataclasses.dataclass
class ReqRecord:
    rid: int
    client: int
    due: float            # host time it was due (open) or submitted (closed)
    prompt: list[int]
    max_new: int
    times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] | None = None   # set when it finished
    status: str | None = None


@dataclasses.dataclass
class StepRecord:
    idx: int
    t0: float
    t1: float
    kind: str             # "decode" | "prefill" | "none"
    lanes: tuple          # decode: contexts before the step; prefill: (start, length)


@dataclasses.dataclass
class WindowRecord:
    t0: float
    t1: float
    requests: dict[int, ReqRecord]
    steps: list[StepRecord]
    trace_span: tuple[float, float] | None = None   # host times
    compiles: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Compiles:
    """Counts JAX compilations (traces and backend compiles) while on."""

    def __init__(self):
        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if self.on and name.startswith("/jax/core/compile/"):
            self.count += 1


class Window:
    def __init__(self, engine, plan: Plan, compiles: Compiles,
                 clock=time.perf_counter):
        self.engine, self.plan, self.compiles = engine, plan, compiles
        self.clock = clock
        self.reqs: dict[int, ReqRecord] = {}
        self.steps: list[StepRecord] = []
        self._log_pos = 0
        self._next_k: dict[int, int] = {}

    # ---------------------------------------------------------- intake
    def _submit(self, req: Request, due: float) -> None:
        with spans.span(spans.SUBMIT):
            self.engine.submit(req.prompt.tolist(), req.max_new, rid=req.idx)
        self.reqs[req.idx] = ReqRecord(req.idx, req.client, due,
                                       req.prompt.tolist(), req.max_new)

    def _next_closed(self, client: int, now: float) -> None:
        k = self._next_k.get(client, 0)
        self._next_k[client] = k + 1
        self._submit(self.plan.closed_request(client, k), now)

    # ------------------------------------------------------------ step
    def _step(self, idx: int) -> list:
        t0 = self.clock()
        with spans.span(spans.STEP, step=idx):
            fins = self.engine.step()
        t1 = self.clock()
        with spans.span(spans.RECORD):
            sched = self.engine.sched
            for seq in sched.running:
                n = (len(sched.full_output(seq)) if seq.evictions
                     else len(seq.out_tokens))
                times = self.reqs[seq.rid].times
                times.extend([t1] * (n - len(times)))
            for comp in fins:
                r = self.reqs[comp.rid]
                r.tokens, r.status = list(comp.tokens), comp.status
                r.times.extend([t1] * (len(comp.tokens) - len(r.times)))
            self.steps.append(self._decision(idx, t0, t1))
        return fins

    def _decision(self, idx: int, t0: float, t1: float) -> StepRecord:
        log = self.engine.sched.trace
        new, self._log_pos = log[self._log_pos:], len(log)
        for entry in new:
            if entry.startswith("decode "):
                rids = [int(r[1:]) for r in entry[7:].split(",")]
                ctx = tuple(len(self.reqs[r].prompt) + len(self.reqs[r].times)
                            - 1 for r in rids)
                return StepRecord(idx, t0, t1, "decode", ctx)
            m = _PREFILL.match(entry)
            if m:
                a, b = int(m.group(2)), int(m.group(3))
                return StepRecord(idx, t0, t1, "prefill", (a, b - a))
        return StepRecord(idx, t0, t1, "none", ())

    # ---------------------------------------------------------- window
    def run(self, seconds: float, trace_dir: str | None = None,
            trace_seconds: float = 6.0) -> WindowRecord:
        """Measure for ``seconds``; with ``trace_dir``, record a profiler
        trace of ``trace_seconds`` in the middle of the window."""
        eng, plan = self.engine, self.plan
        t0 = self.clock()
        end = t0 + seconds
        tr_start = t0 + max(0.0, (seconds - trace_seconds) / 2)
        tr_state, tr_span, trace_span = 0, None, None
        arrivals = [] if plan.loop == "closed" else \
            plan.open_requests(seconds)
        nxt = 0
        if plan.loop == "closed":
            for c in range(plan.clients):
                self._next_closed(c, t0)
        self.compiles.on = True
        idx = 0
        try:
            while True:
                now = self.clock()
                if now >= end:
                    break
                if trace_dir is not None:
                    if tr_state == 0 and now >= tr_start:
                        jax.profiler.start_trace(trace_dir)
                        tr_span = spans.span(spans.WINDOW)
                        tr_span.__enter__()
                        trace_span, tr_state = [self.clock(), None], 1
                    elif tr_state == 1 and now >= trace_span[0] + trace_seconds:
                        # let dispatched work finish inside the span
                        jax.block_until_ready(eng.cache)
                        trace_span[1] = self.clock()
                        tr_span.__exit__(None, None, None)
                        jax.profiler.stop_trace()
                        tr_state = 2
                while nxt < len(arrivals) and t0 + arrivals[nxt].due <= now:
                    self._submit(arrivals[nxt], t0 + arrivals[nxt].due)
                    nxt += 1
                if eng.sched.has_work:
                    fins = self._step(idx)
                    idx += 1
                    if plan.loop == "closed" and self.clock() < end:
                        t = self.clock()
                        for comp in fins:
                            self._next_closed(self.reqs[comp.rid].client, t)
                else:
                    due = (t0 + arrivals[nxt].due if nxt < len(arrivals)
                           else end)
                    with spans.span(spans.WAIT):
                        time.sleep(max(0.0, min(due, end) - self.clock()))
            t1 = self.clock()
            self.compiles.on = False
            compiles = self.compiles.count
            if tr_state == 1:
                jax.block_until_ready(eng.cache)
                trace_span[1] = t1
                tr_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
            # the close: requests that arrived in the window get their
            # first token (its latency counts the wait), nothing new is sent
            limit = t1 + DRAIN_S
            while eng.sched.has_work and self.clock() < limit and any(
                    not r.times and r.status is None
                    for r in self.reqs.values()):
                self._step(idx)
                idx += 1
        finally:
            self.compiles.on = False
        return WindowRecord(t0, t1, self.reqs, self.steps,
                            tuple(trace_span) if trace_span else None,
                            compiles)
