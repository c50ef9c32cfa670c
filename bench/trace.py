"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to what the per-layer metrics read.

The traced window is the benchmark's own host span ``bench.window``.
Within it:

- busy time: the union of the intervals of the device's operations
  (line ``XLA Ops`` of the first TPU plane), clipped to the window;
- per-op device self time (an op's time less that of the ops nested in
  it, as a loop's body is in the loop), summed by the op's HLO name
  without its instance number, for the breakdown;
- module executions (line ``XLA Modules``): the device time of each run
  of a jitted program, found by the program's name;
- the steps: indices of the ``bench.step`` host spans that lie wholly in
  the window (the benchmark's step records give their work);
- idle gaps: the stretches of the window with no device operation, each
  tagged with the benchmark host span that covers most of it.
"""
from __future__ import annotations

import dataclasses

from bench import spans

# the compressed GEMM: the Pallas custom call that XLA names after its
# jitted wrapper, kernels/slide_matmul.compressed_matmul_pallas
GEMM_KERNEL = "compressed_matmul_pallas"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Op:
    name: str       # HLO name without "%" and instance number: "fusion"
    start: int      # ns
    end: int


def op_name(event_name: str) -> str:
    """``%compressed_matmul_pallas.68 = bf16[...] custom-call(...)`` ->
    ``compressed_matmul_pallas``."""
    head = event_name.split(" = ", 1)[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


@dataclasses.dataclass
class Summary:
    window: tuple[int, int]                   # ns, trace clock
    ops: list[Op]
    modules: list[tuple[str, int, int]]       # (name, start, end) ns
    host: list[tuple[str, int, int, dict]]    # bench spans (name, s, e, stats)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _clip(self, s: int, e: int) -> int:
        return max(0, min(e, self.window[1]) - max(s, self.window[0]))

    def busy_intervals(self) -> list[tuple[int, int]]:
        out: list[list[int]] = []
        for op in sorted(self.ops, key=lambda o: o.start):
            s, e = max(op.start, self.window[0]), min(op.end, self.window[1])
            if e <= s:
                continue
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return [tuple(x) for x in out]

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy_intervals()) * 1e-9

    def gaps(self) -> list[tuple[int, int]]:
        edges = [self.window[0]]
        for s, e in self.busy_intervals():
            edges += [s, e]
        edges.append(self.window[1])
        return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    @property
    def steps(self) -> set[int]:
        w0, w1 = self.window
        return {int(st["step"]) for name, s, e, st in self.host
                if name == spans.STEP and "step" in st and w0 <= s and e <= w1}

    def op_seconds(self) -> dict[str, float]:
        """Self time in the window by op name: nested ops (a loop's body
        inside the loop) are subtracted from the op that holds them."""
        out: dict[str, float] = {}
        stack: list[list] = []   # [op, clipped time of its children]

        def close(entry):
            op, child = entry
            own = self._clip(op.start, op.end) - child
            out[op.name] = out.get(op.name, 0.0) + own * 1e-9
            if stack:
                stack[-1][1] += self._clip(op.start, op.end)

        for op in sorted(self.ops, key=lambda o: (o.start, -o.end)):
            # an op nests in the one before it only if it ends within it
            while stack and (stack[-1][0].end <= op.start
                             or stack[-1][0].end < op.end):
                close(stack.pop())
            stack.append([op, 0])
        while stack:
            close(stack.pop())
        return out

    def kernel_seconds(self, kernel: str) -> float:
        return sum(self._clip(op.start, op.end) for op in self.ops
                   if op.name == kernel) * 1e-9

    def module_seconds(self, prefix: str) -> list[float]:
        """Device seconds of each execution of the program ``prefix``
        that starts in the window."""
        w0, w1 = self.window
        return [(e - s) * 1e-9 for name, s, e in self.modules
                if name.startswith(prefix) and w0 <= s < w1]

    def gap_owner(self, s: int, e: int) -> str:
        best, owner = 0, "none"
        for name, hs, he, _ in self.host:
            if name == spans.WINDOW:
                continue
            cover = min(e, he) - max(s, hs)
            if cover > best:
                best, owner = cover, name
        return owner

    def idle_by_span(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for s, e in self.gaps():
            k = self.gap_owner(s, e)
            out[k] = out.get(k, 0.0) + (e - s) * 1e-9
        return out

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])
        return {"device_ops": [[k, v] for k, v in ops[:top]],
                "idle_gaps": [[k, v] for k, v in idle[:top]]}


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce(path: str) -> Summary:
    return summarize(load(path))


def summarize(data) -> Summary:
    host, window = [], None
    device = None
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        st = {k: v for k, v in ev.stats}
                        s, e = int(ev.start_ns), int(ev.end_ns)
                        host.append((ev.name, s, e, st))
                        if ev.name == spans.WINDOW:
                            window = (s, e)
        elif plane.name.startswith("/device:TPU:") and (
                device is None or plane.name < device.name):
            device = plane
    if window is None:
        raise ValueError(f"the trace has no {spans.WINDOW} span")
    if device is None:
        raise ValueError("the trace has no TPU plane")
    ops, modules = [], []
    for line in device.lines:
        if line.name == OPS_LINE:
            ops = [Op(op_name(ev.name), int(ev.start_ns), int(ev.end_ns))
                   for ev in line.events]
        elif line.name == MODULES_LINE:
            modules = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                       for ev in line.events]
    host.sort(key=lambda h: h[1])
    return Summary(window, ops, modules, host)
