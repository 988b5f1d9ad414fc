"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, per
step device time and the breakdown of where the time went.

Device planes are those named ``/device:TPU:<n>``; an operation is an event
of their ``XLA Ops`` line, and runs inside the program of the ``XLA
Modules`` event around it.  Host spans are the events of the host's Python
thread line (``python`` or ``python3``): the harness's own
``TraceAnnotation`` spans (``step``, ``submit``, ``wait_arrival``), the
program's own spans and JAX's events inside them, each with its stats.

The device's clock in the trace runs about a millisecond off the host's
(1.27 ms early on the recorded fixture), more than a short step's device
time.  So each device plane is shifted onto the host clock: a program
cannot start before the host enqueued it, and the host's
``DoEnqueueProgram`` event and the device's ``XLA Modules`` event carry the
same ``run_id``, so the shift is the largest gap between a program's
enqueue and its start on the device clock (the launch that found the
device idle, and started at once).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

HARNESS_SPANS = ("step", "submit", "wait_arrival")
OP_LINE = "XLA Ops"


@dataclasses.dataclass
class Trace:
    ops: dict          # device plane name -> [(name, start_ns, end_ns)]
    host: list         # [(name, start_ns, end_ns, depth)] on the python line
    # [(name, start_ns, end_ns, stats)] of every event on the python line
    python: list = dataclasses.field(default_factory=list)
    # device plane name -> [(start_ns, end_ns, program)], sorted
    modules: dict = dataclasses.field(default_factory=dict)

    def spans(self, name: str) -> list:
        return [(a, b) for n, a, b, _ in self.host if n == name]


def find(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


DEVICE_PREFIX = "/device:TPU:"


def _op_name(text: str) -> str:
    """An XLA Ops event is named by its HLO text; keep the op's name."""
    return text.split(" = ", 1)[0].lstrip("%")


def _program(name: str) -> str:
    """``XLA Modules`` events are named ``<module>(<program id>)``."""
    return name.split("(", 1)[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops: dict = {}
    starts: dict = {}        # (device ordinal, run_id) -> device start
    enqueued: dict = {}      # (device ordinal, run_id) -> host enqueue
    modules: dict = {}
    host: list = []
    python: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ordinal = int(plane.name[len(DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops[plane.name] = [(_op_name(e.name), e.start_ns,
                                        e.end_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = modules.setdefault(plane.name, [])
                    for e in line.events:
                        mods.append((e.start_ns, e.end_ns, _program(e.name)))
                        run = dict(e.stats).get("run_id")
                        if run is not None:
                            starts[(ordinal, run)] = e.start_ns
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if line.name.startswith("python"):
                    evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                           for e in line.events]
                    python.extend(evs)
                    host.extend(_nest(evs))
                for e in line.events:
                    if e.name == "DoEnqueueProgram":
                        st = dict(e.stats)
                        key = (st.get("device_ordinal"), st.get("run_id"))
                        enqueued.setdefault(key, e.start_ns)
    for name in list(ops):
        ordinal = int(name[len(DEVICE_PREFIX):])
        shifts = [enqueued[k] - t for k, t in starts.items()
                  if k[0] == ordinal and k in enqueued]
        shift = max(shifts) if shifts else 0.0
        ops[name] = [(n, a + shift, b + shift) for n, a, b in ops[name]]
        modules[name] = sorted((a + shift, b + shift, p)
                               for a, b, p in modules.get(name, []))
    return Trace(ops=ops, host=host, python=python, modules=modules)


def _nest(events) -> list:
    """Events ``(name, start, end, ...)`` of one thread line with their
    nesting depth."""
    out, stack = [], []
    for name, a, b, *_ in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1] <= a:
            stack.pop()
        out.append((name, a, b, len(stack)))
        stack.append(b)
    return out


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def covered(merged: list, a: float, b: float) -> float:
    """Length of the part of [a, b] that the merged intervals (sorted and
    disjoint, as ``union`` gives them) cover; only those that reach past
    ``a`` are visited, so a trace of millions of operations is read in
    one pass over its steps."""
    k = bisect.bisect_right(merged, a, key=lambda iv: iv[1])
    tot = 0.0
    while k < len(merged) and merged[k][0] < b:
        x, y = merged[k]
        tot += min(b, y) - max(a, x)
        k += 1
    return tot


@dataclasses.dataclass
class Reduction:
    window: tuple          # (start_ns, end_ns) of the traced window
    busy: dict             # device -> merged busy intervals
    steps: list            # (start_ns, end_ns) of each harness step span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, device: str) -> float:
        return covered(self.busy[device], *self.window) * 1e-9

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(d) for d in self.busy) / len(self.busy)

    def step_device_s(self) -> float:
        """Device-busy seconds inside the step spans, averaged over chips."""
        tot = 0.0
        for d in self.busy:
            tot += sum(covered(self.busy[d], a, b) for a, b in self.steps)
        return tot * 1e-9 / len(self.busy)

    def step_host_s(self) -> float:
        return sum(b - a for a, b in self.steps) * 1e-9 - self.step_device_s()


def reduce(tr: Trace):
    """The reduction, or None when the trace holds no device operations
    (a run on the CPU)."""
    if not tr.ops:
        return None
    spans = [(a, b) for n, a, b, _ in tr.host if n in HARNESS_SPANS]
    if not spans:
        raise ValueError("the trace holds none of the harness's host spans")
    window = (min(a for a, _ in spans), max(b for _, b in spans))
    busy = {d: union((a, b) for _, a, b in evs) for d, evs in tr.ops.items()}
    steps = tr.spans("step")
    # Every step runs device work; a device whose operations stop before
    # the last step began lost its events (the profiler's buffer filled).
    last = max((a for a, _ in steps), default=None)
    for d, iv in busy.items():
        if last is not None and (not iv or iv[-1][1] < last):
            raise ValueError(f"{d}'s trace ends before the last traced step: "
                             f"its events were dropped; trace less")
    return Reduction(window=window, busy=busy, steps=steps)


def breakdown(tr: Trace, red: Reduction, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps of the first device, each named by the innermost host event that
    was open at the gap's midpoint."""
    per_op: dict = collections.Counter()
    for evs in tr.ops.values():
        for name, a, b in evs:
            if a >= red.window[0] and b <= red.window[1]:
                per_op[name] += (b - a) * 1e-9
    n_dev = len(tr.ops)
    device_ops = [[name, s / n_dev] for name, s in per_op.most_common(top)]
    dev = sorted(red.busy)[0]
    busy = red.busy[dev]
    lo, hi = red.window
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur and cur < hi:
            gaps.append((cur, min(a, hi)))
        cur = max(cur, b)
    if cur < hi:
        gaps.append((cur, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for a, b in gaps[:top]:
        mid = (a + b) / 2
        inner = [(depth, name) for name, x, y, depth in tr.host
                 if x <= mid < y]
        label = max(inner)[1] if inner else "no host span"
        idle.append([label, (b - a) * 1e-9])
    return {"device_ops": device_ops, "idle_gaps": idle}
