"""Drive one cell through the program's serving path, as a user runs it.

The window drives ``ContinuousScheduler.step`` over an ``Engine`` built as
``launch/serve.py`` builds it, with the cell's configuration, from the
benchmark's seeded weights.  Requests are submitted open loop at their due
times on the host clock (or all at once, for a backlog); a request's latency
counts from when it was due, so a stall also delays the requests behind it.

The harness records, around its calls into the program:
  * each step's host-clock start and end, and the requests that emitted a
    token in it (through the scheduler's sampling policy, which stays the
    program's greedy ``lane`` policy);
  * each step's input, as the engine receives it: the lanes' tokens, the
    live-lane mask, the slots' positions, and which request holds each lane.
    The correctness check replays those streams through the plain reference.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Optional

import numpy as np

from harness import readers, spec, traffic as traffic_gen, weights


@dataclasses.dataclass
class StepInput:
    grid: np.ndarray        # (B, N) request id per lane, -1 free
    tokens: np.ndarray      # (B, N) int32 fed this step
    mask: np.ndarray        # (B, N) live lanes
    pos: np.ndarray         # (B,) position each slot writes


@dataclasses.dataclass
class Served:
    """Everything a run measured, for the metric readers and the check."""
    config: dict                     # the configuration file
    traffic: dict
    requests: dict                   # rid -> program Request
    due: dict                        # rid -> host-clock due time
    tok_times: dict                  # rid -> [host-clock emit time, ...]
    steps: list                      # (scheduler t, start, end) per step
    inputs: list                     # StepInput per scheduler step
    window: tuple = (0.0, 0.0)       # host-clock window [t0, t1]
    run_end: float = 0.0
    traced_steps: tuple = (0, 0)     # scheduler t range inside the trace
    prefix_len: int = 0
    refused: int = 0
    inputs_offset: int = 0           # scheduler t of inputs[0]
    waiting: tuple = (0, 0)          # queued requests at window open, close
    # host-clock [open, close) of the traffic's window segment, on the
    # schedule: the requests due in it are the window's, the same sizes
    # for every seed (the window itself opens and closes on step ends)
    due_window: tuple = (0.0, 0.0)


def _emits(slo):
    """The program's greedy lane sampling, noting which request emitted."""
    from repro.serving.policies import LaneSampling

    class Emits(LaneSampling):
        def __init__(self, slo):
            super().__init__(slo)
            self.rids: list = []

        def select(self, req, logits):
            tok = super().select(req, logits)
            self.rids.append(req.rid)
            return tok

    return Emits(slo)


def build_engine(config: dict, seed: int):
    from repro.serving.engine import Engine
    cfg = spec.model_config(config)
    params = weights.make(cfg, seed)
    engine = Engine(params, cfg, batch=config["batch"],
                    max_len=config["max_len"])
    return cfg, engine


def _record_inputs(engine, sched, inputs: list) -> None:
    """Wrap this engine's ``step`` so each call's inputs are kept."""
    inner = engine.step

    def step(state, tokens, lane_mask=None, block_table=None,
             chunk_lens=None):
        inputs.append(StepInput(grid=sched.table.grid.copy(),
                                tokens=np.array(tokens, np.int32),
                                mask=np.array(lane_mask) > 0,
                                pos=np.array(state.pos, np.int32)))
        return inner(state, tokens, lane_mask=lane_mask,
                     block_table=block_table, chunk_lens=chunk_lens)

    engine.step = step


def warm_programs(sched) -> None:
    """Compile, or load from the cache, every program the window runs: the
    decode step (an empty step), the page-invalidate program (one page
    mapped for slot 0 and freed again) and the slot reset (an empty mask),
    so that nothing compiles inside the window."""
    import jax
    sched.step()
    for c in sched.classes:
        alloc = c.allocator
        if sched.paged:
            pos = np.zeros(c.n_slots, np.int64)
            pos[0] = alloc.page_size * alloc.n_prefix_pages
            live = np.zeros(c.n_slots, bool)
            live[0] = True
            alloc.ensure(pos, live)
            alloc.reset_slots(live)
        alloc.reset_slots(np.zeros(c.n_slots, bool))
    jax.block_until_ready(sched.allocator.cache)


def serve(config: dict, traffic: dict, *, seed: int, seconds: float,
          engine, cfg, on_window_open=None, on_window_close=None,
          on_trace_open=None, trace_s: float = float("inf"),
          annotate=None) -> Served:
    """Warm up, measure for ``seconds``, drain; return what was served.

    ``on_trace_open`` is called when the last ``trace_s`` seconds of the
    window begin (at its open, if it is shorter), and ``traced_steps``
    holds the scheduler steps from then to the window's close."""
    from repro.serving.scheduler import ContinuousScheduler
    from repro.serving.policies import SloClasses

    emits = _emits(SloClasses(cfg.serving.slo_classes))
    sched = ContinuousScheduler(engine, sampling=emits)
    inputs: list = []
    warm_programs(sched)
    _record_inputs(engine, sched, inputs)
    try:
        return _loop(sched, emits, inputs, config, traffic, seed=seed,
                     seconds=seconds, cfg=cfg, on_window_open=on_window_open,
                     on_window_close=on_window_close,
                     on_trace_open=on_trace_open,
                     trace_s=min(trace_s, seconds), annotate=annotate)
    finally:
        del engine.step          # drop the wrapper, and with it the scheduler


def _loop(sched, emits, inputs, config, traffic, *, seed, seconds, cfg,
          on_window_open, on_window_close, on_trace_open, trace_s,
          annotate) -> Served:
    from repro.serving.scheduler import Request
    items = traffic_gen.generate(traffic, seed=seed, vocab=cfg.vocab,
                                 seconds=seconds)
    requests = {it.rid: Request(rid=it.rid, prompt=it.prompt,
                                max_new_tokens=it.max_new) for it in items}
    pending = collections.deque(sorted(items, key=lambda it: it.due_s))
    ann = annotate or (lambda name: contextlib.nullcontext())
    out = Served(config=config, traffic=traffic, requests=requests, due={},
                 tok_times=collections.defaultdict(list), steps=[],
                 inputs=inputs, prefix_len=cfg.mux.prefix_len,
                 inputs_offset=sched.t)
    drain_s = traffic.get("drain_s", 0.0)

    t_base = time.perf_counter()
    warm_end = t_base + traffic["warmup_s"]
    for it in items:
        out.due[it.rid] = t_base + it.due_s
    out.due_window = (warm_end, warm_end + seconds)
    phase, t0, t1, drain_end = "warm", None, None, None
    tracing = False
    window_due: list = []
    while True:
        now = time.perf_counter()
        if pending and out.due[pending[0].rid] <= now:
            with ann("submit"):
                while pending and out.due[pending[0].rid] <= now:
                    it = pending.popleft()
                    req = requests[it.rid]
                    req.arrival = sched.t
                    try:
                        sched.submit(req)
                    except ValueError:
                        out.refused += 1
        busy = bool(sched.table.live_requests()) or \
            sched.admission.waiting() > 0
        if busy:
            start = time.perf_counter()
            with ann("step"):
                sched.step()
            now = time.perf_counter()
            out.steps.append((sched.t - 1, start, now))
            for rid in emits.rids:
                out.tok_times[rid].append(now)
            emits.rids.clear()
        elif not pending:
            now = time.perf_counter()
        else:
            nxt = out.due[pending[0].rid]
            bound = {"warm": warm_end,
                     "window": (t0 or 0) + seconds
                     - (0.0 if tracing else trace_s),
                     "drain": drain_end}[phase]
            with ann("wait_arrival"):
                time.sleep(max(0.0, min(nxt, bound) - time.perf_counter()))
            now = time.perf_counter()

        if phase == "warm" and now >= warm_end:
            phase, t0 = "window", now
            queued_open = sched.admission.waiting()
            if on_window_open:
                on_window_open()
        if phase == "window" and not tracing and \
                now >= t0 + seconds - trace_s:
            tracing = True
            out.traced_steps = (sched.t, sched.t)
            if on_trace_open:
                on_trace_open()
        if phase == "window" and now >= t0 + seconds:
            phase, t1 = "drain", now
            out.traced_steps = (out.traced_steps[0], sched.t)
            out.waiting = (queued_open, sched.admission.waiting())
            if on_window_close:
                on_window_close()
            # The drain runs from here: stopping a profile can take tens of
            # seconds on the chip, which must not eat into it.
            drain_end = time.perf_counter() + drain_s
            window_due = readers.window_due(out)
        if phase == "drain":
            waiting_first = any(not out.tok_times.get(r)
                                for r in window_due)
            if not waiting_first or now >= drain_end or \
                    (not pending and not busy):
                break
    out.window = (t0, t1)
    out.run_end = time.perf_counter()
    return out


def window_steps(served: Served) -> list:
    t0, t1 = served.window
    return [(t, a, b) for t, a, b in served.steps if a >= t0 and b <= t1]


def input_at(served: Served, t: int) -> Optional[StepInput]:
    k = t - served.inputs_offset
    return served.inputs[k] if 0 <= k < len(served.inputs) else None
