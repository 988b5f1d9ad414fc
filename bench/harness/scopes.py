"""The program's own spans and scopes in a profiler trace: host time per
span, a span's stats, and device time per named scope of the decode step
and per program.

Host side: every event of the program's Python thread is kept with its
stats (``trace.Trace.python``), and a reader asks for a span by name.
``ContinuousScheduler.step`` opens five spans (``HOST_SPANS``), nested in
the harness's ``step`` span; ``sched.readback`` carries a ``bytes`` stat,
the logits copied to the host.

Device side: the profiler's ``XLA Ops`` events name each operation by its
HLO text without the op's metadata, so the named scopes of the decode step
(``jax.named_scope`` in ``models/backbone.py`` and ``nn/attention.py``) are
read from the optimized HLO of the decode program that ran
(``Compiled.as_text()``).  Each instruction there keeps the whole scope
path of its ``metadata={op_name=...}``.  ``scope_path_ms`` reads any scope
on that path, nested ones too; ``scope_ms`` reads the partition of the
step by the innermost of ``SCOPES`` on the path, or ``unscoped``.
Control-flow containers (``while``, ``conditional``, ``call``) are left
out: the trace lists the operations inside them too, so their time would
count twice.  An operation belongs to the program of the ``XLA Modules``
event it runs in; the decode step is ``jit__step_impl``, the page programs
``jit__invalidate_impl`` and ``jit__reset_impl``.

Every quantity is per step of the traced window, as ``device_ms_per_step``
and ``host_ms_per_step`` are: divided by the number of harness ``step``
spans, device time counted only inside them, and averaged over chips.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

from harness import trace as tracing

HOST_SPANS = ("sched.admit", "sched.feed", "sched.readback", "sched.sample",
              "sched.release")
SCOPES = ("mux", "attention", "kv_write", "mlp", "demux", "lm_head")
UNSCOPED = "unscoped"
STEP_PROGRAM = "jit__step_impl"
PAGE_PROGRAMS = ("jit__invalidate_impl", "jit__reset_impl")
CONTAINERS = ("while", "conditional", "call")

_OPCODE = re.compile(r"\s(%s)\(" % "|".join(CONTAINERS))
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:body|condition|to_apply|true_computation|"
                     r"false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def scope_map(hlo_text: str) -> dict:
    """Instruction name -> its scope path (the parts of its ``op_name``, a
    tuple); a control-flow container maps to None.

    One with no ``op_name`` at all, as XLA leaves the loop it builds for a
    gather, takes the path of the container that runs its computation, or
    the empty path."""
    comp_of: dict = {}        # instruction -> its computation
    own: dict = {}            # instruction -> path from its op_name, or None
    callers: dict = {}        # computation -> the container that runs it
    containers = set()
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            if line.rstrip().endswith("{"):
                comp = line.removeprefix("ENTRY ").split(" ", 1)[0]
                comp = comp.lstrip("%")
            continue
        head, sep, rest = line.strip().partition(" = ")
        if not sep or comp is None:
            continue
        name = head.removeprefix("ROOT ").lstrip("%")
        comp_of[name] = comp
        m = _OP_NAME.search(rest)
        own[name] = None if m is None else tuple(m.group(1).split("/"))
        attrs = rest.split("metadata=", 1)[0]
        if _OPCODE.search(" " + attrs):
            containers.add(name)
            called = _CALLED.findall(attrs)
            for group in _BRANCHES.findall(attrs):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            for c in called:
                callers[c] = name

    def path(name: str, depth: int = 0) -> tuple:
        if own[name] is not None:
            return own[name]
        caller = callers.get(comp_of[name])
        if caller is None or depth > 64:
            return ()
        return path(caller, depth + 1)

    return {name: None if name in containers else path(name)
            for name in own}


def innermost(path: tuple) -> str:
    """The innermost of ``SCOPES`` on a scope path, or ``UNSCOPED``."""
    return next((p for p in reversed(path) if p in SCOPES), UNSCOPED)


@dataclasses.dataclass
class Layers:
    # device -> {(program, op): [(start, end)]} on the host clock
    ops: dict
    spans: list     # [(name, start, end, stats)] of the program's thread
    scope_of: dict  # decode-program instruction -> scope path, or None


def load(hlo_text: str, tr: tracing.Trace) -> Layers:
    """Group each device op of the trace ``tr`` by its program and name;
    ``hlo_text`` is the decode program's optimized HLO."""
    ops: dict = {}
    for dev, evs in tr.ops.items():
        mods = tr.modules.get(dev, [])
        starts = [m[0] for m in mods]
        groups = collections.defaultdict(list)
        for name, a, b in evs:
            k = bisect.bisect_right(starts, a) - 1
            prog = mods[k][2] if k >= 0 and a < mods[k][1] else None
            groups[(prog, name)].append((a, b))
        ops[dev] = dict(groups)
    return Layers(ops=ops, spans=tr.python, scope_of=scope_map(hlo_text))


def _per_step(red, device_intervals: dict) -> float:
    """Milliseconds per step: the union of each device's intervals inside
    the harness's step spans, averaged over devices."""
    tot = 0.0
    for iv in device_intervals.values():
        merged = tracing.union(iv)
        tot += sum(tracing.covered(merged, a, b) for a, b in red.steps)
    return tot / len(device_intervals) / len(red.steps) * 1e-6


def _inputs(ctx):
    lay, red = getattr(ctx, "layers", None), ctx.trace
    if lay is None or red is None or not red.steps:
        return None, None
    return lay, red


def _spans(ctx, span: str):
    """The traced window's spans named ``span``, with the reduction."""
    lay, red = _inputs(ctx)
    if lay is None:
        return None, None
    lo, hi = red.window
    return [(a, b, st) for n, a, b, st in lay.spans
            if n == span and lo <= a and b <= hi], red


def host_ms(ctx, span: str):
    """Per step: the wall time of the program's ``span`` spans in the
    traced window less the device-busy time inside them."""
    got, red = _spans(ctx, span)
    if not got:
        return None
    wall = sum(b - a for a, b, _ in got)
    busy = sum(tracing.covered(red.busy[d], a, b)
               for d in red.busy for a, b, _ in got) / len(red.busy)
    return (wall - busy) / len(red.steps) * 1e-6


def span_stat(ctx, span: str, stat: str):
    """Mean of the ``stat`` stat over the traced window's ``span`` spans
    that carry it."""
    got, _ = _spans(ctx, span)
    vals = [st[stat] for _, _, st in got or () if stat in st]
    return sum(vals) / len(vals) if vals else None


def _ms(ctx, keep):
    """Per step: the union of the device ops ``(program, op)`` that ``keep``
    accepts, inside the step spans; None without the program's layers."""
    lay, red = _inputs(ctx)
    if lay is None or not lay.ops:
        return None
    iv = {d: [x for key, xs in groups.items() if keep(*key) for x in xs]
          for d, groups in lay.ops.items()}
    return _per_step(red, iv)


def scope_ms(ctx, scope: str):
    """Per step: the decode program's ops whose innermost scope is
    ``scope`` (one of ``SCOPES`` or ``UNSCOPED``)."""
    lay, _ = _inputs(ctx)
    if lay is None or not lay.scope_of:
        return None
    of = lay.scope_of
    return _ms(ctx, lambda prog, op: prog == STEP_PROGRAM
               and of.get(op) is not None and innermost(of[op]) == scope)


def scope_path_ms(ctx, name: str):
    """Per step: the decode program's ops with ``name`` anywhere on their
    scope path, so a scope counts the scopes nested in it."""
    lay, _ = _inputs(ctx)
    if lay is None or not lay.scope_of:
        return None
    of = lay.scope_of
    return _ms(ctx, lambda prog, op: prog == STEP_PROGRAM
               and name in (of.get(op) or ()))


def program_ms(ctx, programs: tuple):
    """Per step: device-busy time of the ops of ``programs`` inside the
    step spans."""
    return _ms(ctx, lambda prog, op: prog in programs)


def top_ops(ctx, top: int = 3) -> dict:
    """Per scope of the decode program (innermost, as ``scope_ms``), the
    ops with the most device time inside the traced window, in ms per
    step on the first device; ``unmapped`` lists op names that the HLO
    text does not hold."""
    lay, red = _inputs(ctx)
    if lay is None or not lay.ops:
        return {}
    lo, hi = red.window
    per: dict = {}
    unmapped = set()
    for (prog, op), xs in lay.ops[sorted(lay.ops)[0]].items():
        if prog != STEP_PROGRAM:
            continue
        if op not in lay.scope_of:
            unmapped.add(op)
        path = lay.scope_of.get(op)
        scope = "container" if path is None else innermost(path)
        ms = sum(b - a for a, b in xs if lo <= a and b <= hi) * 1e-6
        per.setdefault(scope, {})[op] = ms / len(red.steps)
    out = {s: sorted(ops.items(), key=lambda kv: -kv[1])[:top]
           for s, ops in per.items()}
    out["unmapped"] = sorted(unmapped)[:20]
    return out


def record_step_args(engine) -> dict:
    """Keep the arguments of the engine's latest jitted decode-step call
    (``held["args"]``), for ``step_hlo``: the scheduler builds them, so
    they are caught on their way in."""
    held: dict = {}
    inner = engine._step

    def step(*args, **kwargs):
        held["args"], held["kwargs"] = args, kwargs
        return inner(*args, **kwargs)

    engine._step = step
    held["jitted"] = inner
    return held


def step_hlo(held: dict) -> str:
    """Optimized HLO text of the decode program for the shapes of the
    latest recorded call; the persistent compilation cache gives back the
    executable that ran."""
    import jax

    def abstract(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        return x

    args = jax.tree.map(abstract, held["args"])
    kwargs = jax.tree.map(abstract, held["kwargs"])
    return held["jitted"].lower(*args, **kwargs).compile().as_text()
