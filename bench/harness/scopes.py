"""The program's own spans and scopes in a profiler trace: host time per
phase of the scheduler's step, bytes read back per step, and device time
per named part of the decode step and per program.

Host side: ``ContinuousScheduler.step`` opens five spans on the profiler's
host plane (``HOST_SPANS``), nested in the harness's ``step`` span;
``sched.readback`` carries a ``bytes`` stat, the logits copied to the host.

Device side: the profiler's ``XLA Ops`` events name each operation by its
HLO text without the op's metadata, so the named scopes of the decode step
(``SCOPES``, ``jax.named_scope`` in ``models/backbone.py`` and
``nn/attention.py``) are read from the optimized HLO of the decode program
that ran (``Compiled.as_text()``).  Each instruction there is mapped to the
innermost of ``SCOPES`` in its ``metadata={op_name=...}`` path, or to
``unscoped``.  Control-flow containers (``while``, ``conditional``,
``call``) are left out: the trace lists the operations inside them too, so
their time would count twice.  An operation belongs to the program of the
``XLA Modules`` event it runs in; the decode step is ``jit__step_impl``,
the page programs ``jit__invalidate_impl`` and ``jit__reset_impl``.

Every quantity is per step of the traced window, as ``device_ms_per_step``
and ``host_ms_per_step`` are: divided by the number of harness ``step``
spans, device time counted only inside them, and averaged over chips.
"""
from __future__ import annotations

import bisect
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
    """Instruction name -> scope (one of ``SCOPES`` or ``UNSCOPED``); a
    control-flow container maps to None.

    An instruction's scope is the innermost of ``SCOPES`` on its
    ``op_name`` path, or ``UNSCOPED`` when the path holds none.  One with
    no ``op_name`` at all, as XLA leaves the loop it builds for a gather,
    takes the scope of the container that runs its computation."""
    comp_of: dict = {}        # instruction -> its computation
    own: dict = {}            # instruction -> scope from its op_name, or None
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
        own[name] = None if m is None else next(
            (p for p in reversed(m.group(1).split("/")) if p in SCOPES),
            UNSCOPED)
        attrs = rest.split("metadata=", 1)[0]
        if _OPCODE.search(" " + attrs):
            containers.add(name)
            called = _CALLED.findall(attrs)
            for group in _BRANCHES.findall(attrs):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            for c in called:
                callers[c] = name

    def scope(name: str, depth: int = 0) -> str:
        if own[name] is not None:
            return own[name]
        caller = callers.get(comp_of[name])
        if caller is None or depth > 64:
            return UNSCOPED
        return scope(caller, depth + 1)

    return {name: None if name in containers else scope(name)
            for name in own}


@dataclasses.dataclass
class Layers:
    ops: dict       # device -> [(op, program, start, end)] on the host clock
    spans: list     # [(name, start, end, stats)] of the program's host spans
    scope_of: dict  # decode-program instruction name -> scope or None


def _program(name: str) -> str:
    """``XLA Modules`` events are named ``<module>(<program id>)``."""
    return name.split("(", 1)[0]


def load(path: str, hlo_text: str, tr: tracing.Trace = None) -> Layers:
    """Read the program's spans and each device op's program from the
    trace at ``path``; ``tr`` is its ``trace.load`` (read again if not
    given), whose shift onto the host clock the ops take."""
    from jax.profiler import ProfileData
    tr = tr or tracing.load(path)
    data = ProfileData.from_file(path)
    ops: dict = {}
    spans: list = []
    for plane in data.planes:
        if plane.name.startswith(tracing.DEVICE_PREFIX):
            lines = {line.name: list(line.events) for line in plane.lines}
            raw = lines.get(tracing.OP_LINE, [])
            shifted = tr.ops.get(plane.name, [])
            if not raw or len(raw) != len(shifted):
                continue
            mods = sorted((e.start_ns, e.end_ns, _program(e.name))
                          for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            evs = []
            for (name, a, b), e in zip(shifted, raw):
                k = bisect.bisect_right(starts, e.start_ns) - 1
                prog = mods[k][2] if k >= 0 and e.start_ns < mods[k][1] \
                    else None
                evs.append((name, prog, a, b))
            ops[plane.name] = evs
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns, e.end_ns,
                                      dict(e.stats)))
    return Layers(ops=ops, spans=spans, scope_of=scope_map(hlo_text))


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


def host_ms(ctx, span: str):
    """Per step: the wall time of the program's ``span`` spans in the
    traced window less the device-busy time inside them."""
    lay, red = _inputs(ctx)
    if lay is None:
        return None
    lo, hi = red.window
    got = [(a, b) for n, a, b, _ in lay.spans
           if n == span and lo <= a and b <= hi]
    if not got:
        return None
    wall = sum(b - a for a, b in got)
    busy = sum(tracing.covered(red.busy[d], a, b)
               for d in red.busy for a, b in got) / len(red.busy)
    return (wall - busy) / len(red.steps) * 1e-6


def readback_bytes(ctx):
    """Mean ``bytes`` stat of the traced window's ``sched.readback``
    spans: the device-to-host bytes of one step."""
    lay, red = _inputs(ctx)
    if lay is None:
        return None
    lo, hi = red.window
    got = [st["bytes"] for n, a, b, st in lay.spans
           if n == "sched.readback" and lo <= a and b <= hi
           and "bytes" in st]
    return sum(got) / len(got) if got else None


def scope_ms(ctx, scope: str):
    """Per step: the union of the decode program's ops in ``scope`` (one
    of ``SCOPES`` or ``UNSCOPED``) inside the step spans."""
    lay, red = _inputs(ctx)
    if lay is None or not lay.ops or not lay.scope_of:
        return None
    iv = {d: [(a, b) for name, prog, a, b in evs
              if prog == STEP_PROGRAM and lay.scope_of.get(name) == scope]
          for d, evs in lay.ops.items()}
    return _per_step(red, iv)


def program_ms(ctx, programs: tuple):
    """Per step: device-busy time of the ops of ``programs`` inside the
    step spans."""
    lay, red = _inputs(ctx)
    if lay is None or not lay.ops:
        return None
    iv = {d: [(a, b) for _, prog, a, b in evs if prog in programs]
          for d, evs in lay.ops.items()}
    return _per_step(red, iv)


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
