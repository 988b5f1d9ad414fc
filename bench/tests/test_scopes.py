"""The split of a traced window by the program's own spans and scopes
(``harness/scopes.py`` and its readers), on a small trace recorded on one
TPU v5e chip by ``make_layers_fixture.py``: twelve harness ``step`` spans,
each one ``sched.step()`` of the tiny paged scheduler, and the decode
program's optimized HLO."""
from __future__ import annotations

import types

import pytest

from conftest import BENCH
from harness import scopes, trace

DATA = BENCH / "tests" / "data"
XPLANE = DATA / "layers.xplane.pb"
HLO = DATA / "layers.hlo.txt"
STEPS = 12
# 2 slots x 4 lanes x 256 vocabulary, bf16 logits
LOGITS_BYTES = 2 * 4 * 256 * 2
HOST = ("host_admit_ms", "host_feed_ms", "host_readback_ms",
        "host_sample_ms", "host_release_ms")
DEVICE = ("mux_ms_per_step", "attention_ms_per_step", "kv_write_ms_per_step",
          "mlp_ms_per_step", "demux_ms_per_step", "lm_head_ms_per_step",
          "unscoped_ms_per_step", "page_programs_ms_per_step")


def reader(name):
    import run
    return run.load_reader(name)


@pytest.fixture(scope="module")
def ctx():
    tr = trace.load(str(XPLANE))
    red = trace.reduce(tr)
    lay = scopes.load(HLO.read_text(), tr)
    return types.SimpleNamespace(trace=red, layers=lay)


def _step_ops(ctx):
    return {op for groups in ctx.layers.ops.values()
            for prog, op in groups if prog == scopes.STEP_PROGRAM}


SMALL_HLO = "\n".join([
        "HloModule jit__step_impl, is_scheduled=true",
        "",
        "%gather_body (p.1: (s32[], bf16[8])) -> (s32[], bf16[8]) {",
        "  %p.1 = (s32[], bf16[8]{0}) parameter(0)",
        "  %dynamic-update-slice.7 = bf16[8]{0} dynamic-update-slice(%a, %b)",
        "  ROOT %tuple.8 = (s32[], bf16[8]{0}) tuple(%i, %dynamic-update-"
        "slice.7)",
        "}",
        "",
        "ENTRY %main.9 (p: bf16[8]) -> bf16[8] {",
        '  %fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(_step_impl)/while/body/attention/'
        'kv_write/scatter"}',
        '  %dot.2 = bf16[8]{0} dot(%a, %b), metadata={op_name="jit(_step_'
        'impl)/while/body/attention/dot_general"}',
        "  %while.3 = (s32[], bf16[8]{0}) while(%t), condition=%c, "
        'body=%gather_body, metadata={op_name="jit(_step_impl)/attention/'
        'gather"}',
        "  %copy.4 = bf16[8]{0} copy(%x)",
        '  %custom-call.5 = bf16[8]{0} custom-call(%x), custom_call_target='
        '"f", metadata={op_name="jit(_step_impl)/lm_head/dot_general"}',
        '  ROOT %dynamic-slice.6 = bf16[1]{0} dynamic-slice(%x, %i), '
        'metadata={op_name="jit(_step_impl)/while/body/dynamic_slice"}',
        "}",
    ])


def test_scope_map_reads_the_innermost_scope():
    """The innermost scope on an op's path; none on the path reads
    unscoped; an op with no ``op_name`` takes its container's scope
    (``gather_body``'s ops belong to the attention gather loop), and
    containers are dropped."""
    paths = scopes.scope_map(SMALL_HLO)
    assert {op: None if p is None else scopes.innermost(p)
            for op, p in paths.items()} == {
        "p.1": "attention", "dynamic-update-slice.7": "attention",
        "tuple.8": "attention", "fusion.1": "kv_write", "dot.2": "attention",
        "while.3": None, "copy.4": scopes.UNSCOPED, "custom-call.5": "lm_head",
        "dynamic-slice.6": scopes.UNSCOPED}
    # the whole path is kept, and the container's passes to its body
    assert paths["fusion.1"] == ("jit(_step_impl)", "while", "body",
                                 "attention", "kv_write", "scatter")
    assert paths["p.1"] == ("jit(_step_impl)", "attention", "gather")
    assert paths["copy.4"] == ()


def _one_step(ops: dict):
    """A one-step trace of the decode program: op -> (start, end)."""
    red = trace.Reduction(window=(0, 100), busy={"d": [(0, 100)]},
                          steps=[(0, 100)])
    lay = scopes.Layers(
        ops={"d": {(scopes.STEP_PROGRAM, op): [iv] for op, iv in ops.items()}},
        spans=[], scope_of=scopes.scope_map(SMALL_HLO))
    return types.SimpleNamespace(trace=red, layers=lay)


def test_scope_path_ms_counts_nested_scopes_inside_their_parent():
    """``attention`` on the path counts the ``kv_write`` op nested in it and
    the gather loop's body, which ``scope_ms`` gives to ``kv_write`` and
    ``attention``; a name on no path reads 0, a scope's own name alone
    reads its ops."""
    c = _one_step({"fusion.1": (0, 10), "dot.2": (10, 30),
                   "dynamic-update-slice.7": (30, 34), "copy.4": (34, 40),
                   "custom-call.5": (40, 41)})
    ms = 1e-6
    assert scopes.scope_path_ms(c, "attention") == pytest.approx(34 * ms)
    assert scopes.scope_ms(c, "attention") == pytest.approx(24 * ms)
    assert scopes.scope_path_ms(c, "kv_write") == pytest.approx(10 * ms)
    assert scopes.scope_ms(c, "kv_write") == pytest.approx(10 * ms)
    assert scopes.scope_path_ms(c, "while") == pytest.approx(30 * ms)
    assert scopes.scope_path_ms(c, "moe") == 0
    assert scopes.scope_ms(c, scopes.UNSCOPED) == pytest.approx(6 * ms)


def test_fixture_scope_paths_close_on_the_partition(ctx):
    """On the recorded step, ``attention`` with what is nested in it is
    the partition's ``attention`` and ``kv_write`` together, and the decode
    program's own path holds every scope."""
    nested = scopes.scope_path_ms(ctx, "attention")
    assert nested == pytest.approx(scopes.scope_ms(ctx, "attention")
                                   + scopes.scope_ms(ctx, "kv_write"),
                                   rel=1e-6)
    assert scopes.scope_path_ms(ctx, "jit(_step_impl)") >= sum(
        scopes.scope_ms(ctx, s) for s in scopes.SCOPES) * (1 - 1e-9)


def test_fixture_spans_nest_in_the_steps(ctx):
    red, lay = ctx.trace, ctx.layers
    assert len(red.steps) == STEPS
    for lo, hi in red.steps:
        inside = sorted((a, n, b) for n, a, b, _ in lay.spans
                        if lo <= a and b <= hi and n in scopes.HOST_SPANS)
        assert [n for _, n, _ in inside] == list(scopes.HOST_SPANS)
        assert all(b <= a2 for (_, _, b), (a2, _, _)
                   in zip(inside, inside[1:]))
    assert all(st == {"bytes": LOGITS_BYTES} for n, *_, st in lay.spans
               if n == "sched.readback")


def test_fixture_programs_and_names(ctx):
    """Every op of the decode program in the trace is named in the HLO
    text, each scope holds some of them, and a page program ran inside
    the window."""
    progs = {p for groups in ctx.layers.ops.values() for p, _ in groups}
    assert scopes.STEP_PROGRAM in progs
    assert progs & set(scopes.PAGE_PROGRAMS)
    step_ops = _step_ops(ctx)
    assert step_ops <= set(ctx.layers.scope_of)
    found = {scopes.innermost(ctx.layers.scope_of[n]) for n in step_ops
             if ctx.layers.scope_of[n] is not None}
    for scope in scopes.SCOPES:
        assert scope in found, scope


def test_a_span_outside_host_spans_keeps_its_stats(ctx):
    """Every span of the program's thread is kept with its stats, not only
    ``HOST_SPANS``: JAX's own ``PJRT_LoadedExecutable_Execute linkage``
    carries ``_pt`` and ``_p``, and ``span_stat`` reads a stat of any span
    by name."""
    name = "PJRT_LoadedExecutable_Execute linkage"
    kept = [st for n, _, _, st in ctx.layers.spans if n == name]
    assert kept and all({"_pt", "_p"} <= set(st) for st in kept)
    assert scopes.span_stat(ctx, name, "_pt") == 14
    assert scopes.span_stat(ctx, name, "no_such_stat") is None
    assert scopes.span_stat(ctx, "sched.readback", "bytes") == LOGITS_BYTES


def test_top_ops_name_each_scope_s_ops(ctx):
    """Per scope, the ops that took most device time, largest first; every
    decode-program op in the trace is named in the HLO text."""
    top = scopes.top_ops(ctx)
    assert top["unmapped"] == []
    for scope in scopes.SCOPES:
        ops = top[scope]
        assert 0 < len(ops) <= 3
        assert [ms for _, ms in ops] == sorted((ms for _, ms in ops),
                                               reverse=True)
        assert all(scopes.innermost(ctx.layers.scope_of[op]) == scope
                   for op, _ in ops)


@pytest.mark.parametrize("name", HOST + DEVICE + ("readback_bytes_per_step",))
def test_every_new_reader_reads_the_fixture(ctx, name):
    value = reader(name)(ctx)
    assert value is not None and value > 0, name
    if name == "readback_bytes_per_step":
        assert value == LOGITS_BYTES


def test_host_split_closes_on_host_ms_per_step(ctx):
    parts = sum(reader(n)(ctx) for n in HOST)
    whole = reader("host_ms_per_step")(ctx)
    assert parts == pytest.approx(whole, rel=0.05)
    assert parts <= whole


def test_device_split_closes_on_device_ms_per_step(ctx):
    parts = sum(reader(n)(ctx) for n in DEVICE)
    whole = reader("device_ms_per_step")(ctx)
    assert parts == pytest.approx(whole, rel=0.05)


def test_a_program_without_spans_leaves_the_metrics_out():
    """On the trace of a program that opens no ``sched.*`` span and a run
    that kept no HLO text (``steps.xplane.pb``), every new reader gives
    None, so the metric is left out of the run's line."""
    path = str(BENCH / "tests" / "data" / "steps.xplane.pb")
    tr = trace.load(path)
    old = types.SimpleNamespace(trace=trace.reduce(tr),
                                layers=scopes.load("", tr))
    for name in HOST + DEVICE[:-1] + ("readback_bytes_per_step",):
        assert reader(name)(old) is None, name
    assert reader("host_admit_ms")(types.SimpleNamespace(
        trace=old.trace)) is None
