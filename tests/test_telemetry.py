"""Serving telemetry layer (PR 8): lifecycle spans, metrics, export.

The contract under test, in order of importance:

  1. zero interference — the same trace produces bitwise-identical tokens
     and step counts with telemetry on and off (bare scheduler and the
     preempting replica-router path);
  2. fidelity — replaying a fixed trace, the span sequence per request
     reconstructs the scheduler's own canonical record exactly (submit at
     arrival, admit at ``admitted_step``, first_token at
     ``arrival + ttft``, retire at ``finished_step``, one preempt/resume
     pair per park);
  3. export — the Chrome/Perfetto JSON and metrics JSONL pass the same
     schema check CI runs (``tools/check_trace.py``);
  4. naming — ``Request.ttft`` is the single latency source;
     ``first_token_step`` stays as a deprecated alias pinned equal.
"""
import dataclasses
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MuxConfig, ServingConfig
from repro.models import Backbone
from repro.serving.engine import Engine
from repro.serving.router import ReplicaRouter
from repro.serving.scheduler import (ContinuousScheduler, Request,
                                     poisson_trace)
from repro.serving.telemetry import (NULL_TRACER, NullTracer, Tracer,
                                     as_scope, page_pool_timeline,
                                     trace_summary, ttft_histogram)

CFG = ModelConfig(
    name="telemetry-tiny", family="dense", n_layers=2, d_model=64,
    n_heads=2, n_kv_heads=2, d_ff=128, vocab=128, dtype="float32",
    param_dtype="float32", remat="none",
    mux=MuxConfig(n=2, strategy="hadamard", demux="index_embed"))
PARAMS = Backbone.init(jax.random.PRNGKey(0), CFG)
N_SLOTS = 2


def _check_trace_module():
    """Import tools/check_trace.py (not a package) by path."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                        "check_trace.py")
    spec = importlib.util.spec_from_file_location("check_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build(tracer=None, *, preempt=False, policy="fifo", max_len=60):
    serving = ServingConfig(paged=True, page_size=4,
                            policy="slo" if preempt else policy,
                            preempt=preempt)
    cfg = dataclasses.replace(CFG, serving=serving)
    eng = Engine(PARAMS, cfg, batch=N_SLOTS, max_len=max_len)
    return ContinuousScheduler(eng, tracer=tracer)


def _preempt_trace():
    """Deterministic park/resume: long batch generations saturate both
    slots, then a latency burst arrives on the full grid."""
    rng = np.random.default_rng(0)
    victims = [Request(rid=i,
                       prompt=rng.integers(0, CFG.vocab, 3).astype(np.int32),
                       max_new_tokens=12, slo="batch")
               for i in range(N_SLOTS * CFG.mux.n)]
    burst = [Request(rid=100 + i,
                     prompt=rng.integers(0, CFG.vocab, 3).astype(np.int32),
                     max_new_tokens=3, arrival=3, slo="latency")
             for i in range(2)]
    return victims + burst


def _outputs(sched):
    return {q.rid: list(q.output) for q in sched.finished}


def test_traced_scheduler_bitwise_identical():
    trace = poisson_trace(10, rate=2.0, prompt_len=3, gen_len=5,
                          vocab=CFG.vocab, max_total=30, seed=0)
    plain = _build()
    s_plain = plain.run([r.fresh() for r in trace])
    tracer = Tracer()
    traced = _build(tracer)
    s_traced = traced.run([r.fresh() for r in trace])
    assert _outputs(plain) == _outputs(traced)
    assert s_plain.decode_steps == s_traced.decode_steps
    assert s_plain.generated_tokens == s_traced.generated_tokens
    assert tracer.lifecycle_errors() == []
    assert len(tracer.events) > 0


def test_traced_router_preempt_bitwise_identical():
    """The acceptance path: a preempt + router serve traced vs untraced."""
    trace = poisson_trace(16, rate=4.0, prompt_len=3, gen_len=5,
                          vocab=CFG.vocab, max_total=30, seed=1,
                          slo_mix=0.25)
    serving = ServingConfig(paged=True, page_size=4, policy="slo",
                            preempt=True)
    cfg = dataclasses.replace(CFG, serving=serving)

    def run(tracer):
        router = ReplicaRouter.build(PARAMS, cfg, batch=N_SLOTS, max_len=60,
                                     replicas=2, policy="least_loaded",
                                     tracer=tracer)
        stats = router.run([r.fresh() for r in trace])
        return _outputs(router), stats

    out_plain, s_plain = run(None)
    tracer = Tracer()
    out_traced, s_traced = run(tracer)
    assert out_plain == out_traced
    assert s_plain.decode_steps == s_traced.decode_steps
    assert s_plain.router_steps == s_traced.router_steps
    assert tracer.lifecycle_errors() == []
    # one dispatch span origin per admitted request, opened at the router
    dispatched = [e for e in tracer.events if e.kind == "dispatch"]
    assert len(dispatched) == len(trace)
    assert all(e.replica < 0 for e in dispatched)  # emitted by router scope


def test_span_sequence_matches_scheduler_log():
    """Replay a fixed preempting trace: the spans must reconstruct the
    scheduler's own canonical per-request record exactly."""
    tracer = Tracer()
    sched = _build(tracer, preempt=True)
    stats = sched.run([r.fresh() for r in _preempt_trace()])
    assert stats.preemptions > 0, "fixture no longer preempts"
    assert tracer.lifecycle_errors() == []
    for q in sched.finished:
        log = tracer.request_log(q.rid)
        kinds = [e.kind for e in log]
        assert kinds[0] == "submit" and log[0].ts == q.arrival
        assert kinds[-1] == "retire" and log[-1].ts == q.finished_step
        admit = next(e for e in log if e.kind == "admit")
        assert admit.ts == q.admitted_step
        first = next(e for e in log if e.kind == "first_token")
        assert first.ts == q.arrival + q.ttft
        assert sum(k == "preempt" for k in kinds) == q.preempted
        assert sum(k == "resume" for k in kinds) == q.preempted
        retire = log[-1]
        assert retire.args["tokens"] == len(q.output) == q.max_new_tokens
    # park/resume traffic also hit the swap ledger events
    assert any(e.kind == "swap_out" for e in tracer.events)
    assert any(e.kind == "swap_in" for e in tracer.events)


def test_chrome_trace_and_metrics_pass_schema_check(tmp_path):
    check = _check_trace_module()
    tracer = Tracer()
    sched = _build(tracer, preempt=True)
    sched.run([r.fresh() for r in _preempt_trace()])
    trace_path = str(tmp_path / "t.trace.json")
    metrics_path = str(tmp_path / "m.jsonl")
    n = tracer.export_chrome(trace_path)
    tracer.metrics.write_jsonl(metrics_path)
    assert n > 0
    assert check.check_trace(trace_path) == []
    assert check.check_metrics(metrics_path) == []
    # spot-check the span tree: every traced request has one async begin
    # and one async end of its top-level span
    doc = json.load(open(trace_path))
    for rid in tracer.request_ids():
        opens = [e for e in doc["traceEvents"]
                 if e["ph"] == "b" and e.get("id") == str(rid)
                 and e["name"] == f"request {rid}"]
        closes = [e for e in doc["traceEvents"]
                  if e["ph"] == "e" and e.get("id") == str(rid)
                  and e.get("name") == f"request {rid}"]
        assert len(opens) == 1 and len(closes) == 1
        assert closes[0]["ts"] >= opens[0]["ts"]


def test_metrics_rows_and_summary():
    tracer = Tracer()
    sched = _build(tracer, preempt=True)
    stats = sched.run([r.fresh() for r in _preempt_trace()])
    steps = [r["step"] for r in tracer.metrics.rows]
    assert steps == sorted(steps) and len(steps) > 0
    assert all(k == "step" or k.startswith("r0/")
               for r in tracer.metrics.rows for k in r)
    # the per-step gauges end at the run's own totals
    last = tracer.metrics.rows[-1]
    assert last["r0/generated_tokens"] == stats.generated_tokens
    assert last["r0/decode_steps"] == stats.decode_steps
    # trace-derived summaries: TTFT histogram covers every finished
    # request; the page-pool high-water equals the scheduler's peak
    hist = ttft_histogram(tracer)
    assert sum(hist.values()) == len(sched.finished)
    pool = page_pool_timeline(tracer)
    assert pool["high_water"] == stats.peak_pages
    summary = trace_summary(tracer)
    assert summary["events"] == len(tracer.events)
    assert summary["ttft_hist"] == hist


def test_null_tracer_is_inert_default():
    sched = _build()
    assert not sched.tracer.enabled
    assert sched.allocator.tracer is sched.tracer
    assert as_scope(None) is NULL_TRACER
    assert isinstance(NULL_TRACER, NullTracer)
    # events/metrics sinks are no-ops: nothing accumulates anywhere
    NULL_TRACER.event("slot_step", slot=0)
    NULL_TRACER.metrics.count("x")
    NULL_TRACER.snap(3)


def test_first_token_step_is_deprecated_alias():
    trace = poisson_trace(4, rate=2.0, prompt_len=3, gen_len=4,
                          vocab=CFG.vocab, max_total=20, seed=2)
    sched = _build()
    sched.run([r.fresh() for r in trace])
    assert sched.finished
    for q in sched.finished:
        assert q.ttft >= 0
        with pytest.warns(DeprecationWarning):
            assert q.first_token_step == q.arrival + q.ttft
    unfinished = Request(rid=99, prompt=np.zeros(2, np.int32),
                         max_new_tokens=2)
    with pytest.warns(DeprecationWarning):
        assert unfinished.first_token_step == -1


# ---------------------------------------------------------------------------
# Host spans and device scopes: what a profile of the serving path shows
# ---------------------------------------------------------------------------

SCHED_SPANS = ("sched.admit", "sched.feed", "sched.readback", "sched.sample",
               "sched.release")
DEVICE_SCOPES = ("mux", "attention", "kv_write", "mlp", "demux", "lm_head")


def _profile(tmp_path, fn):
    """Run ``fn`` under the profiler (host spans only); return the events
    of the python thread as (name, wall start ns, duration ns, stats) and
    ``fn``'s result."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(path))
    origin = next(dict(p.stats)["profile_start_time"] for p in data.planes
                  if p.name == "Task Environment")
    events = [(e.name, origin + e.start_ns, e.duration_ns, dict(e.stats))
              for p in data.planes if p.name == "/host:CPU"
              for line in p.lines if line.name.startswith("python")
              for e in line.events]
    return events, out


def test_scheduler_step_spans_in_a_profile(tmp_path):
    """Profiled on the CPU, each step of the tiny paged scheduler shows
    the five ``sched.*`` spans once, inside the caller's span and in
    order, and ``sched.readback``'s ``bytes`` is the logits' size."""
    sched = _build()
    rng = np.random.default_rng(3)
    for i in range(6):
        sched.submit(Request(
            rid=i, prompt=rng.integers(0, CFG.vocab, 3).astype(np.int32),
            max_new_tokens=4))
    n_steps = 5

    def steps():
        for _ in range(n_steps):
            with jax.profiler.TraceAnnotation("step"):
                sched.step()

    events, _ = _profile(tmp_path, steps)
    outer = sorted((a, a + d) for n, a, d, _ in events if n == "step")
    assert len(outer) == n_steps
    logits_bytes = N_SLOTS * CFG.mux.n * CFG.vocab * 4      # f32 logits
    for lo, hi in outer:
        inside = sorted((a, n, d, st) for n, a, d, st in events
                        if n in SCHED_SPANS and lo <= a and a + d <= hi)
        assert [n for _, n, _, _ in inside] == list(SCHED_SPANS)
        ends = [a + d for a, _, d, _ in inside]
        starts = [a for a, _, _, _ in inside]
        assert all(e <= s for e, s in zip(ends, starts[1:]))
        assert inside[2][3] == {"bytes": logits_bytes}
    assert not [n for n, a, _, _ in events if n in SCHED_SPANS
                and not any(lo <= a <= hi for lo, hi in outer)]


def test_tracer_span_agrees_with_the_profiler(tmp_path):
    """A ``Tracer`` span and the profiler's annotation it enters time the
    same sleep on the same clock, within a millisecond."""
    import time
    scope = Tracer().scope(0)

    def sleep():
        with scope.span("probe", bytes=7):
            time.sleep(0.02)

    events, _ = _profile(tmp_path, sleep)
    (_, start, dur, stats), = [e for e in events if e[0] == "probe"]
    (ev,) = [e for e in scope.tracer.events if e.kind == "probe"]
    assert stats == {"bytes": 7} and ev.args == {"bytes": 7}
    assert abs(ev.wall_ns - start) < 1e6
    assert abs(ev.dur_ns - dur) < 1e6
    assert ev.dur_ns >= 0.02e9


def test_null_tracer_span_keeps_nothing():
    before = dict(vars(NULL_TRACER))
    with NULL_TRACER.span("sched.readback", bytes=1):
        pass
    assert vars(NULL_TRACER) == before
    assert isinstance(NULL_TRACER.span("x"), jax.profiler.TraceAnnotation)


def test_traced_steps_export_spans_on_the_wall_clock(tmp_path):
    """With a ``Tracer`` attached, every step keeps its five spans as
    events, and the Chrome export puts them, in order, on the scheduler
    thread, with each slot's step inside its step's span bounds."""
    check = _check_trace_module()
    tracer = Tracer()
    sched = _build(tracer)
    stats = sched.run([r.fresh() for r in _preempt_trace()[:4]])
    spans = [e for e in tracer.events if e.dur_ns is not None]
    assert [e.kind for e in spans] == list(SCHED_SPANS) * stats.decode_steps
    path = str(tmp_path / "t.trace.json")
    tracer.export_chrome(path)
    assert check.check_trace(path) == []
    doc = json.load(open(path))["traceEvents"]
    host = [e for e in doc if e.get("cat") == "host"]
    assert [e["name"] for e in host] == [e.kind for e in spans]
    assert all(e["tid"] == 0 and e["ph"] == "X" for e in host)
    assert all(a["ts"] + a["dur"] <= b["ts"] + 1e-3
               for a, b in zip(host, host[1:]))
    slots = [e for e in doc if e.get("cat") == "step"]
    assert slots
    first, last = host[0]["ts"], host[-1]["ts"] + host[-1]["dur"]
    assert all(first <= e["ts"] and e["ts"] + e["dur"] <= last + 1e-3
               for e in slots)


def test_decode_step_hlo_names_every_scope():
    """The tiny engine's compiled decode step carries each named scope in
    the metadata of at least one instruction."""
    import re
    sched = _build()
    sched.submit(Request(rid=0, prompt=np.arange(3, dtype=np.int32),
                         max_new_tokens=2))
    held = {}
    inner = sched.engine._step

    def step(*args):
        held["args"] = args
        return inner(*args)

    sched.engine._step = step
    sched.step()
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            held["args"])
    text = inner.lower(*abstract).compile().as_text()
    paths = [p.split("/") for p in re.findall(r'op_name="([^"]*)"', text)]
    for scope in DEVICE_SCOPES:
        assert any(scope in p for p in paths), scope
