"""Record the small TPU trace that ``test_scopes.py`` splits.

    python3 bench/tests/make_layers_fixture.py <out_dir>

Run on one TPU chip: the tiny dense configuration of the benchmark's own
tests (``conftest.TINY_DENSE``: bf16, 2 layers, N = 4 lanes, 2 slots,
4-position pages) served by ``ContinuousScheduler`` on the benchmark's
seeded weights.  After the harness's warm-up of every program, 24 short
requests are queued and twelve harness ``step`` spans, each around one
``sched.step()``, run between ``wait_arrival`` sleeps of 5 ms under the
profiler; slots cross page boundaries inside the window, so the page
invalidate program runs there too.  Writes ``<out_dir>/layers.xplane.pb``
and ``<out_dir>/layers.hlo.txt`` (the decode program's optimized HLO) and
prints the planes and programs the trace holds and the split read from it.
"""
from __future__ import annotations

import collections
import pathlib
import shutil
import sys
import tempfile
import time
import types

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src"), str(HERE)]

STEPS = 12
PAGE_SIZE = 4


def main(out: str) -> None:
    import jax
    import numpy as np
    from jax.profiler import ProfileData, TraceAnnotation

    from conftest import MUX, TINY_DENSE
    from harness import drive, scopes, spec, trace, weights
    from repro.serving.engine import Engine
    from repro.serving.scheduler import ContinuousScheduler, Request

    cfg = spec.model_config({"name": "tiny-dense", "model": TINY_DENSE,
                             "mux": MUX, "serving": {
                                 "paged": True, "page_size": PAGE_SIZE}})
    engine = Engine(weights.make(cfg, 0), cfg, batch=2, max_len=96)
    sched = ContinuousScheduler(engine)
    drive.warm_programs(sched)
    held = scopes.record_step_args(engine)
    rng = np.random.default_rng(0)
    for rid in range(24):
        sched.submit(Request(
            rid=rid, prompt=rng.integers(0, cfg.vocab, 3).astype(np.int32),
            max_new_tokens=3))
    sched.step()                  # admission's first shapes, outside
    jax.block_until_ready(sched.allocator.cache)
    path = pathlib.Path(out)
    path.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        for _ in range(STEPS):
            with TraceAnnotation("wait_arrival"):
                time.sleep(0.005)
            with TraceAnnotation("step"):
                sched.step()
        jax.profiler.stop_trace()
        shutil.copy(trace.find(tmp), path / "layers.xplane.pb")
    (path / "layers.hlo.txt").write_text(scopes.step_hlo(held))

    xplane = str(path / "layers.xplane.pb")
    data = ProfileData.from_file(xplane)
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("   line", line.name, len(evs),
                  [e.name[:60] for e in evs[:3]])
    tr = trace.load(xplane)
    red = trace.reduce(tr)
    lay = scopes.load((path / "layers.hlo.txt").read_text(), tr)
    print("programs", collections.Counter(
        p for groups in lay.ops.values() for (p, _), xs in groups.items()
        for _ in xs))
    print("scopes in the HLO", collections.Counter(
        scopes.innermost(p) for p in lay.scope_of.values() if p is not None))
    ctx = types.SimpleNamespace(trace=red, layers=lay)
    print("steps", len(red.steps), "step_device_s", red.step_device_s(),
          "step_host_s", red.step_host_s())
    for span in scopes.HOST_SPANS:
        print(span, scopes.host_ms(ctx, span))
    print("readback bytes",
          scopes.span_stat(ctx, "sched.readback", "bytes"))
    for scope in scopes.SCOPES + (scopes.UNSCOPED,):
        print(scope, scopes.scope_ms(ctx, scope))
    print("page programs", scopes.program_ms(ctx, scopes.PAGE_PROGRAMS))


if __name__ == "__main__":
    main(sys.argv[1])
