"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/make_trace_fixture.py <out_dir>

Run on one TPU chip: five harness ``step`` spans, each a jitted matmul
chain waited on, separated by ``wait_arrival`` sleeps of 20 ms, under the
profiler.  Copies the ``.xplane.pb`` to ``<out_dir>/steps.xplane.pb`` and
prints the planes and lines it holds and what the reduction reads from it.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def main(out: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    from harness import trace

    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as tmp:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        for _ in range(5):
            with jax.profiler.TraceAnnotation("wait_arrival"):
                time.sleep(0.02)
            with jax.profiler.TraceAnnotation("step"):
                f(x).block_until_ready()
        jax.profiler.stop_trace()
        path = pathlib.Path(out)
        path.mkdir(parents=True, exist_ok=True)
        shutil.copy(trace.find(tmp), path / "steps.xplane.pb")
    data = ProfileData.from_file(str(path / "steps.xplane.pb"))
    for plane in data.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("   line", line.name, len(evs),
                  [e.name for e in evs[:3]])
    tr = trace.load(str(path / "steps.xplane.pb"))
    red = trace.reduce(tr)
    print("window_s", red.window_s, "busy_s", red.mean_busy_s(),
          "steps", len(red.steps), "step_device_s", red.step_device_s(),
          "step_host_s", red.step_host_s())
    print("breakdown", trace.breakdown(tr, red))


if __name__ == "__main__":
    main(sys.argv[1])
