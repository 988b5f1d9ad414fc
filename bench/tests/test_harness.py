"""The harness end to end on the CPU at smoke size, and how it refuses to
run without a chip or without the program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, add_cell, last_json, run_cell


def _plain_run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _plain_run(ROOT, "--workload", "qwen-chat-poisson", "--seed",
                      "3000000001", "--seconds", "1", "--trace", "0")
    assert proc.returncode == 2
    assert "no CPU fallback" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    proc = run_cell(tmp_path, "--workload", "qwen-batch-backlog",
                    "--seed", "5", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert "repro" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_chat_cell_end_to_end(checkout):
    out = last_json(run_cell(checkout, "--workload", "tiny-chat", "--seed",
                             str(2**31 + 77), "--seconds", "2",
                             "--trace", "0"))
    assert out["correct"] is True
    assert set(out["metrics"]) == {"ttft_p95_ms", "ttft_p50_ms",
                                   "itl_p95_ms", "setup_s"}
    assert out["attempted"] > 10 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    assert list(out)[-1] == "check"
    assert out["check"]["logit_gap"]["value"] <= \
        out["check"]["logit_gap"]["limit"]


def test_batch_cell_end_to_end(checkout):
    out = last_json(run_cell(checkout, "--workload", "tiny-batch", "--seed",
                             "11", "--seconds", "2", "--trace", "0"))
    assert out["correct"] is True
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert out["metrics"]["tokens_per_s"]["value"] > 0


# Stopping a profile stalls the loop at the window's close for longer than
# the tiny cell's 5 s drain.
SLOW_PROFILE_STOP = """
import time
import jax.profiler
_stop = jax.profiler.stop_trace
def stop_trace():
    time.sleep(6.0)
    _stop()
jax.profiler.stop_trace = stop_trace
"""


def test_slow_profile_stop_leaves_the_drain_whole(checkout):
    out = last_json(run_cell(checkout, "--workload", "tiny-chat", "--seed",
                             "51", "--seconds", "2", "--trace", "1",
                             patch=SLOW_PROFILE_STOP))
    assert out["attempted"] > 10 and out["failed"] == 0


def test_new_cell_from_new_files_only(checkout, tmp_path):
    """A later change adds a configuration, a traffic mix, a per-layer
    metric and a plain reference, each in a file of its own, and entries in
    BENCHMARK.json; the harness runs the new cell unedited."""
    ck = tmp_path / "ck"
    shutil.copytree(checkout, ck, symlinks=True,
                    ignore=shutil.ignore_patterns(".jax_cache", "bench_out"))
    before = {p: p.read_bytes() for p in (ck / "bench").rglob("*.py")}
    refs = ck / "bench" / "references"
    (refs / "dense_again.py").write_text((refs / "dense.py").read_text())
    (ck / "bench" / "metrics" / "admitted_per_step.py").write_text(
        "def read(ctx):\n"
        "    s = ctx.served\n"
        "    t0, t1 = s.window\n"
        "    n = sum(1 for r in s.requests.values()\n"
        "            if r.admitted_step >= 0 and t0 <= s.due[r.rid] < t1)\n"
        "    steps = sum(1 for _, a, b in s.steps if a >= t0 and b <= t1)\n"
        "    return n / steps if steps else None\n")
    config = json.loads(
        (ck / "bench" / "configs" / "tiny-dense.json").read_text())
    config.update(name="tiny-dense-again", reference="dense_again")
    traffic = json.loads(
        (ck / "bench" / "traffic" / "tiny-chat.json").read_text())
    traffic["rate_per_s"] = 30
    add_cell(ck, name="tiny-chat-again", config=config,
             traffic_name="tiny-chat-30", traffic=traffic, like="tiny-chat")
    b = json.loads((ck / "BENCHMARK.json").read_text())
    b["per_layer"].append({"name": "admitted_per_step", "unit": "1/step",
                           "better": "higher", "source": "program_counter",
                           "layer": "scheduler", "moves": "ttft_p95_ms",
                           "workloads": ["tiny-chat-again"]})
    (ck / "BENCHMARK.json").write_text(json.dumps(b))
    assert all(p.read_bytes() == v for p, v in before.items())

    out = last_json(run_cell(ck, "--workload", "tiny-chat-again", "--seed",
                             "21", "--seconds", "2", "--trace", "1"))
    assert out["correct"] is True
    assert out["metrics"]["admitted_per_step"]["value"] > 0
    assert "queue_wait_p95_ms" in out["metrics"]
    assert "compile_s" in out["metrics"]


# The trace branch of run.main on the CPU with the chip's trace substituted:
# the small TPU trace and decode-program HLO of test_scopes.py stand in for
# the run's own, and the CPU is given the v5e's peaks.
TPU_TRACE = """
from harness import peaks, scopes, trace
trace.find = lambda log_dir: {xplane!r}
scopes.step_hlo = lambda held: open({hlo!r}).read()
peaks.PEAKS["cpu"] = peaks.PEAKS["TPU v5 lite"]
""".format(xplane=str(BENCH / "tests" / "data" / "layers.xplane.pb"),
           hlo=str(BENCH / "tests" / "data" / "layers.hlo.txt"))


@pytest.mark.parametrize("cell", ["tiny-batch", "tiny-chat"])
def test_trace_run_hands_every_reader_the_layers(checkout, cell):
    """A ``--trace 1`` run gives the readers the program's spans and the
    decode step's scopes: every per-layer metric that BENCHMARK.json gives
    the cell reads a number."""
    proc = run_cell(checkout, "--workload", cell, "--seed", "2147483999",
                    "--seconds", "2", "--trace", "1", patch=TPU_TRACE)
    out = last_json(proc)
    assert out["correct"] is True
    from harness import spec
    want = {m["name"] for m in spec.load_cell(cell, checkout).per_layer}
    assert set(out["metrics"]) == want, want ^ set(out["metrics"])
    assert all(isinstance(m["value"], (int, float))
               for m in out["metrics"].values())
    assert "ops by program and scope" in proc.stderr
    assert out["device"]["busy_s"] > 0


def test_new_scope_and_span_readers_from_new_files_only(checkout, tmp_path):
    """A later change reads a nested scope and a span's stat with a reader
    file each and entries in BENCHMARK.json; the harness runs unedited."""
    ck = tmp_path / "ck"
    shutil.copytree(checkout, ck, symlinks=True,
                    ignore=shutil.ignore_patterns(".jax_cache", "bench_out"))
    before = {p: p.read_bytes() for p in (ck / "bench").rglob("*.py")}
    metrics = ck / "bench" / "metrics"
    (metrics / "attention_nested_ms_per_step.py").write_text(
        "from harness import scopes\n\n\n"
        "def read(ctx):\n"
        "    return scopes.scope_path_ms(ctx, 'attention')\n")
    (metrics / "execute_linkage_pt.py").write_text(
        "from harness import scopes\n\n\n"
        "def read(ctx):\n"
        "    return scopes.span_stat(\n"
        "        ctx, 'PJRT_LoadedExecutable_Execute linkage', '_pt')\n")
    b = json.loads((ck / "BENCHMARK.json").read_text())
    for name, unit in (("attention_nested_ms_per_step", "ms"),
                       ("execute_linkage_pt", "1")):
        b["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                               "source": "device_trace", "layer": "kernels",
                               "moves": "tokens_per_s",
                               "workloads": ["tiny-batch"]})
    (ck / "BENCHMARK.json").write_text(json.dumps(b))
    assert all(p.read_bytes() == v for p, v in before.items())

    out = last_json(run_cell(ck, "--workload", "tiny-batch", "--seed", "23",
                             "--seconds", "2", "--trace", "1",
                             patch=TPU_TRACE))
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert got["execute_linkage_pt"] == 14
    assert got["attention_nested_ms_per_step"] == pytest.approx(
        got["attention_ms_per_step"] + got["kv_write_ms_per_step"], rel=1e-6)


def _metrics():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m["name"] for m in b["end_to_end"] + b["per_layer"]]


@pytest.mark.parametrize("name", _metrics())
def test_every_metric_has_a_reader(name):
    """A metric split by kind of cell (``host_ms_per_step.chat``) is read
    by the reader of the whole quantity."""
    import run
    assert callable(run.load_reader(name))
