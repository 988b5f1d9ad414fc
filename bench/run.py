"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by name
from ``BENCHMARK.json`` at the root of the checkout.  A run builds the
program's serving path from seeded weights, warms it up, measures for
``--seconds`` seconds, checks what the timed path served against the plain
float32 reference, and prints as its last line one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end metrics
with ``--trace 0``, the per-layer ones with ``--trace 1``), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``check``: each number
compared, with its limit.  A ``--trace 1`` run profiles the last ten
seconds of its window and hands the readers the program's spans and the
decode step's scopes (``harness/scopes.py``).

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for: there is no CPU fallback.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = ROOT / "bench_out" / "jax_cache"
TRACE_DIR = ROOT / "bench_out" / "trace"
# The profiler keeps a bounded number of device events: a 51 s trace of a
# qwen1.5-4b window held device operations for its first 28 s only (TPU v5
# lite).  So a --trace 1 run traces the window's last TRACE_SECONDS.
TRACE_SECONDS = 10.0


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_reader(name: str):
    """``bench/metrics/<name>.py``; a name split by kind of cell
    (``host_ms_per_step.chat``) falls back to the reader of the whole
    quantity (``host_ms_per_step.py``)."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = BENCH / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed place inside the checkout that only
    the benchmark writes, whatever the environment names, so that two
    checkouts share nothing; every program is cached, so only the first run
    of a cell compiles."""
    import jax
    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Context:
    """What the metric readers read: the run (``served``), its set-up
    times, the chip's peaks, and in a ``--trace 1`` run the trace's
    reduction (``trace``) and the program's spans and scopes (``layers``,
    ``harness/scopes.py``)."""

    def __init__(self, served, setup_s, compile_setup_s, red, peak_flops,
                 n_chips, peak_hbm=None, layers=None):
        self.served = served
        self.setup_s = setup_s
        self.compile_setup_s = compile_setup_s
        self.trace = red
        self.peak_flops = peak_flops
        self.peak_hbm = peak_hbm
        self.n_chips = n_chips
        self.layers = layers


def main(argv=None, *, require_tpu: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        say(f"FAIL: cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
            f"finds {len(devices)} {devices[0].platform} device(s). There is "
            f"no CPU fallback.")
        sys.exit(2)
    say(f"device: {devices[0].device_kind} x {len(devices)}; compile cache "
        f"{enable_compile_cache()}")

    from harness import check, drive, peaks, scopes, trace as tracing
    from harness.compile_clock import CompileClock
    clock = CompileClock()
    cfg, engine = drive.build_engine(cell.config, args.seed)
    # A traced run keeps the decode step's arguments, to read the scopes
    # of the program that ran; an untraced run leaves the engine as it is.
    held = scopes.record_step_args(engine) if args.trace else None

    annotate = jax.profiler.TraceAnnotation
    marks: dict = {}

    def window_open():
        marks["open"] = (time.perf_counter(), clock.seconds, clock.count,
                         clock.misses)

    def trace_open():
        if args.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    def window_close():
        marks["close"] = (time.perf_counter(), clock.seconds, clock.count,
                          clock.misses)
        if args.trace:
            jax.profiler.stop_trace()

    served = drive.serve(cell.config, cell.traffic, seed=args.seed,
                         seconds=args.seconds, engine=engine, cfg=cfg,
                         on_window_open=window_open,
                         on_window_close=window_close,
                         on_trace_open=trace_open, trace_s=TRACE_SECONDS,
                         annotate=annotate)
    t_open, c_open, n_open, m_open = marks["open"]
    _, _, n_close, m_close = marks["close"]
    say(f"window: {served.window[1] - served.window[0]:.3f} s, "
        f"{len(drive.window_steps(served))} steps; requests queued at open "
        f"{served.waiting[0]}, at close {served.waiting[1]}; compiles or "
        f"cache loads inside it: {n_close - n_open} "
        f"({m_close - m_open} compiled)")
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices[:cell.chips])

    red = breakdown = layers = None
    if args.trace:
        t = time.perf_counter()
        tr = tracing.load(tracing.find(str(TRACE_DIR)))
        red = tracing.reduce(tr)
        t_read = time.perf_counter()
        if red is None:
            say("the trace holds no device operations")
        else:
            breakdown = tracing.breakdown(tr, red)
            hlo = scopes.step_hlo(held)
            t_hlo = time.perf_counter()
            layers = scopes.load(hlo, tr)
            say(f"trace: read {t_read - t:.2f} s, decode program's HLO "
                f"{t_hlo - t_read:.2f} s, ops by program and scope "
                f"{time.perf_counter() - t_hlo:.2f} s")
        del tr
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    # Peaks of a chip not in the table are an error; a run without the chip
    # has none, unless a test puts its device in the table.
    kind = devices[0].device_kind
    chip = peaks.peaks(kind) if devices[0].platform == "tpu" \
        else peaks.PEAKS.get(kind)
    ctx = Context(served, setup_s=served.window[0] - T_PROCESS,
                  compile_setup_s=c_open, red=red,
                  peak_flops=chip and chip.bf16_flops,
                  peak_hbm=chip and chip.hbm_bytes,
                  n_chips=cell.chips, layers=layers)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    t = time.perf_counter()
    for m in wanted:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if layers is not None:
        say(f"readers: {time.perf_counter() - t:.2f} s; top ops of each "
            f"scope, ms per step: {json.dumps(scopes.top_ops(ctx))}")
    attempted, failed = attempts(served)

    # The program's state goes before the reference runs.
    del engine, held, ctx, layers
    gc.collect()
    got = check.judge(cell.config, cfg, args.seed, served)
    correct, compared = got["program"]
    say(f"check: {got['tokens']} served tokens of {got['requests']} "
        f"requests replayed; reference argmax share "
        f"{got['argmax_share']:.4f}")
    for name, c in compared.items():
        say(f"check: {name} {c['value']} limit {c['limit']}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}
    if red is not None:
        device["busy_s"] = red.mean_busy_s()
        device["window_s"] = red.window_s
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = compared
    print(json.dumps(result), flush=True)
    return result


def attempts(served) -> tuple:
    """Requests the window held, and those of them that failed: with arrivals,
    those due in the window (failed: no first token by the run's end); with
    a backlog, those that emitted a token in it."""
    from harness import readers
    t0, t1 = served.window
    if served.traffic["arrival"] == "backlog":
        rids = [r for r, ts in served.tok_times.items()
                if any(t0 < t <= t1 for t in ts)]
        return len(rids), served.refused
    due = readers.window_due(served)
    failed = sum(1 for r in due if not served.tok_times.get(r))
    return len(due), failed + served.refused


if __name__ == "__main__":
    main()
