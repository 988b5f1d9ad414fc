"""Split one traced window of a benchmark cell by the program's own spans
and scopes, and print the split as one JSON line.

    python3 bench/split.py --workload <cell> --seed <n> --seconds <s> \
        [--keep <dir>]

Runs the cell as ``run.py --trace 1`` does (the same engine, warm-up and
window, the window's last ``TRACE_SECONDS`` profiled), then reads per step
of the traced window: the accepted wholes ``host_ms_per_step`` and
``device_ms_per_step``; the host time of each ``sched.*`` span and the
bytes read back; the device time of each named scope of the decode
program, of its unscoped ops and of the page programs
(``harness/scopes.py``); and the device time of every program that ran.
``closure`` gives each split's sum over its whole.  ``--keep`` copies the
trace (``trace.xplane.pb``) and the decode program's HLO text
(``step.hlo.txt``) into a directory.  No correctness check runs here:
``run.py`` makes it.  Exits 2, printing nothing, without a TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402  (bench/run.py: its cache, window and readers)

TRACE_DIR = run.ROOT / "bench_out" / "split_trace"
HOST = ("host_admit_ms", "host_feed_ms", "host_readback_ms",
        "host_sample_ms", "host_release_ms")
DEVICE = ("mux_ms_per_step", "attention_ms_per_step", "kv_write_ms_per_step",
          "mlp_ms_per_step", "demux_ms_per_step", "lm_head_ms_per_step",
          "unscoped_ms_per_step", "page_programs_ms_per_step")


def main(argv=None, *, require_tpu: bool = True) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default="",
                    help="copy the trace and the HLO text to this directory")
    args = ap.parse_args(argv)

    from harness import spec
    cell = spec.load_cell(args.workload)
    import jax
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell.chips):
        run.say(f"FAIL: cell {cell.name} needs {cell.chips} TPU chip(s)")
        sys.exit(2)
    run.enable_compile_cache()

    from harness import drive, scopes, trace as tracing
    cfg, engine = drive.build_engine(cell.config, args.seed)
    held = scopes.record_step_args(engine)

    def trace_open():
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    served = drive.serve(cell.config, cell.traffic, seed=args.seed,
                         seconds=args.seconds, engine=engine, cfg=cfg,
                         on_trace_open=trace_open,
                         on_window_close=jax.profiler.stop_trace,
                         trace_s=run.TRACE_SECONDS,
                         annotate=jax.profiler.TraceAnnotation)
    path = tracing.find(str(TRACE_DIR))
    tr = tracing.load(path)
    red = tracing.reduce(tr)
    ctx = run.Context(served, setup_s=None, compile_setup_s=None, red=red,
                      peak_flops=None, n_chips=cell.chips)
    keep = pathlib.Path(args.keep) if args.keep else None
    if keep:
        keep.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, keep / "trace.xplane.pb")
    hlo = scopes.step_hlo(held)
    if keep:
        (keep / "step.hlo.txt").write_text(hlo)
    ctx.layers = scopes.load(path, hlo, tr)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)

    got = {name: run.load_reader(name)(ctx)
           for name in ("host_ms_per_step", "device_ms_per_step",
                        "readback_bytes_per_step") + HOST + DEVICE}
    programs = sorted({p for evs in ctx.layers.ops.values()
                       for _, p, _, _ in evs if p})
    result = {
        "workload": cell.name, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "steps": len(red.steps) if red else 0,
        "metrics": got,
        "programs_ms_per_step": {p: scopes.program_ms(ctx, (p,))
                                 for p in programs},
        "top_ops": _top_ops(ctx),
        "closure": {
            "host": _ratio([got[n] for n in HOST], got["host_ms_per_step"]),
            "device": _ratio([got[n] for n in DEVICE],
                             got["device_ms_per_step"])},
    }
    print(json.dumps(result), flush=True)
    return result


def _top_ops(ctx, top: int = 4) -> dict:
    """Per scope of the decode program, the ops with the most summed
    device time in the traced window (ms per step, first device), and the
    op names the HLO text does not hold (``unmapped``)."""
    from harness import scopes
    red, lay = ctx.trace, ctx.layers
    if red is None or not lay.ops:
        return {}
    evs = lay.ops[sorted(lay.ops)[0]]
    lo, hi = red.window
    per: dict = {}
    unmapped = set()
    for name, prog, a, b in evs:
        if prog != scopes.STEP_PROGRAM or a < lo or b > hi:
            continue
        if name not in lay.scope_of:
            unmapped.add(name)
        scope = lay.scope_of.get(name) or "container"
        per.setdefault(scope, {}).setdefault(name, 0.0)
        per[scope][name] += (b - a) * 1e-6 / len(red.steps)
    out = {s: sorted(ops.items(), key=lambda kv: -kv[1])[:top]
           for s, ops in per.items()}
    out["unmapped"] = sorted(unmapped)[:20]
    return out


def _ratio(parts: list, whole):
    if whole is None or any(p is None for p in parts) or not whole:
        return None
    return sum(parts) / whole


if __name__ == "__main__":
    main()
