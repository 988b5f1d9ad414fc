"""Measurements that set the benchmark's fixed numbers; not run by the
benchmark's own runs.

    python3 bench/calibrate.py sweep --workload <cell> --rates 2,3,4 \
        --seconds 30 --seed <n>
        One engine, the cell's traffic offered at each rate in turn: the
        queue at the window's open and close, the tails and the rate served.
        The knee is the highest rate whose queue does not grow.

    python3 bench/calibrate.py control --workload <cell> --seeds 1,2,3 \
        --seconds 20
        Per seed, in one process: the cell's own run (weights, warm-up,
        window at the cell's load), then the comparison that decides a
        run's ``correct`` (``check.judge``), once for the program and once
        with the control (the reference with float8 matmul operands) in
        the program's place, on the same sample: each with ``correct``
        and its numbers beside their limits.  The limit in the
        configuration file is set between the two readings.

Each line of output is one JSON object.  Exits 2 without a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parent
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None, *, require_tpu: bool = True) -> list:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from harness import check, drive, readers, spec
    import run
    cell = spec.load_cell(args.workload)
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("[calibrate] FAIL: no TPU", file=sys.stderr)
        sys.exit(2)
    run.enable_compile_cache()
    out = []

    def emit(row):
        out.append(row)
        print(json.dumps(row), flush=True)

    if args.what == "sweep":
        cfg, engine = drive.build_engine(cell.config, args.seed)
        for rate in [float(r) for r in args.rates.split(",")]:
            traffic = dict(cell.traffic, rate_per_s=rate, drain_s=5.0)
            s = drive.serve(cell.config, traffic, seed=args.seed,
                            seconds=args.seconds, engine=engine, cfg=cfg)
            steps = drive.window_steps(s)
            t0, t1 = s.window
            emitted = sum(1 for ts in s.tok_times.values()
                          for t in ts if t0 < t <= t1)
            ttft = readers.ttft_ms(s)
            emit({"rate_per_s": rate, "due": len(readers.window_due(s)),
                  "queued_open": s.waiting[0], "queued_close": s.waiting[1],
                  "no_first_token": sum(1 for r in readers.window_due(s)
                                        if not s.tok_times.get(r)),
                  "ttft_p50_ms": readers.percentile(ttft, 50),
                  "ttft_p95_ms": readers.percentile(ttft, 95),
                  "itl_p95_ms": readers.percentile(readers.itl_ms(s), 95),
                  "tokens_per_s": emitted / (t1 - t0),
                  "step_ms_mean": float(np.mean([b - a for _, a, b in steps])
                                        * 1e3) if steps else None,
                  "steps": len(steps)})
        return out

    for seed in [int(x) for x in args.seeds.split(",")]:
        cfg, engine = drive.build_engine(cell.config, seed)
        s = drive.serve(cell.config, cell.traffic, seed=seed,
                        seconds=args.seconds, engine=engine, cfg=cfg)
        del engine
        gc.collect()
        got = check.judge(cell.config, cfg, seed, s, control=True)
        row = {"seed": seed, "tokens": got["tokens"],
               "requests": got["requests"]}
        for who in ("program", "control"):
            correct, compared = got[who]
            row[who] = {"correct": correct, "check": compared}
        emit(row)
    return out


if __name__ == "__main__":
    main()
