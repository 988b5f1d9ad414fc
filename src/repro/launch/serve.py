"""Serving launcher: batched multiplexed decode on the devices present.

Full width on one chip (qwen1.5-4b's published widths, seeded random
weights; ``chip_smoke.py`` drives exactly this):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b --mux-n 8 \
        --workload poisson --paged --page-size 128 --num-requests 16 \
        --prompt-len 128 --gen 32

Lock-step grid (the classic fixed-(B, N) wave):

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --smoke \
        --device-count 4 --mesh-shape 2,2 --mux-n 4 --gen 16

Continuous batching (stream-level admission/retirement over the slot
scheduler — replays a Poisson arrival trace with mixed prompt/generation
lengths and reports the step count against the static baseline):

    PYTHONPATH=src python -m repro.launch.serve --smoke --workload poisson \
        --gen 8

Paged KV cache (block tables over a shared page pool; admission checks free
pages instead of slot depth, so the long-tail generation that a contiguous
allocator refuses is admitted):

    PYTHONPATH=src python -m repro.launch.serve --smoke --workload poisson \
        --paged --gen 8

Chunked prefill (an admitted prompt feeds up to C tokens per decode step,
so its lane reaches the first generated token in ~Lp/C steps instead of Lp;
the slot's other lanes keep decoding one token per step):

    PYTHONPATH=src python -m repro.launch.serve --smoke --workload poisson \
        --paged --prefill-chunk 4 --gen 8

SLO classes + preempt-and-swap (earliest-deadline-first admission over a
two-class trace; a latency-class request arriving on a full grid parks a
batch-class slot in the swap ledger — the victim resumes later with
bitwise-identical continuation tokens — and ``--report`` prints TTFT
percentiles and per-class deadline attainment):

    PYTHONPATH=src python -m repro.launch.serve --smoke --workload poisson \
        --paged --policy slo --preempt --slo-mix 0.25 --report --gen 8

Replica router (multi-engine tier: R independent engine+scheduler replicas
behind one front door, requests dispatched by a pluggable routing policy,
stats aggregated across the fleet; without ``--mesh-shape`` replica i
lives on device i mod the device count):

    PYTHONPATH=src python -m repro.launch.serve --smoke --workload poisson \
        --replicas 2 --router-policy least_loaded --report --gen 8
"""
import argparse
import contextlib
import os
import time


def _make_tracer(args):
    """A live ``Tracer`` when any telemetry sink is requested, else None —
    the scheduler/router then run with the NULL_TRACER default (the
    zero-overhead untraced path)."""
    if not (args.trace or args.metrics):
        return None
    from repro.serving.telemetry import Tracer
    return Tracer()


def _export_telemetry(args, tracer) -> None:
    if tracer is None:
        return
    if args.trace:
        n = tracer.export_chrome(args.trace)
        print(f"[serve] trace: {n} traceEvents -> {args.trace} "
              f"(load at https://ui.perfetto.dev)")
    if args.metrics:
        n = tracer.metrics.write_jsonl(args.metrics)
        print(f"[serve] metrics: {n} per-step snapshots -> {args.metrics}")


def _fmt_ttft(v) -> str:
    """A TTFT percentile of -1 means no request produced a first token
    (empty trace, all-preempted run): print n/a, not a bogus latency."""
    return "n/a" if v is None or v < 0 else f"{v:.1f}"


def _report_lines(stats) -> list:
    """``--report`` text from a SchedulerStats or RouterStats — robust to
    empty/missing SLO classes and to runs with no finished requests."""
    lines = [f"[serve] ttft: p50 {_fmt_ttft(stats.ttft_p50)} / p99 "
             f"{_fmt_ttft(stats.ttft_p99)} steps from arrival to first token"]
    per_class = getattr(stats, "per_class", None) or {}
    if not per_class:
        lines.append("[serve]   (no SLO classes configured; per-class "
                     "attainment skipped)")
    for name, c in per_class.items():
        lines.append(
            f"[serve]   {name:>8}: {c['finished']} finished, "
            f"ttft p50 {_fmt_ttft(c['ttft_p50'])} "
            f"p99 {_fmt_ttft(c['ttft_p99'])} "
            f"(deadline {c['ttft_deadline']}, hit "
            f"{100 * c['deadline_hit_rate']:.0f}%), "
            f"{c['preempted']} preemptions")
    return lines


def init_params(cfg):
    """Seeded random weights, built on the device by one jitted program (at
    full width, op-by-op init would dispatch thousands of small ops)."""
    import jax
    from repro.models import Backbone
    return jax.jit(Backbone.init, static_argnums=1)(jax.random.PRNGKey(0),
                                                     cfg)


def workload_max_len(prompt_len: int, gen: int) -> int:
    """Per-slot cache length of a ``--workload`` run: room for the longest
    prompt and generation the Poisson trace draws."""
    return prompt_len * 2 + gen * 4 + 1


def _run_lockstep(args, cfg, mesh, mi, jax, Engine):
    key = jax.random.PRNGKey(0)
    params = init_params(cfg)
    with mesh:
        eng = Engine(params, cfg, batch=args.batch,
                     max_len=args.prompt_len + args.gen + 1,
                     mesh=mesh, mesh_info=mi)
        n = max(cfg.mux.n, 1)
        pshape = (args.batch, n, args.prompt_len) if cfg.mux.active \
            else (args.batch, args.prompt_len)
        prompts = jax.random.randint(key, pshape, 0, cfg.vocab)
        t0 = time.time()
        out = eng.generate(prompts, args.gen)
        out.block_until_ready()
        dt = time.time() - t0
    streams = args.batch * n
    print(f"[serve] {streams} streams x {args.gen} tokens in {dt:.2f}s "
          f"({streams * args.gen / dt:.0f} tok/s)")


def _run_workload(args, cfg, mesh, mi, jax, Engine):
    from repro.serving.scheduler import (ContinuousScheduler, poisson_trace,
                                         static_batch_steps)
    params = init_params(cfg)
    n = max(cfg.mux.n, 1)
    max_total = workload_max_len(args.prompt_len, args.gen)
    tracer = _make_tracer(args)
    with mesh:
        eng = Engine(params, cfg, batch=args.batch, max_len=max_total,
                     mesh=mesh, mesh_info=mi)
        sched = ContinuousScheduler(eng, tracer=tracer)
        trace = poisson_trace(
            args.num_requests, rate=args.rate, prompt_len=args.prompt_len,
            gen_len=args.gen, vocab=cfg.vocab, max_total=max_total,
            seed=args.seed, slo_mix=args.slo_mix)
        t0 = time.time()
        stats = sched.run(trace)
        dt = time.time() - t0
    lanes = args.batch * n
    print(f"[serve] workload={args.workload}: {args.num_requests} requests "
          f"over {lanes} lanes ({args.batch} slots x {n})"
          + (f", paged (page_size={cfg.serving.page_size})"
             if cfg.serving.paged else "")
          + (f", kernel (kblock_pages={cfg.serving.kblock_pages})"
             if cfg.serving.use_kernel else "")
          + (", fuse_demux" if cfg.serving.fuse_demux else "")
          + (f", prefill_chunk={cfg.serving.prefill_chunk}"
             if cfg.serving.prefill_chunk > 1 else "")
          + (f", policy={cfg.serving.policy}" if cfg.serving.policy != "fifo"
             else "")
          + (", preempt" if cfg.serving.preempt else "")
          + (f", width_set={','.join(map(str, cfg.serving.width_set))} "
             f"({cfg.serving.width_policy})"
             if cfg.serving.width_set else ""))
    print(f"[serve] continuous: {stats.decode_steps} decode steps, "
          f"{stats.generated_tokens} tokens in {dt:.2f}s "
          f"({stats.generated_tokens / max(dt, 1e-9):.0f} tok/s), "
          f"occupancy {stats.mean_occupancy:.2f}, "
          f"{stats.slot_resets} slot resets")
    if stats.preemptions or stats.resumes:
        print(f"[serve] preempt-and-swap: {stats.preemptions} slots parked, "
              f"{stats.resumes} resumed")
    if stats.per_width:
        compiles = getattr(sched.engine, "variant_compiles", 0)
        print(f"[serve] width classes ({compiles} variant compiles):")
        for w, pw in sorted(stats.per_width.items()):
            print(f"[serve]   n={w}: {pw['count']} finished, "
                  f"{pw['tokens']} tokens, ttft mean "
                  f"{_fmt_ttft(pw['ttft_mean'])} "
                  f"p99 {_fmt_ttft(pw['ttft_p99'])}")
    ramp = [q.ramp_latency for q in sched.finished]
    if ramp:
        import numpy as _np
        print(f"[serve] ramp: mean {_np.mean(ramp):.2f} steps from admission "
              f"to first token (max {max(ramp)})")
    if args.report:
        for line in _report_lines(stats):
            print(line)
    if cfg.serving.paged:
        load = stats.final_load
        print(f"[serve] pool: peak {stats.peak_pages}/{load.usable_pages} "
              f"pages ({sched.allocator.page_bytes()} B/page), "
              f"{load.pages_in_use} in use after drain")
    if args.baseline:
        # Opt-in: the lock-step comparison is extra host work a plain serve
        # shouldn't pay just for a print line.
        static = static_batch_steps(trace, args.batch, n)
        print(f"[serve] static baseline: {static} decode steps "
              f"(continuous saves "
              f"{100 * (1 - stats.decode_steps / static):.0f}%"
              f" on this trace)" if static
              else "[serve] static baseline: n/a")
    _export_telemetry(args, tracer)
    if stats.finished != args.num_requests:
        raise SystemExit(
            f"[serve] FAIL: only {stats.finished}/{args.num_requests} "
            f"requests completed")
    return stats


def _run_router(args, cfg, mesh, mi, jax, Engine):
    """Poisson trace through the replica router: R independent
    engine+scheduler replicas, load-aware dispatch, aggregated report.
    With ``--mesh-shape`` every replica runs on that mesh; otherwise each
    replica gets a device of its own (round-robin over the devices)."""
    from repro.serving.router import ReplicaRouter
    from repro.serving.scheduler import poisson_trace
    params = init_params(cfg)
    n = max(cfg.mux.n, 1)
    max_total = workload_max_len(args.prompt_len, args.gen)
    tracer = _make_tracer(args)
    if args.mesh_shape:
        placement, scope = dict(mesh=mesh, mesh_info=mi), mesh
    else:
        # Each replica on its own device: no mesh, which would span
        # device 0 only.
        placement = dict(devices=jax.devices())
        scope = contextlib.nullcontext()
    with scope:
        router = ReplicaRouter.build(
            params, cfg, batch=args.batch, max_len=max_total,
            replicas=args.replicas, tracer=tracer, **placement)
        trace = poisson_trace(
            args.num_requests, rate=args.rate, prompt_len=args.prompt_len,
            gen_len=args.gen, vocab=cfg.vocab, max_total=max_total,
            seed=args.seed, slo_mix=args.slo_mix)
        t0 = time.time()
        stats = router.run(trace)
        dt = time.time() - t0
    lanes = args.batch * n
    print(f"[serve] router: {args.num_requests} requests over "
          f"{stats.replicas} replicas x {lanes} lanes "
          f"({args.batch} slots x {n}), policy={stats.policy}"
          + (", sync" if stats.sync else "")
          + (f", paged (page_size={cfg.serving.page_size})"
             if cfg.serving.paged else ""))
    print(f"[serve] fleet: {stats.router_steps} router steps, "
          f"{stats.generated_tokens} tokens in {dt:.2f}s "
          f"({stats.tokens_per_step:.2f} tok/step, "
          f"{stats.generated_tokens / max(dt, 1e-9):.0f} tok/s wall), "
          f"{stats.requeues} backpressure requeues")
    for i, rep in enumerate(stats.per_replica):
        print(f"[serve]   replica {i}: {rep['dispatched']} dispatched, "
              f"{rep['finished']} finished, {rep['decode_steps']} steps, "
              f"occupancy {rep['mean_occupancy']:.2f}, "
              f"{rep['preemptions']} preemptions")
    if args.report:
        for line in _report_lines(stats):
            print(line)
    _export_telemetry(args, tracer)
    if stats.finished != args.num_requests:
        raise SystemExit(
            f"[serve] FAIL: only {stats.finished}/{args.num_requests} "
            f"requests completed")
    return stats


def main(argv=None):
    """Parse ``argv`` and serve; returns the workload's SchedulerStats /
    RouterStats (None for the lock-step grid)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tmux-12l-768h")
    ap.add_argument("--mux-n", type=int, default=8)
    ap.add_argument("--batch", type=int, default=None,
                    help="backbone slots (default: 4 lock-step, 2 workload)")
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="prompt tokens (default: 16 lock-step, 4 workload "
                         "— continuous ramps prompts through decode steps)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--device-count", type=int, default=0,
                    help="force N host devices (CPU mesh emulation)")
    ap.add_argument("--mesh-shape", default="",
                    help="data,model mesh over the devices present "
                         "(default 1,1)")
    # continuous-batching workload replay
    ap.add_argument("--workload", choices=["none", "poisson"], default="none",
                    help="replay a Poisson arrival trace through the "
                         "continuous-batching scheduler")
    ap.add_argument("--num-requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="mean arrivals per decode step")
    ap.add_argument("--seed", type=int, default=0)
    # paged KV cache (serving/paging.py)
    ap.add_argument("--paged", action="store_true",
                    help="page the KV cache: block tables over a shared "
                         "pool, free-page admission")
    ap.add_argument("--page-size", type=int, default=16,
                    help="positions per KV page")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="shared pool size (0 = dense equivalent)")
    ap.add_argument("--prefill-chunk", type=int, default=1,
                    help="prompt tokens fed per decode step while a lane "
                         "ramps (1 = classic one-token ramp)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route paged decode attention through the Pallas "
                         "kernel (interpreted off-TPU) instead of the "
                         "jnp gather reference")
    ap.add_argument("--kblock-pages", type=int, default=1,
                    help="block-table entries the paged kernel spans per "
                         "grid step (MXU-shaped multi-page K tiles; "
                         "1 = page-at-a-time)")
    ap.add_argument("--fuse-demux", action="store_true",
                    help="fuse the index-embed demux projection into the "
                         "decode epilogue (all N lanes demuxed in VMEM)")
    # policy-driven serving core (serving/policies.py)
    ap.add_argument("--policy", default="fifo",
                    help="admission policy: fifo | priority | slo (or any "
                         "registered custom policy name)")
    ap.add_argument("--preempt", action="store_true",
                    help="preempt-and-swap: an outranking request parks a "
                         "victim slot in the swap ledger; the victim "
                         "resumes later, bitwise-identical")
    ap.add_argument("--slo-mix", type=float, default=0.0,
                    help="fraction of trace requests tagged latency-class "
                         "(rest batch-class; 0 = unclassed)")
    ap.add_argument("--report", action="store_true",
                    help="print TTFT percentiles and per-SLO-class "
                         "completion stats after the run")
    # adaptive multiplexing width (width classes)
    ap.add_argument("--width-set", default="",
                    help="comma list of mux widths (e.g. 1,4): partition "
                         "the slots into width classes, each on a compiled "
                         "engine variant (empty = fixed native width)")
    ap.add_argument("--width-policy", default="static",
                    help="width policy: static | slo_tiered | load_adaptive "
                         "(or any registered name) — which class a request "
                         "rides")
    ap.add_argument("--max-preemptions", type=int, default=0,
                    help="per-request preemption cap: a request parked this "
                         "many times becomes eviction-immune (0 = no cap)")
    # replica router (serving/router.py)
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine+scheduler replicas behind the router "
                         "(>1 enables the replica-router serving tier)")
    ap.add_argument("--router-policy", default="round_robin",
                    help="routing policy: round_robin | least_loaded | "
                         "slo_headroom (or any registered name)")
    ap.add_argument("--router-sync", action="store_true",
                    help="step every replica each router tick (lock-step) "
                         "instead of skipping idle replicas")
    # telemetry (serving/telemetry.py)
    ap.add_argument("--trace", default="", metavar="OUT.trace.json",
                    help="record request-lifecycle events, the "
                         "scheduler's host spans (sched.admit/feed/"
                         "readback/sample/release) and per-slot steps on "
                         "the wall clock, and write a Chrome/Perfetto "
                         "traceEvents JSON (load at https://ui.perfetto.dev)")
    ap.add_argument("--metrics", default="", metavar="OUT.jsonl",
                    help="write one metrics snapshot per step as JSONL "
                         "(counters + gauges, r{i}/- or router/-prefixed)")
    ap.add_argument("--baseline", action="store_true",
                    help="also compute and print the static lock-step "
                         "baseline step count for the same trace")
    args = ap.parse_args(argv)
    workload = args.workload == "poisson"
    if args.batch is None:
        args.batch = 2 if workload else 4
    if args.prompt_len is None:
        args.prompt_len = 4 if workload else 16

    if args.device_count:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.device_count}")

    import jax
    from repro.configs.registry import get_config, get_smoke_config
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.serving.engine import Engine
    from repro.sharding.specs import mesh_info_from_mesh

    enable_compile_cache()
    mesh = make_mesh(tuple(int(x) for x in args.mesh_shape.split(",") if x))
    mi = mesh_info_from_mesh(mesh)

    getter = get_smoke_config if args.smoke else get_config
    cfg = getter(args.arch, mux_n=args.mux_n)
    width_set = tuple(int(w) for w in args.width_set.split(",") if w)
    if (args.paged or args.prefill_chunk > 1 or args.policy != "fifo"
            or args.preempt or args.replicas > 1 or args.use_kernel
            or args.kblock_pages > 1 or args.fuse_demux or width_set
            or args.max_preemptions):
        import dataclasses
        from repro.configs.base import ServingConfig
        cfg = dataclasses.replace(cfg, serving=ServingConfig(
            paged=args.paged, page_size=args.page_size,
            pool_pages=args.pool_pages,
            use_kernel=args.use_kernel,
            kblock_pages=args.kblock_pages,
            fuse_demux=args.fuse_demux,
            prefill_chunk=args.prefill_chunk,
            policy=args.policy, preempt=args.preempt,
            max_preemptions=args.max_preemptions,
            width_set=width_set, width_policy=args.width_policy,
            replicas=args.replicas, router_policy=args.router_policy,
            router_sync=args.router_sync))
    print(f"[serve] {cfg.name} N={cfg.mux.n} on mesh "
          f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")

    if args.workload == "poisson" and args.replicas > 1:
        return _run_router(args, cfg, mesh, mi, jax, Engine)
    if args.workload == "poisson":
        return _run_workload(args, cfg, mesh, mi, jax, Engine)
    _run_lockstep(args, cfg, mesh, mi, jax, Engine)


if __name__ == "__main__":
    main()
