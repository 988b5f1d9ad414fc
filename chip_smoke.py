"""Smoke test of the serving path on a TPU, at qwen1.5-4b's published widths.

Run from the repository root:

    python chip_smoke.py               # one chip: phases serve, kernels
    python chip_smoke.py --four-chips  # four chips: phase router only

Weights are seeded random (``Backbone.init``); the model is qwen1.5-4b
(40 layers, d 2560, 20 heads, d_ff 6912, vocab 151936) multiplexing N = 8
requests per slot (hadamard mux, index_embed demux), served paged.

  serve    ``repro.launch.serve.main`` — Engine + ContinuousScheduler over
           the paged KV cache replaying a Poisson trace; fails unless every
           request completes.
  kernels  the same configuration on fixed prompts: once through the XLA
           reference path, then with the Pallas kernels on (paged decode
           attention, fused decode demux, mux/demux kernels) at each of
           ``KBLOCK_PAGES``.  Each prompt spans three 128-position pages
           and enters the paged cache through the scheduler's ramp (the
           serving path's prefill), then 4 greedy decode steps follow, so
           the kernel walks several block-table entries per sequence.  The
           kernel runs are teacher-forced with the reference's tokens, and
           at every emitted position ||kernel - reference|| / ||reference||
           over the vocabulary must stay within ``LOGIT_RTOL``.  Each
           compiled kernel decode step must contain ``tpu_custom_call``
           (Mosaic kernels, not interpreted).
  router   (``--four-chips``) ``repro.launch.serve.main`` with four
           replicas behind ``ReplicaRouter`` (round_robin, lock-step), each
           with its params, cache and page pool on its own chip; then each
           replica's share of the trace (its requests with their arrival
           steps) is replayed on one chip, one replica at a time.  Generated
           tokens must match request for request.

Exits non-zero on any failure, and before any phase when JAX's first device
is not a TPU: there is no CPU fallback.  The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

# Importing these touches no backend; outside a checkout they fail here.
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import ServingConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import init_params  # noqa: E402
from repro.serving.engine import Engine  # noqa: E402
from repro.serving.policies import SamplingPolicy, SloClasses  # noqa: E402
from repro.serving.scheduler import ContinuousScheduler, Request  # noqa: E402

ARCH = "qwen1.5-4b"
MUX_N = 8
PAGE_SIZE = 128
BATCH = 2                      # serve.py's workload default: 2 slots x N lanes
SERVE_ARGS = ["--arch", ARCH, "--mux-n", str(MUX_N), "--workload", "poisson",
              "--paged", "--page-size", str(PAGE_SIZE), "--batch", str(BATCH),
              "--num-requests", "16", "--prompt-len", "128", "--gen", "32"]
# The kernels phase keeps the serve phase's engine shape (same cache and
# page-pool shapes, so the reference step is the serve phase's program).
MAX_LEN = serve.workload_max_len(128, 32)
# Three pages per prompt: the paged kernel must walk and merge several
# block-table entries (one per K-block at kblock 1, all in one at kblock 4).
KERNEL_PROMPT = 2 * PAGE_SIZE + 16
KBLOCK_PAGES = (1, 4)
DECODE_STEPS = 4
# Relative L2 limit on each position's logits.  bf16 compute over 40
# layers: the kernels keep f32 where the XLA path rounds to bf16 between
# ops, so the two differ by bf16 rounding (about 1e-2 of the logits' norm);
# a wrong page, mask or lane differs by the order of the logits themselves.
LOGIT_RTOL = 0.05
REPLICAS = 4
# Long enough that each slot spans two pages: the launcher's dense pool then
# holds every slot's prefix page and a working page.
ROUTER_PROMPT = 48
ROUTER_GEN = 8
ROUTER_ARGS = ["--arch", ARCH, "--mux-n", str(MUX_N), "--workload", "poisson",
               "--paged", "--page-size", str(PAGE_SIZE), "--batch", str(BATCH),
               "--num-requests", "8", "--prompt-len", str(ROUTER_PROMPT),
               "--gen", str(ROUTER_GEN), "--replicas", str(REPLICAS),
               "--router-sync", "--router-policy", "round_robin"]


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class CompileClock:
    """Seconds JAX spends in backend compiles (persistent-cache loads
    included), read per phase."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration

    def since(self, mark: float) -> float:
        return self.total - mark


def phase_report(name: str, clock: CompileClock, mark: float,
                 t0: float) -> None:
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
            for d in jax.devices()]
    say(f"phase {name}: {time.perf_counter() - t0:.1f} s wall, "
        f"{clock.since(mark):.1f} s compiling, "
        f"peak_bytes_in_use {peak}")


def paged_config(cfg, **serving):
    return dataclasses.replace(cfg, serving=ServingConfig(
        paged=True, page_size=PAGE_SIZE, **serving))


def weight_bytes(cfg) -> int:
    shapes = jax.eval_shape(lambda: init_params(cfg))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


def run_serve():
    """Phase serve: the launcher, exactly as a user calls it."""
    stats = serve.main(SERVE_ARGS)
    say(f"serve: {stats.finished} requests completed, "
        f"{stats.generated_tokens} tokens, {stats.decode_steps} decode steps")


class Recorder(SamplingPolicy):
    """Greedy sampling that keeps every emitted lane's logits; with
    ``forced``, emits those tokens instead (teacher forcing), so a second
    path sees exactly the first path's token history."""

    name = "record"

    def __init__(self, slo, forced=None):
        super().__init__(slo)
        self.forced = forced
        self.logits: dict = {}
        self.tokens: dict = {}

    def select(self, req, logits):
        key = (req.rid, len(req.output))
        self.logits[key] = np.asarray(logits, np.float32)
        tok = self.forced[key] if self.forced is not None \
            else int(np.argmax(logits))
        self.tokens[key] = tok
        return tok


def kernel_requests(cfg):
    rng = np.random.default_rng(0)
    return [Request(rid=i, max_new_tokens=1 + DECODE_STEPS,
                    prompt=rng.integers(0, cfg.vocab, KERNEL_PROMPT)
                    .astype(np.int32))
            for i in range(BATCH * cfg.mux.n)]


def run_kernels(base_cfg) -> None:
    """Phase kernels: reference path vs kernel path on fixed prompts."""
    ref_cfg = paged_config(base_cfg)
    params = init_params(ref_cfg)
    slo = SloClasses(ref_cfg.serving.slo_classes)

    ref = Recorder(slo)
    ContinuousScheduler(Engine(params, ref_cfg, batch=BATCH, max_len=MAX_LEN),
                        sampling=ref).run(kernel_requests(ref_cfg))
    for kblock in KBLOCK_PAGES:
        ker_cfg = dataclasses.replace(
            paged_config(base_cfg, use_kernel=True, fuse_demux=True,
                         kblock_pages=kblock),
            mux=dataclasses.replace(base_cfg.mux, use_kernel=True))
        ker = Recorder(slo, forced=ref.tokens)
        eng = Engine(params, ker_cfg, batch=BATCH, max_len=MAX_LEN)
        sched = ContinuousScheduler(eng, sampling=ker)
        sched.run(kernel_requests(ker_cfg))
        if set(ker.logits) != set(ref.logits):
            fail("kernel and reference paths emitted different positions")
        rel = max(float(np.linalg.norm(ker.logits[k] - v) / np.linalg.norm(v))
                  for k, v in ref.logits.items())
        diff = max(float(np.abs(ker.logits[k] - v).max())
                   for k, v in ref.logits.items())
        scale = max(float(np.abs(v).max()) for v in ref.logits.values())
        pages = -(-(base_cfg.mux.prefix_len + KERNEL_PROMPT + DECODE_STEPS)
                  // PAGE_SIZE)
        say(f"kernels kblock_pages={kblock}: {len(ref.logits)} lane-positions "
            f"({BATCH * base_cfg.mux.n} lanes x prefill + {DECODE_STEPS} "
            f"decode steps, {pages} pages per slot); max |logit| "
            f"{scale:.4f}, largest |kernel - reference| {diff:.4f}; "
            f"largest |kernel - reference| / |reference| per position "
            f"{rel:.5f} (limit {LOGIT_RTOL})")
        if not rel <= LOGIT_RTOL:
            fail(f"kernel logits at kblock_pages={kblock} differ from the "
                 f"reference by {rel:.5f} (relative L2) > {LOGIT_RTOL}")

        c = sched.classes[0]
        hlo = eng._step.lower(
            eng.params, jnp.zeros((BATCH, ker_cfg.mux.n), jnp.int32),
            c.allocator.cache, jnp.asarray(sched.pos), c.index_embeds, None,
            jnp.asarray(sched.table.lane_mask()), c.allocator.block_table,
            None).compile().as_text()
        kernels = hlo.count("tpu_custom_call")
        say(f"kernels kblock_pages={kblock}: compiled decode step holds "
            f"{kernels} tpu_custom_call ops")
        if not kernels:
            fail("the kernel decode step compiled without tpu_custom_call")


def run_router(base_cfg, devices) -> None:
    """Phase router: the launcher with one replica per device, then a
    one-device replay of each replica's share of the trace; tokens must
    match."""
    stats = serve.main(ROUTER_ARGS)
    if stats.replicas != len(devices):
        fail(f"the router ran {stats.replicas} replicas, not {len(devices)}")
    say(f"router: {stats.finished} requests, {stats.generated_tokens} "
        f"tokens over {stats.replicas} replicas in {stats.router_steps} "
        f"router steps")
    # Replica i's weights were on device i: each device's peak holds them.
    wbytes = weight_bytes(base_cfg)
    for d in devices:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        if peak < wbytes:
            fail(f"{d} peaked at {peak} bytes, less than one replica's "
                 f"{wbytes} bytes of weights")

    # Replay on device 0 (the default device), one share at a time, each on
    # a fresh scheduler over one engine with the launcher's shapes.
    cfg = paged_config(base_cfg, replicas=REPLICAS,
                       router_policy="round_robin", router_sync=True)
    engine = Engine(init_params(cfg), cfg, batch=BATCH,
                    max_len=serve.workload_max_len(ROUTER_PROMPT, ROUTER_GEN))
    for i, served in enumerate(stats.served):
        replay = ContinuousScheduler(engine)
        replay.run([q.fresh() for q in served])
        got = {q.rid: list(q.output) for q in replay.finished}
        want = {q.rid: list(q.output) for q in served}
        tokens = sum(len(v) for v in want.values())
        say(f"router: replica {i} on {devices[i]}: {len(served)} requests "
            f"(arrivals {[q.arrival for q in served]}), {tokens} tokens; "
            f"replay on {devices[0]}: "
            f"{'identical' if got == want else 'DIFFERENT'}")
        if got != want:
            fail(f"replica {i}'s tokens differ from its one-chip replay")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica router phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX's first device is {devices[0].platform!r}, not a TPU; "
             f"this smoke test has no CPU fallback")
    need = REPLICAS if args.four_chips else 1
    if len(devices) < need:
        fail(f"--four-chips needs {REPLICAS} TPU devices, JAX sees "
             f"{len(devices)}")

    kind = devices[0].device_kind
    say(f"device: {kind} x {len(devices)}, compile cache "
        f"{enable_compile_cache() or 'off'}")
    cfg = get_config(ARCH, mux_n=MUX_N)
    say(f"{cfg.name}: {cfg.n_layers} layers, d {cfg.d_model}, "
        f"{cfg.n_heads} heads (kv {cfg.n_kv_heads}), d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab}, N {cfg.mux.n}; weights "
        f"{weight_bytes(cfg)} bytes")
    clock = CompileClock()

    phases = [("router", lambda: run_router(cfg, devices[:REPLICAS]))] \
        if args.four_chips else [("serve", run_serve),
                                 ("kernels", lambda: run_kernels(cfg))]
    for name, run in phases:
        mark, t0 = clock.total, time.perf_counter()
        run()
        phase_report(name, clock, mark, t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
