"""Decode-kernel benchmark: MXU-shaped K-blocks + fused demux epilogue.

Sweeps ``page_size x kblock_pages x prefill_chunk`` through the continuous
scheduler with the Pallas paged-decode kernel on (``use_kernel`` +
``fuse_demux``), recording per-run kernel grid geometry — grid steps,
compute-skipped all-unmapped K-blocks (the ``pl.when`` early-out), modeled
HBM bytes streamed per K-block — and end-to-end tokens per decode step.
Two acceptance properties are asserted on the same trace:

  * at ``page_size=4`` the ``kblock_pages=4`` grid runs >= 2x fewer steps
    than ``kblock_pages=1``;
  * the token streams (and decode-step counts) are identical across
    ``kblock_pages`` and match a contiguous-cache baseline, so tokens/step
    cannot regress as the K-block widens.

Writes ``results/bench/decode_kernel.json`` (the ``decode_kernel`` suite of
``benchmarks.run``) plus one roofline record per K-block width under
``results/dryrun/`` so ``benchmarks.roofline`` tabulates the decode kernel
alongside the dry-run shapes: compute/memory seconds model one production
decode step (tmux-12l-768h, 128 slots at 32k live positions) on the chip
peaks from ``repro.launch.peaks``, with ``useful_flops_frac`` the fraction
of streamed K-block rows holding real keys (padding shrinks it).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import numpy as np

from benchmarks import common
from repro.configs.base import ModelConfig, MuxConfig, ServingConfig
from repro.launch.peaks import V5E, peaks
from repro.models import Backbone
from repro.serving.engine import Engine
from repro.serving.paging import pages_for
from repro.serving.scheduler import ContinuousScheduler, poisson_trace
# Grid-geometry math lives with the rest of the observability layer now;
# the scheduler's per-step kernel counters use the same function.
from repro.serving.telemetry import kblock_stats as _kblock_stats

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN", "results/dryrun")

# Tiny causal dense backbone (the fuzz-test config): decode-with-cache is
# exact and float32, so identical tokens across kblock_pages is a hard
# assertion, not a tolerance check — and interpret-mode Pallas stays fast.
CFG = ModelConfig(
    name="bench-decode-kernel", family="dense", n_layers=2, d_model=64,
    n_heads=2, n_kv_heads=2, d_ff=128, vocab=128, dtype="float32",
    param_dtype="float32", remat="none",
    mux=MuxConfig(n=2, strategy="hadamard", demux="index_embed"))


class _GridProbe(ContinuousScheduler):
    """Scheduler that tallies the decode kernel's grid geometry each step
    (per layer — every layer launches the same grid over the same table)."""

    def __init__(self, eng, *, kblock: int, kvh: int):
        super().__init__(eng)
        self._kblock, self._kvh = kblock, kvh
        self._page_size = self.allocator.page_size if self.paged else 0
        self.grid_steps = 0
        self.skipped_blocks = 0
        self.streamed_rows = 0
        self.mapped_rows = 0

    def step(self) -> None:
        super().step()
        if self.paged:
            bt = np.asarray(self.allocator.block_table)
            grid, skipped, mapped = _kblock_stats(bt, self._kblock,
                                                  self._kvh)
            self.grid_steps += grid
            self.skipped_blocks += skipped
            self.streamed_rows += grid * self._kblock * self._page_size
            self.mapped_rows += mapped * self._page_size


def _block_bytes(kblock: int, page_size: int, hd: int, itemsize: int) -> int:
    """HBM bytes one grid step streams: K + V tiles plus the int32
    position page(s)."""
    return kblock * page_size * (hd * itemsize * 2 + 4)


def _roofline_record(ps: int, kb: int, *, layers=12, d=768, heads=12,
                     kv_heads=12, hd=64, batch=128, live=32768, mux_n=8):
    """Model one production decode step at 32k live positions per slot.
    Attention flops only (the fused demux epilogue adds O(d*hidden) per
    slot — noise next to B*H*S*hd); K/V streamed as bf16."""
    pages = pages_for(live, ps)
    n_blocks = -(-pages // kb)
    rows = n_blocks * kb * ps
    mem = batch * kv_heads * n_blocks * _block_bytes(kb, ps, hd, 2) * layers
    flops = 4 * live * hd * heads * batch * layers
    chip = peaks(V5E)
    c_s, m_s = flops / chip.bf16_flops, mem / chip.hbm_bytes
    return {
        "arch": "tmux-12l-768h", "shape": f"decode32k-ps{ps}-kb{kb}",
        "mesh": "pod", "mux_n": mux_n,
        "compute_s": round(c_s, 6), "memory_s": round(m_s, 6),
        "collective_s": 0.0,
        "dominant": "memory" if m_s >= c_s else "compute",
        "useful_flops_frac": round(live / rows, 2),
        "grid_steps": batch * kv_heads * n_blocks,
        "kblock_rows": kb * ps,
    }


def run(*, batch=2, num_requests=10, rate=2.0, prompt_len=3, gen_len=4,
        seed=0):
    common.banner("Decode kernel — K-block grid + fused demux epilogue")
    if os.environ.get("REPRO_BENCH_FAST"):
        num_requests = 6
    page_sizes, kblocks, chunks = (4, 8), (1, 2, 4), (1, 2)
    if os.environ.get("REPRO_BENCH_FAST"):
        page_sizes, kblocks = (4,), (1, 4)

    cfg = CFG
    params = Backbone.init(jax.random.PRNGKey(0), cfg)
    max_total = 2 * prompt_len + 4 * gen_len + 1
    trace = poisson_trace(num_requests, rate=rate, prompt_len=prompt_len,
                          gen_len=gen_len, vocab=cfg.vocab,
                          max_total=max_total, seed=seed)
    hd = cfg.d_model // cfg.n_heads
    itemsize = np.dtype(cfg.dtype).itemsize

    payload = {"config": {
        "arch": cfg.name, "batch": batch, "num_requests": num_requests,
        "rate": rate, "prompt_len": prompt_len, "gen_len": gen_len,
        "seed": seed, "page_sizes": list(page_sizes),
        "kblock_pages": list(kblocks), "chunks": list(chunks),
        "n_layers": cfg.n_layers, "grid_steps_are_per_layer_launch": True,
    }, "runs": []}

    tokens_ref = {}          # (ps, chunk) -> kb=1 token streams
    grid_by_kb = {}          # (ps, chunk) -> {kb: grid_steps}
    for chunk in chunks:
        # Contiguous baseline: the token stream every paged+kernel run must
        # reproduce exactly.
        cfg_c = dataclasses.replace(cfg, serving=ServingConfig(
            prefill_chunk=chunk))
        sched_c = ContinuousScheduler(
            Engine(params, cfg_c, batch=batch, max_len=max_total))
        sched_c.run([r.fresh() for r in trace])
        contig = {q.rid: list(q.output) for q in sched_c.finished}

        for ps in page_sizes:
            pool = pages_for(batch * (max_total + cfg.mux.prefix_len),
                             ps) + 2
            for kb in kblocks:
                serving = ServingConfig(
                    paged=True, page_size=ps, pool_pages=pool,
                    prefill_chunk=chunk, use_kernel=True, kblock_pages=kb,
                    fuse_demux=True)
                cfg_p = dataclasses.replace(cfg, serving=serving)
                sched = _GridProbe(Engine(params, cfg_p, batch=batch,
                                          max_len=max_total),
                                   kblock=kb, kvh=cfg.n_kv_heads)
                t0 = time.time()
                stats = sched.run([r.fresh() for r in trace])
                dt = time.time() - t0
                got = {q.rid: list(q.output) for q in sched.finished}
                assert got == contig, \
                    f"ps={ps} kb={kb} chunk={chunk}: kernel tokens " \
                    f"diverged from the contiguous baseline"
                key = (ps, chunk)
                base = tokens_ref.setdefault(key, (got,
                                                   stats.decode_steps))
                assert (got, stats.decode_steps) == base, \
                    f"ps={ps} chunk={chunk}: kb={kb} changed the token " \
                    f"stream or step count vs kb=1"
                grid_by_kb.setdefault(key, {})[kb] = sched.grid_steps

                bb = _block_bytes(kb, ps, hd, itemsize)
                rec = {
                    "page_size": ps, "kblock_pages": kb, "chunk": chunk,
                    "decode_steps": stats.decode_steps,
                    "generated_tokens": stats.generated_tokens,
                    "tok_per_step": round(stats.generated_tokens
                                          / max(1, stats.decode_steps), 3),
                    "tok_per_s": round(stats.generated_tokens / dt, 1),
                    "grid_steps": sched.grid_steps,
                    "skipped_blocks": sched.skipped_blocks,
                    "skipped_frac": round(sched.skipped_blocks
                                          / max(1, sched.grid_steps), 3),
                    "block_bytes": bb,
                    "streamed_bytes": sched.grid_steps * bb,
                    "mapped_row_frac": round(sched.mapped_rows
                                             / max(1, sched.streamed_rows),
                                             3),
                }
                payload["runs"].append(rec)
                print(f"  ps={ps} kb={kb} chunk={chunk}: "
                      f"{rec['grid_steps']} grid steps "
                      f"({rec['skipped_blocks']} skipped), "
                      f"{rec['tok_per_step']} tok/step over "
                      f"{rec['decode_steps']} steps")

    # Acceptance: K-blocks shrink the grid >= 2x at page_size 4 without
    # touching the token stream (asserted identical above).
    reductions = {}
    for (ps, chunk), per_kb in grid_by_kb.items():
        kb_max = max(per_kb)
        reductions[f"ps{ps}_chunk{chunk}"] = round(
            per_kb[1] / max(1, per_kb[kb_max]), 2)
    payload["grid_step_reduction"] = reductions
    ps4 = [v for k, v in reductions.items() if k.startswith("ps4_")]
    assert ps4 and all(r >= 2.0 for r in ps4), \
        f"kblock_pages=4 must shrink the page_size=4 grid >= 2x: {reductions}"
    print(f"  grid-step reduction (kb=1 vs widest): {reductions}")

    # Roofline records: the production decode shape at both K-block widths,
    # rendered by ``benchmarks.roofline`` next to the dry-run shapes.
    os.makedirs(DRYRUN_DIR, exist_ok=True)
    recs = []
    for kb in (1, 4):
        rec = _roofline_record(4, kb)
        fn = os.path.join(
            DRYRUN_DIR,
            f"tmux-12l-768h__{rec['shape']}__pod__n{rec['mux_n']}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
        recs.append(rec)
        print(f"  [roofline] {rec['shape']}: {rec['grid_steps']} grid "
              f"steps/layer, memory {rec['memory_s']:.4f}s vs compute "
              f"{rec['compute_s']:.4f}s -> {rec['dominant']}")
    payload["roofline"] = recs

    common.save("decode_kernel", payload)
    return payload


if __name__ == "__main__":
    run()
