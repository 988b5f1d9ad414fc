"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination with ShapeDtypeStruct inputs — no allocation — and record
memory_analysis / cost_analysis / collective bytes for §Roofline.

MUST be run as its own process: run as a script it sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before jax starts
(importing the module sets nothing):

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-4b \
        --shape train_4k --mesh pod [--mux-n 8] [--out results/dryrun]

    PYTHONPATH=src python -m repro.launch.dryrun --all   # full sweep
"""
import argparse
import dataclasses
import json
import os
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import INPUT_SHAPES, ModelConfig, ShapeConfig
from repro.configs.registry import (ARCHS, get_config, get_smoke_config,
                                    long_500k_supported)
from repro.launch import inputs as I
from repro.launch.mesh import make_production_mesh
from repro.launch.peaks import V5E, peaks
from repro.models import Backbone
from repro.sharding.specs import (cache_specs, mesh_info_from_mesh,
                                  param_specs, state_specs)
from repro.training.trainer import Trainer, TrainConfig

COLLECTIVE_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"[^\n=]*=\s*([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "f8": 1}


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum result sizes of every collective op in the post-SPMD HLO."""
    totals: dict[str, float] = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        op, dtype, dims = m.group(1), m.group(2), m.group(3)
        size = 1
        for d in dims.split(","):
            if d:
                size *= int(d)
        nbytes = size * _DTYPE_BYTES.get(dtype[:3].rstrip("0123456789"),
                                         _DTYPE_BYTES.get(dtype, 4))
        totals[op] = totals.get(op, 0.0) + nbytes
    totals["total"] = sum(v for k, v in totals.items() if k != "total")
    return totals


# ---------------------------------------------------------------------------
# step builders (lower-only; inputs are ShapeDtypeStructs)
# ---------------------------------------------------------------------------

def _shardings(mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


def _batch_specs(batch, mi):
    """Input sharding per batch tensor: batch dim over (pod, data) when
    divisible; for full-sequence token inputs, spill undivisible batch axes
    onto the sequence (last) dim (bl_entries)."""
    def spec(name, leaf):
        is_seq = name == "tokens" and leaf.ndim >= 2
        b = leaf.shape[0]
        seq = leaf.shape[-1] if is_seq else 1
        bat, sq = mi.bl_entries(b, seq)
        if leaf.ndim == 1:
            return P(bat)
        if is_seq:
            return P(bat, *([None] * (leaf.ndim - 2)), sq)
        return P(bat, *([None] * (leaf.ndim - 1)))
    return {k: spec(k, v) for k, v in batch.items()}


def _ep2d(cfg):
    return bool(cfg.moe is not None and cfg.moe.ep2d)


MICROBATCH = 0


def lower_train(cfg: ModelConfig, shape: ShapeConfig, mesh):
    mi = mesh_info_from_mesh(mesh)
    tcfg = TrainConfig(task="lm", total_steps=1000,
                       state_dtype="float32", microbatch=MICROBATCH)
    state = jax.eval_shape(
        lambda: Trainer.init_state(jax.random.PRNGKey(0), cfg, tcfg))
    sspecs = state_specs(state, mi, moe_ep2d=_ep2d(cfg))
    batch = I.train_inputs(cfg, shape)
    bspecs = _batch_specs(batch, mi)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)

    step = Trainer.make_train_step(cfg, tcfg, mesh=mesh, mesh_info=mi)
    jitted = jax.jit(step,
                     in_shardings=(_shardings(mesh, sspecs),
                                   _shardings(mesh, bspecs), None),
                     out_shardings=(_shardings(mesh, sspecs), None),
                     donate_argnums=(0,))
    with mesh:
        return jitted.lower(state, batch, rng)


def lower_prefill(cfg: ModelConfig, shape: ShapeConfig, mesh):
    mi = mesh_info_from_mesh(mesh)
    params = I.param_struct(cfg)
    pspecs = param_specs(params, mi, moe_ep2d=_ep2d(cfg))
    batch = I.prefill_inputs(cfg, shape)
    bspecs = _batch_specs(batch, mi)

    def prefill_step(params, batch):
        # serving prefill: next-token logits only (§Perf A5 — the full-L
        # demux tensor is the N-fold cost the paper's technique adds, and
        # next-token serving never materialises it)
        out = Backbone.apply(params, batch["tokens"], cfg,
                             context=batch.get("context"), mesh=mesh,
                             mesh_info=mi, last_only=True)
        return out["logits"][..., -1, :], out["index_embeds"]

    jitted = jax.jit(prefill_step,
                     in_shardings=(_shardings(mesh, pspecs),
                                   _shardings(mesh, bspecs)))
    with mesh:
        return jitted.lower(params, batch)


def lower_decode(cfg: ModelConfig, shape: ShapeConfig, mesh):
    mi = mesh_info_from_mesh(mesh)
    params = I.param_struct(cfg)
    pspecs = param_specs(params, mi, moe_ep2d=_ep2d(cfg))
    dec = I.decode_inputs(cfg, shape)
    cspecs = cache_specs(dec["cache"], mi)

    def serve_step(params, tokens, cache, pos, index_embeds, cross_kv):
        return Backbone.decode_step(params, tokens, cache, pos, cfg,
                                    index_embeds=index_embeds,
                                    cross_kv=cross_kv, mesh=mesh,
                                    mesh_info=mi)

    bat, _ = mi.bl_entries(I.backbone_batch(cfg, shape), 1)
    in_shardings = (
        _shardings(mesh, pspecs),
        NamedSharding(mesh, P(bat)),
        _shardings(mesh, cspecs),
        None,
        NamedSharding(mesh, P(bat, None, None))
        if "index_embeds" in dec else None,
        None,
    )
    jitted = jax.jit(serve_step, in_shardings=in_shardings,
                     donate_argnums=(2,))
    with mesh:
        return jitted.lower(params, dec["tokens"], dec["cache"], dec["pos"],
                            dec.get("index_embeds"), dec.get("cross_kv"))


LOWER = {"train": lower_train, "prefill": lower_prefill,
         "decode": lower_decode}


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def analyse(lowered, compiled, cfg: ModelConfig, shape: ShapeConfig,
            n_chips: int) -> dict:
    cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)

    # cost_analysis reports the PER-DEVICE SPMD program; scale to global so
    # the recorded numbers follow the spec's HLO_FLOPs / (chips × peak) form.
    flops = float(cost.get("flops", 0.0)) * n_chips
    hbm_bytes = float(cost.get("bytes accessed", 0.0)) * n_chips
    chip = peaks(V5E)
    t_compute = flops / (n_chips * chip.bf16_flops)
    t_memory = hbm_bytes / (n_chips * chip.hbm_bytes)
    # collective sizes parsed from the per-device HLO = bytes crossing each
    # chip's links; one effective link per chip.
    t_coll = coll.get("total", 0.0) / chip.ici_link_bytes
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    n_params = cfg.param_count()
    n_active = cfg.active_param_count()
    if cfg.mux.active:
        instances = I.backbone_batch(cfg, shape) * cfg.mux.n
    else:
        instances = I.backbone_batch(cfg, shape)
    tokens = instances * (shape.seq_len if shape.kind != "decode" else 1)
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens

    out = {
        "arch": cfg.name, "shape": shape.name, "kind": shape.kind,
        "mux_n": cfg.mux.n, "instances": instances, "n_chips": n_chips,
        "hlo_flops": flops, "hbm_bytes": hbm_bytes,
        "collective_bytes": coll,
        **terms,
        "dominant": dominant.replace("_s", ""),
        "params": n_params, "active_params": n_active,
        "model_flops": model_flops,
        "useful_flops_frac": model_flops / flops if flops else 0.0,
    }
    if mem is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if v is not None:
                out[k] = int(v)
        # per-device working set (args are sharded; temp is per-device)
        args = out.get("argument_size_in_bytes", 0)
        temp = out.get("temp_size_in_bytes", 0)
        out["bytes_per_device"] = args // n_chips + temp
    return out


def run_one(arch: str, shape_name: str, mesh_kind: str, mux_n: int,
            out_dir: str, *, smoke: bool = False,
            prefix_pad: int = 0, seq_parallel: bool = False,
            moe_scatter: bool = False, moe_ep2d: bool = False,
            remat: str = "", microbatch: int = 0) -> dict:
    shape = INPUT_SHAPES[shape_name]
    getter = get_smoke_config if smoke else get_config
    cfg = getter(arch)
    if mux_n != cfg.mux.n or prefix_pad:
        cfg = dataclasses.replace(
            cfg, mux=dataclasses.replace(cfg.mux, n=mux_n,
                                         prefix_pad=prefix_pad))
    if seq_parallel:
        cfg = dataclasses.replace(cfg, seq_parallel=True)
    if moe_scatter and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, psum_scatter=True))
    if moe_ep2d and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, ep2d=True))
    if remat:
        cfg = dataclasses.replace(cfg, remat=remat)
    global MICROBATCH
    MICROBATCH = microbatch
    if shape.name == "long_500k" and not long_500k_supported(arch):
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "mux_n": mux_n, "skipped": "quadratic-attention"}
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
            fn = (f"{arch.replace('.', '_')}__{shape_name}__{mesh_kind}"
                  f"__n{mux_n}.json")
            with open(os.path.join(out_dir, fn), "w") as f:
                json.dump(rec, f, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_chips = mesh.devices.size
    t0 = time.time()
    lowered = LOWER[shape.kind](cfg, shape, mesh)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    rec = analyse(lowered, compiled, cfg, shape, n_chips)
    rec.update(mesh=mesh_kind, lower_s=round(t1 - t0, 1),
               compile_s=round(t2 - t1, 1))

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fn = f"{arch.replace('.', '_')}__{shape_name}__{mesh_kind}__n{mux_n}.json"
        with open(os.path.join(out_dir, fn), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--mux-n", type=int, default=8,
                    help="DataMUX width (1 = vanilla baseline)")
    ap.add_argument("--prefix-pad", type=int, default=0,
                    help="pad mux prefix to a multiple (mesh-divisible "
                         "mixed-stream length; beyond-paper §Perf)")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="Megatron-SP activation constraint (§Perf A3)")
    ap.add_argument("--moe-scatter", action="store_true",
                    help="reduce-scatter MoE pre-activation (§Perf A4a)")
    ap.add_argument("--moe-ep2d", action="store_true",
                    help="experts over BOTH mesh axes, pure EP (§Perf A4b)")
    ap.add_argument("--remat", default="",
                    choices=["", "none", "dots", "full"],
                    help="override the config's remat policy (§Perf D)")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="gradient-accumulation chunks (§Perf D2)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape) on --mesh")
    ap.add_argument("--smoke", action="store_true",
                    help="use reduced configs (CI sanity, not the deliverable)")
    args = ap.parse_args(argv)

    assigned = [a for a in ARCHS if not a.startswith("tmux")]
    combos = ([(a, s) for a in assigned for s in INPUT_SHAPES]
              if args.all else [(args.arch, args.shape)])
    failures = 0
    for arch, shape in combos:
        try:
            rec = run_one(arch, shape, args.mesh, args.mux_n, args.out,
                          smoke=args.smoke, prefix_pad=args.prefix_pad,
                          seq_parallel=args.seq_parallel,
                          moe_scatter=args.moe_scatter,
                          moe_ep2d=args.moe_ep2d, remat=args.remat,
                          microbatch=args.microbatch)
            status = rec.get("skipped") and f"SKIP({rec['skipped']})" or \
                f"{rec['dominant']}-bound c={rec['compute_s']:.4f}s " \
                f"m={rec['memory_s']:.4f}s x={rec['collective_s']:.4f}s"
            print(f"[dryrun] {arch} x {shape} x {args.mesh} n={args.mux_n}: "
                  f"{status}", flush=True)
        except Exception:
            failures += 1
            print(f"[dryrun] FAIL {arch} x {shape} x {args.mesh}:",
                  flush=True)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    # Before the first backend use (the imports above make none), so this
    # process sees 512 placeholder devices.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
