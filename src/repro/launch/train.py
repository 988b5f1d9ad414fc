"""Training launcher: pjit train loop on a mesh over the devices present.

It runs the same code the dry-run lowers — state sharded by
repro/sharding specs (ZeRO-1 moments), batch sharded over (pod, data),
DataMUX width from --mux-n.  On a CPU host use --device-count to emulate a
small mesh end-to-end (actually executes, unlike the dry-run):

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b \
        --smoke --device-count 4 --mesh-shape 2,2 --steps 20 --mux-n 4
"""
import argparse
import os
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tmux-12l-768h")
    ap.add_argument("--mux-n", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8, help="backbone batch")
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--device-count", type=int, default=0,
                    help="force N host devices (CPU mesh emulation)")
    ap.add_argument("--mesh-shape", default="",
                    help="data,model mesh over the devices present "
                         "(default 1,1)")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args(argv)

    if args.device_count:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.device_count}")

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.registry import get_config, get_smoke_config
    from repro.data.pipeline import mux_batches
    from repro.data.synthetic import RetrievalTask
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.sharding.specs import mesh_info_from_mesh, state_specs
    from repro.training.trainer import Trainer, TrainConfig
    from repro.checkpoint.io import save_checkpoint

    enable_compile_cache()
    mesh = make_mesh(tuple(int(x) for x in args.mesh_shape.split(",") if x))
    mi = mesh_info_from_mesh(mesh)
    print(f"[train] mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    getter = get_smoke_config if args.smoke else get_config
    cfg = getter(args.arch, mux_n=args.mux_n)
    tcfg = TrainConfig(task="retrieval" if cfg.mux.active else "lm",
                       lr=3e-3, warmup=args.steps // 10,
                       total_steps=args.steps)
    print(f"[train] {cfg.name} N={cfg.mux.n} params~{cfg.param_count()/1e6:.0f}M")

    state = Trainer.init_state(jax.random.PRNGKey(0), cfg, tcfg)
    sspecs = state_specs(state, mi)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                             is_leaf=lambda x: isinstance(x, P))
    with mesh:
        state = jax.device_put(state, shardings)
        bat, _ = mi.bl_entries(args.batch, args.seq_len)
        bshard = NamedSharding(mesh, P(bat))
        step = jax.jit(
            Trainer.make_train_step(cfg, tcfg, mesh=mesh, mesh_info=mi),
            in_shardings=(shardings, bshard, None),
            out_shardings=(shardings, None), donate_argnums=(0,))

        task = RetrievalTask(vocab=cfg.vocab, seq_len=args.seq_len)
        key = jax.random.PRNGKey(1)
        for i, batch in enumerate(mux_batches(
                task, args.batch, max(cfg.mux.n, 1), args.steps)):
            key, rng = jax.random.split(key)
            jb = {k: jax.device_put(jnp.asarray(v), bshard)
                  for k, v in batch.items()}
            state, m = step(state, jb, rng)
            if i % max(1, args.steps // 10) == 0:
                print(f"  step {i:4d}  loss {float(m['loss']):.4f}  "
                      f"gnorm {float(m['grad_norm']):.2f}")
    print(f"[train] done; final loss {float(m['loss']):.4f}")
    if args.ckpt:
        save_checkpoint(args.ckpt, jax.device_get(state), step=args.steps)
        print(f"[train] saved {args.ckpt}")


if __name__ == "__main__":
    main()
