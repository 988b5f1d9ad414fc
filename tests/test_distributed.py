"""Distributed-equivalence tests on a real multi-device host mesh
(subprocesses: jax locks device count at first init).

  * sharded muxed train step == single-device train step (bitwise-ish)
  * launch/train.py runs end-to-end on a 4-device (2, 2) mesh
  * prefix_pad model decodes correctly through the serving engine
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code, timeout=900):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    _run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.registry import get_smoke_config
        from repro.launch.mesh import make_mesh
        from repro.sharding.specs import mesh_info_from_mesh, state_specs
        from repro.training.trainer import Trainer, TrainConfig

        cfg = get_smoke_config("qwen1.5-4b", mux_n=2)
        tcfg = TrainConfig(task="lm", lr=1e-3, warmup=2, total_steps=10)
        key = jax.random.PRNGKey(0)
        state = Trainer.init_state(key, cfg, tcfg)
        batch = {"tokens": jax.random.randint(key, (4, 2, 16), 0, cfg.vocab)}

        # single device
        s1, m1 = jax.jit(Trainer.make_train_step(cfg, tcfg))(
            jax.device_put(state), batch, key)

        # (2, 2) mesh with explicit shardings
        mesh = make_mesh((2, 2))
        mi = mesh_info_from_mesh(mesh)
        specs = state_specs(state, mi)
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda x: isinstance(x, P))
        step = jax.jit(Trainer.make_train_step(cfg, tcfg, mesh=mesh,
                                               mesh_info=mi),
                       in_shardings=(sh, NamedSharding(mesh, P("data")),
                                     None),
                       out_shardings=(sh, None))
        with mesh:
            s2, m2 = step(jax.device_put(state, sh), batch, key)

        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-4)
        d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         jax.device_get(s1["params"]),
                         jax.device_get(s2["params"]))
        worst = max(jax.tree.leaves(d))
        assert worst < 1e-3, worst
        print("OK", float(m1["loss"]), worst)
    """))


def test_train_launcher_on_emulated_mesh():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "gemma3-4b",
         "--smoke", "--device-count", "4", "--mesh-shape", "2,2",
         "--steps", "6", "--mux-n", "2", "--batch", "4", "--seq-len", "16"],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "done; final loss" in out.stdout


def test_serve_launcher_on_emulated_mesh():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen1.5-4b",
         "--smoke", "--device-count", "4", "--mesh-shape", "2,2",
         "--mux-n", "2", "--batch", "2", "--prompt-len", "8", "--gen", "4"],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "tok/s" in out.stdout


def test_device_pinned_engine_keeps_its_scheduler_on_its_device():
    """A scheduler built over ``Engine(device=d)`` anywhere (not only
    behind the router) holds its weights, primed prefix and page pool on
    d, and serves from there."""
    _run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax, numpy as np
        from repro.configs.base import ServingConfig
        from repro.configs.registry import get_smoke_config
        from repro.models import Backbone
        from repro.serving.engine import Engine
        from repro.serving.scheduler import ContinuousScheduler, Request

        cfg = get_smoke_config("qwen1.5-4b", mux_n=2)
        cfg = dataclasses.replace(
            cfg, serving=ServingConfig(paged=True, page_size=4))
        params = Backbone.init(jax.random.PRNGKey(0), cfg)
        dev = jax.devices()[2]
        sched = ContinuousScheduler(
            Engine(params, cfg, batch=2, max_len=16, device=dev))
        held = (sched.engine.params, sched.allocator.cache,
                sched.index_embeds)
        where = {d for x in jax.tree.leaves(held) for d in x.devices()}
        assert where == {dev}, where
        rng = np.random.default_rng(0)
        stats = sched.run([Request(
            rid=i, max_new_tokens=3,
            prompt=rng.integers(0, cfg.vocab, 3).astype(np.int32))
            for i in range(3)])
        assert stats.finished == 3
        where = {d for x in jax.tree.leaves(sched.allocator.cache)
                 for d in x.devices()}
        assert where == {dev}, where
        print("OK")
    """))


def test_serve_launcher_router_one_replica_per_device():
    """``serve --replicas 4`` without --mesh-shape: one replica per device;
    each replica's share of the trace, replayed alone on device 0, gives
    the same tokens."""
    _run_py(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import dataclasses
        import jax
        from repro.configs.base import ServingConfig
        from repro.configs.registry import get_smoke_config
        from repro.launch import serve
        from repro.serving.engine import Engine
        from repro.serving.scheduler import ContinuousScheduler

        stats = serve.main([
            "--arch", "qwen1.5-4b", "--smoke", "--mux-n", "2",
            "--workload", "poisson", "--paged", "--page-size", "4",
            "--num-requests", "8", "--prompt-len", "4", "--gen", "4",
            "--replicas", "4", "--router-sync"])
        assert stats.finished == 8
        assert [len(s) for s in stats.served] == [2, 2, 2, 2]
        cfg = dataclasses.replace(
            get_smoke_config("qwen1.5-4b", mux_n=2),
            serving=ServingConfig(paged=True, page_size=4))
        engine = Engine(serve.init_params(cfg), cfg, batch=2,
                        max_len=serve.workload_max_len(4, 4))
        for served in stats.served:
            replay = ContinuousScheduler(engine)
            replay.run([q.fresh() for q in served])
            assert ({q.rid: q.output for q in replay.finished} ==
                    {q.rid: q.output for q in served})
        print("OK")
    """))


def test_prefix_pad_decode_matches_full(key):
    """prefix_pad model: decode-with-cache equals full forward."""
    import dataclasses
    from repro.configs.registry import get_smoke_config
    from repro.models import Backbone

    cfg = get_smoke_config("qwen1.5-4b", mux_n=3)
    cfg = dataclasses.replace(
        cfg, mux=dataclasses.replace(cfg.mux, prefix_pad=8))
    params = Backbone.init(key, cfg)
    B, L = 1, 10
    toks = jax.random.randint(key, (B, 3, L + 1), 0, cfg.vocab)
    full = Backbone.apply(params, toks, cfg)
    want = full["logits"][:, :, -1]

    cache = Backbone.init_cache(cfg, B, cfg.mux.prefix_len + L + 2,
                                dtype=jnp.float32)
    pre = Backbone.apply(params, toks[:, :, :L], cfg, cache=cache)
    got, _ = Backbone.decode_step(
        params, toks[:, :, L], pre["cache"],
        jnp.int32(cfg.mux.prefix_len + L), cfg,
        index_embeds=pre["index_embeds"])
    np.testing.assert_allclose(
        np.asarray(jax.nn.log_softmax(got.astype(np.float32))),
        np.asarray(jax.nn.log_softmax(want.astype(np.float32))),
        rtol=1e-4, atol=1e-4)
