"""Sharding specs: structural validity for every arch + jit on a named mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import ARCHS, get_smoke_config
from repro.launch.mesh import make_mesh
from repro.models import Backbone
from repro.sharding.specs import (cache_specs, mesh_info_from_mesh,
                                  param_specs, state_specs)
from repro.training.trainer import Trainer, TrainConfig

SAMPLE = ["qwen1.5-4b", "deepseek-v3-671b", "jamba-1.5-large-398b",
          "xlstm-125m", "whisper-base", "gemma3-4b"]


def _axes_valid(spec, leaf, mesh_axes=("pod", "data", "model")):
    entries = tuple(spec)
    assert len(entries) <= leaf.ndim, (spec, leaf.shape)
    for e in entries:
        if e is None:
            continue
        names = e if isinstance(e, tuple) else (e,)
        for nm in names:
            assert nm in mesh_axes, spec


@pytest.mark.parametrize("arch", SAMPLE)
def test_param_specs_structurally_valid(key, arch):
    cfg = get_smoke_config(arch, mux_n=2)
    params = Backbone.init(key, cfg)
    mesh = make_mesh()
    mi = mesh_info_from_mesh(mesh)
    specs = param_specs(params, mi)
    jax.tree.map(lambda s, l: _axes_valid(s, l), specs, params)


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "deepseek-v3-671b",
                                  "jamba-1.5-large-398b"])
def test_cache_specs_structurally_valid(arch):
    cfg = get_smoke_config(arch, mux_n=1)
    cache = Backbone.init_cache(cfg, 4, 32)
    mesh = make_mesh()
    mi = mesh_info_from_mesh(mesh)
    specs = cache_specs(cache, mi)
    jax.tree.map(lambda s, l: _axes_valid(s, l), specs, cache)


def test_state_specs_and_jit_train_step(key):
    """jit with explicit in/out shardings on a named (1,1) mesh — the same
    code path the production dry-run exercises."""
    cfg = get_smoke_config("tmux-4l-768h", mux_n=2)
    tcfg = TrainConfig(task="lm", total_steps=10)
    mesh = make_mesh()
    mi = mesh_info_from_mesh(mesh)
    state = Trainer.init_state(key, cfg, tcfg)
    sspecs = state_specs(state, mi)

    def shardings(tree_specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                            is_leaf=lambda x: isinstance(x, P))

    step = Trainer.make_train_step(cfg, tcfg, mesh=mesh, mesh_info=mi)
    batch_spec = {"tokens": P(mi.batch_spec)}
    jitted = jax.jit(
        step,
        in_shardings=(shardings(sspecs), shardings(batch_spec), None),
        out_shardings=(shardings(sspecs), None))
    batch = {"tokens": jax.random.randint(key, (2, 2, 8), 0, cfg.vocab)}
    with mesh:
        state2, metrics = jitted(state, batch, key)
    assert np.isfinite(float(metrics["loss"]))


def test_zero1_extends_replicated_dims(key):
    """ZeRO-1: moments of replicated matrices gain a data-axis entry when a
    dim is divisible (checked on a fake 4-way data mesh)."""
    from repro.nn.moe import MeshInfo
    mi = MeshInfo(data_axis="data", model_axis="model", pod_axis=None,
                  data_size=4, model_size=1, pod_size=1)
    cfg = get_smoke_config("tmux-4l-768h", mux_n=1)
    tcfg = TrainConfig(task="lm", total_steps=10)
    state = Trainer.init_state(key, cfg, tcfg)
    sspecs = state_specs(state, mi, zero1=True)
    flat_p = jax.tree_util.tree_leaves_with_path(sspecs["params"])
    flat_m = dict(jax.tree_util.tree_leaves_with_path(sspecs["opt_state"]["mu"]))
    n_extended = 0
    for path, pspec in flat_p:
        mspec = flat_m[path]
        if tuple(mspec) != tuple(pspec):
            n_extended += 1
            assert "data" in jax.tree.leaves(tuple(mspec))
    assert n_extended > 0
