"""Seeded random weights in the program's parameter layout, made by the
benchmark and not by the program.

Each leaf is drawn from a key folded from the run's seed and the leaf's path
(and, for a layer stacked under ``blocks``, its group), so one jitted call
makes every weight on the device in the type it is served in, and the plain
reference can make any one layer again, alone, after the program's state is
freed.  Distributions: norm scales 1 + 0.1·N, biases 0.1·N, embedding and
demux prefix tables 0.02·N, the hadamard mux vectors N, every matrix
N / sqrt(fan_in).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _path_str(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def _draw(key, path: str, shape, dtype):
    name = path.rsplit("/", 1)[-1]
    z = jax.random.normal(key, shape, jnp.float32)
    if name == "scale":
        x = 1.0 + 0.1 * z
    elif name in ("b", "bias"):
        x = 0.1 * z
    elif name in ("table", "prefix_table"):
        x = 0.02 * z
    elif name == "v":
        x = z
    elif len(shape) >= 2:
        x = z / float(np.sqrt(shape[-2]))
    else:
        x = 0.1 * z
    return x.astype(dtype)


def _leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def shapes(cfg):
    from repro.models import Backbone
    return jax.eval_shape(lambda: Backbone.init(jax.random.PRNGKey(0), cfg))


def make(cfg, seed: int):
    """Every weight of ``cfg``, on the default device, in one jitted call."""
    tree = shapes(cfg)

    def build(key):
        def leaf(path, sds):
            p = _path_str(path)
            k = _leaf_key(key, p)
            if p.startswith("blocks/"):
                groups = sds.shape[0]
                return jax.vmap(
                    lambda g: _draw(jax.random.fold_in(k, g), p,
                                    sds.shape[1:], sds.dtype))(
                    jnp.arange(groups))
            return _draw(k, p, sds.shape, sds.dtype)
        return jax.tree_util.tree_map_with_path(leaf, tree)

    return jax.jit(build)(seed_key(seed))


class Weights:
    """f32 weights for the plain reference, made leaf by leaf from the seed.

    ``glob(path)`` gives a leaf outside the layers ("embed/table");
    ``layer(i)`` gives layer i's nested dict, as the program lays it out.
    """

    def __init__(self, cfg, seed: int):
        self.tree = shapes(cfg)
        self.key = seed_key(seed)
        self.head, self.period, self.groups = cfg.layer_pattern()
        # One program per distinct leaf; the layer's group is traced.
        self._draw = jax.jit(self._draw_impl, static_argnums=(1, 2, 3, 4))

    @staticmethod
    def _draw_impl(key, path, shape, dtype, stacked, group):
        k = _leaf_key(key, path)
        if stacked:
            k = jax.random.fold_in(k, group)
        return _draw(k, path, shape, dtype).astype(jnp.float32)

    def _subtree(self, prefix: str, sub, group: int):
        def leaf(path, sds):
            shape = sds.shape[1:] if group >= 0 else sds.shape
            p = prefix + "/" + _path_str(path)
            return self._draw(self.key, p, tuple(shape), sds.dtype,
                              group >= 0, max(group, 0))
        return jax.tree_util.tree_map_with_path(leaf, sub)

    def glob(self, path: str):
        node = self.tree
        for part in path.split("/"):
            node = node[part]
        return self._draw(self.key, path, tuple(node.shape), node.dtype,
                          False, 0)

    def layer(self, i: int):
        head, period, groups = self.head, self.period, self.groups
        if i < head:
            return self._subtree(f"head_layers/{i}",
                                 self.tree["head_layers"][i], -1)
        if i < head + period * groups:
            g, j = divmod(i - head, period)
            return self._subtree(f"blocks/{j}", self.tree["blocks"][j], g)
        t = i - head - period * groups
        return self._subtree(f"tail_layers/{t}",
                             self.tree["tail_layers"][t], -1)
