"""Pieces shared by the plain references: the DataMUX mux and demux, norms,
and the lower-precision matmul that the correctness control uses.

Everything here is plain ``jax.numpy`` in float32.  The callers run under
``jax.default_matmul_precision("highest")``, so a float32 matmul on the TPU
is computed in float32 and not in bfloat16 passes.

``quant`` is None for the reference itself, or a dtype (float8_e4m3fn) to
which every matmul operand is rounded first: the control, computed one
precision below the bfloat16 that the configurations state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rnd(x, quant):
    return x if quant is None else x.astype(quant).astype(jnp.float32)


def mm(x, w, quant=None):
    return rnd(x, quant) @ rnd(w, quant)


def linear(p, x, quant=None):
    y = mm(x, p["w"], quant)
    return y + p["b"] if "b" in p else y


def rmsnorm(p, x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * p["scale"]


def gelu(x):
    """tanh approximation, as the DataMUX demux uses."""
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi)
                                     * (x + 0.044715 * x ** 3)))


def mux_stream(table, v, prefix_table, tokens, mask, quant=None):
    """The mixed input stream of each epoch (paper Eq. 1 with the prefix
    protocol of Sec 3.2).

    tokens, mask: (E, T, N) — the lanes' tokens and liveness at each of the
    epoch's T steps.  Lane i's prefix is ε^pad everywhere but position i,
    which holds ε^i; a dead lane contributes zero, and the mean is over all
    N lanes.  Returns (E, N + T, d).
    """
    e, t, n = tokens.shape
    d = table.shape[1]
    eps, pad = prefix_table[:n], prefix_table[n]
    prefix = jnp.broadcast_to(pad, (n, n, d)).at[jnp.arange(n),
                                                 jnp.arange(n)].set(eps)
    pre = jnp.mean(v[:, None, :] * prefix, axis=0)                 # (N, d)
    emb = rnd(table, quant)[tokens] * mask[..., None]              # (E,T,N,d)
    content = jnp.mean(emb * v[None, None], axis=2)                # (E,T,d)
    return jnp.concatenate([jnp.broadcast_to(pre, (e, n, d)), content], 1)


def demux(p, h, index_embeds, quant=None):
    """Index-embedding demux: a shared 2-layer MLP on [h ; p^i]."""
    x = jnp.concatenate([h, index_embeds], axis=-1)
    x = gelu(linear(p["l0"], x, quant))
    return linear(p["l1"], x, quant)
