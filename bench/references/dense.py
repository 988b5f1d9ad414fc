"""Plain float32 reference of a dense decoder (Qwen1.5: pre-RMSNorm, MHA/GQA
with QKV bias and rotate-half RoPE, SwiGLU MLP, untied or tied LM head)
under the DataMUX mux and demux.

It runs whole epochs of a slot's stream at once (full causal attention, no
cache, no paging, no batching of slots), one layer at a time, each layer's
weights made from the seed just before use.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from references.common import demux, linear, mm, mux_stream, rmsnorm


def _rope(x, theta):
    """x: (E, S, H, hd), positions 0..S-1; rotate-half layout."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs       # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(p, x, *, m, quant):
    e, s, d = x.shape
    h_, kvh, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = p["attn"]
    h = rmsnorm(p["norm1"], x)
    q = linear(a["wq"], h, quant).reshape(e, s, h_, hd)
    k = linear(a["wk"], h, quant).reshape(e, s, kvh, hd)
    v = linear(a["wv"], h, quant).reshape(e, s, kvh, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    rep = h_ // kvh
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("eqhd,ekhd->ehqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("ehqk,ekhd->eqhd", probs, v).reshape(e, s, h_ * hd)
    x = x + mm(out, a["wo"]["w"], quant)
    h = rmsnorm(p["norm2"], x)
    f = p["mlp"]
    act = jax.nn.silu(mm(h, f["gate"]["w"], quant)) * mm(h, f["up"]["w"], quant)
    return x + mm(act, f["down"]["w"], quant)


def logits(model: dict, mux: dict, weights, tokens, mask, queries,
           quant=None) -> np.ndarray:
    """Reference logits at ``queries`` (M, 3) = (epoch, step, lane) rows.

    tokens, mask: (E, T, N) per epoch step.  Returns (M, vocab) float32.
    """
    n = mux["n"]
    m = dict(model)
    m.setdefault("head_dim", m["d_model"] // m["n_heads"])
    with jax.default_matmul_precision("highest"):
        x = mux_stream(weights.glob("embed/table"), weights.glob("mux/v"),
                       weights.glob("demux/prefix_table"),
                       jnp.asarray(tokens), jnp.asarray(mask, jnp.float32),
                       quant)
        layer = jax.jit(lambda p, x: _layer(p, x, m=m, quant=quant))
        for i in range(m["n_layers"]):
            x = layer(weights.layer(i), x)
        h = rmsnorm({"scale": weights.glob("final_norm/scale")}, x)
        q = np.asarray(queries)
        hq = h[q[:, 0], n + q[:, 1]]
        pq = h[q[:, 0], q[:, 2]]
        dm = demux({"l0": {"w": weights.glob("demux/mlp/l0/w"),
                           "b": weights.glob("demux/mlp/l0/b")},
                    "l1": {"w": weights.glob("demux/mlp/l1/w"),
                           "b": weights.glob("demux/mlp/l1/b")}},
                   hq, pq, quant)
        if m.get("tie_embeddings", True):
            out = mm(dm, weights.glob("embed/table").T, quant)
        else:
            out = mm(dm, weights.glob("lm_head/w"), quant)
        return np.asarray(out, np.float32)
