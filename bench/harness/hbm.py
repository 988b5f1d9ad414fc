"""HBM bytes a scope of the decode step must move, from shapes: what the
window's work needs, whatever implements it, as ``flops.py`` counts
operations.

Weights are read once a step, when any slot has a live lane; the K and V
of a live slot are read at the positions it attends to (``pos`` + 1), for
the model's own key-value heads only: a head padded in for the layout, a
page's unused tail and a dead slot need nothing.  The step's own K/V
writes belong to the ``kv_write`` scope, whose time ``attention`` leaves
out.  Activations and norm weights are left out too: under a thousandth of
either scope's bytes at the benchmark's widths, and leaving them out can
only lower a share.
"""
from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def attention_weights(m: dict) -> int:
    """QKV (with its bias) and O of every layer."""
    d, h, kv = m["d_model"], m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // h
    qkv = (h + 2 * kv) * hd
    params = d * qkv + (qkv if m.get("qkv_bias") else 0) + h * hd * d
    return m["n_layers"] * params * BYTES[m["param_dtype"]]


def kv_per_position(m: dict) -> int:
    """K and V of one position, over every layer."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return m["n_layers"] * 2 * m["n_kv_heads"] * hd * BYTES[m["dtype"]]


def mlp_weights(m: dict) -> int:
    """Gate (if gated), up and down of every layer."""
    mats = 3 if m.get("gated_mlp", True) else 2
    return m["n_layers"] * mats * m["d_model"] * m["d_ff"] * \
        BYTES[m["param_dtype"]]


def step(config: dict, mask, pos) -> dict:
    """Bytes per scope of one decode step: ``mask`` (B, N) live lanes,
    ``pos`` (B,) the position each slot writes."""
    m = config["model"]
    live = [int(pos[s]) for s in range(len(pos)) if mask[s].any()]
    if not live:
        return {"attention": 0, "mlp": 0}
    return {"attention": attention_weights(m)
            + kv_per_position(m) * sum(p + 1 for p in live),
            "mlp": mlp_weights(m)}
