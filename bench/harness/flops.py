"""Operations a decode step requires, from shapes: what the window's work
needs, not what the program happens to compute.

Only live work counts: a slot with no live lane needs nothing, and the demux
and the LM head are needed per live lane.  A multiply-add is 2 operations.
"""
from __future__ import annotations


def _dense_stream(m: dict, position: int) -> float:
    """One stream's token through a dense decoder at ``position`` (the
    number of keys it attends to is position + 1)."""
    d, f = m["d_model"], m["d_ff"]
    h, kv = m["n_heads"], m["n_kv_heads"]
    hd = m.get("head_dim") or d // h
    proj = 2 * d * (h + 2 * kv) * hd + 2 * h * hd * d
    attn = 2 * 2 * h * hd * (position + 1)
    mlp = 2 * (3 if m.get("gated_mlp", True) else 2) * d * f
    return m["n_layers"] * (proj + attn + mlp)


def per_lane(m: dict) -> float:
    """Demux (2-layer MLP on [h ; p^i]) and LM head, for one live lane."""
    d = m["d_model"]
    return 2 * (2 * d * 2 * d + 2 * d * d) + 2 * d * m["vocab"]


def step(config: dict, mask, pos) -> float:
    """Operations one decode step needs: ``mask`` (B, N) live lanes, ``pos``
    (B,) the position each slot writes."""
    m = config["model"]
    n = config["mux"]["n"]
    d = m["d_model"]
    total = 0.0
    for s in range(len(pos)):
        lanes = int(mask[s].sum())
        if not lanes:
            continue
        total += 2 * n * d                           # mux: N scaled rows
        total += _dense_stream(m, int(pos[s]))
        total += lanes * per_lane(m)
    return total
