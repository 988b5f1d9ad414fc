"""Pure-jnp oracle for paged-attention decode: gather-from-block-table.

The reference reassembles each slot's pages into position order and then
runs exactly the expression sequence of the contiguous decode path in
``repro.nn.attention`` (same einsums, same f32 mask/softmax, same dtype
casts), so on a pool that mirrors a contiguous cache the output is
bit-for-bit identical — masked (unwritten / unmapped) entries contribute an
exact 0 to the softmax regardless of the stale values the pool holds.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Safe import: nn.attention only pulls the paged ops lazily inside
# Attention.apply, and reusing its GQA expansion keeps the head order the
# kernel's (kvh, n_rep) grouping depends on in one place.
from repro.nn.attention import _repeat_kv

NEG_INF = -1e30


def gather_pages(pages, block_table, layer=None, heads=None):
    """pages: (P, ps, KVH, hd) pool, or (G, P, ps, KVH, hd) with ``layer``;
    ``heads``: the real heads of a pool padded to ``pool_kv_heads``
    (default all).  block_table: (B, max_pages) int32 (-1 = unmapped).
    Returns (B, max_pages * ps, heads, hd) in position order — entry
    j*ps+o of row b is position j*ps+o of slot b's stream.

    A stacked pool is read as (G*P, ps, ...) pages (a free reshape) at
    ``layer * P + page``: one gather, with no per-layer slice first.  Whole
    pages are gathered and the heads cut after: on a v5e a gather of part
    of each page's padded heads spends one more device op per page (26k
    against 14k ops a qwen1.5-4b step, where a traced window holds only so
    many)."""
    safe = jnp.maximum(block_table, 0)
    if layer is not None:
        safe = layer * pages.shape[1] + safe
        pages = pages.reshape((-1,) + pages.shape[2:])
    g = pages[safe][..., :heads, :]                  # (B, mp, ps, heads, hd)
    return g.reshape((g.shape[0], -1) + g.shape[3:])


def gather_positions(pos_pages, block_table, layer=None):
    """Written-position array for the gathered view; unmapped pages read as
    -1 (never written) so stale pool contents cannot leak into the mask."""
    safe = jnp.maximum(block_table, 0)
    g = pos_pages[safe] if layer is None else pos_pages[layer, safe]
    g = jnp.where(block_table[:, :, None] >= 0, g, -1)   # (B, mp, ps)
    return g.reshape(g.shape[0], -1)


def paged_attention(q, k_pages, v_pages, pos_pages, block_table, q_pos, *,
                    scale: float, causal: bool = True,
                    window: Optional[int] = None, layer=None,
                    kv_heads: Optional[int] = None):
    """Decode attention over a paged KV pool for a C-row query block
    (C == 1: classic single-token decode; C > 1: chunked prefill).

    q: (B, C, H, hd) post-RoPE queries; k_pages/v_pages: (P, ps, KVH, hd);
    pos_pages: (P, ps) int32 written positions (-1 = unwritten) — or, with
    ``layer``, the stacked (G, ...) pools and the layer to read;
    ``kv_heads``: the real heads of pools padded to ``pool_kv_heads``;
    block_table: (B, max_pages) int32 pool-page ids (-1 = unmapped);
    q_pos: (B, C) int32 absolute query positions.  Returns (B, C, H, hd).

    Rows with zero valid keys (an emptied slot) produce a uniform average of
    garbage — callers mask those lanes out, exactly as the contiguous path
    does.
    """
    kv_heads = kv_heads or k_pages.shape[-2]
    n_rep = q.shape[2] // kv_heads
    k = _repeat_kv(gather_pages(k_pages, block_table, layer,
                                kv_heads).astype(q.dtype), n_rep)
    v = _repeat_kv(gather_pages(v_pages, block_table, layer,
                                kv_heads).astype(q.dtype), n_rep)
    k_pos = gather_positions(pos_pages, block_table, layer)

    diff = q_pos[:, :, None] - k_pos[:, None, :]     # (B, 1, S)
    mask = jnp.ones_like(diff, dtype=bool)
    if causal:
        mask &= diff >= 0
    if window is not None:
        mask &= diff < window
    mask &= (k_pos >= 0)[:, None, :]
    mask = mask[:, None, :, :]                       # (B, 1, 1, S)

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)
