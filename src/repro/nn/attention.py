"""Attention variants for the assigned architecture families.

Covers: MHA / GQA / MQA (n_kv_heads), RoPE, sliding-window (ring-buffer KV
cache), cross-attention (VLM / enc-dec), and DeepSeek-style MLA with a
compressed latent KV cache.  Every variant supports two modes:

  * full-sequence (training / prefill):  ``cache is None``
  * single-token decode:                 ``cache`` holds the KV state and the
                                         write index.

KV caches are plain dict pytrees so they shard/pjit like everything else.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn.layers import Linear

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    dim: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    causal: bool = True
    window: Optional[int] = None  # sliding-window size; None = full attention
    use_flash: bool = False  # route prefill through the Pallas flash kernel
    paged_kernel: bool = False  # paged decode: Pallas gather kernel vs jnp ref
    kblock_pages: int = 1    # block-table entries the paged kernel spans per
                             # grid step (MXU-shaped multi-page K tiles);
                             # 1 = page-at-a-time, ignored by the jnp ref
    softmax_scale: Optional[float] = None

    @property
    def scale(self) -> float:
        return self.softmax_scale if self.softmax_scale is not None \
            else self.head_dim ** -0.5


def paged_eligible(window: Optional[int], max_len: int) -> bool:
    """Whether an attention-family layer's decode cache is paged under
    ``cfg.serving.paged``.  Applies to full-attention K/V *and* MLA latent
    caches — both are position-indexed, so they page identically.  Windowed
    layers whose ring buffer is already smaller than ``max_len`` keep the
    bounded contiguous ring — paging them gains nothing and would break the
    ``pos % slots`` layout."""
    return window is None or window >= max_len


def pool_kv_heads(n_kv_heads: int) -> int:
    """K/V heads a paged pool page holds: ``n_kv_heads`` rounded up to a
    whole number of 8-row tiles when it exceeds one tile.

    A TPU tiles an array's two minor dimensions in 8-row tiles.  For a page
    of ``(ps, KVH, hd)`` whose KVH fills no whole tile, XLA's default layout
    puts ``ps`` second-minor instead (no padding), and the decode step's
    in-place write of one ``(KVH, hd)`` row per slot into the stacked pool
    then makes it relayout the whole pool into and out of the layer loop,
    every step.  Padded heads give a row-major default layout, the one that
    write wants, with no layout to pin.  (v5e compiler, bf16 and f32 pages
    of 1-48 heads: row-major at 2, 4 and multiples of 8, ps second-minor at
    every other count.)  Up to one tile the heads stay as they are: there
    padding would cost up to 8x the pool.  The extra heads are written as
    zeros and never read."""
    if n_kv_heads <= 8:
        return n_kv_heads
    return -(-n_kv_heads // 8) * 8


def _pool_rows(x, pool):
    """K/V rows ``x`` (..., KVH, hd) in the pool's dtype, padded with zero
    heads to the pool's head count (``pool_kv_heads``)."""
    pad = pool.shape[-2] - x.shape[-2]
    x = x.astype(pool.dtype)
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, pad), (0, 0)])
    return x


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., L, H, head_dim); positions: broadcastable to (..., L)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)  # (half,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., L, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., L, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Core soft-max attention
# ---------------------------------------------------------------------------

def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)) \
        .reshape(b, s, h * n_rep, d)


def dot_product_attention(q, k, v, mask, scale: float):
    """q: (B, Lq, H, hd)  k,v: (B, Lk, H, hd)  mask: (B, 1, Lq, Lk) bool."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# Beyond-paper §Perf lever: above this many keys the full (B, H, Lq, Lk)
# f32 score tensor dominates the memory roofline term (e.g. 32k prefill:
# hundreds of GB/device); switch to the chunked online-softmax form.
CHUNKED_ATTN_THRESHOLD = 8192
CHUNK_SIZE = 1024


def chunked_dot_product_attention(q, k, v, q_pos, k_pos, scale: float, *,
                                  causal: bool, window: Optional[int],
                                  k_valid=None, chunk: int = CHUNK_SIZE):
    """Flash-style attention in pure XLA: lax.scan over KV chunks with a
    running (max, sum, acc) — O(Lq·chunk) live scores instead of O(Lq·Lk).
    Lowers on every backend (the Pallas kernel is the TPU-tuned variant).

    q: (B, Lq, H, hd); k, v: (B, Lk, H, hd); q_pos (B, Lq); k_pos (B, Lk).
    """
    b, lq, h, hd_k = q.shape
    hd_v = v.shape[-1]
    lk = k.shape[1]
    pad = -lk % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        valid_pad = jnp.pad(
            k_valid if k_valid is not None
            else jnp.ones((b, lk), bool), ((0, 0), (0, pad)))
    else:
        valid_pad = k_valid if k_valid is not None \
            else jnp.ones((b, lk), bool)
    n_chunks = (lk + pad) // chunk

    kc = k.reshape(b, n_chunks, chunk, h, hd_k).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, hd_v).transpose(1, 0, 2, 3, 4)
    pc = k_pos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    mc = valid_pad.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    qf = q.astype(jnp.float32)

    def body(carry, xs):
        m_run, l_run, acc = carry                    # (B,H,Lq,1) ×2, (B,Lq,H,hd)
        kb, vb, pb, mb = xs                           # (B,C,H,hd), …, (B,C)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf,
                       kb.astype(jnp.float32)) * scale   # (B,H,Lq,C)
        diff = q_pos[:, None, :, None] - pb[:, None, None, :]
        keep = mb[:, None, None, :]
        if causal:
            keep = keep & (diff >= 0)
        if window is not None:
            keep = keep & (diff < window)
        s = jnp.where(keep, s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_run - m_new)                # (B,H,Lq,1)
        p = jnp.exp(s - m_new)                        # (B,H,Lq,C)
        l_new = l_run * alpha + jnp.sum(p, axis=-1, keepdims=True)
        upd = jnp.einsum("bhqk,bkhd->bqhd", p, vb.astype(jnp.float32))
        acc = acc * alpha.transpose(0, 2, 1, 3) + upd   # (B,Lq,H,1) bcast
        return (m_new, l_new, acc), None

    init = (jnp.full((b, h, lq, 1), NEG_INF, jnp.float32),
            jnp.zeros((b, h, lq, 1), jnp.float32),
            jnp.zeros((b, lq, h, hd_v), jnp.float32))
    (m_run, l_run, acc), _ = jax.lax.scan(body, init, (kc, vc, pc, mc))
    denom = jnp.maximum(l_run, 1e-30).transpose(0, 2, 1, 3)  # (B,Lq,H,1)
    return (acc / denom).astype(v.dtype)


def masked_chunk_write(cache, idx, row_ok, values: dict, pos_q):
    """Row-masked chunk scatter shared by the chunked-decode paths: write C
    rows per slot at ``idx`` (B, C) into each ``cache[key]`` (B, S, ...),
    keeping the existing entry wherever ``row_ok`` (B, C) is False (the
    invalid row writes back the value already there, so it is an exact
    no-op; ``idx`` rows are distinct because C <= S, so the scatter is
    deterministic).  ``pos`` is merged the same way from ``pos_q``.
    """
    with jax.named_scope("kv_write"):
        b = idx.shape[0]
        rows = jnp.arange(b)[:, None]
        out = {}
        for key, new in values.items():
            old = cache[key][rows, idx]
            keep = row_ok.reshape(row_ok.shape + (1,) * (new.ndim - 2))
            out[key] = cache[key].at[rows, idx].set(
                jnp.where(keep, new.astype(cache[key].dtype), old))
        p_new = jnp.where(row_ok, pos_q, cache["pos"][rows, idx])
        out["pos"] = cache["pos"].at[rows, idx].set(p_new)
        return out


def _pool_index(layer, page_ids, off):
    """Index of the paged-pool rows a decode step writes: ``(page, offset)``
    in one layer's ``(P, ps, ...)`` pool, with ``layer`` in front for the
    layer scan's stacked ``(G, P, ps, ...)`` pool, which is then updated in
    place (no per-layer slice is taken out of it)."""
    return (page_ids, off) if layer is None else (layer, page_ids, off)


def make_attention_mask(q_pos, k_pos, *, causal: bool, window: Optional[int],
                        k_valid=None):
    """Boolean (B, 1, Lq, Lk) mask from query/key positions.

    q_pos: (B, Lq) int; k_pos: (B, Lk) int; k_valid: optional (B, Lk) bool for
    ring-buffer slots that have not been written yet.
    """
    diff = q_pos[:, :, None] - k_pos[:, None, :]  # (B, Lq, Lk)
    m = jnp.ones_like(diff, dtype=bool)
    if causal:
        m &= diff >= 0
    if window is not None:
        m &= diff < window
    if k_valid is not None:
        m &= k_valid[:, None, :]
    return m[:, None, :, :]


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

class Attention:
    """GQA/MQA/MHA with RoPE and optional sliding window."""

    @staticmethod
    def init(key, cfg: AttnConfig, *, param_dtype=jnp.float32):
        keys = jax.random.split(key, 4)
        return {
            "wq": Linear.init(keys[0], cfg.dim, cfg.n_heads * cfg.head_dim,
                              use_bias=cfg.qkv_bias, param_dtype=param_dtype),
            "wk": Linear.init(keys[1], cfg.dim, cfg.n_kv_heads * cfg.head_dim,
                              use_bias=cfg.qkv_bias, param_dtype=param_dtype),
            "wv": Linear.init(keys[2], cfg.dim, cfg.n_kv_heads * cfg.head_dim,
                              use_bias=cfg.qkv_bias, param_dtype=param_dtype),
            "wo": Linear.init(keys[3], cfg.n_heads * cfg.head_dim, cfg.dim,
                              use_bias=False, param_dtype=param_dtype),
        }

    @staticmethod
    def init_cache(cfg: AttnConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
        """Ring buffer of size ``window`` for windowed layers, else ``max_len``."""
        slots = min(cfg.window, max_len) if cfg.window else max_len
        shape = (batch, slots, cfg.n_kv_heads, cfg.head_dim)
        return {
            "k": jnp.zeros(shape, dtype),
            "v": jnp.zeros(shape, dtype),
            "pos": jnp.full((batch, slots), -1, jnp.int32),  # -1 = unwritten
        }

    @staticmethod
    def init_paged_cache(cfg: AttnConfig, pool_pages: int, page_size: int,
                         dtype=jnp.bfloat16):
        """Pooled K/V for paged decode: ``pool_pages`` pages of ``page_size``
        positions, shared by every backbone slot through a per-slot block
        table (which lives in the ``PagedKVSlotAllocator``, not here — it is
        identical across layers).  ``pos`` mirrors the contiguous cache's
        written-position array per page; -1 = unwritten.  Page 0 is the
        allocator's trash page (writes from empty slots land there).  A page
        holds ``pool_kv_heads(n_kv_heads)`` heads, the first
        ``n_kv_heads`` of them real."""
        shape = (pool_pages, page_size, pool_kv_heads(cfg.n_kv_heads),
                 cfg.head_dim)
        return {
            "k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype),
            "pos": jnp.full((pool_pages, page_size), -1, jnp.int32),
        }

    @staticmethod
    def apply(params, x, cfg: AttnConfig, *, positions, cache=None,
              cache_index=None, block_table=None, chunk_lens=None,
              layer=None):
        """x: (B, L, D). Returns (out, new_cache).

        Full-sequence mode (cache None): causal/window mask over x itself.
        Decode mode: L == 1; writes k/v at ``cache_index`` — a scalar int32
        (all batch rows at the same position: the classic lock-step engine)
        or a (B,) int32 vector (continuous batching: each backbone slot at
        its own position, so slots can be admitted/retired independently).
        Paged decode (cache holds ``k_pages``): ``block_table`` (B, max_pages)
        maps each slot's page index to a pool page; writes and the attention
        gather go through the table.  With ``layer`` (a traced int32) the
        pools are the scan's stacked ``(G, P, ps, ...)`` ones and this is
        layer ``layer`` of them: writes and the gather index that layer in
        place, and the whole stacked pool comes back.
        Chunked decode (``chunk_lens`` (B,) int32 given): L == C is a token
        chunk; row i of slot b sits at position ``positions[b, i]`` and only
        rows ``i < chunk_lens[b]`` are real — a ramping prompt writes C
        cache rows per call while other slots advance one.  Invalid rows are
        exact no-op writes (contiguous) or land on the trash page (paged).
        """
        b, l, _ = x.shape
        q = Linear.apply(params["wq"], x).reshape(b, l, cfg.n_heads, cfg.head_dim)
        k = Linear.apply(params["wk"], x).reshape(b, l, cfg.n_kv_heads,
                                                  cfg.head_dim)
        v = Linear.apply(params["wv"], x).reshape(b, l, cfg.n_kv_heads,
                                                  cfg.head_dim)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

        n_rep = cfg.n_heads // cfg.n_kv_heads

        if cache is not None and chunk_lens is not None:
            out, new_cache = Attention._chunked_decode(
                q, k, v, cfg, cache, positions, chunk_lens, block_table,
                layer)
            out = out.reshape(b, l, cfg.n_heads * cfg.head_dim)
            return Linear.apply(params["wo"], out), new_cache

        if cache is not None and l > 1:
            # Prefill: compute full attention AND fill the cache.  Ring-buffer
            # layout: position p lives at slot p % slots (must match decode).
            slots = cache["k"].shape[1]
            keep = min(l, slots)
            if l <= slots:
                new_cache = {
                    "k": jax.lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0)),
                    "v": jax.lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0)),
                    "pos": jax.lax.dynamic_update_slice(
                        cache["pos"],
                        jnp.broadcast_to(positions, (b, l)).astype(jnp.int32),
                        (0, 0)),
                }
            else:
                slot_idx = (positions[0, l - keep:] % slots).astype(jnp.int32)
                new_cache = {
                    "k": cache["k"].at[:, slot_idx].set(
                        k[:, l - keep:].astype(cache["k"].dtype)),
                    "v": cache["v"].at[:, slot_idx].set(
                        v[:, l - keep:].astype(cache["v"].dtype)),
                    "pos": cache["pos"].at[:, slot_idx].set(
                        jnp.broadcast_to(positions[:, l - keep:],
                                         (b, keep)).astype(jnp.int32)),
                }
            if l >= CHUNKED_ATTN_THRESHOLD:
                out = chunked_dot_product_attention(
                    q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                    positions, positions, cfg.scale, causal=cfg.causal,
                    window=cfg.window)
            else:
                mask = make_attention_mask(positions, positions,
                                           causal=cfg.causal,
                                           window=cfg.window)
                out = dot_product_attention(q, _repeat_kv(k, n_rep),
                                            _repeat_kv(v, n_rep), mask,
                                            cfg.scale)
            out = out.reshape(b, l, cfg.n_heads * cfg.head_dim)
            return Linear.apply(params["wo"], out), new_cache

        if cache is None:
            if cfg.use_flash and cfg.causal and cfg.window is None:
                from repro.kernels.attention import ops as flash_ops
                out = flash_ops.flash_attention(
                    q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                    causal=True, scale=cfg.scale)
            elif l >= CHUNKED_ATTN_THRESHOLD:
                out = chunked_dot_product_attention(
                    q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                    positions, positions, cfg.scale, causal=cfg.causal,
                    window=cfg.window)
            else:
                mask = make_attention_mask(positions, positions,
                                           causal=cfg.causal,
                                           window=cfg.window)
                out = dot_product_attention(q, _repeat_kv(k, n_rep),
                                            _repeat_kv(v, n_rep), mask,
                                            cfg.scale)
            new_cache = None
        elif "k_pages" in cache:
            # Paged decode: ``cache_index`` -> (page, offset) through the
            # block table; the attention gather reassembles each slot's pages
            # in position order, so the result is bit-for-bit identical to
            # the contiguous per-slot cache (stale pool entries are masked by
            # their pos sentinel exactly like unwritten contiguous slots).
            assert block_table is not None, "paged cache needs a block_table"
            ps = cache["pos"].shape[-1]
            ci_v = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
            rows = jnp.arange(b)
            page_idx = jnp.clip(ci_v // ps, 0, block_table.shape[1] - 1)
            # Slots with no mapped page (emptied and recycled, masked out by
            # lane_mask upstream) write to the reserved trash page 0, which
            # no block table ever references.
            page_ids = jnp.maximum(block_table[rows, page_idx], 0)
            off = ci_v % ps
            pos_q = jnp.broadcast_to(positions, (b, 1))
            at = _pool_index(layer, page_ids, off)
            with jax.named_scope("kv_write"):
                k_pages = cache["k_pages"].at[at].set(
                    _pool_rows(k[:, 0], cache["k_pages"]))
                v_pages = cache["v_pages"].at[at].set(
                    _pool_rows(v[:, 0], cache["v_pages"]))
                pos_pages = cache["pos"].at[at].set(
                    pos_q[:, 0].astype(jnp.int32))
            new_cache = {"k_pages": k_pages, "v_pages": v_pages,
                         "pos": pos_pages}
            from repro.kernels.paged_attention import ops as paged_ops
            out = paged_ops.paged_attention(
                q, k_pages, v_pages, pos_pages, block_table, pos_q,
                scale=cfg.scale, causal=cfg.causal, window=cfg.window,
                use_kernel=cfg.paged_kernel, kblock_pages=cfg.kblock_pages,
                layer=layer, kv_heads=cfg.n_kv_heads)
        else:
            slots = cache["k"].shape[1]
            ci = jnp.asarray(cache_index, jnp.int32)
            with jax.named_scope("kv_write"):
                if ci.ndim:
                    # Per-slot positions (B,): each batch row writes its own
                    # slot.
                    rows = jnp.arange(b)
                    slot = (ci % slots).astype(jnp.int32)
                    k_cache = cache["k"].at[rows, slot].set(
                        k[:, 0].astype(cache["k"].dtype))
                    v_cache = cache["v"].at[rows, slot].set(
                        v[:, 0].astype(cache["v"].dtype))
                    pos = cache["pos"].at[rows, slot].set(
                        jnp.broadcast_to(positions, (b, 1))[:, 0]
                        .astype(jnp.int32))
                else:
                    slot = (ci % slots).astype(jnp.int32)
                    k_cache = jax.lax.dynamic_update_slice(
                        cache["k"], k.astype(cache["k"].dtype),
                        (0, slot, 0, 0))
                    v_cache = jax.lax.dynamic_update_slice(
                        cache["v"], v.astype(cache["v"].dtype),
                        (0, slot, 0, 0))
                    pos = jax.lax.dynamic_update_slice(
                        cache["pos"],
                        jnp.broadcast_to(positions, (b, 1)).astype(jnp.int32),
                        (0, slot))
            new_cache = {"k": k_cache, "v": v_cache, "pos": pos}
            mask = make_attention_mask(
                jnp.broadcast_to(positions, (b, 1)), pos, causal=cfg.causal,
                window=cfg.window, k_valid=pos >= 0)
            out = dot_product_attention(
                q, _repeat_kv(k_cache.astype(q.dtype), n_rep),
                _repeat_kv(v_cache.astype(q.dtype), n_rep), mask, cfg.scale)

        out = out.reshape(b, l, cfg.n_heads * cfg.head_dim)
        return Linear.apply(params["wo"], out), new_cache

    @staticmethod
    def _chunked_decode(q, k, v, cfg: AttnConfig, cache, positions,
                        chunk_lens, block_table, layer=None):
        """Multi-token decode: write up to C cache rows per slot, then attend
        each chunk row against the full (updated) cache.

        q: (B, C, H, hd); k/v: (B, C, KVH, hd); positions: (B, C) absolute;
        chunk_lens: (B,) valid rows per slot.  Rows ``i >= chunk_lens[b]``
        must not disturb the cache: contiguous caches get a gather → where →
        scatter (the invalid row writes back the value already there, and
        because C <= slots every row targets a distinct cache slot, the
        scatter is deterministic); paged caches route invalid rows to the
        reserved trash page.  Row i's causal mask covers rows <= i of the
        same chunk — they are written before the attention runs — so a
        C-wide ramp is exactly the C sequential single-token steps.
        """
        b, c = positions.shape
        rows = jnp.arange(b)[:, None]
        row_ok = jnp.arange(c)[None, :] < jnp.asarray(chunk_lens,
                                                      jnp.int32)[:, None]
        pos_q = jnp.asarray(positions, jnp.int32)
        n_rep = cfg.n_heads // cfg.n_kv_heads

        if "k_pages" in cache:
            assert block_table is not None, "paged cache needs a block_table"
            ps = cache["pos"].shape[-1]
            page_idx = jnp.clip(pos_q // ps, 0, block_table.shape[1] - 1)
            page_ids = jnp.maximum(block_table[rows, page_idx], 0)
            page_ids = jnp.where(row_ok, page_ids, 0)   # invalid rows: trash
            off = pos_q % ps
            at = _pool_index(layer, page_ids, off)
            with jax.named_scope("kv_write"):
                k_pages = cache["k_pages"].at[at].set(
                    _pool_rows(k, cache["k_pages"]))
                v_pages = cache["v_pages"].at[at].set(
                    _pool_rows(v, cache["v_pages"]))
                pos_pages = cache["pos"].at[at].set(
                    jnp.where(row_ok, pos_q, -1))
            new_cache = {"k_pages": k_pages, "v_pages": v_pages,
                         "pos": pos_pages}
            from repro.kernels.paged_attention import ops as paged_ops
            out = paged_ops.paged_attention(
                q, k_pages, v_pages, pos_pages, block_table, pos_q,
                scale=cfg.scale, causal=cfg.causal, window=cfg.window,
                use_kernel=cfg.paged_kernel, kblock_pages=cfg.kblock_pages,
                layer=layer, kv_heads=cfg.n_kv_heads)
            return out, new_cache

        slots = cache["k"].shape[1]
        slot = (pos_q % slots).astype(jnp.int32)        # distinct: C <= slots
        new_cache = masked_chunk_write(
            cache, slot, row_ok, {"k": k, "v": v}, pos_q)
        if cfg.window is not None:
            # Ring semantics: all C writes land before the attention runs,
            # so a later chunk row's write can physically evict an in-window
            # key an earlier row still needs (sequentially, position p+i-W
            # is evicted only at step i).  Attend over the *pre-write* ring
            # plus the chunk itself: an old key inside row i's window is
            # never one the chunk rows <= i overwrite (eviction targets are
            # exactly the out-of-window positions), and chunk positions are
            # disjoint from the old ring's, so each position is counted
            # once — bitwise the C sequential steps.
            chunk_pos = jnp.where(row_ok, pos_q, -1)
            # round-trip through the cache dtype, as stored keys would be
            k_att = jnp.concatenate(
                [cache["k"], k.astype(cache["k"].dtype)],
                axis=1).astype(q.dtype)
            v_att = jnp.concatenate(
                [cache["v"], v.astype(cache["v"].dtype)],
                axis=1).astype(q.dtype)
            pos_att = jnp.concatenate([cache["pos"], chunk_pos], axis=1)
        else:
            k_att = new_cache["k"].astype(q.dtype)
            v_att = new_cache["v"].astype(q.dtype)
            pos_att = new_cache["pos"]
        mask = make_attention_mask(pos_q, pos_att, causal=cfg.causal,
                                   window=cfg.window, k_valid=pos_att >= 0)
        out = dot_product_attention(q, _repeat_kv(k_att, n_rep),
                                    _repeat_kv(v_att, n_rep), mask, cfg.scale)
        return out, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (VLM image layers / enc-dec decoder)
# ---------------------------------------------------------------------------

class CrossAttention:
    @staticmethod
    def init(key, cfg: AttnConfig, *, kv_dim: Optional[int] = None,
             param_dtype=jnp.float32):
        kv_dim = kv_dim or cfg.dim
        keys = jax.random.split(key, 4)
        return {
            "wq": Linear.init(keys[0], cfg.dim, cfg.n_heads * cfg.head_dim,
                              use_bias=cfg.qkv_bias, param_dtype=param_dtype),
            "wk": Linear.init(keys[1], kv_dim, cfg.n_kv_heads * cfg.head_dim,
                              use_bias=cfg.qkv_bias, param_dtype=param_dtype),
            "wv": Linear.init(keys[2], kv_dim, cfg.n_kv_heads * cfg.head_dim,
                              use_bias=cfg.qkv_bias, param_dtype=param_dtype),
            "wo": Linear.init(keys[3], cfg.n_heads * cfg.head_dim, cfg.dim,
                              use_bias=False, param_dtype=param_dtype),
        }

    @staticmethod
    def precompute_kv(params, context, cfg: AttnConfig):
        """Compute K/V once per request from context embeddings (B, Lc, kv_dim)."""
        b, lc, _ = context.shape
        k = Linear.apply(params["wk"], context).reshape(b, lc, cfg.n_kv_heads,
                                                        cfg.head_dim)
        v = Linear.apply(params["wv"], context).reshape(b, lc, cfg.n_kv_heads,
                                                        cfg.head_dim)
        return {"k": k, "v": v}

    @staticmethod
    def apply(params, x, kv, cfg: AttnConfig, *, context_mask=None):
        b, l, _ = x.shape
        lc = kv["k"].shape[1]
        q = Linear.apply(params["wq"], x).reshape(b, l, cfg.n_heads, cfg.head_dim)
        n_rep = cfg.n_heads // cfg.n_kv_heads
        if context_mask is None:
            mask = jnp.ones((b, 1, l, lc), dtype=bool)
        else:
            mask = context_mask[:, None, None, :]
        out = dot_product_attention(q, _repeat_kv(kv["k"].astype(q.dtype), n_rep),
                                    _repeat_kv(kv["v"].astype(q.dtype), n_rep),
                                    mask, cfg.scale)
        out = out.reshape(b, l, cfg.n_heads * cfg.head_dim)
        return Linear.apply(params["wo"], out)


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V3, arXiv:2412.19437)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MLAConfig:
    dim: int
    n_heads: int
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def scale(self) -> float:
        return self.qk_head_dim ** -0.5

    @property
    def cache_width(self) -> int:
        # Compressed cache per token: latent + shared rope key.
        return self.kv_lora_rank + self.qk_rope_head_dim


class MLA:
    """DeepSeek MLA: low-rank compressed Q and KV; the decode cache stores the
    (kv_lora_rank + rope) latent per token instead of per-head K/V."""

    @staticmethod
    def init(key, cfg: MLAConfig, *, param_dtype=jnp.float32):
        keys = jax.random.split(key, 7)
        h, r = cfg.n_heads, cfg.kv_lora_rank
        return {
            "wq_a": Linear.init(keys[0], cfg.dim, cfg.q_lora_rank,
                                param_dtype=param_dtype),
            "wq_b": Linear.init(keys[1], cfg.q_lora_rank,
                                h * cfg.qk_head_dim, param_dtype=param_dtype),
            "wkv_a": Linear.init(keys[2], cfg.dim,
                                 r + cfg.qk_rope_head_dim,
                                 param_dtype=param_dtype),
            "wk_b": Linear.init(keys[3], r, h * cfg.qk_nope_head_dim,
                                param_dtype=param_dtype),
            "wv_b": Linear.init(keys[4], r, h * cfg.v_head_dim,
                                param_dtype=param_dtype),
            "wo": Linear.init(keys[5], h * cfg.v_head_dim, cfg.dim,
                              param_dtype=param_dtype),
        }

    @staticmethod
    def init_cache(cfg: MLAConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
        return {
            "ckv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
            "pos": jnp.full((batch, max_len), -1, jnp.int32),
        }

    @staticmethod
    def init_paged_cache(cfg: MLAConfig, pool_pages: int, page_size: int,
                         dtype=jnp.bfloat16):
        """Pooled latent cache for paged decode: the per-token
        (kv_lora_rank + rope) latent rows are position-indexed exactly like
        K/V, so they share the page pool / block-table machinery of
        ``Attention.init_paged_cache`` unchanged (same trash page 0, same
        ``pos`` sentinel layout)."""
        return {
            "ckv_pages": jnp.zeros((pool_pages, page_size, cfg.kv_lora_rank),
                                   dtype),
            "krope_pages": jnp.zeros(
                (pool_pages, page_size, cfg.qk_rope_head_dim), dtype),
            "pos": jnp.full((pool_pages, page_size), -1, jnp.int32),
        }

    @staticmethod
    def _gather_paged_latents(cache, block_table):
        """Reassemble each slot's latent rows from the pool in position
        order (jnp gather reference path): page j of a slot's block table
        covers positions [j*ps, (j+1)*ps), so gathered index p*ps + off ==
        the position itself — the same index↔position layout the contiguous
        cache has.  Unmapped table entries read the trash page with their
        positions forced to -1, contributing an exact zero to the softmax —
        the absorbed-matrix attention consumes the gathered block unchanged
        and bitwise-matches the contiguous path."""
        bt = block_table                               # (B, max_pages)
        safe = jnp.maximum(bt, 0)
        ckv = cache["ckv_pages"][safe]                 # (B, P, ps, r)
        krope = cache["krope_pages"][safe]
        pos = jnp.where(bt[:, :, None] >= 0, cache["pos"][safe], -1)
        b, p, ps = pos.shape
        return (ckv.reshape(b, p * ps, ckv.shape[-1]),
                krope.reshape(b, p * ps, krope.shape[-1]),
                pos.reshape(b, p * ps))

    @staticmethod
    def _queries(params, x, cfg: MLAConfig, positions):
        b, l, _ = x.shape
        q = Linear.apply(params["wq_b"], Linear.apply(params["wq_a"], x))
        q = q.reshape(b, l, cfg.n_heads, cfg.qk_head_dim)
        q_nope = q[..., : cfg.qk_nope_head_dim]
        q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], positions,
                            cfg.rope_theta)
        return jnp.concatenate([q_nope, q_rope], axis=-1)

    @staticmethod
    def _expand_kv(params, ckv, krope, cfg: MLAConfig):
        """latent (B, S, r) + shared rope key (B, S, rope) -> per-head K/V."""
        b, s, _ = ckv.shape
        k_nope = Linear.apply(params["wk_b"], ckv).reshape(
            b, s, cfg.n_heads, cfg.qk_nope_head_dim)
        v = Linear.apply(params["wv_b"], ckv).reshape(
            b, s, cfg.n_heads, cfg.v_head_dim)
        k_rope = jnp.broadcast_to(krope[:, :, None, :],
                                  (b, s, cfg.n_heads, cfg.qk_rope_head_dim))
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
        return k, v

    @staticmethod
    def apply(params, x, cfg: MLAConfig, *, positions, cache=None,
              cache_index=None, block_table=None, chunk_lens=None):
        b, l, _ = x.shape
        q = MLA._queries(params, x, cfg, positions)
        kv_a = Linear.apply(params["wkv_a"], x)
        ckv, krope_raw = jnp.split(kv_a, [cfg.kv_lora_rank], axis=-1)
        krope = apply_rope(krope_raw[:, :, None, :], positions,
                           cfg.rope_theta)[:, :, 0, :]

        if cache is not None and chunk_lens is not None:
            # Chunked decode: write up to C latent rows per slot (invalid
            # rows are exact no-op writes, same gather → where → scatter as
            # the GQA path; paged: invalid rows land on the trash page),
            # then run the absorbed-matrix attention with a (B, C) query
            # block.
            row_ok = jnp.arange(l)[None, :] < jnp.asarray(chunk_lens,
                                                          jnp.int32)[:, None]
            pos_q = jnp.asarray(positions, jnp.int32)
            if "ckv_pages" in cache:
                assert block_table is not None, \
                    "paged MLA cache needs a block_table"
                ps = cache["pos"].shape[1]
                rows = jnp.arange(b)[:, None]
                page_idx = jnp.clip(pos_q // ps, 0, block_table.shape[1] - 1)
                page_ids = jnp.maximum(block_table[rows, page_idx], 0)
                page_ids = jnp.where(row_ok, page_ids, 0)  # invalid: trash
                off = pos_q % ps
                with jax.named_scope("kv_write"):
                    new_cache = {
                        "ckv_pages": cache["ckv_pages"].at[page_ids, off].set(
                            ckv.astype(cache["ckv_pages"].dtype)),
                        "krope_pages":
                            cache["krope_pages"].at[page_ids, off].set(
                                krope.astype(cache["krope_pages"].dtype)),
                        "pos": cache["pos"].at[page_ids, off].set(
                            jnp.where(row_ok, pos_q, -1)),
                    }
                ckv_g, krope_g, pos_g = MLA._gather_paged_latents(
                    new_cache, block_table)
                out = MLA._absorbed_attention(
                    params, q, ckv_g, krope_g, pos_g, pos_q, cfg)
            else:
                s_len = cache["ckv"].shape[1]
                idx = (pos_q % s_len).astype(jnp.int32)
                new_cache = masked_chunk_write(
                    cache, idx, row_ok, {"ckv": ckv, "krope": krope}, pos_q)
                out = MLA._absorbed_attention(
                    params, q, new_cache["ckv"], new_cache["krope"],
                    new_cache["pos"], pos_q, cfg)
            out = out.reshape(b, l, cfg.n_heads * cfg.v_head_dim)
            return Linear.apply(params["wo"], out), new_cache

        if cache is None or l > 1:
            k, v = MLA._expand_kv(params, ckv, krope, cfg)
            if l >= CHUNKED_ATTN_THRESHOLD:
                out = chunked_dot_product_attention(
                    q, k, v, positions, positions, cfg.scale, causal=True,
                    window=None)
            else:
                mask = make_attention_mask(positions, positions, causal=True,
                                           window=None)
                out = dot_product_attention(q, k, v, mask, cfg.scale)
            new_cache = None
            if cache is not None:  # prefill: fill the compressed cache
                new_cache = {
                    "ckv": jax.lax.dynamic_update_slice(
                        cache["ckv"], ckv.astype(cache["ckv"].dtype),
                        (0, 0, 0)),
                    "krope": jax.lax.dynamic_update_slice(
                        cache["krope"], krope.astype(cache["krope"].dtype),
                        (0, 0, 0)),
                    "pos": jax.lax.dynamic_update_slice(
                        cache["pos"],
                        jnp.broadcast_to(positions, (b, l)).astype(jnp.int32),
                        (0, 0)),
                }
        elif "ckv_pages" in cache:
            # Paged absorbed-matrix decode: the latent write routes through
            # the block table exactly like the GQA paged path (empty slots
            # land on the reserved trash page 0); the attention gathers each
            # slot's pages in position order, so it is bit-for-bit the
            # contiguous latent cache.
            assert block_table is not None, \
                "paged MLA cache needs a block_table"
            ps = cache["pos"].shape[1]
            ci_v = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
            rows = jnp.arange(b)
            page_idx = jnp.clip(ci_v // ps, 0, block_table.shape[1] - 1)
            page_ids = jnp.maximum(block_table[rows, page_idx], 0)
            off = ci_v % ps
            pos_q = jnp.broadcast_to(positions, (b, 1))
            with jax.named_scope("kv_write"):
                new_cache = {
                    "ckv_pages": cache["ckv_pages"].at[page_ids, off].set(
                        ckv[:, 0].astype(cache["ckv_pages"].dtype)),
                    "krope_pages": cache["krope_pages"].at[page_ids, off].set(
                        krope[:, 0].astype(cache["krope_pages"].dtype)),
                    "pos": cache["pos"].at[page_ids, off].set(
                        pos_q[:, 0].astype(jnp.int32)),
                }
            ckv_g, krope_g, pos_g = MLA._gather_paged_latents(
                new_cache, block_table)
            out = MLA._absorbed_attention(
                params, q, ckv_g, krope_g, pos_g, pos_q, cfg)
        else:
            # Absorbed-matrix decode (DeepSeek-V3 serving form): attention is
            # computed entirely in the compressed latent space, so the cache is
            # never expanded to per-head K/V (that would be O(S*H*d) bytes).
            ci = jnp.asarray(cache_index, jnp.int32)
            with jax.named_scope("kv_write"):
                if ci.ndim:
                    # Per-slot positions (B,): per-row latent-cache writes.
                    rows = jnp.arange(b)
                    ckv_c = cache["ckv"].at[rows, ci].set(
                        ckv[:, 0].astype(cache["ckv"].dtype))
                    krope_c = cache["krope"].at[rows, ci].set(
                        krope[:, 0].astype(cache["krope"].dtype))
                    pos = cache["pos"].at[rows, ci].set(
                        jnp.broadcast_to(positions, (b, 1))[:, 0]
                        .astype(jnp.int32))
                else:
                    ckv_c = jax.lax.dynamic_update_slice(
                        cache["ckv"], ckv.astype(cache["ckv"].dtype),
                        (0, ci, 0))
                    krope_c = jax.lax.dynamic_update_slice(
                        cache["krope"], krope.astype(cache["krope"].dtype),
                        (0, ci, 0))
                    pos = jax.lax.dynamic_update_slice(
                        cache["pos"],
                        jnp.broadcast_to(positions, (b, 1)).astype(jnp.int32),
                        (0, ci))
            new_cache = {"ckv": ckv_c, "krope": krope_c, "pos": pos}
            out = MLA._absorbed_attention(
                params, q, ckv_c, krope_c, pos,
                jnp.broadcast_to(positions, (b, 1)), cfg)

        out = out.reshape(b, l, cfg.n_heads * cfg.v_head_dim)
        return Linear.apply(params["wo"], out), new_cache

    @staticmethod
    def _absorbed_attention(params, q, ckv_c, krope_c, pos, q_pos,
                            cfg: MLAConfig):
        """Absorbed-matrix decode attention (DeepSeek-V3 serving form) for a
        (B, Lq) query block over the compressed latent cache — attention is
        computed entirely in latent space, never expanding per-head K/V."""
        q_nope = q[..., : cfg.qk_nope_head_dim]
        q_rope = q[..., cfg.qk_nope_head_dim:]
        # Absorb W_uk into the query:  q_lat[h] = W_uk[h]^T q_nope[h]
        w_uk = params["wk_b"]["w"].astype(q.dtype).reshape(
            cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim)
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, w_uk)
        ckv_f = ckv_c.astype(q.dtype)
        logits = (jnp.einsum("bqhr,bsr->bhqs", q_lat, ckv_f) +
                  jnp.einsum("bqhd,bsd->bhqs", q_rope,
                             krope_c.astype(q.dtype)))
        logits = logits.astype(jnp.float32) * cfg.scale
        mask = make_attention_mask(q_pos, pos, causal=True, window=None,
                                   k_valid=pos >= 0)
        logits = jnp.where(mask, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", probs, ckv_f)
        # Absorb W_uv on the way out:  out[h] = W_uv[h] o_lat[h]
        w_uv = params["wv_b"]["w"].astype(q.dtype).reshape(
            cfg.kv_lora_rank, cfg.n_heads, cfg.v_head_dim)
        return jnp.einsum("bqhr,rhv->bqhv", o_lat, w_uv)
