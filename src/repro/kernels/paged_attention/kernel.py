"""Paged-attention decode as a Pallas TPU kernel (serving hot spot).

A C-row query block per backbone slot (C == 1 for plain decode, C > 1 for
chunked prefill) attends over that slot's KV pages, gathered from the
shared pool through a scalar-prefetched block table:

  grid (B, KVH, ceil(max_pages / kblock_pages)) — the K-block axis is the
  last (fastest) grid dim; the block table rides in SMEM via
  ``PrefetchScalarGridSpec`` so the K/V/pos BlockSpec index maps can turn a
  (slot, page-index) grid point into a pool-page DMA before the body runs —
  the kernel never materialises the gathered (B, S, H, hd) view the jnp
  reference builds.  Per-row query positions ride in SMEM too (a second
  scalar-prefetch operand): they gate masking, and a (1, C) VMEM block of a
  (B, C) array is not a tile Mosaic accepts.

One invocation spans a *K-block* of ``kblock_pages`` consecutive
block-table entries: the same pool arrays are passed once per block
position with per-position index maps ``bt[i, p*kblock + j]``, and the body
concatenates the fetched (ps, hd) tiles into a single
(kblock_pages·ps, hd) K/V tile for one MXU-shaped dot_general.  At the
allocator-friendly small page sizes this is what reaches the >=128-row
tiles the MXU wants — kblock_pages=1 reproduces the historical
page-at-a-time kernel exactly.

Per-program blocks are (C·n_rep, hd) queries (the GQA group sharing one KV
head, per chunk row) against one K-block of (ps, hd) page tiles (the pool
viewed as (P, ps, KVH·hd), so every block is whole or 128-aligned in its
last two dims, as Mosaic requires), with the canonical online-softmax
scratch (f32 accumulator + running max / normaliser) flushed on the final
K-block.  VMEM claim is O(C·n_rep·hd + kblock_pages·ps·hd) — independent of
both the pool size and the slot's live length; ``kernels.tiling``
validates the K-block claim against the budget at config time and here.

Masking: a page's ``pos`` row carries -1 for unwritten entries, and an
unmapped block-table entry (-1) folds its whole page to -1 positions, so
both contribute an exact zero through the shared ``k_pos >= 0`` term.
Unmapped entries are clamped to pool page 0 for the DMA (the streamed bytes
are garbage but masked); a K-block whose entries are *all* -1 is
``pl.when``-skipped outright — no dot_generals issued, no garbage streamed
through the softmax.  The skip changes nothing for any query row with at
least one valid key anywhere in the slot (a masked block's contribution is
annihilated exactly: exp(-1e30 - m) underflows to 0.0 and the alpha
rescale from a NEG_INF running max is an exact 0); rows with *zero* valid
keys are garbage in every implementation and callers mask those lanes out.

Off-TPU the kernel runs in interpret mode; numerics match the jnp
reference either way.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling

NEG_INF = -1e30


def _paged_kernel(bt_ref, qp_ref, q_ref, *refs, scale: float, causal: bool,
                  window: Optional[int], n_blocks: int, kblock: int,
                  c: int, n_rep: int):
    k_refs = refs[:kblock]
    v_refs = refs[kblock:2 * kblock]
    pos_refs = refs[2 * kblock:3 * kblock]
    o_ref = refs[3 * kblock]
    acc_ref, m_ref, l_ref = refs[3 * kblock + 1:]
    i, p = pl.program_id(0), pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    # Block-table entries of this K-block (SMEM scalars; also feed the
    # BlockSpec index maps, so an in-bounds read is guaranteed: the wrapper
    # pads the table to a multiple of kblock with -1).
    bts = [bt_ref[i, p * kblock + j] for j in range(kblock)]
    mapped_any = bts[0] >= 0
    for e in bts[1:]:
        mapped_any = mapped_any | (e >= 0)

    @pl.when(mapped_any)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)       # (C*n_rep, hd)
        # Assemble the K-block: kblock (ps, hd) page tiles -> one MXU-shaped
        # (kblock*ps, hd) tile, then a single dot_general over it.
        k = jnp.concatenate(
            [k_refs[j][0] for j in range(kblock)],
            axis=0).astype(jnp.float32)           # (kblock*ps, hd)
        # (C*n_rep, kblock*ps): contract hd, no batch dims.
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

        # Positions, with unmapped pages folded to the -1 sentinel so the
        # single ``k_pos >= 0`` term masks unwritten AND unmapped entries.
        k_pos = jnp.concatenate(
            [jnp.where(bts[j] >= 0, pos_refs[j][0], -1)
             for j in range(kblock)], axis=1)     # (1, kblock*ps) int32
        # Query row r is chunk row r // n_rep: broadcast its SMEM position
        # across the row, one select per chunk row.
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        q_pos = jnp.full(s.shape, qp_ref[i, 0], jnp.int32)
        for r in range(1, c):
            q_pos = jnp.where(row >= r * n_rep, qp_ref[i, r], q_pos)
        diff = q_pos - k_pos                      # (C*n_rep, kblock*ps)
        keep = k_pos >= 0
        if causal:
            keep = keep & (diff >= 0)
        if window is not None:
            keep = keep & (diff < window)
        s = jnp.where(keep, s, NEG_INF)

        m_prev, l_prev = m_ref[...], l_ref[...]   # (C*n_rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pr = jnp.exp(s - m_new)                   # (C*n_rep, kblock*ps)
        l_ref[...] = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        m_ref[...] = m_new
        v = jnp.concatenate(
            [v_refs[j][0] for j in range(kblock)],
            axis=0).astype(jnp.float32)           # (kblock*ps, hd)
        acc_ref[...] = acc_ref[...] * alpha + \
            jax.lax.dot_general(pr, v, (((1,), (0,)), ((), ())))

    @pl.when(p == n_blocks - 1)
    def _done():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "causal", "window",
                                    "kblock_pages", "kv_heads", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, pos_pages, block_table,
                           q_pos, *, scale: float, causal: bool = True,
                           window: Optional[int] = None,
                           kblock_pages: int = 1,
                           kv_heads: Optional[int] = None,
                           interpret: bool = False):
    """q: (B, C, H, hd); k_pages/v_pages: (P, ps, KVH, hd); pos_pages:
    (P, ps) int32; block_table: (B, max_pages) int32; q_pos: (B, C) int32.
    Returns (B, C, H, hd).  C == 1 is the classic single-token decode.

    ``kblock_pages``: block-table entries spanned per kernel invocation —
    the grid's K axis shrinks to ceil(max_pages / kblock_pages) and each
    step runs one (kblock_pages·ps)-row dot_general.  1 = the historical
    page-at-a-time grid, bit-identical.  ``kv_heads``: the real heads of
    pools padded to ``nn.attention.pool_kv_heads`` (default all); the grid
    visits those alone.
    """
    b, c, h, hd = q.shape
    _, ps, pool_heads, _ = k_pages.shape
    kvh = kv_heads or pool_heads
    n_rep = h // kvh
    kblock = int(kblock_pages)
    tiling.validate_kblock(kblock, ps, hd, itemsize=k_pages.dtype.itemsize)
    n_pages = block_table.shape[1]
    pad = -n_pages % kblock
    bt = block_table.astype(jnp.int32)
    if pad:
        # Padded entries are unmapped: masked to exact zero in the body and
        # skipped entirely when a whole K-block lands in the padding.
        bt = jnp.pad(bt, ((0, 0), (0, pad)), constant_values=-1)
    n_blocks = (n_pages + pad) // kblock
    # Mosaic blocks must be tile-aligned or whole in their last two dims.
    # Queries: head order matches _repeat_kv (q head kv*n_rep + r shares KV
    # head kv), folded to (B, KVH, C*n_rep, hd) so each program's block is
    # whole in its last two dims.  Pool pages: the (P, ps, KVH, hd) pool is
    # viewed as (P, ps, KVH*hd) — a free reshape — so one KV head's page is
    # a (ps, hd) block; positions as (P, 1, ps).
    rows = c * n_rep
    qr = q.reshape(b, c, kvh, n_rep, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, kvh, rows, hd)
    k_view = k_pages.reshape(k_pages.shape[0], ps, pool_heads * hd)
    v_view = v_pages.reshape(v_pages.shape[0], ps, pool_heads * hd)
    pos_view = pos_pages.reshape(pos_pages.shape[0], 1, ps)

    def page_spec(j):
        # Pool-page DMA for K-block position j (static per spec): entry
        # bt[i, p*kblock + j], clamped to the trash page when unmapped.
        return pl.BlockSpec(
            (1, ps, hd),
            lambda i, jj, p, bt, qp, j=j:
            (jnp.maximum(bt[i, p * kblock + j], 0), 0, jj))

    def pos_spec(j):
        return pl.BlockSpec(
            (1, 1, ps),
            lambda i, jj, p, bt, qp, j=j:
            (jnp.maximum(bt[i, p * kblock + j], 0), 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # block_table, q_pos
        grid=(b, kvh, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd),
                         lambda i, j, p, bt, qp: (i, j, 0, 0)),
        ] + [page_spec(j) for j in range(kblock)] * 2
          + [pos_spec(j) for j in range(kblock)],
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda i, j, p, bt, qp: (i, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, causal=causal,
                          window=window, n_blocks=n_blocks, kblock=kblock,
                          c=c, n_rep=n_rep),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rows, hd), q.dtype),
        interpret=interpret,
    )(bt, q_pos.astype(jnp.int32), qr,
      *([k_view] * kblock), *([v_view] * kblock), *([pos_view] * kblock))
    return out.reshape(b, kvh, c, n_rep, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(b, c, h, hd)
