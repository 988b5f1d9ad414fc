"""Fused index-embed demultiplexer MLP as a Pallas TPU kernel.

The jnp reference materialises the concatenated (B, N, L, 2d) tensor in HBM
(the demux is applied per multiplex index ⇒ the one place DataMUX pays an
N-fold activation cost).  Splitting the first weight into its h-rows and
p-rows turns the concat into two matmuls that never leave VMEM:

  out[b, n, l] = gelu(h[b, l]·W1h + p[b, n]·W1p + b1) · W2 + b2

Grid (B, N, L/BL, H/BH) — the hidden axis is the *last* (fastest) grid dim,
so the f32 accumulator scratch stays resident while the H tiles stream
through; the (BL, d) output tile is written once on the final H step.

VMEM claim per step: h (BL·d) + W1h/W1p (d·BH each) + W2 (BH·d) + acc
(BL·d f32); ``kernels.tiling.pick_tiles`` keeps the total under the v5e
budget, last dims 128-aligned.

``decode_demux`` is the decode-epilogue specialisation (L == C small): one
program holds ALL N lanes with h resident in VMEM, so the shared h·W1h
matmul is computed once per slot instead of once per lane — the demux is
applied before the hidden state ever round-trips through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import pick_hidden_tile, pick_tiles  # noqa: F401
# (pick_tiles re-exported: it lived here before moving to kernels.tiling)


def _demux_kernel(h_ref, p_ref, w1h_ref, w1p_ref, b1_ref, w2_ref, b2_ref,
                  o_ref, acc_ref, *, n_hblocks: int):
    kh = pl.program_id(3)

    @pl.when(kh == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(
            b2_ref[...].astype(jnp.float32), acc_ref.shape)

    h = h_ref[0].astype(jnp.float32)          # (BL, d)
    p = p_ref[0, 0].astype(jnp.float32)       # (1, d)
    w1h = w1h_ref[...].astype(jnp.float32)    # (d, BH)
    w1p = w1p_ref[...].astype(jnp.float32)
    z = h @ w1h + p @ w1p + b1_ref[...].astype(jnp.float32)  # (BL, BH)
    a = jax.nn.gelu(z)
    acc_ref[...] += a @ w2_ref[...].astype(jnp.float32)      # (BL, d)

    @pl.when(kh == n_hblocks - 1)
    def _done():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_embed_demux(mlp_params, h, index_embeds, *, interpret: bool = False):
    """2-layer shared demux MLP, fused.  h (B, L, d); p (B, N, d) ->
    (B, N, L, d).  p enters as (B, N, 1, d) so its per-lane (1, d) block is
    whole in its last two dims (Mosaic refuses a (1, d) block of (N, d))."""
    b, l, d = h.shape
    n = index_embeds.shape[1]
    w1 = mlp_params["l0"]["w"]
    b1 = mlp_params["l0"]["b"]
    w2 = mlp_params["l1"]["w"]
    b2 = mlp_params["l1"]["b"]
    hidden = w1.shape[1]
    assert w1.shape[0] == 2 * d and w2.shape == (hidden, d)
    w1h, w1p = w1[:d], w1[d:]

    bl, bh = pick_tiles(d, hidden, h.dtype.itemsize)
    lp, hp = -l % bl, -hidden % bh
    if lp:
        h = jnp.pad(h, ((0, 0), (0, lp), (0, 0)))
    if hp:
        w1h = jnp.pad(w1h, ((0, 0), (0, hp)))
        w1p = jnp.pad(w1p, ((0, 0), (0, hp)))
        b1 = jnp.pad(b1, (0, hp))
        w2 = jnp.pad(w2, ((0, hp), (0, 0)))
    lpad, hpad = l + lp, hidden + hp
    n_hblocks = hpad // bh
    dt = h.dtype

    out = pl.pallas_call(
        functools.partial(_demux_kernel, n_hblocks=n_hblocks),
        grid=(b, n, lpad // bl, n_hblocks),
        in_specs=[
            pl.BlockSpec((1, bl, d), lambda i, j, m, k: (i, m, 0)),     # h
            pl.BlockSpec((1, 1, 1, d), lambda i, j, m, k: (i, j, 0, 0)),  # p
            pl.BlockSpec((d, bh), lambda i, j, m, k: (0, k)),           # W1h
            pl.BlockSpec((d, bh), lambda i, j, m, k: (0, k)),           # W1p
            pl.BlockSpec((1, bh), lambda i, j, m, k: (0, k)),           # b1
            pl.BlockSpec((bh, d), lambda i, j, m, k: (k, 0)),           # W2
            pl.BlockSpec((1, d), lambda i, j, m, k: (0, 0)),            # b2
        ],
        out_specs=pl.BlockSpec((1, 1, bl, d), lambda i, j, m, k: (i, j, m, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, lpad, d), dt),
        scratch_shapes=[pltpu.VMEM((bl, d), jnp.float32)],
        interpret=interpret,
    )(h, index_embeds.astype(dt)[:, :, None, :], w1h.astype(dt),
      w1p.astype(dt), b1.reshape(1, -1).astype(dt), w2.astype(dt),
      b2.reshape(1, -1).astype(dt))
    return out[:, :, :l, :]


def _decode_demux_kernel(h_ref, p_ref, w1h_ref, w1p_ref, b1_ref, w2_ref,
                         b2_ref, o_ref, acc_ref, *, n_hblocks: int):
    kh = pl.program_id(1)

    @pl.when(kh == 0)
    def _init():
        acc_ref[...] = jnp.broadcast_to(
            b2_ref[...].astype(jnp.float32)[None], acc_ref.shape)

    h = h_ref[0].astype(jnp.float32)          # (C, d)
    p = p_ref[0].astype(jnp.float32)          # (N, d)
    w1h = w1h_ref[...].astype(jnp.float32)    # (d, BH)
    w1p = w1p_ref[...].astype(jnp.float32)
    zh = h @ w1h                              # (C, BH): once, not per lane
    zp = p @ w1p                              # (N, BH)
    z = zh[None] + zp[:, None] + b1_ref[...].astype(jnp.float32)
    a = jax.nn.gelu(z)                        # (N, C, BH)
    # (N, C, d): contract BH, no batch dims.
    acc_ref[...] += jax.lax.dot_general(
        a, w2_ref[...].astype(jnp.float32), (((2,), (0,)), ((), ())))

    @pl.when(kh == n_hblocks - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_demux(mlp_params, h, index_embeds, *, interpret: bool = False):
    """Decode-epilogue demux: h (B, C, d), C the (small) decode chunk width;
    p (B, N, d) -> (B, N, C, d).

    Same split-W1 math as ``index_embed_demux`` but one grid step holds all
    N lanes of a slot: grid (B, H/BH), the mixed state h stays resident in
    VMEM across the whole epilogue, and the shared z_h = h·W1h is computed
    once per slot instead of N times.  The f32 accumulator is (N, C, d) —
    tiny at decode widths — and the demuxed output is written once on the
    final H step, so the attention-side hidden state is demuxed in VMEM
    before anything is written back to HBM.
    """
    b, c, d = h.shape
    n = index_embeds.shape[1]
    w1 = mlp_params["l0"]["w"]
    b1 = mlp_params["l0"]["b"]
    w2 = mlp_params["l1"]["w"]
    b2 = mlp_params["l1"]["b"]
    hidden = w1.shape[1]
    assert w1.shape[0] == 2 * d and w2.shape == (hidden, d)
    w1h, w1p = w1[:d], w1[d:]

    bh = pick_hidden_tile(d, hidden, n * c, h.dtype.itemsize)
    hp = -hidden % bh
    if hp:
        w1h = jnp.pad(w1h, ((0, 0), (0, hp)))
        w1p = jnp.pad(w1p, ((0, 0), (0, hp)))
        b1 = jnp.pad(b1, (0, hp))
        w2 = jnp.pad(w2, ((0, hp), (0, 0)))
    n_hblocks = (hidden + hp) // bh
    dt = h.dtype

    out = pl.pallas_call(
        functools.partial(_decode_demux_kernel, n_hblocks=n_hblocks),
        grid=(b, n_hblocks),
        in_specs=[
            pl.BlockSpec((1, c, d), lambda i, k: (i, 0, 0)),      # h
            pl.BlockSpec((1, n, d), lambda i, k: (i, 0, 0)),      # p
            pl.BlockSpec((d, bh), lambda i, k: (0, k)),           # W1h
            pl.BlockSpec((d, bh), lambda i, k: (0, k)),           # W1p
            pl.BlockSpec((1, bh), lambda i, k: (0, k)),           # b1
            pl.BlockSpec((bh, d), lambda i, k: (k, 0)),           # W2
            pl.BlockSpec((1, d), lambda i, k: (0, 0)),            # b2
        ],
        out_specs=pl.BlockSpec((1, n, c, d), lambda i, k: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, c, d), dt),
        scratch_shapes=[pltpu.VMEM((n, c, d), jnp.float32)],
        interpret=interpret,
    )(h, index_embeds.astype(dt), w1h.astype(dt), w1p.astype(dt),
      b1.reshape(1, -1).astype(dt), w2.astype(dt),
      b2.reshape(1, -1).astype(dt))
    return out
