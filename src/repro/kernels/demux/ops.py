"""Public op: fused index-embed demux (interpreted off-TPU).

Reached through the strategy registry: ``IndexEmbedDemux.kernel_apply``
(``repro.core.strategies.demux``) routes here when ``cfg.use_kernel`` is
set.  Falls back to the jnp reference when the shared MLP is not the
fused-kernel 2-layer shape (``demux_layers != 2``).
"""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.demux import kernel, ref


def index_embed_demux(mlp_params, h, index_embeds):
    """h: (B, L, d); index_embeds: (B, N, d) -> (B, N, L, d)."""
    if set(mlp_params) != {"l0", "l1"}:
        return ref.index_embed_demux(mlp_params, h, index_embeds)
    return kernel.index_embed_demux(mlp_params, h, index_embeds,
                                    interpret=interpret_mode())


def decode_demux(mlp_params, h, index_embeds):
    """Decode-epilogue fused demux: h (B, C, d) with C the decode chunk
    width -> (B, N, C, d).  Reached through ``IndexEmbedDemux.decode_apply``
    when ``ServingConfig.fuse_demux`` is set; falls back to the jnp
    reference when the shared MLP is not the fused-kernel 2-layer shape."""
    if set(mlp_params) != {"l0", "l1"}:
        return ref.index_embed_demux(mlp_params, h, index_embeds)
    return kernel.decode_demux(mlp_params, h, index_embeds,
                               interpret=interpret_mode())
