"""Public op: paged-attention decode (interpreted off-TPU).

``use_kernel=False`` (the default) routes through the jnp gather reference,
which is bit-for-bit identical to the contiguous decode path; the Pallas
kernel streams pages through the block table instead of materialising the
gathered (B, S, H, hd) view.
"""
from __future__ import annotations

from typing import Optional

from repro.kernels import interpret_mode
from repro.kernels.paged_attention import kernel, ref


def paged_attention(q, k_pages, v_pages, pos_pages, block_table, q_pos, *,
                    scale: float, causal: bool = True,
                    window: Optional[int] = None, use_kernel: bool = False,
                    kblock_pages: int = 1, layer=None,
                    kv_heads: Optional[int] = None):
    """q: (B, C, H, hd) -> (B, C, H, hd); see ``ref.paged_attention``.

    ``kblock_pages`` only shapes the kernel's grid (block-table entries
    spanned per invocation); the reference is layout-free and ignores it.
    ``layer``: the pools are the layer scan's stacked ones; the reference
    gathers that layer's pages straight from them, the kernel takes the
    layer's (P, ps, ...) pool out first (its (P, ps, KVH*hd) view is not a
    free reshape of the stacked pool's tiled layout).  ``kv_heads``: the
    real heads of pools padded to ``nn.attention.pool_kv_heads``.
    """
    if use_kernel:
        if layer is not None:
            k_pages, v_pages, pos_pages = (
                a[layer] for a in (k_pages, v_pages, pos_pages))
        return kernel.paged_decode_attention(
            q, k_pages, v_pages, pos_pages, block_table, q_pos, scale=scale,
            causal=causal, window=window, kblock_pages=kblock_pages,
            kv_heads=kv_heads, interpret=interpret_mode())
    return ref.paged_attention(q, k_pages, v_pages, pos_pages, block_table,
                               q_pos, scale=scale, causal=causal,
                               window=window, layer=layer, kv_heads=kv_heads)
