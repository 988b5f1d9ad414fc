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
                    kblock_pages: int = 1):
    """q: (B, C, H, hd) -> (B, C, H, hd); see ``ref.paged_attention``.

    ``kblock_pages`` only shapes the kernel's grid (block-table entries
    spanned per invocation); the reference is layout-free and ignores it.
    """
    if use_kernel:
        return kernel.paged_decode_attention(
            q, k_pages, v_pages, pos_pages, block_table, q_pos, scale=scale,
            causal=causal, window=window, kblock_pages=kblock_pages,
            interpret=interpret_mode())
    return ref.paged_attention(q, k_pages, v_pages, pos_pages, block_table,
                               q_pos, scale=scale, causal=causal,
                               window=window)
