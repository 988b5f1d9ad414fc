"""Public op: causal flash attention (interpreted off-TPU)."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.attention import kernel


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None):
    """q, k, v: (B, L, H, hd) -> (B, L, H, hd)."""
    return kernel.flash_attention(q, k, v, causal=causal, scale=scale,
                                  interpret=interpret_mode())
