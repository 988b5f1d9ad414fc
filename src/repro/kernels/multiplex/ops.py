"""Public op: fused Hadamard multiplexer (interpreted off-TPU).

Reached through the strategy registry: ``HadamardMux.kernel_apply``
(``repro.core.strategies.linear``) routes here when ``cfg.use_kernel`` is
set.  A new strategy gets a fused path by implementing its own
``kernel_apply`` + ``uses_kernel = True`` — this module stays
strategy-agnostic.
"""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.multiplex import kernel


def hadamard_mux(x, v):
    """x: (B, N, L, d); v: (N, d) -> (B, L, d)."""
    return kernel.hadamard_mux(x, v, interpret=interpret_mode())
