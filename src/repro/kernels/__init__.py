"""Pallas TPU kernels for DataMUX hot spots (DESIGN.md §3).

Four kernels, each a package with:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — public wrapper; interprets the kernel off-TPU
              (``interpret_mode``, asked at call time)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

  multiplex/  fused φ-transform + accumulate:  (B,N,L,d)×(N,d) -> (B,L,d)
              in ONE VMEM pass instead of N HBM round-trips.
  demux/      fused index-embed demultiplexer MLP: computes
              gelu(h·W1h + p·W1p + b1)·W2 + b2 without materialising the
              (B,N,L,2d) concat in HBM.
  attention/  causal flash attention (prefill hot spot), online-softmax
              accumulation over K tiles.
  paged_attention/  decode attention over a paged KV pool through a
              scalar-prefetched block table.
"""
import jax


def interpret_mode() -> bool:
    """True unless the default backend is a TPU: Pallas kernels compile
    with Mosaic there and run in the interpreter everywhere else.  The ops
    wrappers ask at call (trace) time, so importing a kernel never starts a
    backend, and a run on the chip never interprets."""
    return jax.default_backend() != "tpu"
