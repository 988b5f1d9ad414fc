"""The Pallas kernels compile for a TPU v5e at qwen1.5-4b's widths.

Interpret-mode tests (``test_kernels.py``) check numerics; they cannot see
what Mosaic refuses (blocks that are neither tile-aligned nor whole in
their last two dims, VMEM overruns).  Here each kernel is compiled with
the TPU compiler for one chip of a *described* v5e:2x2 — no chip attached,
nothing runs — and the compiled program must hold the kernel as a
``tpu_custom_call``.  Widths: d 2560, 20 heads of 128, mux N = 8 (and the
paper's N = 40 at d 768 for the mux), bf16.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.attention import kernel as att_kernel
from repro.kernels.demux import kernel as demux_kernel
from repro.kernels.multiplex import kernel as mux_kernel
from repro.kernels.paged_attention import kernel as paged_kernel

D, HEADS, HD, N = 2560, 20, 128, 8
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off while this module compiles (a described-chip entry could not
    be read back without the chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def S(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compiled_text(one_chip, fn, *args):
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        args)
    return jax.jit(fn).lower(*args).compile().as_text()


def _demux_mlp(d):
    hidden = 2 * d
    return {"l0": {"w": S((2 * d, hidden)), "b": S((hidden,))},
            "l1": {"w": S((hidden, d)), "b": S((d,))}}


@pytest.mark.parametrize("n,d,l", [(8, D, 1), (8, D, 128), (40, 768, 128)])
def test_hadamard_mux_compiles(one_chip, n, d, l):
    text = _compiled_text(one_chip, mux_kernel.hadamard_mux,
                          S((2, n, l, d)), S((n, d)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("l", [1, 128])
def test_index_embed_demux_compiles(one_chip, l):
    text = _compiled_text(one_chip, demux_kernel.index_embed_demux,
                          _demux_mlp(D), S((2, l, D)), S((2, N, D)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("c", [1, 4])
def test_decode_demux_compiles(one_chip, c):
    text = _compiled_text(one_chip, demux_kernel.decode_demux,
                          _demux_mlp(D), S((2, c, D)), S((2, N, D)))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(one_chip):
    q = S((1, 1024, HEADS, HD))
    text = _compiled_text(one_chip, att_kernel.flash_attention, q, q, q)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("ps,kblock,c", [(16, 1, 1), (16, 4, 1), (128, 1, 1),
                                         (128, 4, 1), (16, 1, 4)])
def test_paged_decode_attention_compiles(one_chip, ps, kblock, c):
    b, max_pages = 4, 16
    pool = b * max_pages + 1

    def attend(q, k, v, pos, bt, q_pos):
        return paged_kernel.paged_decode_attention(
            q, k, v, pos, bt, q_pos, scale=HD ** -0.5, kblock_pages=kblock)

    text = _compiled_text(
        one_chip, attend, S((b, c, HEADS, HD)), S((pool, ps, HEADS, HD)),
        S((pool, ps, HEADS, HD)), S((pool, ps), jnp.int32),
        S((b, max_pages), jnp.int32), S((b, c), jnp.int32))
    assert "tpu_custom_call" in text
