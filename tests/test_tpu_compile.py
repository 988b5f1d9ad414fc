"""The Pallas kernels compile for a TPU v5e at qwen1.5-4b's widths.

Interpret-mode tests (``test_kernels.py``) check numerics; they cannot see
what Mosaic refuses (blocks that are neither tile-aligned nor whole in
their last two dims, VMEM overruns).  Here each kernel is compiled with
the TPU compiler for one chip of a *described* v5e:2x2 — no chip attached,
nothing runs — and the compiled program must hold the kernel as a
``tpu_custom_call``.  Widths: d 2560, 20 heads of 128, mux N = 8 (and the
paper's N = 40 at d 768 for the mux), bf16.

The engine's paged decode step is compiled the same way, at qwen1.5-4b's
attention widths: the stacked K/V pools must be updated in place, with no
slice, copy or fresh buffer of a pool, in the row-major layout of the
step's writes, and every page program must take and return the pool in
that layout.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
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


# ---------------------------------------------------------------------------
# The paged decode step and the page programs
# ---------------------------------------------------------------------------

LAYERS, SLOTS, PAGE = 3, 2, 128
# Ops that would move a whole pool (or one layer's pool out of the stack):
# XLA names a fusion after the ops it fuses, so the names count too.
POOL_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice", "broadcast",
              "AllocateBuffer")
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (\S+?)\{.*?\} (\S+?)\((.*)")


def _qwen_paged(chunk):
    """qwen1.5-4b's attention and MLP widths over three scanned layers,
    a small vocabulary, mux N = 8, 128-position pages."""
    from repro.configs.base import MuxConfig, ServingConfig
    from repro.configs.registry import get_config
    return dataclasses.replace(
        get_config("qwen1.5-4b"), n_layers=LAYERS, vocab=1024,
        mux=MuxConfig(n=N, strategy="hadamard", demux="index_embed"),
        serving=ServingConfig(paged=True, page_size=PAGE,
                              prefill_chunk=chunk))


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding), tree)


def _pool_layouts(formats):
    return [{k: f.layout for k, f in layer.items()}
            for layer in formats["blocks"]]


@pytest.mark.parametrize("chunk", [1, 4])
def test_paged_decode_step_updates_pools_in_place(one_chip, chunk):
    from repro.models import Backbone
    from repro.serving.engine import Engine
    from repro.serving.paging import PagedKVSlotAllocator
    cfg = _qwen_paged(chunk)
    params = _on(one_chip, jax.eval_shape(
        lambda k: Backbone.init(k, cfg), jax.random.PRNGKey(0)))
    eng = Engine(params, cfg, batch=SLOTS, max_len=2 * PAGE)
    assert eng.pool_layers_in_carry == LAYERS

    # The page allocator at the same structure, built on the host; its
    # programs are compiled for the described chip from their shapes.
    alloc = PagedKVSlotAllocator(cfg, SLOTS, eng.max_len)
    cache = _on(one_chip, alloc.cache)
    mp = alloc.pages_per_slot
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    lanes = (SLOTS, N) if chunk == 1 else (SLOTS, N, chunk)
    step = eng._step.lower(
        params, i32(*lanes), cache, i32(SLOTS),
        jax.ShapeDtypeStruct((SLOTS, N, cfg.d_model), cfg.compute_dtype,
                             sharding=one_chip),
        None, jax.ShapeDtypeStruct(lanes, jnp.float32, sharding=one_chip),
        i32(SLOTS, mp), None if chunk == 1 else i32(SLOTS)).compile()

    pool = alloc.cache["blocks"][0]["k_pages"]
    shapes = {f"bf16[{','.join(map(str, s))}]"
              for s in (pool.shape, pool.shape[1:])}
    moved = []
    for line in step.as_text().splitlines():
        m = _INSTR.match(line)
        if m and m.group(2) in shapes and any(
                w in m.group(1) or w in m.group(3) or
                (m.group(3) == "custom-call" and w in m.group(4))
                for w in POOL_MOVES):
            moved.append(f"{m.group(1)} {m.group(3)}")
    assert not moved, moved

    want = _pool_layouts(step.input_formats[0][2])
    assert want[0]["k_pages"].major_to_minor == (0, 1, 2, 3, 4)
    assert _pool_layouts(step.output_formats[1]) == want
    npp = alloc.n_prefix_pages
    snap = alloc._snapshot_impl(alloc.cache, 0)
    programs = {
        "invalidate": (alloc._invalidate, (cache, i32(SLOTS))),
        "reset": (alloc._reset, (cache, _on(one_chip, alloc.template),
                                 _on(one_chip, np.zeros(SLOTS, bool)),
                                 i32(SLOTS))),
        "import": (alloc._import, (cache, _on(one_chip, alloc.template),
                                   _on(one_chip, alloc._prefix_chunks),
                                   i32(SLOTS, npp))),
        "import_slot": (alloc._import_slot,
                        (cache, _on(one_chip, alloc._prefix_chunks),
                         i32(npp), i32())),
        "snapshot": (alloc._snapshot, (cache, i32())),
        "restore": (alloc._restore, (cache, _on(one_chip, snap), i32())),
    }
    for name, (prog, args) in programs.items():
        compiled = prog.lower(*args).compile()
        got = _pool_layouts(compiled.input_formats[0][0])
        if name == "snapshot":
            # It copies out the contiguous layers only; qwen has none, so
            # the pool is an unused argument and pruned (no layout).
            assert all(v is None for layer in got for v in layer.values())
            continue
        assert got == want, name
        assert _pool_layouts(compiled.output_formats) == want, name
