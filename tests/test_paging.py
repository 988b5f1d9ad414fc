"""Paged KV cache (ISSUE 3): block-table allocator, bit-for-bit parity with
the contiguous slot allocator, free-page admission where contiguous
refuses, and page-leak checks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServingConfig
from repro.configs.registry import get_smoke_config
from repro.models import Backbone
from repro.nn.attention import pool_kv_heads
from repro.serving.engine import Engine, ServeState
from repro.serving.kvcache import KVSlotAllocator
from repro.serving.paging import PagedKVSlotAllocator, PageTable, pages_for
from repro.serving.scheduler import ContinuousScheduler, Request


def _cfg(n=2, **serving):
    cfg = get_smoke_config("qwen1.5-4b", mux_n=n)
    if serving:
        cfg = dataclasses.replace(cfg, serving=ServingConfig(**serving))
    return cfg


def _requests(spec, *, prompt_len=2, vocab=512, seed=0, **kw):
    rng = np.random.default_rng(seed)
    reqs = []
    for i, s in enumerate(spec):
        gen, arr = s if isinstance(s, tuple) else (s, 0)
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, vocab, prompt_len).astype(np.int32),
            max_new_tokens=gen, arrival=arr, **kw))
    return reqs


def _fresh(reqs):
    return [r.fresh() for r in reqs]


# ---------------------------------------------------------------------------
# PageTable bookkeeping
# ---------------------------------------------------------------------------

def test_page_table_alloc_free_cycle():
    t = PageTable(n_slots=2, pages_per_slot=4, pool_pages=6)
    assert t.usable_pages == 5 and t.free_pages == 5
    p0 = t.allocate(0, 0)
    p1 = t.allocate(0, 1)
    p2 = t.allocate(1, 0)
    assert p0 != p1 != p2 and 0 not in (p0, p1, p2)   # trash page reserved
    assert t.pages_in_use == 3 and t.peak_in_use == 3
    freed = t.free_slot(0, keep=1)
    assert freed == [p1]
    assert t.pages_in_use == 2 and t.free_pages == 3
    assert t.rows[0, 0] == p0 and t.rows[0, 1] == -1
    # freed page is reused before untouched ones (LIFO)
    assert t.allocate(0, 1) == p1
    # errors: double-map, non-sequential, table width, exhaustion
    with pytest.raises(ValueError, match="already mapped"):
        t.allocate(0, 1)
    with pytest.raises(ValueError, match="sequential"):
        t.allocate(1, 3)
    with pytest.raises(ValueError, match="table width"):
        t.allocate(1, 4)
    t.allocate(1, 1)
    t.allocate(1, 2)
    with pytest.raises(RuntimeError, match="exhausted"):
        t.allocate(1, 3)


def test_pool_must_hold_prefix_pages():
    cfg = _cfg(paged=True, page_size=4, pool_pages=2)
    with pytest.raises(ValueError, match="prefix pages"):
        PagedKVSlotAllocator(cfg, 3, 16)


# ---------------------------------------------------------------------------
# Bit-for-bit parity with the contiguous allocator
# ---------------------------------------------------------------------------

def _gathered(pages, block_table, width):
    """A paged layer's pool read back per slot in position order,
    ``width`` positions wide: what the contiguous cache holds."""
    bt = np.asarray(block_table)
    g = np.asarray(pages)[np.maximum(bt, 0)]        # (B, mp, ps, ...)
    if g.ndim == 3:                                  # pos: unmapped -> -1
        g = np.where(bt[:, :, None] >= 0, g, -1)
    return g.reshape((g.shape[0], -1) + g.shape[3:])[:, :width]


@pytest.mark.parametrize("chunk,kernel,heads", [
    (1, False, 4), (1, True, 4), (4, False, 4), (4, True, 4),
    (1, False, 12), (4, True, 12)])
def test_paged_decode_matches_contiguous_bitwise(key, chunk, kernel, heads):
    """Step-level: with a dense pool, the paged decode path — its pools
    riding the layer scan's carry, written and read in place — produces
    logits bit-for-bit equal to the contiguous path, and its pools read
    back through the block table equal the contiguous caches: gathered
    pages cover the same positions in the same order, and masked pool
    entries contribute an exact zero to the softmax.  The steps cross
    pages, and a recycled slot's next steps reuse freed pages.  One-token
    and 4-row chunked steps; the jnp gather and the Pallas kernel
    (interpret mode; its online softmax matches the gather to rounding,
    so its logits are held to the tokens they pick and the K/V its later
    layers write to float32 rounding).  At 12 heads the pool pads each
    page's heads to 16 (``pool_kv_heads``)."""
    def widths(c):
        return dataclasses.replace(c, n_heads=heads, n_kv_heads=heads,
                                   head_dim=64)

    cfg = widths(_cfg(prefill_chunk=chunk))
    params = Backbone.init(key, cfg)
    B, n = 2, cfg.mux.n
    cfg_p = widths(_cfg(paged=True, page_size=4, prefill_chunk=chunk,
                        use_kernel=kernel))
    eng_c = Engine(params, cfg, batch=B, max_len=30)      # +2 prefix = 32
    eng_p = Engine(params, cfg_p, batch=B, max_len=30)
    groups = cfg.layer_pattern()[2]
    assert groups >= 3
    assert eng_p.pool_layers_in_carry == groups
    assert eng_c.pool_layers_in_carry == 0

    primed_c = eng_c.prime()
    alloc_c = KVSlotAllocator(cfg, B, eng_c.max_len, template=primed_c.cache)
    primed_p = eng_p.prime()
    alloc_p = PagedKVSlotAllocator(cfg_p, B, eng_p.max_len,
                                   template=primed_p.cache)

    pos = np.asarray(primed_c.pos).copy()
    prefix = pos.copy()
    toks = jax.random.randint(key, (B, n), 0, cfg.vocab)
    for t in range(12):
        if t == 7:                       # recycle slot 1: pages go free
            drained = np.array([False, True])
            alloc_c.reset_slots(drained)
            alloc_p.reset_slots(drained)
            pos = np.where(drained, prefix, pos)
        if chunk == 1:
            lens, step_toks = None, toks
            mask = jnp.ones((B, n), jnp.float32)
        else:
            lens = np.array([chunk if t % 4 == 0 else 1, 1 + t % chunk],
                            np.int32)
            step_toks = jnp.broadcast_to(toks[..., None], (B, n, chunk))
            mask = jnp.asarray(np.arange(chunk)[None, None, :] <
                               lens[:, None, None], jnp.float32)
            mask = jnp.broadcast_to(mask, (B, n, chunk))
        st_c = ServeState(cache=alloc_c.cache, pos=jnp.asarray(pos),
                          index_embeds=primed_c.index_embeds)
        la, st_c = eng_c.step(st_c, step_toks, lane_mask=mask,
                              chunk_lens=lens)
        alloc_c.adopt(st_c.cache)

        alloc_p.ensure(pos, np.ones(B, bool), lens)
        st_p = ServeState(cache=alloc_p.cache, pos=jnp.asarray(pos),
                          index_embeds=primed_p.index_embeds)
        lb, st_p = eng_p.step(st_p, step_toks, lane_mask=mask,
                              block_table=alloc_p.block_table,
                              chunk_lens=lens)
        alloc_p.adopt(st_p.cache)

        if kernel:
            np.testing.assert_array_equal(np.argmax(np.asarray(la), -1),
                                          np.argmax(np.asarray(lb), -1))
        else:
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        toks = jnp.argmax(la if chunk == 1 else la[:, :, 0], axis=-1)
        pos = pos + (1 if lens is None else lens)
    assert alloc_p.table.free_pages < alloc_p.table.usable_pages

    # The kernel's rounding reaches the K/V of the layers after the first
    # through their inputs; the first layer's and every position are exact.
    width = eng_c.max_len
    for cont, pool in zip(alloc_c.cache["blocks"], alloc_p.cache["blocks"]):
        for g in range(groups):
            want_pos = np.asarray(cont["pos"][g])
            got_pos = _gathered(pool["pos"][g], alloc_p.block_table, width)
            np.testing.assert_array_equal(got_pos, want_pos)
            live = want_pos >= 0
            for kc, kp in (("k", "k_pages"), ("v", "v_pages")):
                rows = _gathered(pool[kp][g], alloc_p.block_table, width)
                assert rows.shape[-2] == pool_kv_heads(heads)
                # Padded heads: written, never real.
                assert not rows[live][:, heads:].any()
                got = rows[live][:, :heads]
                want = np.asarray(cont[kc][g])[live]
                if kernel and g:
                    np.testing.assert_allclose(got, want, atol=1e-5)
                else:
                    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("chunk", [1, 4])
def test_paged_scheduler_matches_contiguous_outputs(key, chunk):
    """Trace-level: the paged scheduler reproduces the contiguous
    scheduler's outputs token-for-token on a mixed trace (admissions,
    ramps, retirements, and slot recycles all land identically), and
    gauges how many scanned layers' pools ride the layer scan's carry."""
    from repro.serving.telemetry import Tracer
    cfg = _cfg(prefill_chunk=chunk)
    params = Backbone.init(key, cfg)
    base = _requests([(3, 0), (5, 0), (2, 0), (4, 1), (6, 2), (3, 4)])

    t1, t2 = Tracer(), Tracer()
    s1 = ContinuousScheduler(Engine(params, cfg, batch=2, max_len=30),
                             tracer=t1)
    st1 = s1.run(_fresh(base))
    s2 = ContinuousScheduler(
        Engine(params, _cfg(paged=True, page_size=8, prefill_chunk=chunk),
               batch=2, max_len=30), tracer=t2)
    st2 = s2.run(_fresh(base))

    assert st1.decode_steps == st2.decode_steps
    out1 = {q.rid: q.output for q in s1.finished}
    out2 = {q.rid: q.output for q in s2.finished}
    assert out1 == out2
    assert t1.metrics.gauges["r0/pool_layers_in_carry"] == 0
    assert t2.metrics.gauges["r0/pool_layers_in_carry"] == \
        cfg.layer_pattern()[2]


# ---------------------------------------------------------------------------
# Free-page admission where the contiguous allocator refuses
# ---------------------------------------------------------------------------

def test_paged_admits_long_tail_contiguous_refuses(key):
    """A long-tail generation overflowing a contiguous slot region is
    refused outright; the paged scheduler (wide position table, pool of
    comparable size) admits and completes the whole trace."""
    cfg = _cfg()
    params = Backbone.init(key, cfg)

    def trace():
        reqs = _requests([(3, 1), (2, 2), (4, 2), (3, 3)])
        reqs.append(Request(rid=9, prompt=reqs[0].prompt.copy(),
                            max_new_tokens=38))
        return reqs

    with pytest.raises(ValueError, match="paged"):
        ContinuousScheduler(
            Engine(params, cfg, batch=2, max_len=16)).run(trace())

    cfg_p = _cfg(paged=True, page_size=4, pool_pages=14)
    sched = ContinuousScheduler(Engine(params, cfg_p, batch=2, max_len=46))
    stats = sched.run(trace(), max_steps=500)
    assert stats.finished == 5
    assert stats.peak_pages <= sched.allocator.table.usable_pages
    long = next(q for q in sched.finished if q.rid == 9)
    assert len(long.output) == 38


def test_paged_submit_rejects_impossible_request(key):
    """A request whose page footprint can never fit the pool fails fast at
    submit instead of starving in the queue."""
    cfg = _cfg(paged=True, page_size=4, pool_pages=6)
    params = Backbone.init(key, cfg)
    sched = ContinuousScheduler(Engine(params, cfg, batch=2, max_len=46))
    with pytest.raises(ValueError, match="pool"):
        sched.submit(Request(rid=0, prompt=np.zeros(2, np.int32),
                             max_new_tokens=30))


# ---------------------------------------------------------------------------
# Page recycling: free-on-retire, no leaks
# ---------------------------------------------------------------------------

def test_no_page_leak_after_trace_drains(key):
    """After every request retires, all non-prefix pages are back on the
    free list (free-on-retire recycles a slot the step it drains)."""
    cfg = _cfg(paged=True, page_size=4)
    params = Backbone.init(key, cfg)
    sched = ContinuousScheduler(Engine(params, cfg, batch=2, max_len=30))
    stats = sched.run(_requests([(3, 0), (6, 0), (2, 1), (4, 3), (5, 8)]))
    assert stats.finished == 5
    table = sched.allocator.table
    keep = sched.allocator.n_prefix_pages * sched.n_slots
    assert table.pages_in_use == keep
    assert table.free_pages == table.usable_pages - keep
    assert stats.peak_pages > keep          # pages really were allocated
    assert stats.slot_resets >= 1


def test_paged_unmuxed_no_prefix(key):
    """N=1, no demux prefix: slots start at position 0 with zero prefix
    pages; everything allocates on demand and frees on retire."""
    cfg = get_smoke_config("qwen1.5-4b", mux_n=1)
    cfg = dataclasses.replace(cfg, serving=ServingConfig(paged=True,
                                                         page_size=4))
    params = Backbone.init(key, cfg)
    sched = ContinuousScheduler(Engine(params, cfg, batch=2, max_len=16))
    stats = sched.run(_requests([3, 5, 2]))
    assert stats.finished == 3
    assert sched.allocator.n_prefix_pages == 0
    assert sched.allocator.table.pages_in_use == 0


def test_paged_kernel_end_to_end(key):
    """cfg.serving.use_kernel routes decode attention through the Pallas
    gather kernel (interpret mode on CPU); the trace still drains and
    matches the jnp-ref paged run's outputs."""
    cfg_ref = _cfg(paged=True, page_size=8)
    cfg_ker = _cfg(paged=True, page_size=8, use_kernel=True)
    params = Backbone.init(key, cfg_ref)
    base = _requests([(2, 0), (3, 0), (2, 1)])

    s_ref = ContinuousScheduler(
        Engine(params, cfg_ref, batch=1, max_len=22))
    s_ref.run(_fresh(base))
    s_ker = ContinuousScheduler(
        Engine(params, cfg_ker, batch=1, max_len=22))
    s_ker.run(_fresh(base))
    out_ref = {q.rid: q.output for q in s_ref.finished}
    out_ker = {q.rid: q.output for q in s_ker.finished}
    assert out_ref == out_ker


# ---------------------------------------------------------------------------
# K-block grid + fused demux epilogue (MXU-shaped decode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kblock", [2, 4])
def test_paged_kernel_kblock_end_to_end(key, kblock):
    """kblock_pages > 1 spans several block-table entries per kernel
    invocation; the served token stream must match the jnp-ref paged run
    exactly — the grid shape is not allowed to move the tokens."""
    cfg_ref = _cfg(paged=True, page_size=4)
    cfg_ker = _cfg(paged=True, page_size=4, use_kernel=True,
                   kblock_pages=kblock)
    params = Backbone.init(key, cfg_ref)
    base = _requests([(2, 0), (4, 0), (2, 1), (3, 2)])

    s_ref = ContinuousScheduler(Engine(params, cfg_ref, batch=2, max_len=22))
    s_ref.run(_fresh(base))
    s_ker = ContinuousScheduler(Engine(params, cfg_ker, batch=2, max_len=22))
    s_ker.run(_fresh(base))
    assert {q.rid: q.output for q in s_ref.finished} == \
           {q.rid: q.output for q in s_ker.finished}


def test_fuse_demux_token_stream_bitwise_unchanged(key):
    """ServingConfig.fuse_demux routes decode demux through the fused
    epilogue kernel; the scheduler's token stream must be bitwise-unchanged
    vs the plain contiguous run at the same prefill chunk (chunk width
    changes lane co-residency and so legitimately changes the DataMUX
    superposition — the baseline must share it)."""
    cfg_c = _cfg()
    params = Backbone.init(key, cfg_c)
    base = _requests([(3, 0), (5, 0), (2, 1), (4, 2)])

    for chunk in (1, 2):
        s_c = ContinuousScheduler(
            Engine(params, _cfg(prefill_chunk=chunk), batch=2, max_len=30))
        s_c.run(_fresh(base))
        want = {q.rid: q.output for q in s_c.finished}
        cfg_f = _cfg(paged=True, page_size=4, prefill_chunk=chunk,
                     use_kernel=True, kblock_pages=2, fuse_demux=True)
        s_f = ContinuousScheduler(Engine(params, cfg_f, batch=2, max_len=30))
        s_f.run(_fresh(base))
        got = {q.rid: q.output for q in s_f.finished}
        assert got == want, f"fuse_demux changed tokens at chunk={chunk}"


def test_fuse_demux_contiguous_serving(key):
    """fuse_demux is independent of paging: a contiguous engine with the
    fused epilogue on still reproduces the baseline token stream."""
    cfg_c = _cfg()
    params = Backbone.init(key, cfg_c)
    base = _requests([(3, 0), (2, 1), (4, 1)])
    s_c = ContinuousScheduler(Engine(params, cfg_c, batch=2, max_len=24))
    s_c.run(_fresh(base))
    s_f = ContinuousScheduler(
        Engine(params, _cfg(fuse_demux=True), batch=2, max_len=24))
    s_f.run(_fresh(base))
    assert {q.rid: q.output for q in s_c.finished} == \
           {q.rid: q.output for q in s_f.finished}


# ---------------------------------------------------------------------------
# Paged MLA latents (ISSUE 9): (r + rope) latent rows page like K/V
# ---------------------------------------------------------------------------

def _mla_cfg(n=2, **serving):
    cfg = get_smoke_config("deepseek-v3-671b", mux_n=n)
    if serving:
        cfg = dataclasses.replace(cfg, serving=ServingConfig(**serving))
    return cfg


def test_mla_latent_layers_are_paged():
    """Every deepseek layer is MLA with no window, so paged eligibility is
    total: the allocator pools ckv/krope latent rows, keeps no contiguous
    layers, and parks without a contiguous snapshot."""
    cfg = _mla_cfg(paged=True, page_size=8)
    alloc = PagedKVSlotAllocator(cfg, 2, 32)
    assert all(f for flags in alloc._paged.values() for f in flags)
    assert not alloc._has_contiguous
    for sec in ("head", "tail", "blocks"):
        for layer in alloc.cache[sec]:
            assert set(layer) == {"ckv_pages", "krope_pages", "pos"}
    park = alloc.park_slot(0)
    assert park.snapshot is None
    alloc.resume_slot(0, park)


def test_mla_paged_decode_matches_contiguous_bitwise(key):
    """Step-level: the gathered (page, offset) latent row IS the contiguous
    position row, masked pool entries contribute exact zeros to the
    absorbed-matrix softmax — deepseek decode logits bit-for-bit."""
    cfg = _mla_cfg()
    params = Backbone.init(key, cfg)
    B, n = 2, cfg.mux.n
    cfg_p = _mla_cfg(paged=True, page_size=8)
    eng_c = Engine(params, cfg, batch=B, max_len=30)
    eng_p = Engine(params, cfg_p, batch=B, max_len=30)
    # MLA latents keep the per-layer slices: no pool rides the carry.
    assert eng_p.pool_layers_in_carry == 0

    primed_c = eng_c.prime()
    alloc_c = KVSlotAllocator(cfg, B, eng_c.max_len, template=primed_c.cache)
    primed_p = eng_p.prime()
    alloc_p = PagedKVSlotAllocator(cfg_p, B, eng_p.max_len,
                                   template=primed_p.cache)

    ones = jnp.ones((B, n), jnp.float32)
    pos = np.asarray(primed_c.pos).copy()
    toks = jax.random.randint(key, (B, n), 0, cfg.vocab)
    for _ in range(6):
        st_c = ServeState(cache=alloc_c.cache, pos=jnp.asarray(pos),
                          index_embeds=primed_c.index_embeds)
        la, st_c = eng_c.step(st_c, toks, lane_mask=ones)
        alloc_c.adopt(st_c.cache)

        alloc_p.ensure(pos, np.ones(B, bool))
        st_p = ServeState(cache=alloc_p.cache, pos=jnp.asarray(pos),
                          index_embeds=primed_p.index_embeds)
        lb, st_p = eng_p.step(st_p, toks, lane_mask=ones,
                              block_table=alloc_p.block_table)
        alloc_p.adopt(st_p.cache)

        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        toks = jnp.argmax(la, axis=-1)
        pos += 1


@pytest.mark.parametrize("chunk", [1, 4])
def test_mla_paged_scheduler_matches_contiguous(key, chunk):
    """Trace-level, both ramp widths: the paged deepseek scheduler (MLA
    latents pooled, MoE row-masked at chunk > 1) reproduces the contiguous
    scheduler token-for-token.  Same chunk on both sides, so MoE capacity
    competition is identical and the comparison is exact even with a
    binding capacity factor."""
    cfg = _mla_cfg(prefill_chunk=chunk)
    params = Backbone.init(key, cfg)
    base = _requests([(3, 0), (5, 0), (2, 1), (4, 2)],
                     vocab=cfg.vocab)

    s_c = ContinuousScheduler(Engine(params, cfg, batch=2, max_len=30))
    st_c = s_c.run(_fresh(base))
    cfg_p = _mla_cfg(paged=True, page_size=8, prefill_chunk=chunk)
    s_p = ContinuousScheduler(Engine(params, cfg_p, batch=2, max_len=30))
    st_p = s_p.run(_fresh(base))

    assert st_c.decode_steps == st_p.decode_steps
    assert st_c.finished == st_p.finished == len(base)
    assert ({q.rid: q.output for q in s_c.finished} ==
            {q.rid: q.output for q in s_p.finished})


def test_mla_no_page_leak_after_trace_drains(key):
    """Latent pages recycle exactly like K/V pages: after the deepseek
    trace drains only the resident prefix pages stay mapped."""
    cfg = _mla_cfg(paged=True, page_size=4)
    params = Backbone.init(key, cfg)
    sched = ContinuousScheduler(Engine(params, cfg, batch=2, max_len=30))
    stats = sched.run(_requests([(3, 0), (6, 0), (2, 1), (4, 3)],
                                vocab=cfg.vocab))
    assert stats.finished == 4
    table = sched.allocator.table
    keep = sched.allocator.n_prefix_pages * sched.n_slots
    assert table.pages_in_use == keep
    assert table.free_pages == table.usable_pages - keep
    assert stats.peak_pages > keep


def test_kblock_config_validation_fails_fast():
    """An over-budget kblock_pages x page_size x head_dim claim raises at
    config construction with the knob to turn — not inside lowering."""
    with pytest.raises(ValueError, match="kblock_pages must be >= 1"):
        ServingConfig(kblock_pages=0)
    with pytest.raises(ValueError, match="lower kblock_pages to <="):
        _cfg(paged=True, page_size=16, use_kernel=True, kblock_pages=1 << 16)
    # kernel off -> the knob is inert, any value constructs
    _cfg(paged=True, page_size=16, kblock_pages=1 << 16)


@pytest.mark.parametrize("paged", [False, True])
def test_dropped_engine_frees_its_buffers_without_gc(key, paged):
    """Jitted engine and allocator methods hold their instance weakly:
    dropping the last reference to a scheduler frees the weights and the
    cache / page pool at once, with the cycle collector off — engines built
    one after another in one process never pile up on the device."""
    import gc
    import weakref
    cfg = _cfg(paged=paged, page_size=4) if paged else _cfg()
    params = Backbone.init(key, cfg)
    was = gc.isenabled()
    gc.disable()
    try:
        sched = ContinuousScheduler(Engine(params, cfg, batch=2, max_len=16))
        assert sched.run(_requests([3, 2, 4, (2, 1)],
                                   vocab=cfg.vocab)).finished == 4
        weights = weakref.ref(jax.tree.leaves(sched.engine.params)[0])
        cache = weakref.ref(jax.tree.leaves(sched.allocator.cache)[0])
        del params, sched
        assert weights() is None and cache() is None
    finally:
        if was:
            gc.enable()
