"""Chunked multi-token prefill (ISSUE 4): the decode step accepts a (B, C)
token chunk with per-slot base positions and valid lengths, so a ramping
prompt consumes ~Lp/C steps instead of Lp.

Parity contract: a pure ramp (every live lane feeding prompt tokens) is the
same computation chunked or sequential — identical cache positions and
greedy tokens, cache contents equal to f32 matmul-shape tolerance (a
(B, C, d) GEMM may accumulate in a different order than C (B, 1, d) ones).
``prefill_chunk=1`` routes through the legacy single-token path untouched.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServingConfig
from repro.configs.registry import get_smoke_config
from repro.models import Backbone
from repro.serving.engine import Engine, ServeState
from repro.serving.kvcache import KVSlotAllocator, pytree_bytes
from repro.serving.paging import PagedKVSlotAllocator
from repro.serving.scheduler import (ContinuousScheduler, Request,
                                     poisson_trace)

# attn / MLA+MoE / window / mamba+attn+MoE
ARCHS = ["qwen1.5-4b", "deepseek-v3-671b", "gemma3-4b",
         "jamba-1.5-large-398b"]
B, N, LP, MAX_LEN = 2, 2, 6, 30
DECODE_STEPS = 4


@functools.lru_cache(maxsize=None)
def _setup(arch):
    cfg = get_smoke_config(arch, mux_n=N)
    if cfg.moe is not None:
        # Row masking (nn/moe.py) makes chunked MoE decode row-exact, so
        # MoE archs ride the parity sweep.  Capacity stays no-drop: under a
        # *binding* capacity the chunk width legitimately changes which
        # rows compete for expert slots, so parity is only defined when no
        # token drops (test_moe_masking pins the tight-capacity contract).
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    params = Backbone.init(jax.random.PRNGKey(0), cfg)
    prompts = np.asarray(jax.random.randint(
        jax.random.PRNGKey(1), (B, N, LP), 0, cfg.vocab))
    return cfg, params, prompts


def _ramp_then_decode(cfg, params, prompts, chunk, *, paged=False,
                      page_size=8):
    """Ramp equal-length prompts through the chunked decode step (every
    lane feeds ``chunk`` tokens per call), then greedy-decode.  Returns
    (cache, pos, tokens); the cache is the raw contiguous pytree when not
    paged (for content parity checks)."""
    serving = ServingConfig(paged=paged, page_size=page_size,
                            prefill_chunk=chunk)
    cfgx = dataclasses.replace(cfg, serving=serving)
    eng = Engine(params, cfgx, batch=B, max_len=MAX_LEN)
    primed = eng.prime(compact=paged)
    if paged:
        alloc = PagedKVSlotAllocator(cfgx, B, eng.max_len,
                                     template=primed.cache)
    else:
        alloc = KVSlotAllocator(cfgx, B, eng.max_len, template=primed.cache)
    pos = np.asarray(primed.pos).copy()
    toks = []
    fed, decoded, last = 0, 0, None
    while fed < LP or decoded < DECODE_STEPS:
        if fed < LP:
            take = min(chunk, LP - fed)
            tokens = np.zeros((B, N, chunk), np.int32)
            tokens[:, :, :take] = prompts[:, :, fed:fed + take]
        else:
            take = 1
            tokens = np.zeros((B, N, chunk), np.int32)
            tokens[:, :, 0] = last
            decoded += 1
        lane_mask = np.zeros((B, N, chunk), np.float32)
        lane_mask[:, :, :take] = 1.0
        block_table = None
        if paged:
            alloc.ensure(pos, np.ones(B, bool), lens=np.full(B, take))
            block_table = alloc.block_table
        st = ServeState(cache=alloc.cache, pos=jnp.asarray(pos),
                        index_embeds=primed.index_embeds)
        logits, st = eng.step(st, tokens, lane_mask=lane_mask,
                              block_table=block_table,
                              chunk_lens=np.full(B, take, np.int32))
        alloc.adopt(st.cache)
        pos = pos + take
        if fed < LP:
            fed += take
        last = np.asarray(jnp.argmax(logits[:, :, take - 1], axis=-1))
        if fed >= LP:          # first generated token + decode stream
            toks.append(last.copy())
    return alloc.cache, pos, np.stack(toks)


def _ramp_sequential(cfg, params, prompts):
    """The legacy one-token ramp (chunk_lens=None single-token decode)."""
    eng = Engine(params, cfg, batch=B, max_len=MAX_LEN)
    primed = eng.prime()
    alloc = KVSlotAllocator(cfg, B, eng.max_len, template=primed.cache)
    pos = np.asarray(primed.pos).copy()
    toks = []
    fed, decoded, last = 0, 0, None
    ones = np.ones((B, N), np.float32)
    while fed < LP or decoded < DECODE_STEPS:
        if fed < LP:
            tokens = prompts[:, :, fed]
        else:
            tokens = last
            decoded += 1
        st = ServeState(cache=alloc.cache, pos=jnp.asarray(pos),
                        index_embeds=primed.index_embeds)
        logits, st = eng.step(st, tokens, lane_mask=ones)
        alloc.adopt(st.cache)
        pos = pos + 1
        if fed < LP:
            fed += 1
        last = np.asarray(jnp.argmax(logits, axis=-1))
        if fed >= LP:
            toks.append(last.copy())
    return alloc.cache, pos, np.stack(toks)


# ---------------------------------------------------------------------------
# Chunked-vs-unchunked parity across attention / MLA / windowed archs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("chunk", [1, 2, 5, LP])
def test_chunked_ramp_parity(arch, chunk):
    """A pure ramp is chunk-invariant: identical cache positions, identical
    greedy tokens from the ramp's last row onward, and cache contents equal
    to f32 tolerance for every prefill_chunk."""
    cfg, params, prompts = _setup(arch)
    cache_ref, pos_ref, toks_ref = _ramp_sequential(cfg, params, prompts)
    cache, pos, toks = _ramp_then_decode(cfg, params, prompts, chunk)
    np.testing.assert_array_equal(pos, pos_ref)
    # first generated token + the decode stream, token-for-token
    np.testing.assert_array_equal(toks, toks_ref)
    for leaf, ref in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_ref)):
        if jnp.issubdtype(leaf.dtype, jnp.integer):   # pos arrays: exact
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))
        else:
            np.testing.assert_allclose(np.asarray(leaf, np.float32),
                                       np.asarray(ref, np.float32),
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("chunk", [2, 4])
def test_chunked_ramp_parity_window_wrap(chunk):
    """Ring eviction mid-chunk: with window=4 the ramp + decode crosses the
    ring boundary repeatedly, so a later chunk row's write physically
    evicts in-window keys earlier rows still need — the chunked step must
    attend over the pre-write ring and still match the sequential path."""
    cfg, params, prompts = _setup("gemma3-4b")
    cfg = dataclasses.replace(cfg, window=4)   # ring smaller than LP+decode
    cache_ref, pos_ref, toks_ref = _ramp_sequential(cfg, params, prompts)
    cache, pos, toks = _ramp_then_decode(cfg, params, prompts, chunk)
    np.testing.assert_array_equal(pos, pos_ref)
    np.testing.assert_array_equal(toks, toks_ref)
    for leaf, ref in zip(jax.tree.leaves(cache), jax.tree.leaves(cache_ref)):
        if jnp.issubdtype(leaf.dtype, jnp.integer):
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(ref))
        else:
            np.testing.assert_allclose(np.asarray(leaf, np.float32),
                                       np.asarray(ref, np.float32),
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("chunk", [2, 5])
def test_chunked_paged_matches_contiguous_bitwise(chunk):
    """At equal chunk width the paged and contiguous chunked decode paths
    are the same expression over the same positions — tokens match
    token-for-token on a dense pool."""
    cfg, params, prompts = _setup("qwen1.5-4b")
    _, pos_c, toks_c = _ramp_then_decode(cfg, params, prompts, chunk)
    _, pos_p, toks_p = _ramp_then_decode(cfg, params, prompts, chunk,
                                         paged=True)
    np.testing.assert_array_equal(pos_c, pos_p)
    np.testing.assert_array_equal(toks_c, toks_p)


def test_chunk_one_matches_legacy_bitwise(key):
    """The chunked code path at C=1 degrades to the exact legacy
    single-token computation (same shapes, same writes) — logits bitwise."""
    cfg, params, prompts = _setup("qwen1.5-4b")
    _, pos_ref, toks_ref = _ramp_sequential(cfg, params, prompts)
    _, pos, toks = _ramp_then_decode(cfg, params, prompts, 1)
    np.testing.assert_array_equal(pos, pos_ref)
    np.testing.assert_array_equal(toks, toks_ref)


# ---------------------------------------------------------------------------
# Scheduler: prefill_chunk=1 is the old engine bit-for-bit; chunked traces
# complete with the ramp amortised
# ---------------------------------------------------------------------------

def _trace(seed=3, n=10):
    cfg, _, _ = _setup("qwen1.5-4b")
    return poisson_trace(n, rate=1.0, prompt_len=4, gen_len=4,
                         vocab=cfg.vocab, max_total=40, seed=seed)


def _run_sched(serving, trace, batch=2, max_len=96):
    cfg, params, _ = _setup("qwen1.5-4b")
    cfgx = dataclasses.replace(cfg, serving=serving)
    sched = ContinuousScheduler(Engine(params, cfgx, batch=batch,
                                       max_len=max_len))
    stats = sched.run([r.fresh() for r in trace])
    return sched, stats


def test_prefill_chunk_one_scheduler_unchanged():
    trace = _trace()
    s_def, st_def = _run_sched(ServingConfig(), trace)
    s_one, st_one = _run_sched(ServingConfig(prefill_chunk=1), trace)
    assert st_def.decode_steps == st_one.decode_steps
    assert ({q.rid: q.output for q in s_def.finished} ==
            {q.rid: q.output for q in s_one.finished})


@pytest.mark.parametrize("paged", [False, True])
def test_chunked_trace_completes_and_amortises_ramp(paged):
    """prefill_chunk=4 on a Poisson trace: every request completes, paged
    and contiguous emit identical tokens, and mean admission-to-first-token
    latency drops by >= 2x vs the unchunked run (the acceptance bar)."""
    trace = _trace()
    serving1 = ServingConfig(paged=paged, page_size=8, prefill_chunk=1)
    serving4 = ServingConfig(paged=paged, page_size=8, prefill_chunk=4)
    s1, st1 = _run_sched(serving1, trace)
    s4, st4 = _run_sched(serving4, trace)
    assert st1.finished == st4.finished == len(trace)

    def ramp(s):
        return np.mean([q.ramp_latency for q in s.finished])

    assert ramp(s4) * 2 <= ramp(s1)
    for q in s4.finished:
        assert len(q.output) == q.max_new_tokens


def test_chunked_paged_scheduler_matches_contiguous():
    trace = _trace(seed=5)
    s_c, st_c = _run_sched(ServingConfig(prefill_chunk=4), trace)
    s_p, st_p = _run_sched(ServingConfig(paged=True, page_size=8,
                                         prefill_chunk=4), trace)
    assert st_c.decode_steps == st_p.decode_steps
    assert ({q.rid: q.output for q in s_c.finished} ==
            {q.rid: q.output for q in s_p.finished})


def test_decode_lane_rides_chunked_ramp():
    """A decoding lane shares its slot with a chunked ramp: the ramping
    request reaches its first token in ceil(Lp/C) steps while the decode
    lane keeps emitting exactly one token per step to completion."""
    cfg, params, _ = _setup("qwen1.5-4b")
    cfgx = dataclasses.replace(cfg,
                               serving=ServingConfig(prefill_chunk=3))
    sched = ContinuousScheduler(Engine(params, cfgx, batch=1, max_len=64))
    rng = np.random.default_rng(0)
    r0 = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 1).astype(np.int32),
                 max_new_tokens=10)
    r1 = Request(rid=1, prompt=rng.integers(0, cfg.vocab, 6).astype(np.int32),
                 max_new_tokens=2, arrival=3)
    stats = sched.run([r0, r1])
    assert stats.finished == 2
    done = {q.rid: q for q in sched.finished}
    # ramp amortised: 6 prompt tokens at C=3 -> first token in 2 steps
    assert done[1].ramp_latency == 2
    # the co-lane emitted one token per scheduler step, start to finish
    assert len(done[0].output) == 10
    assert done[0].finished_step - done[0].admitted_step + 1 == 10


# ---------------------------------------------------------------------------
# Paged prime: no dense (B, max_len) transient
# ---------------------------------------------------------------------------

def test_compact_prime_is_prefix_sized():
    """Engine.prime(compact=True) primes against a prefix-sized cache —
    the peak-bytes regression guard for the paged prime path."""
    cfg, params, _ = _setup("qwen1.5-4b")
    eng = Engine(params, cfg, batch=B, max_len=96)
    compact = eng.prime(compact=True)
    full = eng.prime()
    p = cfg.mux.prefix_len
    for leaf in jax.tree.leaves(
            jax.tree.map(lambda a: a, compact.cache["blocks"])):
        if leaf.ndim >= 3:          # (G, B, S, ...) position-indexed leaves
            assert leaf.shape[2] == p, leaf.shape
    # the dense transient is gone: prefix-sized vs max_len-sized template
    assert pytree_bytes(compact.cache) * 10 < pytree_bytes(full.cache)
    np.testing.assert_array_equal(np.asarray(compact.index_embeds),
                                  np.asarray(full.index_embeds))


def test_paged_allocator_accepts_compact_template():
    """The paged allocator imports a compact template into a pool bitwise
    identical to the one built from the full-width primed template."""
    cfg, params, _ = _setup("qwen1.5-4b")
    cfgp = dataclasses.replace(cfg, serving=ServingConfig(paged=True,
                                                          page_size=8))
    eng = Engine(params, cfgp, batch=B, max_len=94)
    a_compact = PagedKVSlotAllocator(cfgp, B, eng.max_len,
                                     template=eng.prime(compact=True).cache)
    a_full = PagedKVSlotAllocator(cfgp, B, eng.max_len,
                                  template=eng.prime().cache)
    for got, want in zip(jax.tree.leaves(a_compact.cache),
                         jax.tree.leaves(a_full.cache)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_scheduler_primes_compact(monkeypatch):
    cfg, params, _ = _setup("qwen1.5-4b")
    cfgp = dataclasses.replace(cfg, serving=ServingConfig(paged=True,
                                                          page_size=8))
    eng = Engine(params, cfgp, batch=B, max_len=30)
    seen = {}
    orig = Engine.prime

    def spy(self, context=None, *, compact=False):
        seen["compact"] = compact
        return orig(self, context, compact=compact)

    monkeypatch.setattr(Engine, "prime", spy)
    ContinuousScheduler(eng)
    assert seen["compact"] is True


# ---------------------------------------------------------------------------
# Guard rails
# ---------------------------------------------------------------------------

def test_prefill_chunk_validation():
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingConfig(prefill_chunk=0)


def test_chunked_rejects_xlstm_archs(key):
    """Mamba chunked decode exists now (``Mamba._chunked_decode``), so
    jamba serves with prefill_chunk > 1; xLSTM state updates still have no
    row-masked form and must keep failing fast at engine construction."""
    cfg = get_smoke_config("xlstm-125m", mux_n=1)
    cfg = dataclasses.replace(cfg, serving=ServingConfig(prefill_chunk=2))
    params = Backbone.init(key, cfg)
    with pytest.raises(ValueError, match="xLSTM"):
        Engine(params, cfg, batch=1, max_len=16)


def test_chunked_accepts_mamba_archs(key):
    cfg = get_smoke_config("jamba-1.5-large-398b", mux_n=1)
    cfg = dataclasses.replace(cfg, serving=ServingConfig(prefill_chunk=2))
    params = Backbone.init(key, cfg)
    Engine(params, cfg, batch=1, max_len=16)   # no raise


def test_chunked_rejects_chunk_wider_than_window(key):
    cfg = get_smoke_config("gemma3-4b", mux_n=1)   # smoke window = 16
    cfg = dataclasses.replace(cfg, serving=ServingConfig(prefill_chunk=17))
    params = Backbone.init(key, cfg)
    with pytest.raises(ValueError, match="ring"):
        Engine(params, cfg, batch=1, max_len=64)
