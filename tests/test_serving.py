"""Serving: decode-with-cache must agree with full-sequence forward — the
core KV-cache correctness invariant, checked per architecture family and
with multiplexing active (beyond-paper: muxed autoregressive serving)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ServingConfig
from repro.configs.registry import get_smoke_config
from repro.models import Backbone
from repro.serving.engine import Engine

# Families whose decode path is exact (attention: cache == recompute).
# Causal archs only: T-MUX (the paper's encoder) is bidirectional, so
# decode-with-cache is not defined for it.  MoE archs need a no-drop
# capacity factor — the router drops different tokens at different batch
# shapes otherwise.  SSM scan chunking gives small numeric drift.
CASES = [("qwen1.5-4b", 1e-4),
         ("gemma3-4b", 1e-4), ("deepseek-v3-671b", 1e-3),
         ("xlstm-125m", 2e-2), ("jamba-1.5-large-398b", 2e-2),
         ("whisper-base", 1e-4), ("llama-3.2-vision-11b", 1e-4)]


def _no_drop(cfg):
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=64.0))
    return cfg


@pytest.mark.parametrize("arch,tol", CASES)
def test_decode_matches_full_forward(key, arch, tol):
    """Prefill L tokens, decode token L+1; its logits must match the full
    (L+1)-token forward's last position."""
    cfg = _no_drop(get_smoke_config(arch, mux_n=2))
    params = Backbone.init(key, cfg)
    B, L = 2, 12
    toks = jax.random.randint(key, (B, cfg.mux.n, L + 1), 0, cfg.vocab)
    ctx = jnp.zeros((B, cfg.context_len, cfg.context_dim)) \
        if cfg.context_len else None

    # full forward over L+1 tokens
    full = Backbone.apply(params, toks, cfg, context=ctx)
    want = full["logits"][:, :, -1]                      # (B, N, V)

    # prefill L, then decode the (L+1)-th token
    maxlen = cfg.mux.prefix_len + L + 2
    cache = Backbone.init_cache(cfg, B, maxlen, dtype=jnp.float32)
    pre = Backbone.apply(params, toks[:, :, :L], cfg, context=ctx,
                         cache=cache)
    cross_kv = Backbone.encode_context(params, ctx, cfg) \
        if ctx is not None else None
    got, _ = Backbone.decode_step(
        params, toks[:, :, L], pre["cache"],
        jnp.int32(cfg.mux.prefix_len + L), cfg,
        index_embeds=pre["index_embeds"], cross_kv=cross_kv)

    np.testing.assert_allclose(
        jax.nn.log_softmax(got.astype(np.float32)),
        jax.nn.log_softmax(want.astype(np.float32)), rtol=tol, atol=tol)


def test_engine_generate_muxed(key):
    cfg = get_smoke_config("tmux-12l-768h", mux_n=4)
    params = Backbone.init(key, cfg)
    B, Lp, steps = 2, 6, 5
    eng = Engine(params, cfg, batch=B, max_len=Lp + steps + 1)
    prompts = jax.random.randint(key, (B, cfg.mux.n, Lp), 0, cfg.vocab)
    out = eng.generate(prompts, steps)
    assert out.shape == (B, cfg.mux.n, steps + 1)
    assert not bool(jnp.isnan(out).any())


@pytest.mark.parametrize("paged", [False, True])
def test_engine_generate_unmuxed(key, paged):
    """Lock-step generation; under a paged configuration ``generate``
    decodes from prefill's contiguous cache, as the unpaged engine does."""
    cfg = get_smoke_config("qwen1.5-4b", mux_n=1)
    params = Backbone.init(key, cfg)
    eng = Engine(params, cfg, batch=2, max_len=12)
    prompts = jax.random.randint(key, (2, 6), 0, cfg.vocab)
    out = eng.generate(prompts, 4)
    assert out.shape == (2, 5)
    if paged:
        cfg_p = dataclasses.replace(
            cfg, serving=ServingConfig(paged=True, page_size=4))
        eng_p = Engine(params, cfg_p, batch=2, max_len=12)
        assert eng_p.pool_layers_in_carry == cfg.layer_pattern()[2]
        np.testing.assert_array_equal(eng_p.generate(prompts, 4), out)


def test_sliding_window_ring_buffer(key):
    """Decoding past the window: ring buffer must only keep the last
    ``window`` positions and still match the full windowed forward."""
    cfg = get_smoke_config("gemma3-4b", mux_n=1)
    cfg = dataclasses.replace(cfg, window=8, global_every=0, n_layers=2)
    params = Backbone.init(key, cfg)
    B, T = 1, 20  # decode well past window=8
    toks = jax.random.randint(key, (B, T), 0, cfg.vocab)

    full = Backbone.apply(params, toks, cfg)
    want = full["logits"][:, -1]

    cache = Backbone.init_cache(cfg, B, T + 1, dtype=jnp.float32)
    pre = Backbone.apply(params, toks[:, :T - 1], cfg, cache=cache)
    got, _ = Backbone.decode_step(params, toks[:, T - 1], pre["cache"],
                                  jnp.int32(T - 1), cfg)
    np.testing.assert_allclose(
        jax.nn.log_softmax(got.astype(np.float32)),
        jax.nn.log_softmax(want.astype(np.float32)), rtol=1e-4, atol=1e-4)


def test_multi_step_decode_consistency(key):
    """Greedy generation step-by-step equals teacher-forced full forwards
    (causal arch; T-MUX is bidirectional so it is excluded)."""
    cfg = get_smoke_config("qwen1.5-4b", mux_n=2)
    params = Backbone.init(key, cfg)
    B, Lp, T = 1, 5, 4
    prompts = jax.random.randint(key, (B, cfg.mux.n, Lp), 0, cfg.vocab)
    eng = Engine(params, cfg, batch=B, max_len=Lp + T + 1, jit=False)
    gen = eng.generate(prompts, T)                     # (B, N, T+1)

    # teacher-forced check: feeding prompt+gen[:t] reproduces gen[t]
    seq = jnp.concatenate([prompts, gen[:, :, :-1]], axis=-1)
    out = Backbone.apply(params, seq, cfg)
    for t in range(T):
        pred = jnp.argmax(out["logits"][:, :, Lp - 1 + t], axis=-1)
        np.testing.assert_array_equal(np.asarray(pred),
                                      np.asarray(gen[:, :, t]))
