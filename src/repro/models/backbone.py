"""Generic pattern-scanned decoder backbone.

One model implementation interprets every assigned architecture's
ModelConfig:

  * layer heterogeneity (MoE interleave, Jamba attn:Mamba 1:7, Gemma-3
    5-local:1-global windows, xLSTM mLSTM/sLSTM mix, VLM cross-attention
    insertion) is compiled by ``ModelConfig.layer_pattern()`` into
    (head, period, groups): ``head`` unscanned layers, then ``groups``
    repeats of a ``period``-layer super-block run under ``jax.lax.scan``
    (stacked params ⇒ HLO size independent of depth), then an unscanned tail.
  * DataMUX (the paper's technique) is integrated natively: token embedding →
    prefix protocol → mux strategy → blocks → demux strategy → per-instance
    logits.  Mux/demux schemes are resolved by name from the strategy
    registry (``repro.core.strategies``), so new codecs plug in without
    touching this file.  ``cfg.mux.n == 1`` degrades to a vanilla LM.
  * Decode mode threads per-layer caches (KV / ring-buffer / MLA-latent /
    SSM state) through the same scan: each layer's cache is sliced out of
    the stacked caches (``xs``) and written back into fresh ones (``ys``).
    Paged GQA pools are the exception: they ride the scan's carry whole,
    indexed by the layer number, so XLA updates them in place; their pages
    hold whole tiles of heads (``nn.attention.pool_kv_heads``), so the
    device's default layout is the one of the in-loop writes and no slice
    or copy of a pool is left.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MuxConfig
from repro.core.strategies import get_demux, get_mux
from repro.nn.attention import MLA, Attention, CrossAttention, paged_eligible
from repro.nn.layers import Embedding, Linear, MLP, make_norm
from repro.nn.moe import SINGLE, MeshInfo, MoE
from repro.nn.ssm import MLSTM, Mamba, SLSTM

Params = Any


def _constrain(x, mesh, spec):
    if mesh is None:
        return x
    from jax.sharding import NamedSharding
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Per-layer init / apply
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: ModelConfig, kind: dict):
    keys = jax.random.split(key, 6)
    norm = make_norm(cfg.norm)
    pdtype = cfg.pdtype
    p: dict = {"norm1": norm.init(keys[0], cfg.d_model, param_dtype=pdtype)}
    mixer = kind["mixer"]
    if mixer == "attn":
        p["attn"] = Attention.init(
            keys[1], cfg.attn_config(window=kind["window"]),
            param_dtype=pdtype)
    elif mixer == "mla":
        p["attn"] = MLA.init(keys[1], cfg.mla, param_dtype=pdtype)
    elif mixer == "mamba":
        p["mamba"] = Mamba.init(keys[1], cfg.mamba, param_dtype=pdtype)
    elif mixer == "mlstm":
        p["mlstm"] = MLSTM.init(keys[1], cfg.xlstm, param_dtype=pdtype)
    elif mixer == "slstm":
        p["slstm"] = SLSTM.init(keys[1], cfg.xlstm, param_dtype=pdtype)
    else:
        raise ValueError(mixer)
    if kind["cross"]:
        p["norm_x"] = norm.init(keys[2], cfg.d_model, param_dtype=pdtype)
        p["cross"] = CrossAttention.init(
            keys[3], cfg.attn_config(), kv_dim=cfg.context_dim or cfg.d_model,
            param_dtype=pdtype)
        p["cross_gate"] = jnp.zeros((), pdtype)  # llama-3.2 style tanh gate
    if kind["mlp"] == "dense":
        p["norm2"] = norm.init(keys[4], cfg.d_model, param_dtype=pdtype)
        p["mlp"] = MLP.init(keys[5], cfg.d_model, cfg.d_ff,
                            gated=cfg.gated_mlp, param_dtype=pdtype)
    elif kind["mlp"] == "moe":
        p["norm2"] = norm.init(keys[4], cfg.d_model, param_dtype=pdtype)
        p["moe"] = MoE.init(keys[5], cfg.moe, param_dtype=pdtype)
    return p


def _layer_cache(cfg: ModelConfig, kind: dict, batch: int, max_len: int,
                 dtype, page_pool=None):
    mixer = kind["mixer"]
    if mixer == "attn":
        acfg = cfg.attn_config(window=kind["window"])
        if page_pool is not None and paged_eligible(kind["window"], max_len):
            return Attention.init_paged_cache(acfg, *page_pool, dtype)
        return Attention.init_cache(acfg, batch, max_len, dtype)
    if mixer == "mla":
        if page_pool is not None and paged_eligible(kind["window"], max_len):
            return MLA.init_paged_cache(cfg.mla, *page_pool, dtype)
        return MLA.init_cache(cfg.mla, batch, max_len, dtype)
    if mixer == "mamba":
        return Mamba.init_cache(cfg.mamba, batch, dtype)
    if mixer == "mlstm":
        return MLSTM.init_cache(cfg.xlstm, batch)
    if mixer == "slstm":
        return SLSTM.init_cache(cfg.xlstm, batch)
    raise ValueError(mixer)


def carries_pool(layer_cache) -> bool:
    """Whether a scanned layer's decode cache rides the layer scan's carry:
    a paged GQA pool (``Attention.init_paged_cache``), stacked over the
    groups.  Every other cache — contiguous K/V, windowed rings, MLA
    latents, SSM and xLSTM state — is sliced per layer (``xs``/``ys``)."""
    return isinstance(layer_cache, dict) and "k_pages" in layer_cache


def pool_layers_in_carry(cache) -> int:
    """Scanned layers of ``cache`` (arrays or shapes) whose paged pools
    ride the layer scan's carry."""
    return sum(layer["k_pages"].shape[0] for layer in cache["blocks"]
               if carries_pool(layer))


def _layer_apply(p, x, cfg: ModelConfig, kind: dict, *, positions,
                 cache=None, cache_index=None, cross_kv=None,
                 block_table=None, chunk_lens=None, row_mask=None, mesh=None,
                 mesh_info: MeshInfo = SINGLE, layer=None):
    norm = make_norm(cfg.norm)
    mixer = kind["mixer"]
    aux = jnp.zeros((), jnp.float32)
    new_cache = None
    if chunk_lens is not None and mixer in ("mlstm", "slstm"):
        raise ValueError(
            f"chunked decode (serving.prefill_chunk > 1) is not supported "
            f"for {mixer!r} mixers — xLSTM state updates have no row-masked "
            f"form yet; set prefill_chunk=1 for xLSTM archs")
    # Named scopes tag the device ops of each part of a layer in the
    # compiled program's metadata (``attention`` for both attention
    # mixers, the mixer's own name otherwise; ``kv_write`` nests inside
    # ``nn/attention.py``); they change no numerics.
    with jax.named_scope("attention" if mixer in ("attn", "mla") else mixer):
        h = norm.apply(p["norm1"], x)
        if mixer == "attn":
            out, new_cache = Attention.apply(
                p["attn"], h, cfg.attn_config(window=kind["window"]),
                positions=positions, cache=cache, cache_index=cache_index,
                block_table=block_table, chunk_lens=chunk_lens, layer=layer)
        elif mixer == "mla":
            out, new_cache = MLA.apply(p["attn"], h, cfg.mla,
                                       positions=positions, cache=cache,
                                       cache_index=cache_index,
                                       block_table=block_table,
                                       chunk_lens=chunk_lens)
        elif mixer == "mamba":
            out, new_cache = Mamba.apply(p["mamba"], h, cfg.mamba,
                                         cache=cache, chunk_lens=chunk_lens)
        elif mixer == "mlstm":
            out, new_cache = MLSTM.apply(p["mlstm"], h, cfg.xlstm,
                                         cache=cache)
        elif mixer == "slstm":
            out, new_cache = SLSTM.apply(p["slstm"], h, cfg.xlstm,
                                         cache=cache)
        else:
            raise ValueError(mixer)
    x = x + out

    if kind["cross"]:
        assert cross_kv is not None, "cross-attn layer needs context kv"
        h = norm.apply(p["norm_x"], x)
        out = CrossAttention.apply(p["cross"], h, cross_kv, cfg.attn_config())
        x = x + jnp.tanh(p["cross_gate"].astype(x.dtype)) * out

    if kind["mlp"] == "dense":
        with jax.named_scope("mlp"):
            h = norm.apply(p["norm2"], x)
            out = MLP.apply(p["mlp"], h, activation=cfg.activation)
        x = x + out
    elif kind["mlp"] == "moe":
        with jax.named_scope("mlp"):
            h = norm.apply(p["norm2"], x)
            out, aux = MoE.apply(p["moe"], h, cfg.moe, mesh_info, mesh=mesh,
                                 row_mask=row_mask)
        x = x + out
    return x, new_cache, aux


def _demux_decode(params, h, cfg: ModelConfig, index_embeds):
    """Decode-step demux of the (B, C, d) final hidden block -> (B, N, C, d).

    ``serving.fuse_demux`` routes strategies with a fused decode epilogue
    (index_embed: all N lanes demuxed in VMEM, the shared h·W1h computed
    once per slot) through ``decode_apply``; everything else — and the
    default — takes the ordinary strategy ``apply``, bit-for-bit today's
    path."""
    mux = cfg.mux
    demux_s = get_demux(mux.demux)
    if cfg.serving.fuse_demux and demux_s.fused_decode:
        return demux_s.decode_apply(params["demux"], h, mux,
                                    index_embeds=index_embeds)
    return demux_s.apply(params["demux"], h, mux, index_embeds=index_embeds)


# ---------------------------------------------------------------------------
# Backbone
# ---------------------------------------------------------------------------

class Backbone:
    # -- init -------------------------------------------------------------------

    @staticmethod
    def init(key, cfg: ModelConfig) -> Params:
        keys = jax.random.split(key, 8)
        kinds = cfg.layer_kinds()
        head, period, groups = cfg.layer_pattern()
        pdtype = cfg.pdtype
        norm = make_norm(cfg.norm)

        params: dict = {
            "embed": Embedding.init(keys[0], cfg.vocab, cfg.d_model,
                                    param_dtype=pdtype),
            "final_norm": norm.init(keys[1], cfg.d_model, param_dtype=pdtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = Linear.init(keys[2], cfg.d_model, cfg.vocab,
                                            param_dtype=pdtype)
        if cfg.mux.active:
            params["mux"] = get_mux(cfg.mux.strategy).init(
                keys[3], cfg.mux, cfg.d_model, param_dtype=pdtype)
            params["demux"] = get_demux(cfg.mux.demux).init(
                keys[4], cfg.mux, cfg.d_model, param_dtype=pdtype)

        lkeys = jax.random.split(keys[5], cfg.n_layers)
        params["head_layers"] = [
            _layer_init(lkeys[i], cfg, kinds[i]) for i in range(head)]
        # scanned pattern: per pattern-position params stacked over groups
        blocks = []
        for j in range(period if groups else 0):
            idx = jnp.array([head + g * period + j for g in range(groups)])
            gkeys = lkeys[idx]
            blocks.append(jax.vmap(
                lambda k, kd=kinds[head + j]: _layer_init(k, cfg, kd))(gkeys))
        params["blocks"] = blocks
        tail_start = head + period * groups
        params["tail_layers"] = [
            _layer_init(lkeys[i], cfg, kinds[i])
            for i in range(tail_start, cfg.n_layers)]

        if cfg.encoder is not None:
            params["encoder"] = Backbone.init_encoder(keys[6], cfg.encoder)
        return params

    @staticmethod
    def init_encoder(key, enc_cfg: ModelConfig):
        """Encoder stack (whisper): blocks only, input is stub embeddings."""
        kinds = enc_cfg.layer_kinds()
        lkeys = jax.random.split(key, enc_cfg.n_layers + 1)
        norm = make_norm(enc_cfg.norm)
        return {
            "layers": [
                _layer_init(lkeys[i], enc_cfg, kinds[i])
                for i in range(enc_cfg.n_layers)],
            "final_norm": norm.init(lkeys[-1], enc_cfg.d_model,
                                    param_dtype=enc_cfg.pdtype),
        }

    # -- caches -----------------------------------------------------------------

    @staticmethod
    def init_cache(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=None, *, page_pool=None) -> Params:
        """``page_pool``: optional (pool_pages, page_size) — eligible
        full-attention layers get pooled paged K/V and MLA layers pooled
        paged latents (see ``serving/paging.py``) instead of per-slot
        contiguous regions.  Windowed ring buffers and SSM states stay
        contiguous either way."""
        dtype = dtype or cfg.compute_dtype
        kinds = cfg.layer_kinds()
        head, period, groups = cfg.layer_pattern()
        cache: dict = {
            "head": [_layer_cache(cfg, kinds[i], batch, max_len, dtype,
                                  page_pool)
                     for i in range(head)],
            "blocks": [
                jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (groups,) + a.shape).copy()
                    if hasattr(a, "shape") else a,
                    _layer_cache(cfg, kinds[head + j], batch, max_len, dtype,
                                 page_pool))
                for j in range(period if groups else 0)],
            "tail": [_layer_cache(cfg, kinds[i], batch, max_len, dtype,
                                  page_pool)
                     for i in range(head + period * groups, cfg.n_layers)],
        }
        return cache

    # -- context (stub multimodal frontend / encoder) -----------------------------

    @staticmethod
    def encode_context(params, context, cfg: ModelConfig, *, mesh=None,
                       mesh_info: MeshInfo = SINGLE):
        """context: (B, Lc, context_dim) stub embeddings -> cross-attn K/V per
        cross layer.  For enc-dec (whisper) the encoder stack runs first."""
        kinds = cfg.layer_kinds()
        ctx = context.astype(cfg.compute_dtype)
        if cfg.encoder is not None:
            enc = params["encoder"]
            ecfg = cfg.encoder
            ekinds = ecfg.layer_kinds()
            x = ctx
            pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
            for i, lp in enumerate(enc["layers"]):
                x, _, _ = _layer_apply(lp, x, ecfg, ekinds[i], positions=pos,
                                       mesh=mesh, mesh_info=mesh_info)
            ctx = make_norm(ecfg.norm).apply(enc["final_norm"], x)

        head, period, groups = cfg.layer_pattern()
        acfg = cfg.attn_config()

        def precompute(lp):
            return CrossAttention.precompute_kv(lp["cross"], ctx, acfg)

        kv = {"head": {}, "blocks": {}, "tail": {}}
        for i in range(head):
            if kinds[i]["cross"]:
                kv["head"][i] = precompute(params["head_layers"][i])
        for j in range(period if groups else 0):
            if kinds[head + j]["cross"]:
                kv["blocks"][j] = jax.vmap(precompute)(params["blocks"][j])
        tail_start = head + period * groups
        for i in range(tail_start, cfg.n_layers):
            if kinds[i]["cross"]:
                kv["tail"][i - tail_start] = precompute(
                    params["tail_layers"][i - tail_start])
        return kv

    # -- block runner --------------------------------------------------------------

    @staticmethod
    def _run_blocks(params, x, cfg: ModelConfig, *, positions, cache=None,
                    cache_index=None, cross_kv=None, block_table=None,
                    chunk_lens=None, row_mask=None, mesh=None,
                    mesh_info: MeshInfo = SINGLE):
        kinds = cfg.layer_kinds()
        head, period, groups = cfg.layer_pattern()
        aux_total = jnp.zeros((), jnp.float32)
        new_cache: Optional[dict] = None if cache is None else \
            {"head": [], "blocks": [], "tail": []}

        sp_spec = None
        if (cfg.seq_parallel and mesh is not None and
                cfg.d_model % max(mesh_info.model_size, 1) == 0):
            bat, seq = mesh_info.bl_entries(x.shape[0], x.shape[1])
            sp_spec = jax.sharding.PartitionSpec(bat, seq,
                                                 mesh_info.model_axis)

        def run_one(lp, x, kind, lcache, ckv, layer=None):
            x, nc, aux = _layer_apply(lp, x, cfg, kind, positions=positions,
                                      cache=lcache, cache_index=cache_index,
                                      cross_kv=ckv, block_table=block_table,
                                      chunk_lens=chunk_lens,
                                      row_mask=row_mask,
                                      mesh=mesh, mesh_info=mesh_info,
                                      layer=layer)
            if sp_spec is not None:
                x = _constrain(x, mesh, sp_spec)
            return x, nc, aux

        # head (unscanned)
        for i in range(head):
            lc = cache["head"][i] if cache is not None else None
            ckv = (cross_kv or {}).get("head", {}).get(i)
            x, nc, aux = run_one(params["head_layers"][i], x, kinds[i], lc, ckv)
            aux_total = aux_total + aux
            if new_cache is not None:
                new_cache["head"].append(nc)

        # scanned groups
        if groups:
            stacked_lcs = cache["blocks"] if cache is not None else None
            # Paged GQA pools ride the carry whole (indexed by the group
            # number ``g``); every other stacked cache is sliced per group.
            pools = {j: lc for j, lc in enumerate(stacked_lcs or [])
                     if carries_pool(lc)}

            def group_body(carry, sliced):
                x, pools = carry
                lps, lcs, ckvs, g = sliced
                pools = dict(pools)
                aux_g = jnp.zeros((), jnp.float32)
                ncs = []
                for j in range(period):
                    lc = pools.get(j, lcs[j] if lcs is not None else None)
                    x, nc, aux = run_one(lps[j], x, kinds[head + j], lc,
                                         ckvs.get(j) if ckvs else None,
                                         layer=g if j in pools else None)
                    aux_g = aux_g + aux
                    if j in pools:
                        pools[j], nc = nc, None
                    ncs.append(nc)
                return (x, pools), (ncs if lcs is not None else None, aux_g)

            if cfg.remat == "full":
                group_body = jax.checkpoint(group_body)
            elif cfg.remat == "dots":
                group_body = jax.checkpoint(
                    group_body,
                    policy=jax.checkpoint_policies
                    .dots_with_no_batch_dims_saveable)

            stacked_lps = params["blocks"]  # list over pattern positions
            sliced_lcs = None if stacked_lcs is None else [
                None if j in pools else lc
                for j, lc in enumerate(stacked_lcs)]
            block_ckvs = (cross_kv or {}).get("blocks", {}) or None
            (x, pools), (ncs, aux_g) = jax.lax.scan(
                group_body, (x, pools),
                (stacked_lps,
                 sliced_lcs if sliced_lcs is not None else
                 [None] * period if period else None,
                 {j: v for j, v in (block_ckvs or {}).items()},
                 jnp.arange(groups, dtype=jnp.int32) if pools else None))
            aux_total = aux_total + jnp.sum(aux_g)
            if new_cache is not None:
                new_cache["blocks"] = [pools.get(j, nc)
                                       for j, nc in enumerate(ncs)]

        # tail (unscanned)
        tail_start = head + period * groups
        for t, i in enumerate(range(tail_start, cfg.n_layers)):
            lc = cache["tail"][t] if cache is not None else None
            ckv = (cross_kv or {}).get("tail", {}).get(t)
            x, nc, aux = run_one(params["tail_layers"][t], x, kinds[i], lc, ckv)
            aux_total = aux_total + aux
            if new_cache is not None:
                new_cache["tail"].append(nc)

        with jax.named_scope("lm_head"):
            x = make_norm(cfg.norm).apply(params["final_norm"], x)
        return x, new_cache, aux_total

    # -- embedding / logits ----------------------------------------------------------

    @staticmethod
    def embed(params, tokens, cfg: ModelConfig):
        return Embedding.apply(params["embed"], tokens,
                               dtype=cfg.compute_dtype)

    @staticmethod
    def logits(params, h, cfg: ModelConfig):
        if cfg.tie_embeddings:
            out = Embedding.attend(params["embed"], h)
        else:
            out = Linear.apply(params["lm_head"], h)
        if cfg.logits_softcap:
            c = cfg.logits_softcap
            out = c * jnp.tanh(out / c)
        return out

    # -- full-sequence forward (train / prefill) ----------------------------------

    @staticmethod
    def apply(params, tokens, cfg: ModelConfig, *, context=None,
              cross_kv=None, mesh=None, mesh_info: MeshInfo = SINGLE,
              cache=None, last_only: bool = False):
        """tokens: (B, N, L) when mux active else (B, L).

        Returns dict(hidden, demuxed, logits, index_embeds, aux, cache).
        ``demuxed``/``logits`` are (B, N, L, ·) when mux active else (B, L, ·).
        Passing a fresh ``cache`` turns this into a prefill: the cache comes
        back filled (KV / ring / latent / SSM state) ready for decode_step.

        ``last_only``: serving prefill — demux + logits for the final
        position only.  The demultiplexer expands activations N-fold (the
        one place DataMUX pays an N× cost); at 32k prefill that tensor
        dominates the memory AND collective roofline terms (§Perf A5), and
        next-token serving never needs it.

        ``cross_kv``: pre-encoded context K/V (``encode_context``) — pass it
        to skip re-encoding ``context`` (the serving engine encodes once per
        request and threads it through prefill and every decode step).
        """
        mux = cfg.mux
        if cross_kv is None and context is not None:
            cross_kv = Backbone.encode_context(params, context, cfg,
                                               mesh=mesh, mesh_info=mesh_info)
        if mux.active:
            demux_s = get_demux(mux.demux)
            b, n, l = tokens.shape
            emb = Backbone.embed(params, tokens, cfg)  # (B, N, L, d)
            p = mux.prefix_len
            if p:
                pre = demux_s.prefix_embeddings(
                    params["demux"], mux, emb.dtype)  # (N, P, d)
                pre = jnp.broadcast_to(pre[None], (b, n, p, emb.shape[-1]))
                emb = jnp.concatenate([pre, emb], axis=2)
            x = get_mux(mux.strategy).apply(params["mux"], emb,
                                            mux)  # (B, P+L, d)
        else:
            b, l = tokens.shape
            p = 0
            x = Backbone.embed(params, tokens, cfg)

        bat, seq = mesh_info.bl_entries(x.shape[0], x.shape[1])
        x = _constrain(x, mesh, jax.sharding.PartitionSpec(bat, seq, None))
        positions = jnp.broadcast_to(jnp.arange(x.shape[1]), (b, x.shape[1]))
        h, new_cache, aux = Backbone._run_blocks(
            params, x, cfg, positions=positions, cross_kv=cross_kv,
            cache=cache, mesh=mesh, mesh_info=mesh_info)

        out = {"hidden": h, "aux": aux, "index_embeds": None,
               "cache": new_cache}
        if mux.active:
            if demux_s.uses_prefix:
                index_embeds = h[:, :mux.n]       # p^i = h at prefix pos i
                h_rest = h[:, p:]                 # drop padding positions too
            else:
                index_embeds = None
                h_rest = h
            if last_only:
                h_rest = h_rest[:, -1:]
            demuxed = demux_s.apply(params["demux"], h_rest, mux,
                                    index_embeds=index_embeds)
            out["demuxed"] = demuxed
            out["index_embeds"] = index_embeds
            out["logits"] = Backbone.logits(params, demuxed, cfg)
        else:
            out["demuxed"] = h[:, -1:] if last_only else h
            out["logits"] = Backbone.logits(params, out["demuxed"], cfg)
        return out

    # -- single-token decode (serving) ---------------------------------------------

    @staticmethod
    def decode_step(params, tokens, cache, cache_index, cfg: ModelConfig, *,
                    index_embeds=None, cross_kv=None, lane_mask=None,
                    block_table=None, chunk_lens=None, mesh=None,
                    mesh_info: MeshInfo = SINGLE):
        """One decode step.

        tokens: (B, N) last generated token per stream when mux active,
        else (B,).  cache_index: absolute position (including the prefix)
        being written — a scalar int32 (all slots in lock-step) or a (B,)
        int32 vector (continuous batching: each backbone slot decodes at
        its own position).  lane_mask: optional (B, N) 0/1 — retired lanes
        contribute nothing to the mixed stream (φ^i(0) = 0 for the linear
        strategies) and their logits are zeroed, so a freed lane neither
        pollutes the superposition nor leaks stale predictions.
        block_table: (B, max_pages) int32 when the cache is paged
        (``serving/paging.py``): maps each slot's page index to a pool page
        for the paged attention layers' writes and gathers.
        Returns (logits, new_cache): logits (B, N, vocab) when mux active
        else (B, vocab).

        Chunked decode (``chunk_lens`` (B,) int32 given): tokens carry a
        trailing chunk axis — (B, N, C) / (B, C) — and ``cache_index`` is
        the (B,) base position of each slot's chunk; slot b writes cache
        rows ``[cache_index[b], cache_index[b] + chunk_lens[b])`` in one
        call, so a ramping prompt consumes ~Lp/C steps instead of Lp.
        ``lane_mask`` becomes (B, N, C): a non-ramping lane contributes its
        token at row 0 only — its extra chunk rows are masked out of the
        mixed stream (and therefore the KV write) and of the logits.
        Returns logits (B, N, C, vocab) / (B, C, vocab).
        """
        mux = cfg.mux
        ci = jnp.asarray(cache_index, jnp.int32)
        if chunk_lens is not None:
            return Backbone._chunked_decode_step(
                params, tokens, cache, ci, cfg,
                chunk_lens=jnp.asarray(chunk_lens, jnp.int32),
                index_embeds=index_embeds, cross_kv=cross_kv,
                lane_mask=lane_mask, block_table=block_table, mesh=mesh,
                mesh_info=mesh_info)
        with jax.named_scope("mux"):
            if mux.active:
                b, n = tokens.shape
                emb = Backbone.embed(params, tokens[:, :, None],
                                     cfg)                         # (B,N,1,d)
                if lane_mask is not None:
                    emb = emb * lane_mask[:, :, None, None].astype(emb.dtype)
                x = get_mux(mux.strategy).apply(params["mux"], emb,
                                                mux)              # (B,1,d)
            else:
                b = tokens.shape[0]
                x = Backbone.embed(params, tokens[:, None], cfg)   # (B,1,d)
                if lane_mask is not None:
                    x = x * lane_mask[:, :1, None].astype(x.dtype)

        positions = jnp.broadcast_to(
            ci[:, None] if ci.ndim else ci, (b, 1))
        # Row validity for row-exact MoE dispatch: a slot with no live lane
        # carries a garbage row that must not compete for expert capacity.
        # Lock-step ``generate`` passes no lane_mask -> no masking (all rows
        # are real), keeping that path bitwise-unchanged.
        row_mask = None
        if lane_mask is not None:
            row_mask = lane_mask.astype(bool).any(axis=1)[:, None]   # (B, 1)
        h, new_cache, _ = Backbone._run_blocks(
            params, x, cfg, positions=positions, cache=cache,
            cache_index=ci, cross_kv=cross_kv, block_table=block_table,
            row_mask=row_mask, mesh=mesh, mesh_info=mesh_info)

        if mux.active:
            with jax.named_scope("demux"):
                demuxed = _demux_decode(params, h, cfg, index_embeds)
            with jax.named_scope("lm_head"):
                logits = Backbone.logits(params, demuxed[:, :, 0],
                                         cfg)                     # (B,N,V)
                if lane_mask is not None:
                    logits = jnp.where(lane_mask[:, :, None].astype(bool),
                                       logits, 0.0)
        else:
            with jax.named_scope("lm_head"):
                logits = Backbone.logits(params, h[:, 0], cfg)    # (B,V)
                if lane_mask is not None:
                    logits = jnp.where(lane_mask[:, :1].astype(bool),
                                       logits, 0.0)
        return logits, new_cache

    @staticmethod
    def _chunked_decode_step(params, tokens, cache, ci, cfg: ModelConfig, *,
                             chunk_lens, index_embeds=None, cross_kv=None,
                             lane_mask=None, block_table=None, mesh=None,
                             mesh_info: MeshInfo = SINGLE):
        """Chunked-prefill decode step (see ``decode_step``): a (B, ·, C)
        token chunk advances slot b by ``chunk_lens[b]`` positions."""
        mux = cfg.mux
        with jax.named_scope("mux"):
            if mux.active:
                b, n, c = tokens.shape
                emb = Backbone.embed(params, tokens, cfg)      # (B,N,C,d)
                if lane_mask is not None:
                    emb = emb * lane_mask[..., None].astype(emb.dtype)
                x = get_mux(mux.strategy).apply(params["mux"], emb,
                                                mux)           # (B,C,d)
            else:
                b, c = tokens.shape
                x = Backbone.embed(params, tokens, cfg)        # (B,C,d)
                if lane_mask is not None:
                    x = x * lane_mask[:, 0, :, None].astype(x.dtype)

        positions = ci[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
        # Row validity for row-exact MoE dispatch: rows at or past a slot's
        # chunk_lens are padding, and a row of a slot with no live lane at
        # that chunk position is a garbage superposition — neither may
        # compete for expert capacity or pollute the aux statistics.
        row_mask = jnp.arange(c, dtype=jnp.int32)[None, :] < \
            chunk_lens[:, None]                                      # (B, C)
        if lane_mask is not None:
            row_mask = row_mask & lane_mask.astype(bool).any(axis=1)
        h, new_cache, _ = Backbone._run_blocks(
            params, x, cfg, positions=positions, cache=cache,
            cache_index=ci, cross_kv=cross_kv, block_table=block_table,
            chunk_lens=chunk_lens, row_mask=row_mask, mesh=mesh,
            mesh_info=mesh_info)

        if mux.active:
            with jax.named_scope("demux"):
                demuxed = _demux_decode(params, h, cfg, index_embeds)
            with jax.named_scope("lm_head"):
                logits = Backbone.logits(params, demuxed, cfg)  # (B,N,C,V)
                if lane_mask is not None:
                    logits = jnp.where(lane_mask[..., None].astype(bool),
                                       logits, 0.0)
        else:
            with jax.named_scope("lm_head"):
                logits = Backbone.logits(params, h, cfg)        # (B,C,V)
                if lane_mask is not None:
                    logits = jnp.where(
                        lane_mask[:, 0, :, None].astype(bool), logits, 0.0)
        return logits, new_cache
