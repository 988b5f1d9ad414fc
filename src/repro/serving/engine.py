"""Batched serving engine with first-class data multiplexing.

Beyond-paper extension (DESIGN.md §3): the paper evaluates DataMUX on
encoder classification only; here N user streams share one backbone stream
end-to-end through autoregressive decoding — one KV-cache slot, one decode
matmul, demux applied per step to the final hidden state.

Flow:  prefill(prompts (B, N, Lp)) -> ServeState{cache, index_embeds, pos}
       step(state, last_tokens (B, N)) -> (logits (B, N, V), state)

Two decode regimes share the same jitted step:

  * lock-step (``generate``): scalar ``pos`` — every slot at the same
    position, the classic fixed-(B, N) grid.
  * continuous batching (``serving.scheduler``): ``pos`` is a (B,) vector
    and ``lane_mask`` (B, N) marks live lanes, so slots prefill/decode/retire
    independently.  ``prime()`` builds the prefix-primed cache the slot
    allocator resets retired slots back to.

The decode-step cache is donated to the jitted step (``donate_argnums``):
each step updates the cache buffers in place instead of copying the whole
pytree (measured in ``benchmarks/memory_overhead.py``).  The cache inside a
``ServeState`` is therefore consumed by ``step`` — keep only the returned
state, never re-step a stale one.

The engine is strategy-agnostic: mux/demux schemes resolve by name from
``repro.core.strategies`` inside the backbone, so any registered strategy
(including fused ``kernel_apply`` paths via ``cfg.mux.use_kernel``) serves
through this class unchanged.  ``index_embeds`` is populated only for
prefix-protocol demuxers (``uses_prefix``) and stays None otherwise.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import Backbone
from repro.models.backbone import pool_layers_in_carry
from repro.nn.moe import SINGLE, MeshInfo
from repro.serving.weakjit import weak_method


@dataclasses.dataclass
class ServeState:
    cache: Any
    pos: jnp.ndarray                     # int32: next absolute position —
                                         # scalar (lock-step) or (B,) vector
                                         # (continuous batching)
    index_embeds: Optional[jnp.ndarray]  # (B, N, d) for prefix-protocol demux
                                         # strategies (uses_prefix), else None
    cross_kv: Any = None


class Engine:
    """``device``: put the params on this one device, and with them every
    cache and page pool built for this engine (``placed``), so a scheduler
    over it runs on that device alone (one-device replicas behind
    ``ReplicaRouter``); leave it None to keep the params where they are —
    and do not combine it with a ``mesh``."""

    def __init__(self, params, cfg: ModelConfig, *, batch: int, max_len: int,
                 mesh=None, mesh_info: MeshInfo = SINGLE, jit: bool = True,
                 device=None):
        if device is not None:
            if mesh is not None:
                raise ValueError("Engine takes a device or a mesh, not both")
            params = jax.device_put(params, device)
        self.params = params
        self.device = device
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len + cfg.mux.prefix_len
        self.mesh = mesh
        self.mesh_info = mesh_info
        chunk = cfg.serving.prefill_chunk
        if chunk > 1:
            # Chunked decode needs per-row write validity: attention caches
            # mask row writes, MLA latents do the same, and Mamba gates its
            # recurrence per row (``Mamba._chunked_decode``).  xLSTM state
            # updates have no row-masked form yet.  Also C distinct ring
            # slots per chunk.
            kinds = cfg.layer_kinds()
            bad = sorted({k["mixer"] for k in kinds
                          if k["mixer"] in ("mlstm", "slstm")})
            if bad:
                raise ValueError(
                    f"serving.prefill_chunk={chunk} unsupported with "
                    f"{bad} mixers (xLSTM has no row-masked state update); "
                    f"set prefill_chunk=1")
            slots = min([self.max_len] +
                        [k["window"] for k in kinds if k["window"]])
            if chunk > slots:
                raise ValueError(
                    f"serving.prefill_chunk={chunk} exceeds the smallest "
                    f"cache ring ({slots} slots); shrink the chunk")
        self._validate_serving_policy(cfg)
        self._jit = jit
        # Width-class engine variants (``variant``): lazily built, cached by
        # (width, batch), counted so telemetry can gauge compile pressure.
        self._variants: dict[tuple[int, int], "Engine"] = {}
        self.variant_compiles = 0
        # Weakly bound (serving/weakjit.py): a dropped engine frees its
        # weights at once instead of at the next cycle collection.
        maybe_jit = jax.jit if jit else (lambda f, **kw: f)
        self._prefill = maybe_jit(weak_method(self._prefill_impl))
        # Donate the cache: the decode step aliases the KV buffers instead of
        # allocating a second full cache every token (no-op on backends
        # without donation support, e.g. CPU — then it simply copies).
        self._step = maybe_jit(weak_method(self._step_impl),
                               donate_argnums=(2,))
        # Scanned layers whose paged pools (the page allocator's, under
        # ``serving.paged``) ride the layer scan's carry.
        self.pool_layers_in_carry = pool_layers_in_carry(jax.eval_shape(
            lambda: Backbone.init_cache(
                cfg, batch, self.max_len,
                page_pool=(2, cfg.serving.page_size)))) \
            if cfg.serving.paged else 0
        self._prime = maybe_jit(weak_method(self._prime_impl),
                                static_argnames=("prime_len",))

    @staticmethod
    def _validate_serving_policy(cfg: ModelConfig) -> None:
        """Fail fast on a typo'd serving policy name at engine
        construction, before params and caches build.  The preempt /
        eviction pairing is *not* checked here: the scheduler accepts an
        explicit ``eviction=`` override (e.g. fifo admission + priority
        eviction), so only it can tell whether ``preempt=True`` is
        satisfiable."""
        from repro.serving import policies as serving_policies
        slo = serving_policies.SloClasses(cfg.serving.slo_classes)
        serving_policies.resolve("admission", cfg.serving.policy, slo)

    # -- impl -------------------------------------------------------------------

    def _prefill_impl(self, params, tokens, cross_kv):
        cfg = self.cfg
        cache = Backbone.init_cache(cfg, self.batch, self.max_len)
        # last_only: never materialise the (B, N, L, d) demux tensor —
        # serving prefill needs next-token logits only (§Perf A5)
        out = Backbone.apply(params, tokens, cfg, cross_kv=cross_kv,
                             cache=cache, mesh=self.mesh,
                             mesh_info=self.mesh_info, last_only=True)
        lp = tokens.shape[-1] + cfg.mux.prefix_len
        last_logits = out["logits"][..., -1, :]
        return (out["cache"], out["index_embeds"], last_logits,
                jnp.asarray(lp, jnp.int32))

    def _prime_impl(self, params, prime_len: int):
        """Prefix-only prefill: run the demux prefix (no content tokens)
        through the backbone so the cache holds exactly the prefix K/V and
        ``index_embeds`` are captured.  For causal models the prefix hidden
        states attend only to the prefix, so this primed state is
        input-independent — the slot allocator resets retired slots back to
        it without re-running any prefill.

        ``prime_len``: width of the primed cache.  ``max_len`` gives the
        full-size template the contiguous allocator swaps in on slot reset;
        ``prefix_len`` gives a prefix-sized template — the paged allocator
        imports the prefix pages from it without ever materialising a dense
        (B, max_len) transient (the positions beyond the prefix are all
        unwritten, so nothing is lost)."""
        cfg = self.cfg
        cache = Backbone.init_cache(cfg, self.batch, prime_len)
        empty = jnp.zeros((self.batch, cfg.mux.n, 0), jnp.int32)
        out = Backbone.apply(params, empty, cfg, cache=cache,
                             mesh=self.mesh, mesh_info=self.mesh_info,
                             last_only=True)
        return out["cache"], out["index_embeds"]

    def _step_impl(self, params, tokens, cache, pos, index_embeds, cross_kv,
                   lane_mask, block_table, chunk_lens=None):
        return Backbone.decode_step(
            params, tokens, cache, pos, self.cfg,
            index_embeds=index_embeds, cross_kv=cross_kv,
            lane_mask=lane_mask, block_table=block_table,
            chunk_lens=chunk_lens, mesh=self.mesh,
            mesh_info=self.mesh_info)

    # -- public API -----------------------------------------------------------------

    def prefill(self, prompts, context=None) -> tuple[jnp.ndarray, ServeState]:
        """prompts: (B, N, Lp) muxed or (B, Lp).  Returns (last-token logits,
        state).  ``context`` is encoded exactly once here; the resulting
        ``cross_kv`` threads through prefill and every decode step."""
        cross_kv = None
        if context is not None:
            cross_kv = Backbone.encode_context(
                self.params, jnp.asarray(context), self.cfg,
                mesh=self.mesh, mesh_info=self.mesh_info)
        cache, index_embeds, last_logits, pos = self._prefill(
            self.params, jnp.asarray(prompts), cross_kv)
        return last_logits, ServeState(cache=cache, pos=pos,
                                       index_embeds=index_embeds,
                                       cross_kv=cross_kv)

    def prime(self, context=None, *, compact: bool = False) -> ServeState:
        """Prefix-primed state for continuous batching: cache holds only the
        demux-prefix K/V, ``pos`` is a (B,) vector at ``prefix_len``.  With a
        non-prefix demux (or mux inactive) the cache is simply fresh and
        ``pos`` starts at 0.

        ``compact``: prime against a *prefix-sized* cache (width
        ``prefix_len``, or 1 when there is no prefix) instead of the full
        ``max_len`` one.  The prefix K/V values are bitwise identical either
        way; the paged allocator imports from the compact template directly,
        so priming never materialises the dense (B, max_len) transient."""
        cfg = self.cfg
        cross_kv = None
        if context is not None:
            cross_kv = Backbone.encode_context(
                self.params, jnp.asarray(context), self.cfg,
                mesh=self.mesh, mesh_info=self.mesh_info)
        p = cfg.mux.prefix_len
        if cfg.mux.active and p:
            cache, index_embeds = self._prime(
                self.params, prime_len=(p if compact else self.max_len))
        else:
            with self.placed():
                cache = Backbone.init_cache(cfg, self.batch,
                                            1 if compact else self.max_len)
            index_embeds = None
        pos = jnp.full((self.batch,), p, jnp.int32)
        return ServeState(cache=cache, pos=pos, index_embeds=index_embeds,
                          cross_kv=cross_kv)

    def placed(self):
        """Context in which arrays built eagerly for this engine (caches,
        page pools) land on its ``device``; a no-op without one.  Jitted
        calls need none: they run where the params are."""
        if self.device is None:
            return contextlib.nullcontext()
        return jax.default_device(self.device)

    def variant(self, width: int, batch: int) -> "Engine":
        """Width-class serving variant: an engine serving ``batch`` slots at
        mux width ``width`` <= cfg.mux.n, sharing this engine's backbone
        weights but carrying narrowed mux/demux params (each strategy's
        ``narrow``), its own jitted prefill/step/prime, and its own
        KV/page-template shapes.  ``width == 1`` is a true unmuxed baseline
        (mux inactive: no prefix, no demux).  Variants are built lazily and
        cached by (width, batch); the native (cfg.mux.n, self.batch) pair
        returns ``self`` — bit-for-bit the single-engine path."""
        if width == self.cfg.mux.n and batch == self.batch:
            return self
        key = (width, batch)
        if key not in self._variants:
            self._variants[key] = self._build_variant(width, batch)
            self.variant_compiles += 1
        return self._variants[key]

    def _build_variant(self, width: int, batch: int) -> "Engine":
        from repro.core import strategies
        cfg = self.cfg
        if not 1 <= width <= cfg.mux.n:
            raise ValueError(
                f"variant width must satisfy 1 <= w <= mux.n={cfg.mux.n}, "
                f"got {width}")
        vcfg = dataclasses.replace(
            cfg,
            mux=dataclasses.replace(cfg.mux, n=width),
            # The variant serves exactly one class: clear the width set so
            # the class-vs-native cross-check cannot trip on siblings.
            serving=dataclasses.replace(cfg.serving, width_set=()))
        params = dict(self.params)
        if width == 1:
            params.pop("mux", None)
            params.pop("demux", None)
        elif cfg.mux.active:
            params["mux"] = strategies.get_mux(cfg.mux.strategy).narrow(
                self.params["mux"], cfg.mux, width)
            params["demux"] = strategies.get_demux(cfg.mux.demux).narrow(
                self.params["demux"], cfg.mux, width)
        serve_len = self.max_len - cfg.mux.prefix_len
        eng = Engine(params, vcfg, batch=batch, max_len=serve_len,
                     mesh=self.mesh, mesh_info=self.mesh_info, jit=self._jit,
                     device=self.device)
        return eng

    def step(self, state: ServeState, tokens, lane_mask=None,
             block_table=None, chunk_lens=None
             ) -> tuple[jnp.ndarray, ServeState]:
        """One decode step.  ``state.pos`` may be scalar (lock-step) or (B,)
        (continuous); ``lane_mask`` (B, N) masks retired lanes out of the
        mixed stream and the logits; ``block_table`` (B, max_pages) routes
        paged-cache writes/gathers (``serving/paging.py``).  ``state.cache``
        is donated — use the returned state from here on.

        Chunked prefill: with ``chunk_lens`` (B,), ``tokens`` carries a
        trailing chunk axis (B, N, C) / (B, C), ``lane_mask`` is (B, N, C),
        and slot b advances ``chunk_lens[b]`` positions (see
        ``Backbone.decode_step``); logits come back per chunk row."""
        if lane_mask is not None:
            lane_mask = jnp.asarray(lane_mask)
        if chunk_lens is not None:
            chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
        logits, cache = self._step(self.params, jnp.asarray(tokens),
                                   state.cache, state.pos,
                                   state.index_embeds, state.cross_kv,
                                   lane_mask, block_table, chunk_lens)
        advance = 1 if chunk_lens is None else chunk_lens
        return logits, dataclasses.replace(state, cache=cache,
                                           pos=state.pos + advance)

    def generate(self, prompts, steps: int, *, context=None,
                 greedy: bool = True, rng=None):
        """Greedy/sampled generation for all (B, N) streams simultaneously."""
        logits, state = self.prefill(prompts, context=context)
        toks = []
        last = jnp.argmax(logits, axis=-1)
        for t in range(steps):
            toks.append(last)
            logits, state = self.step(state, last)
            if greedy:
                last = jnp.argmax(logits, axis=-1)
            else:
                rng, k = jax.random.split(rng)
                last = jax.random.categorical(k, logits)
        toks.append(last)
        return jnp.stack(toks, axis=-1)  # (B, N, steps+1) or (B, steps+1)
