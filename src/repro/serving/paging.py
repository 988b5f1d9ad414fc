"""Paged KV cache: block-table allocator over a shared page pool.

The contiguous ``KVSlotAllocator`` gives every backbone slot a private
``max_len`` cache region, so admission must refuse any request that would
overflow a deep slot and one long generation pins a whole slot's memory.
This module pages the position axis instead (vLLM-style, applied to
DataMUX's N-streams-per-slot cache):

  * the pool: every eligible attention layer holds ``pool_pages`` pages of
    ``page_size`` positions (``Attention.init_paged_cache``), and MLA
    layers page their (r + rope)-wide latent rows the same way
    (``MLA.init_paged_cache``); page 0 is a reserved trash page — writes
    from emptied slots land there and no block table ever references it;
  * the ``PageTable``: host-side free list + per-slot page rows.  A slot's
    page row is identical across layers (same positions everywhere), so one
    (B, max_pages) device block table serves the whole pytree;
  * allocate-on-demand: ``ensure`` maps each live slot's next write position
    to a page just before the decode step — a slot's footprint is its live
    tokens, not ``max_len``;
  * free-on-retire: when a slot's lanes have all retired its non-prefix
    pages return to the free list in O(pages) host work; the device-side
    cost is one scatter invalidating the recycled prefix tail.  Freed pages
    are lazily invalidated (pos ← -1) when next allocated, so recycling
    never touches pages that are not about to be reused.

Ineligible layers (windowed ring buffers, SSM states — all O(window) or
O(1) per slot) keep their contiguous per-slot caches and reset through the
same masked-restore the contiguous allocator uses.

Admission economics: the scheduler sizes requests in pages
(``pages_for``) against ``usable_pages`` instead of slot depth, so a
long-running slot never blocks admission as long as the pool has room.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.nn.attention import paged_eligible
from repro.serving.kvcache import _masked_restore
from repro.serving.telemetry import NULL_TRACER
from repro.serving.weakjit import weak_method

# Cache pytree sections and the axis their *contiguous* leaves carry the
# slot dimension on (paged pool leaves carry the pool on the same axis).
_SECTIONS = (("head", 0), ("tail", 0), ("blocks", 1))

TRASH_PAGE = 0


def pages_for(n_positions: int, page_size: int) -> int:
    """Pages needed to hold positions [0, n_positions)."""
    return -(-n_positions // page_size)


class PageTable:
    """Host-side page bookkeeping: free list + per-slot page rows.

    ``rows[s, j]`` is the pool page holding slot ``s``'s positions
    ``[j*page_size, (j+1)*page_size)``, or -1.  Page 0 is reserved (trash);
    ``usable_pages = pool_pages - 1``.  Allocation within a slot is
    sequential in ``j`` — decode positions grow one at a time — which makes
    slot recycle O(pages) list ops with no search.
    """

    def __init__(self, n_slots: int, pages_per_slot: int, pool_pages: int):
        if pool_pages < 2:
            raise ValueError(f"pool needs >= 2 pages (1 usable + trash), "
                             f"got {pool_pages}")
        self.n_slots = n_slots
        self.pages_per_slot = pages_per_slot
        self.pool_pages = pool_pages
        # LIFO free list: recently freed pages are reused first (their pool
        # rows are likelier to still be in cache on real hardware).
        self.free: list[int] = list(range(pool_pages - 1, TRASH_PAGE, -1))
        self.rows = np.full((n_slots, pages_per_slot), -1, np.int32)
        self.n_allocated = np.zeros(n_slots, np.int64)
        self.peak_in_use = 0

    @property
    def usable_pages(self) -> int:
        return self.pool_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self.free)

    @property
    def pages_in_use(self) -> int:
        return self.usable_pages - len(self.free)

    def allocate(self, slot: int, page_idx: int) -> int:
        """Map ``rows[slot, page_idx]`` to a fresh pool page."""
        if page_idx >= self.pages_per_slot:
            raise ValueError(
                f"slot {slot} page index {page_idx} exceeds table width "
                f"{self.pages_per_slot} (raise max_len)")
        if self.rows[slot, page_idx] >= 0:
            raise ValueError(f"slot {slot} page {page_idx} already mapped")
        if page_idx != self.n_allocated[slot]:
            raise ValueError(
                f"slot {slot} allocation must be sequential: asked for page "
                f"{page_idx} with {self.n_allocated[slot]} allocated")
        if not self.free:
            raise RuntimeError(
                "page pool exhausted — admission accounting should have "
                "reserved this page")
        pid = self.free.pop()
        self.rows[slot, page_idx] = pid
        self.n_allocated[slot] += 1
        self.peak_in_use = max(self.peak_in_use, self.pages_in_use)
        return pid

    def free_slot(self, slot: int, *, keep: int = 0) -> list[int]:
        """Return the slot's pages beyond the first ``keep`` (its prefix
        pages) to the free list.  O(1) per page: no compaction, no copies —
        the pool rows themselves are lazily invalidated on reallocation."""
        freed = [int(p) for p in self.rows[slot, keep:] if p >= 0]
        self.free.extend(reversed(freed))
        self.rows[slot, keep:] = -1
        self.n_allocated[slot] = min(self.n_allocated[slot], keep)
        return freed

    def detach_row(self, slot: int) -> tuple[np.ndarray, int]:
        """Park the slot's page row (preempt-and-swap): the pages leave the
        table without being freed — the caller's swap ledger owns them until
        ``attach_row`` — and the slot shows empty.  Host-side O(1): no page
        content moves."""
        row = self.rows[slot].copy()
        n = int(self.n_allocated[slot])
        self.rows[slot] = -1
        self.n_allocated[slot] = 0
        return row, n

    def attach_row(self, slot: int, row: np.ndarray, n_pages: int) -> None:
        """Reattach a detached row into an empty ``slot`` (resume): the
        parked pages come back exactly as parked, on whichever slot index
        was free."""
        if self.n_allocated[slot] or (self.rows[slot] >= 0).any():
            raise ValueError(
                f"slot {slot} still holds pages; free it before attaching "
                f"a parked row")
        self.rows[slot] = row
        self.n_allocated[slot] = n_pages


@dataclasses.dataclass
class PagedPark:
    """Parked cache state of one preempted slot (the swap-ledger payload
    under paging): the detached block-table row — its pool pages stay
    resident, untouched, until resumption — plus a snapshot of the
    ineligible contiguous layers' slot slice (None when every layer
    pages)."""
    row: np.ndarray
    n_pages: int
    snapshot: Any = None


class PagedKVSlotAllocator:
    """Paged counterpart of ``KVSlotAllocator``: owns the pooled decode
    cache pytree plus the page table.

    Construction imports the primed contiguous ``template`` (from
    ``Engine.prime``): prefix K/V is scattered into per-slot prefix pages
    (never freed afterwards — recycling a slot keeps its prefix resident,
    the same skip-the-prefill trick the contiguous allocator plays) and
    ineligible layers' state is copied through contiguous.

    Flow mirrors the contiguous allocator: the decode step consumes
    ``.cache`` (donated) and the scheduler hands the update back via
    ``adopt``; ``ensure`` runs just before each step to map every live
    slot's write position to a page; ``reset_slots`` recycles drained slots.
    """

    def __init__(self, cfg: ModelConfig, batch: int, max_len: int, *,
                 template: Optional[Any] = None, page_size: int = 0,
                 pool_pages: int = 0, jit: bool = True):
        from repro.models import Backbone
        self.cfg = cfg
        self.batch = batch
        self.max_len = max_len
        ps = page_size or cfg.serving.page_size
        self.page_size = ps
        # Telemetry recorder; rebound by ``ContinuousScheduler.set_tracer``.
        self.tracer = NULL_TRACER
        self.pages_per_slot = pages_for(max_len, ps)
        dense = batch * self.pages_per_slot + 1  # + trash page
        self.pool_pages = pool_pages or cfg.serving.pool_pages or dense

        self.prefix_len = cfg.mux.prefix_len
        self.n_prefix_pages = pages_for(self.prefix_len, ps)
        self.table = PageTable(batch, self.pages_per_slot, self.pool_pages)
        if self.table.usable_pages < batch * self.n_prefix_pages + 1:
            raise ValueError(
                f"pool_pages={self.pool_pages} cannot hold "
                f"{batch} slots x {self.n_prefix_pages} prefix pages "
                f"+ 1 working page")

        # Static per-layer paged/contiguous split, aligned with
        # Backbone.init_cache's section structure.
        kinds = cfg.layer_kinds()
        head, period, groups = cfg.layer_pattern()
        by_section = {
            "head": kinds[:head],
            "blocks": [kinds[head + j] for j in range(period if groups else 0)],
            "tail": kinds[head + period * groups:],
        }
        self._paged = {
            sec: [k["mixer"] in ("attn", "mla") and
                  paged_eligible(k["window"], max_len)
                  for k in sec_kinds]
            for sec, sec_kinds in by_section.items()}

        if template is None:
            template = Backbone.init_cache(cfg, batch, max_len)

        def make_pool():
            return Backbone.init_cache(cfg, batch, max_len,
                                       page_pool=(self.pool_pages, ps))

        # Made by one program, the stacked pools are written once; made op
        # by op, each is a broadcast and then its copy, two pools' worth of
        # device memory at once.
        self.cache = jax.jit(make_pool)() if jit else make_pool()
        # The template may be compact (prefix-sized, from
        # ``Engine.prime(compact=True)``): paged layers import from it
        # as-is, but ineligible contiguous layers must match the live
        # cache's width — pad them out (positions beyond the prime are
        # simply unwritten).
        template = self._expand_template(template)
        # Primed prefix content reshaped to page chunks, kept resident: the
        # construction-time import scatters every slot's prefix pages from
        # it, and ``park_slot`` re-imports one slot's worth when
        # reprovisioning a freed slot (B x prefix_len per paged layer —
        # cheap next to the pool).
        self._prefix_chunks = self._prefix_chunks_from(template)
        # Reset template: contiguous layers only — paged layers reset via
        # the page table, so their (B, max_len) template slices are dropped
        # (the full contiguous pytree would shadow the pool's memory win).
        self.template = {
            sec: [({} if self._paged[sec][i]
                   else jax.tree.map(jnp.copy, layer))
                  for i, layer in enumerate(template[sec])]
            for sec, _ in _SECTIONS}
        self._has_contiguous = any(
            not p for flags in self._paged.values() for p in flags)

        self._jit = jit
        # Weakly bound (serving/weakjit.py): a dropped allocator frees its
        # page pool at once instead of at the next cycle collection.
        maybe_jit = (lambda f, **kw: jax.jit(weak_method(f), **kw)) if jit \
            else (lambda f, **kw: weak_method(f))
        self._invalidate = maybe_jit(self._invalidate_impl,
                                     donate_argnums=(0,))
        self._reset = maybe_jit(self._reset_impl, donate_argnums=(0,))
        self._import = maybe_jit(self._import_impl, donate_argnums=(0,))
        self._import_slot = maybe_jit(self._import_slot_impl,
                                      donate_argnums=(0,))
        self._snapshot = maybe_jit(self._snapshot_impl)
        self._restore = maybe_jit(self._restore_impl, donate_argnums=(0,))

        # Pre-allocate each slot's prefix pages and scatter the primed
        # prefix K/V into them (plus the contiguous leaves wholesale).
        for s in range(batch):
            for j in range(self.n_prefix_pages):
                self.table.allocate(s, j)
        prefix_rows = jnp.asarray(self.table.rows[:, :self.n_prefix_pages])
        self.cache = self._import(self.cache, template,
                                  self._prefix_chunks, prefix_rows)
        # The last prefix page of each slot (partial iff prefix % ps != 0):
        # recycling must re-invalidate its tail, which the drained
        # generation overwrote.
        self._partial_off = self.prefix_len % ps
        if self.n_prefix_pages and self._partial_off:
            self._partial_pages = jnp.asarray(
                self.table.rows[:, self.n_prefix_pages - 1])
        else:
            self._partial_pages = jnp.zeros(batch, jnp.int32)

        self._device_table: Optional[jnp.ndarray] = None

    # -- structure walk --------------------------------------------------------

    def _walk(self, cache):
        """Yield (section, axis, layer-index, layer-cache, is-paged)."""
        for sec, axis in _SECTIONS:
            for i, layer in enumerate(cache[sec]):
                yield sec, axis, i, layer, self._paged[sec][i]

    def _expand_template(self, template):
        """Pad a compact (prefix-sized) primed template's *contiguous*
        layers out to the live cache's width.  Positions beyond the primed
        prefix are unwritten either way, so padding k/v/state with zeros and
        ``pos`` with the -1 sentinel reproduces the full-size prime bitwise.
        Paged layers stay compact — the prefix-page import reads only the
        prefix region.  A full-size template passes through untouched."""
        out = {sec: list(template[sec]) for sec, _ in _SECTIONS}
        for sec, axis, i, live, paged in self._walk(self.cache):
            if paged:
                continue
            tmpl = template[sec][i]
            new = {}
            for key, leaf in tmpl.items():
                target = live[key].shape
                if not hasattr(leaf, "shape") or leaf.shape == target:
                    new[key] = leaf
                    continue
                pad = [(0, t - s) for s, t in zip(leaf.shape, target)]
                new[key] = jnp.pad(leaf, pad,
                                   constant_values=-1 if key == "pos" else 0)
            out[sec][i] = new
        return out

    def _prefix_chunks_from(self, template):
        """Primed prefix content of every paged layer, reshaped slot-major
        into page chunks — k/v/pos each ``(B, npp, ps, ...)`` (blocks:
        ``(G, B, npp, ps, ...)``).  ``pos`` is padded with the -1 sentinel
        past the prefix, so scattering a chunk into freshly allocated pages
        also invalidates whatever their previous owner wrote."""
        ps = self.page_size
        npp = self.n_prefix_pages
        width = npp * ps
        chunks: dict[str, dict] = {}
        if npp == 0:
            return chunks
        for sec, axis, i, layer, paged in self._walk(self.cache):
            if not paged:
                continue
            tmpl = template[sec][i]
            ch = {}
            # Pool keys name their contiguous-template twin by suffix:
            # k_pages/v_pages/ckv_pages/krope_pages <- k/v/ckv/krope; the
            # shared "pos" maps to itself.  Keeps this import generic over
            # GQA K/V pools and MLA latent pools alike.
            for pool_key in layer:
                tmpl_key = pool_key[:-len("_pages")] \
                    if pool_key.endswith("_pages") else pool_key
                src = tmpl[tmpl_key]            # (B, S, ...) or (G, B, S, ...)
                pool = layer[pool_key]          # (P, ps, ...) or (G, P, ps, ...)
                seq_ax = axis + 1               # position axis of the template
                take = min(width, src.shape[seq_ax])
                src = jax.lax.slice_in_dim(src, 0, take, axis=seq_ax)
                pad = width - take
                if pad:                         # prefix page wider than cache
                    cfgpad = [(0, 0)] * src.ndim
                    cfgpad[seq_ax] = (0, pad)
                    fill = -1 if tmpl_key == "pos" else 0
                    src = jnp.pad(src, cfgpad, constant_values=fill)
                shape = (src.shape[:seq_ax] + (npp, ps) +
                         src.shape[seq_ax + 1:])
                # Row widths up to the pool's: K/V pools hold their heads
                # padded to whole tiles (``nn.attention.pool_kv_heads``).
                rows = [(0, p - c) for c, p in zip(
                    src.shape[seq_ax + 1:], pool.shape[axis + 2:])]
                ch[pool_key] = jnp.pad(
                    src.reshape(shape).astype(pool.dtype),
                    [(0, 0)] * (seq_ax + 2) + rows)
            chunks[f"{sec}/{i}"] = ch
        return chunks

    # -- jitted pytree ops ----------------------------------------------------

    def _import_impl(self, cache, template, chunks, prefix_rows):
        """Scatter the primed prefix chunks into every slot's pre-allocated
        prefix pages; copy contiguous layers through from the template."""
        out = {sec: list(cache[sec]) for sec, _ in _SECTIONS}
        for sec, axis, i, layer, paged in self._walk(cache):
            if not paged:
                # Real copies: the live cache is donated into the jitted
                # step and must never alias the template's buffers.
                out[sec][i] = jax.tree.map(jnp.copy, template[sec][i])
                continue
            key = f"{sec}/{i}"
            if key not in chunks:
                continue
            new_layer = dict(layer)
            for pool_key in layer:
                pool = layer[pool_key]
                chunk = chunks[key][pool_key]
                if axis == 0:                   # head/tail: pool axis 0
                    new_layer[pool_key] = pool.at[prefix_rows].set(chunk)
                else:                           # blocks: (G, P, ...) pool
                    new_layer[pool_key] = pool.at[:, prefix_rows].set(chunk)
            out[sec][i] = new_layer
        return out

    def _import_slot_impl(self, cache, chunks, rows, slot):
        """Scatter one slot's primed prefix chunk into freshly allocated
        prefix pages (``rows``, the park-reprovision path).  The chunk's
        ``pos`` covers the whole page region (-1 past the prefix), so the
        pages' stale previous content is invalidated by the same write."""
        out = {sec: list(cache[sec]) for sec, _ in _SECTIONS}
        for sec, axis, i, layer, paged in self._walk(cache):
            key = f"{sec}/{i}"
            if not paged or key not in chunks:
                continue
            new_layer = dict(layer)
            for pool_key in layer:
                pool = layer[pool_key]
                ch = jax.lax.dynamic_index_in_dim(
                    chunks[key][pool_key], slot, axis=axis, keepdims=False)
                if axis == 0:
                    new_layer[pool_key] = pool.at[rows].set(ch)
                else:
                    new_layer[pool_key] = pool.at[:, rows].set(ch)
            out[sec][i] = new_layer
        return out

    def _snapshot_impl(self, cache, slot):
        """Copy the ineligible contiguous layers' slice of ``slot`` (the
        park payload half that block tables cannot carry).  ``slot`` is
        traced — one compilation serves every slot."""
        out = {}
        for sec, axis, i, layer, paged in self._walk(cache):
            if paged:
                continue
            out[f"{sec}/{i}"] = jax.tree.map(
                lambda leaf, a=axis: jax.lax.dynamic_index_in_dim(
                    leaf, slot, axis=a, keepdims=True),
                layer)
        return out

    def _restore_impl(self, cache, snap, slot):
        """Scatter a park snapshot back into ``slot``'s contiguous layers;
        every other slot passes through bit-for-bit."""
        out = {sec: list(cache[sec]) for sec, _ in _SECTIONS}
        for sec, axis, i, layer, paged in self._walk(cache):
            key = f"{sec}/{i}"
            if paged or key not in snap:
                continue
            out[sec][i] = jax.tree.map(
                lambda leaf, s, a=axis: jax.lax.dynamic_update_index_in_dim(
                    leaf, s.astype(leaf.dtype), slot, axis=a),
                layer, snap[key])
        return out

    def _invalidate_impl(self, cache, page_ids):
        """pos ← -1 on the given pool pages (padded with the trash page, so
        the scatter shape is fixed and duplicates all write the same
        value).  Called when freed pages are reallocated: stale K/V from the
        previous owner is masked exactly like unwritten contiguous slots."""
        out = {sec: list(cache[sec]) for sec, _ in _SECTIONS}
        for sec, axis, i, layer, paged in self._walk(cache):
            if not paged:
                continue
            new_layer = dict(layer)
            if axis == 0:
                new_layer["pos"] = layer["pos"].at[page_ids].set(-1)
            else:
                new_layer["pos"] = layer["pos"].at[:, page_ids].set(-1)
            out[sec][i] = new_layer
        return out

    def _reset_impl(self, cache, template, slot_mask, partial_pages):
        """Recycle masked slots: contiguous layers masked-restore to the
        primed template; paged layers re-invalidate the tail of the partial
        prefix page (offsets >= prefix_len % page_size, which the drained
        generation overwrote).  Freed full pages wait for
        ``_invalidate_impl`` at their next allocation."""
        mask = jnp.asarray(slot_mask, bool)
        off = self._partial_off
        ps = self.page_size
        col = jnp.arange(ps) >= off
        out = {sec: list(cache[sec]) for sec, _ in _SECTIONS}
        for sec, axis, i, layer, paged in self._walk(cache):
            if not paged:
                out[sec][i] = jax.tree.map(
                    lambda c, z, a=axis: _masked_restore(c, z, mask, a),
                    layer, template[sec][i])
                continue
            if not (self.n_prefix_pages and off):
                continue
            new_layer = dict(layer)
            pos = layer["pos"]
            if axis == 0:
                cur = pos[partial_pages]                       # (B, ps)
                new = jnp.where(mask[:, None] & col[None], -1, cur)
                new_layer["pos"] = pos.at[partial_pages].set(new)
            else:
                cur = pos[:, partial_pages]                    # (G, B, ps)
                new = jnp.where(mask[None, :, None] & col[None, None],
                                -1, cur)
                new_layer["pos"] = pos.at[:, partial_pages].set(new)
            out[sec][i] = new_layer
        return out

    # -- public API ------------------------------------------------------------

    @property
    def block_table(self) -> jnp.ndarray:
        """(B, max_pages) int32 device view of the page table rows."""
        if self._device_table is None:
            self._device_table = jnp.asarray(self.table.rows)
        return self._device_table

    def adopt(self, cache) -> None:
        """Take ownership of the post-step cache pytree."""
        self.cache = cache

    def ensure(self, positions, live_mask, lens=None) -> None:
        """Map every live slot's write range to pages before a decode step.
        ``lens`` (B,) is the number of positions slot s writes this step
        (default 1): chunked prefill covers ``[pos, pos + lens)``, so up to
        ``ceil(chunk / page_size) + 1`` pages per slot may be allocated in
        one call.  Admission accounting guarantees the pool has room."""
        ps = self.page_size
        lens = np.ones(self.batch, np.int64) if lens is None \
            else np.asarray(lens)
        fresh: list[int] = []
        for s in np.nonzero(np.asarray(live_mask))[0]:
            first = int(positions[s]) // ps
            last = (int(positions[s]) + max(1, int(lens[s])) - 1) // ps
            for j in range(first, last + 1):
                if self.table.rows[s, j] < 0:
                    fresh.append(self.table.allocate(s, j))
        if fresh:
            if self.tracer.enabled:
                self.tracer.event("page_alloc", count=len(fresh),
                                  free_after=self.table.free_pages)
            # Pad to a multiple of B so the jitted invalidate sees a handful
            # of shapes at most (single-token decode always lands on B).
            pad_to = self.batch * (1 + (len(fresh) - 1) // self.batch)
            padded = np.full(pad_to, TRASH_PAGE, np.int32)
            padded[:len(fresh)] = fresh
            self.cache = self._invalidate(self.cache, jnp.asarray(padded))
            self._device_table = None

    def reset_slots(self, slot_mask) -> None:
        """Recycle masked slots: free their non-prefix pages and restore
        contiguous state to the primed template.  Live slots are untouched
        bit-for-bit."""
        mask = np.asarray(slot_mask, bool)
        n_freed = 0
        for s in np.nonzero(mask)[0]:
            n_freed += len(self.table.free_slot(int(s),
                                                keep=self.n_prefix_pages))
        if n_freed and self.tracer.enabled:
            self.tracer.event("page_free", count=n_freed,
                              free_after=self.table.free_pages)
        self.cache = self._reset(self.cache, self.template,
                                 jnp.asarray(mask), self._partial_pages)
        self._device_table = None

    # -- preempt-and-swap ------------------------------------------------------

    def _refresh_partial_pages(self) -> None:
        """Re-derive the per-slot partial-prefix-page ids after a park or
        resume changed a slot's prefix row (empty rows map to the trash
        page — invalidating its tail is a no-op by construction)."""
        if not (self.n_prefix_pages and self._partial_off):
            return
        last = self.table.rows[:, self.n_prefix_pages - 1]
        self._partial_pages = jnp.asarray(
            np.where(last >= 0, last, TRASH_PAGE).astype(np.int32))

    def park_slot(self, slot: int) -> PagedPark:
        """Preempt-and-swap, paged flavour: detach the slot's block-table
        row — its pages stay resident in the pool, owned by the returned
        payload, with zero KV copies — and snapshot the ineligible
        contiguous layers' slot slice.  The freed slot is reprovisioned
        with fresh prefix pages (content re-imported from the primed
        prefix chunks) so its next occupant admits at ``prefix_len``
        exactly like a recycled slot.  Needs ``free_pages >=
        n_prefix_pages`` for the reprovision — the scheduler checks before
        preempting."""
        row, n = self.table.detach_row(slot)
        snap = self._snapshot(self.cache, jnp.int32(slot)) \
            if self._has_contiguous else None
        if self.n_prefix_pages:
            for j in range(self.n_prefix_pages):
                self.table.allocate(slot, j)
            rows = jnp.asarray(self.table.rows[slot, :self.n_prefix_pages])
            self.cache = self._import_slot(self.cache, self._prefix_chunks,
                                           rows, jnp.int32(slot))
            self._refresh_partial_pages()
        self._device_table = None
        return PagedPark(row=row, n_pages=n, snapshot=snap)

    def resume_slot(self, slot: int, payload: PagedPark) -> None:
        """Reattach a parked row into (any) drained slot: the slot's fresh
        prefix pages return to the free list and the parked pages come
        back exactly as parked — a host-side row swap.  Ineligible
        contiguous layers restore from the park snapshot, so the resumed
        group's decode continues bit-for-bit."""
        self.table.free_slot(slot, keep=0)
        self.table.attach_row(slot, payload.row, payload.n_pages)
        if payload.snapshot is not None:
            self.cache = self._restore(self.cache, payload.snapshot,
                                       jnp.int32(slot))
        self._refresh_partial_pages()
        self._device_table = None

    # -- accounting ------------------------------------------------------------

    def page_bytes(self) -> int:
        """Bytes of one pool page summed across every paged layer."""
        total = 0
        for _, _, _, layer, paged in self._walk(self.cache):
            if paged:
                total += sum(leaf.size * leaf.dtype.itemsize
                             for leaf in jax.tree.leaves(layer))
        return total // self.pool_pages

    def bytes_in_use(self) -> int:
        """Bytes of pages actually allocated (incl. trash) plus contiguous
        layers — the paged analogue of ``batch * max_len`` accounting."""
        contiguous = 0
        for _, _, _, layer, paged in self._walk(self.cache):
            if not paged:
                contiguous += sum(leaf.size * leaf.dtype.itemsize
                                  for leaf in jax.tree.leaves(layer)
                                  if hasattr(leaf, "dtype"))
        return contiguous + (self.table.pages_in_use + 1) * self.page_bytes()
