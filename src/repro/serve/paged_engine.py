"""Paged serving: block-granular KV storage behind the ladder-locked loop.

:class:`~repro.serve.slot_engine.SlotServeEngine` removed the serving
loop's recompiles but kept the slot cache dense: every slot reserves the
full ``max_seq`` sequence capacity, so one long-context tenant dictates
the memory footprint of every co-resident request — exactly the
worst-case over-provisioning the paper's scale-in argument is against.
This module applies the SISA idea to serving memory:

* **Flat page pool** (:class:`PagedKVCache`): KV lives in
  ``(layers, num_pages, page_size, ...)`` buffers shared by all
  requests, plus one reserved *sink* page (index ``num_pages``) that
  absorbs the masked writes of released rows.  A request holds exactly
  the pages its sequence occupies, so a 4k-token tenant and a 30-token
  tenant stop paying the same rent.  With ``quant="int8"`` the pool
  stores symmetric int8 K/V plus bf16 per-page scale planes
  (``pk_s``/``pv_s``), quantized once at the admission scatter and per
  token at the decode scatter — ~0.31x the f32 pool bytes — and
  dequantized inside the fused attention kernel.

* **Per-slot page table**: a fixed-shape
  ``(max_slots, max_pages_per_slot) int32`` indirection from logical
  sequence blocks to physical pages.  Admission maps
  ``ceil(padded_prompt / page_size)`` pages with a single donated
  scatter of the prefilled cache; decode *appends* a page only when a
  row's write position crosses a page boundary (entries are written,
  shapes never change, so growth never recompiles anything); release
  returns the pages to the free list and points the row at the sink.

* **Windowed page rings with dead-page reclamation**: sliding-window
  (``local``) layers never need more live KV than their window, so each
  slot maps a fixed ring of ``R = ceil((w + window_tokens) / page_size)
  + 1`` local pages (``w = min(sliding_window, max_seq)``) through a
  separate ``(max_slots, R) int32`` ring table.  Position ``p`` lives
  at ring column ``(p // page_size) % R``; at every window boundary
  :meth:`PagedKVCache.advance_ring` *frees* each column whose old block
  has fallen entirely behind the attention window and remaps it from
  the free list (FIFO, so pages genuinely rotate) before the decode
  window writes it.  A gemma3-style stack (5 of 6 layers local) holds
  ~``R`` local pages per slot no matter how long it decodes — the
  behind-window pages are dead and the allocator reclaims them
  (``stats["engine"]["window_pages_reclaimed"]``, gated by the
  ``serve_window_kv_bytes`` bench row).

* **Fixed-slab recurrent-state pools**: RG-LRU / RWKV6 layer states
  have no sequence axis at all, so they live in ``(L, max_slots, ...)``
  slabs inside the same pools pytree — admission is one donated
  dynamic-slice write of the prefilled state into the slot's row, and
  the decode window slices the slab to the active rung exactly like the
  dense slot cache.  O(1) bytes per slot, zero pages, zero growth.

* **Paged cross-attention KV (enc-dec)**: whisper-style decoders read a
  static encoder KV block.  It is written once at admission into
  ``C = ceil(enc_frames / page_size)`` cross pages (ring table
  ``(max_slots, C)``), is read-only for the request's whole life, and
  is refcount-shared: requests with byte-identical encoder features map
  the *same* physical cross pages (keyed on the feature bytes), so N
  decodes of one audio clip hold one cross-KV copy.

* **Refcounted prefix sharing (copy-on-write)**: global-attention pages
  carry a refcount, so two requests whose token prefixes agree through
  a page boundary map the *same* physical page (admission passes
  ``shared_pages``; causal attention guarantees identical token
  prefixes produce identical K/V for those positions, independent of
  bucket padding or continuations).  Shared pages are only freed when
  the last holder releases; a holder that must write a shared page
  first gets a private copy (:meth:`PagedKVCache.make_writable`).  The
  engine keys sharing on a host-side prefix registry, purged as pages
  drain.  Enc-dec configs disable token-prefix sharing: decoder K/V
  depends on the encoder output, not on tokens alone.

* **Reservation-based admission**: at admit time a request *reserves*
  its worst case global-page count (minus by-reference shared pages)
  without mapping it, plus one local ring (``R`` pages) and one cross
  block (``C`` pages, or zero on a cross-registry hit) where the
  architecture needs them, so lazy boundary mapping can never find a
  free list empty, decode never stalls or deadlocks, and
  ``admit_cap`` keeps the ladder sweep from targeting a rung the pools
  cannot back.

The serve loop, ladder quantization, window pipeline, bucketed
prefill, and coexec backfill are inherited from ``SlotServeEngine``
unchanged; only storage and the decode step differ
(:func:`repro.models.attention.paged_attn_decode_step` reads the global
pool through the page table, ``paged_local_attn_decode_step`` reads the
ring pages, ``paged_cross_attn_decode`` gathers the cross block).  Rows
stay independent, so the paged engine is token-identical to the slot
engine on every workload — fuzzed across random workloads and every
registry architecture in ``tests/test_serve_differential.py``.

Scope: **every registry architecture serves here** — pure global
stacks, sliding-window and mixed local/global stacks (gemma3),
recurrent and hybrid recurrent stacks (recurrentgemma, rwkv6), MoE
(dbrx, phi3.5 — routing is masked-exact under bucket padding), frontend
configs (internvl2 — serving is the pure token path), and enc-dec
(whisper).  KV quantization applies to the *global* page pool only
(``kv_quant="int8"``); local rings, cross pages, and recurrent slabs
stay at model precision.  The dense engines' ``CACHE_QUANT`` flag is
still rejected.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN, BIDIR, LOCAL, ModelConfig, RGLRU, WKV
from repro.kernels.paged_attn import quantize_page_pool
from repro.models.attention import CACHE_QUANT
from repro.models.transformer import init_cache
from repro.serve.engine import effective_tokens, encoder_inputs, Request
from repro.serve.serve_step import make_paged_decode_step
from repro.serve.slot_engine import (decode_body, default_window,
                                     SlotServeEngine)

PyTree = Any

POOL_QUANTS = (None, "int8")

# Leaf names that live in a shared *pool* (page-indirected, never sliced
# to the decode rung); everything else in the pools pytree is a per-slot
# recurrent slab with the slot axis at position 1.
_POOL_LEAF_NAMES = frozenset(
    {"pk", "pv", "pk_s", "pv_s", "lk", "lv", "ck", "cv"})


def _leaf_name(path) -> Optional[str]:
    """Innermost dict key on a tree path (the cache leaf name)."""
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return entry.key
    return None


def _map_named(f, tree, *rest):
    """``jax.tree.map`` that also hands ``f`` each leaf's dict name."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    rests = [treedef.flatten_up_to(r) for r in rest]
    leaves = [f(_leaf_name(path), leaf, *(r[i] for r in rests))
              for i, (path, leaf) in enumerate(flat)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _named_leaves(tree) -> List[Tuple[Optional[str], Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(_leaf_name(path), leaf) for path, leaf in flat]


def _rename_kv(tree):
    """Prefill cache ``{"k","v"}`` leaves -> pool ``{"pk","pv"}`` keys.

    The decode path dispatches a layer to the paged attention step by
    the presence of ``"pk"`` in its cache dict, so the pool pytree must
    carry the paged key names while keeping the group/layer structure
    of the dense cache.  Local (``lk``/``lv``), cross (``ck``/``cv``)
    and recurrent-slab leaves are renamed upstream by the engine
    (:meth:`PagedServeEngine._rename_cache_tree`) and pass through here
    untouched.
    """
    if isinstance(tree, dict):
        ren = {"k": "pk", "v": "pv"}
        return {ren.get(k, k): _rename_kv(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_rename_kv(t) for t in tree]
    return tree


def _quantize_pool_tree(tree):
    """Renamed f32 chunks -> int8 pool leaves with bf16 scale planes
    (``{"pk","pv"} -> {"pk","pk_s","pv","pv_s"}``), per-position
    symmetric over the head dim — the same numerics the decode scatter
    applies to new tokens, so admitted and decoded cells dequantize
    identically.  Only global-attention leaves quantize; local rings,
    cross pages, and recurrent slabs stay at model precision."""
    if isinstance(tree, dict):
        if "pk" in tree:
            kq, ks = quantize_page_pool(tree["pk"])
            vq, vs = quantize_page_pool(tree["pv"])
            return {"pk": kq, "pk_s": ks, "pv": vq, "pv_s": vs}
        return {k: _quantize_pool_tree(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_quantize_pool_tree(t) for t in tree]
    return tree


class PagedKVCache:
    """Flat page pools + per-slot page tables + refcounting allocator.

    Physical storage per cache leaf class (all inside one ``pools``
    pytree mirroring the dense cache structure):

    * global attention: ``(L, num_pages + 1, page_size, ...)`` (the
      ``+1`` is the sink page) indirected by ``table``
      ``(max_slots, max_pages_per_slot) int32``; with ``quant="int8"``
      each K/V leaf is int8 plus a bf16 scale-plane leaf;
    * sliding-window attention: ``(L, num_local_pages + 1, page_size,
      ...)`` indirected by the ring table ``ltable``
      ``(max_slots, local_ring) int32`` — position ``p`` maps to column
      ``(p // page_size) % local_ring``;
    * cross attention (enc-dec): ``(L, num_cross_pages + 1, page_size,
      ...)`` indirected by ``ctable`` ``(max_slots, cross_pages)``,
      written once at admission, refcount-shareable;
    * recurrent state: ``(L, max_slots, ...)`` slabs addressed by slot
      directly (no pages, no growth).

    The global allocator is reservation-based and refcounted exactly as
    before (``admit`` / ``ensure_capacity`` / ``make_writable`` /
    ``release``).  The local allocator is a FIFO free list of ring
    pages: :meth:`advance_ring` frees each ring column whose block fell
    behind the window and remaps it from the *front* of the list, so
    reclaimed pages genuinely rotate through the pool.  Cross pages
    carry their own refcounts (``cross_shared`` admission maps a block
    by reference); pages that drain to zero are buffered in
    ``drain_freed_cross`` for the engine's registry purge.
    """

    def __init__(self, max_slots: int, num_pages: int, page_size: int,
                 max_pages_per_slot: int, quant: Optional[str] = None,
                 sharding_fn=None, table_sharding=None, *,
                 local_ring: int = 0, num_local_pages: int = 0,
                 cross_pages: int = 0, num_cross_pages: int = 0):
        if num_pages < max_pages_per_slot:
            raise ValueError(
                f"pool of {num_pages} pages cannot hold one full-length "
                f"request ({max_pages_per_slot} pages)")
        if quant not in POOL_QUANTS:
            raise ValueError(f"quant={quant!r} not in {POOL_QUANTS}")
        if local_ring and num_local_pages < local_ring:
            raise ValueError(
                f"local pool of {num_local_pages} pages cannot hold one "
                f"ring ({local_ring} pages)")
        if cross_pages and num_cross_pages < cross_pages:
            raise ValueError(
                f"cross pool of {num_cross_pages} pages cannot hold one "
                f"encoder block ({cross_pages} pages)")
        self.max_slots = max_slots
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_pages_per_slot = max_pages_per_slot
        self.quant = quant
        self.local_ring = local_ring
        self.num_local_pages = num_local_pages
        self.cross_pages = cross_pages
        self.num_cross_pages = num_cross_pages
        self.sink = num_pages                      # physical sink page id
        self.lsink = num_local_pages
        self.csink = num_cross_pages
        self.pools: Optional[PyTree] = None        # built at preshape/admit
        self.table = jnp.full((max_slots, max_pages_per_slot), self.sink,
                              jnp.int32)
        self.ltable = (jnp.full((max_slots, local_ring), self.lsink,
                                jnp.int32) if local_ring else None)
        self.ctable = (jnp.full((max_slots, cross_pages), self.csink,
                                jnp.int32) if cross_pages else None)
        self._free_slots = list(range(max_slots - 1, -1, -1))
        self._free_pages = list(range(num_pages - 1, -1, -1))  # pop->lowest
        # Local ring pages rotate: freed columns go to the *back*, fresh
        # mappings come from the *front*, so a reclaimed page transits
        # the whole free list before reuse (observable rotation).
        self._free_local: deque = deque(range(num_local_pages))
        self._free_cross = list(range(num_cross_pages - 1, -1, -1))
        self._mapped: List[List[int]] = [[] for _ in range(max_slots)]
        self._lrow: List[List[int]] = [[] for _ in range(max_slots)]
        self._lblock = [-1] * max_slots            # highest ring block mapped
        self._cmapped: List[List[int]] = [[] for _ in range(max_slots)]
        self._cross_ref = [0] * num_cross_pages
        self._freed_cross: List[int] = []
        self._reserved = [0] * max_slots
        self._shared = [0] * max_slots             # pages mapped by ref
        self._refcount = [0] * num_pages
        self._owner: List[Optional[int]] = [None] * num_pages
        self._orphaned = 0                         # refcount>0, no owner
        self.reserved_total = 0

        # Mesh-aware pools: committed to cache_specs shardings at
        # allocation, with every jitted op re-constraining its outputs
        # (pools AND tables) so the decode window's input shardings
        # never drift — a drift would change the jit compile key and
        # cost one recompile per window.
        self._sharding_fn = sharding_fn
        self._table_sharding = table_sharding

        def _cp(pools):
            if sharding_fn is not None:
                pools = jax.lax.with_sharding_constraint(
                    pools, sharding_fn(pools))
            return pools

        def _ct(table):
            if table_sharding is not None:
                table = jax.lax.with_sharding_constraint(
                    table, table_sharding)
            return table

        donate = () if jax.default_backend() == "cpu" else (0,)
        psz = page_size

        def admit_op(pools, chunks, fresh, lpages, cpages, slot, last, *,
                     n_shared: int, write_cross: bool):
            """One donated scatter of a prefilled cache into the pools.

            Dispatch is by leaf name: global pages scatter at ``fresh``
            (skipping the first ``n_shared`` by-reference chunks), local
            chunks re-gather into ring-cell order and scatter at
            ``lpages``, cross chunks pad to whole pages and scatter at
            ``cpages`` (skipped when mapped by reference), and
            recurrent slabs dynamic-slice into the slot's row.
            """
            if quant is not None:
                chunks = _quantize_pool_tree(chunks)

            def write(name, b, c):
                if name in ("pk", "pv", "pk_s", "pv_s"):
                    c = c.reshape((c.shape[0], -1, psz) + c.shape[3:])
                    return b.at[:, fresh].set(c[:, n_shared:])
                if name in ("lk", "lv"):
                    # The dense prefill laid the window's live tokens at
                    # dense cell ``p mod cap`` (identity when the bucket
                    # fits the ring).  Re-gather them into ring-cell
                    # order: flat ring cell t holds the position
                    # p == t (mod R*psz) closest below ``last``; cells
                    # ahead of the prompt are zeroed (decode overwrites
                    # them before any read — the per-step write lands
                    # before the gather).
                    cells = lpages.shape[0] * psz
                    t = jnp.arange(cells)
                    p = last - jnp.mod(last - t, cells)
                    src = jnp.mod(jnp.maximum(p, 0), c.shape[2])
                    g = jnp.take(c[:, 0], src, axis=1)
                    valid = (p >= 0).reshape((1, cells)
                                             + (1,) * (g.ndim - 2))
                    g = jnp.where(valid, g, 0)
                    g = g.reshape((c.shape[0], lpages.shape[0], psz)
                                  + c.shape[3:])
                    return b.at[:, lpages].set(g)
                if name in ("ck", "cv"):
                    if not write_cross:
                        return b
                    pad = cpages.shape[0] * psz - c.shape[2]
                    cc = jnp.pad(c[:, 0], ((0, 0), (0, pad))
                                 + ((0, 0),) * (c.ndim - 3))
                    cc = cc.reshape((c.shape[0], cpages.shape[0], psz)
                                    + c.shape[3:])
                    return b.at[:, cpages].set(cc)
                # Recurrent slab: (L, 1, ...) chunk -> slot's row.
                return jax.lax.dynamic_update_slice_in_dim(
                    b, c, slot, axis=1)

            return _cp(_map_named(write, pools, chunks))

        self._admit_op = jax.jit(
            admit_op, static_argnames=("n_shared", "write_cross"),
            donate_argnums=donate)
        # One row-writer serves all three tables (separate compile
        # entries per table width; the replicated table sharding is
        # shape-agnostic).
        self._row_op = jax.jit(
            lambda table, pages, slot, start: _ct(
                jax.lax.dynamic_update_slice(
                    table, pages[None], (slot, start))),
            donate_argnums=donate)
        self._clear_op = jax.jit(
            lambda table, slot, sink: _ct(jax.lax.dynamic_update_slice(
                table, jnp.full((1, table.shape[1]), sink, jnp.int32),
                (slot, jnp.int32(0)))),
            donate_argnums=donate)

        def cow_op(pools, table, src, dst, slot, idx):
            def copy(name, b):
                if name in ("pk", "pv", "pk_s", "pv_s"):
                    return b.at[:, dst].set(b[:, src])
                return b
            pools = _map_named(copy, pools)
            return _cp(pools), _ct(jax.lax.dynamic_update_slice(
                table, dst[None, None], (slot, idx)))

        self._cow_op = jax.jit(
            cow_op,
            donate_argnums=() if jax.default_backend() == "cpu" else (0, 1))
        if table_sharding is not None:
            self.table = jax.device_put(self.table, table_sharding)
            if self.ltable is not None:
                self.ltable = jax.device_put(self.ltable, table_sharding)
            if self.ctable is not None:
                self.ctable = jax.device_put(self.ctable, table_sharding)

    # -- slot free list (same discipline as SlotKVCache) ---------------
    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    @property
    def n_free_pages(self) -> int:
        return len(self._free_pages)

    @property
    def n_free_local(self) -> int:
        return len(self._free_local)

    @property
    def n_free_cross(self) -> int:
        return len(self._free_cross)

    @property
    def orphaned_pages(self) -> int:
        """Occupied pages charged to no live reservation (their owner
        released while sharers still hold them)."""
        return self._orphaned

    def acquire(self) -> int:
        """Claim the lowest free slot (keeps the ladder rung minimal)."""
        return self._free_slots.pop()

    def can_reserve(self, n_pages: int) -> bool:
        """True iff the global pool can still back ``n_pages``
        worst-case exclusive pages on top of every live reservation and
        every orphaned (shared, owner-released) page."""
        return (self.num_pages - self.reserved_total - self._orphaned
                >= n_pages)

    def mapped_pages(self, slot: int) -> List[int]:
        """Physical global pages currently mapped by ``slot``."""
        return list(self._mapped[slot])

    def local_pages_of(self, slot: int) -> List[int]:
        """Physical ring pages mapped by ``slot`` (column order)."""
        return list(self._lrow[slot])

    def cross_pages_of(self, slot: int) -> List[int]:
        """Physical cross pages mapped by ``slot`` (logical order)."""
        return list(self._cmapped[slot])

    def reserved_pages(self, slot: int) -> int:
        """Worst-case exclusive page reservation held by ``slot``."""
        return self._reserved[slot]

    def shared_pages_of(self, slot: int) -> int:
        """Pages ``slot`` maps by reference (admitted shared, not yet
        copied-on-write)."""
        return self._shared[slot]

    def page_refcount(self, page: int) -> int:
        """Number of slots currently mapping global ``page``."""
        return self._refcount[page]

    def cross_refcount(self, page: int) -> int:
        """Number of slots currently mapping cross ``page``."""
        return self._cross_ref[page]

    # -- pool allocation ------------------------------------------------
    def _alloc_pools(self, struct) -> PyTree:
        """Zero pools shaped from a (possibly abstract) renamed chunk
        tree: pages for attention leaves, slot slabs for the rest."""
        def shape_of(name, x):
            if name in ("pk", "pv", "pk_s", "pv_s"):
                return (x.shape[:1] + (self.num_pages + 1, self.page_size)
                        + x.shape[3:])
            if name in ("lk", "lv"):
                return (x.shape[:1]
                        + (self.num_local_pages + 1, self.page_size)
                        + x.shape[3:])
            if name in ("ck", "cv"):
                return (x.shape[:1]
                        + (self.num_cross_pages + 1, self.page_size)
                        + x.shape[3:])
            return x.shape[:1] + (self.max_slots,) + x.shape[2:]

        pools = _map_named(
            lambda n, x: jnp.zeros(shape_of(n, x), x.dtype), struct)
        if self._sharding_fn is not None:
            pools = jax.device_put(pools, self._sharding_fn(pools))
        return pools

    def preshape(self, struct) -> None:
        """Allocate the pools eagerly from an abstract single-request
        cache structure (``jax.eval_shape`` of the model's
        ``init_cache``), so :meth:`resident_bytes` reports the
        configured footprint from construction — before any admission —
        and keeps reporting it across :meth:`reset`."""
        renamed = _rename_kv(struct)
        if self.quant is not None:
            renamed = jax.eval_shape(_quantize_pool_tree, renamed)
        self.pools = self._alloc_pools(renamed)

    # -- page lifecycle -------------------------------------------------
    def admit(self, prefill_cache: PyTree, slot: int, reserve_pages: int,
              shared_pages: Sequence[int] = (), *,
              last_index: Optional[int] = None,
              cross_shared: Optional[Sequence[int]] = None) -> int:
        """Map a prefilled cache into ``slot`` and reserve its worst case.

        Global-attention leaves must have page-aligned sequence capacity
        (the paged engine buckets prompts to page multiples).  The first
        ``len(shared_pages)`` logical global pages are mapped *by
        reference* (refcount bump — the caller asserts their content
        equals the prefill's leading chunks, which the engine's prefix
        registry guarantees); the remaining chunks are scattered into
        freshly mapped physical pages.  ``reserve_pages`` is the global
        *exclusive* worst case (shared pages excluded — they are never
        rewritten without :meth:`make_writable`).

        Local-attention leaves map one full ring of ``local_ring``
        fresh pages regardless of prompt length (``last_index`` — the
        position of the last real prompt token — orients the ring
        re-gather).  Cross leaves map ``cross_pages`` fresh pages and
        write the encoder KV once, unless ``cross_shared`` names an
        already-resident block to map by reference.  Recurrent slabs
        write the slot's row.  Returns the number of fresh *global*
        pages mapped.
        """
        renamed = _rename_kv(prefill_cache)
        named = _named_leaves(renamed)
        names = {n for n, _ in named}
        gcaps = sorted({leaf.shape[2] for n, leaf in named if n == "pk"})
        has_local = "lk" in names
        has_cross = "ck" in names
        has_slab = any(n not in _POOL_LEAF_NAMES for n in names)
        if has_local and not self.local_ring:
            raise ValueError("cache has local-attention leaves but the "
                             "pool was built with local_ring=0")
        if has_cross and not self.cross_pages:
            raise ValueError("cache has cross-attention leaves but the "
                             "pool was built with cross_pages=0")
        n = 0
        if gcaps:
            cap = gcaps[-1]
            if cap % self.page_size:
                raise ValueError(f"prefill cache capacity {cap} is not a "
                                 f"multiple of page_size {self.page_size}")
            n = cap // self.page_size
            if n > self.max_pages_per_slot:
                raise ValueError(
                    f"prompt needs {n} pages > max_pages_per_slot "
                    f"{self.max_pages_per_slot}")
        shared = list(shared_pages)
        n_fresh = n - len(shared)
        if n_fresh < 0:
            raise ValueError(f"{len(shared)} shared pages exceed the "
                             f"prompt's {n} pages")
        for pg in shared:
            if self._refcount[pg] < 1:
                raise ValueError(f"shared page {pg} is not live")
        if reserve_pages < n_fresh or not self.can_reserve(reserve_pages):
            raise ValueError(
                f"cannot reserve {reserve_pages} pages (fresh now: "
                f"{n_fresh}, unreserved: "
                f"{self.num_pages - self.reserved_total - self._orphaned})")
        if self.pools is None:
            struct = (jax.eval_shape(_quantize_pool_tree, renamed)
                      if self.quant is not None else renamed)
            self.pools = self._alloc_pools(struct)
        fresh = [self._free_pages.pop() for _ in range(n_fresh)]
        pages = shared + fresh
        for pg in shared:
            self._refcount[pg] += 1
        for pg in fresh:
            self._refcount[pg] = 1
            self._owner[pg] = slot
        lrow: List[int] = []
        if has_local:
            lrow = [self._free_local.popleft()
                    for _ in range(self.local_ring)]
        crow: List[int] = []
        write_cross = False
        if has_cross:
            if cross_shared is not None:
                crow = list(cross_shared)
                for pg in crow:
                    if self._cross_ref[pg] < 1:
                        raise ValueError(f"cross page {pg} is not live")
                    self._cross_ref[pg] += 1
            else:
                write_cross = True
                crow = [self._free_cross.pop()
                        for _ in range(self.cross_pages)]
                for pg in crow:
                    self._cross_ref[pg] = 1
        if n_fresh or has_local or write_cross or has_slab:
            self.pools = self._admit_op(
                self.pools, renamed,
                jnp.asarray(fresh, jnp.int32),
                jnp.asarray(lrow, jnp.int32),
                jnp.asarray(crow, jnp.int32),
                jnp.int32(slot),
                jnp.int32(last_index if last_index is not None else 0),
                n_shared=len(shared), write_cross=write_cross)
        if pages:
            self.table = self._row_op(self.table,
                                      jnp.asarray(pages, jnp.int32),
                                      jnp.int32(slot), jnp.int32(0))
        if lrow:
            self.ltable = self._row_op(self.ltable,
                                       jnp.asarray(lrow, jnp.int32),
                                       jnp.int32(slot), jnp.int32(0))
            self._lblock[slot] = (max(last_index or 0, 0)
                                  // self.page_size)
        if crow:
            self.ctable = self._row_op(self.ctable,
                                       jnp.asarray(crow, jnp.int32),
                                       jnp.int32(slot), jnp.int32(0))
        self._mapped[slot] = pages
        self._lrow[slot] = lrow
        self._cmapped[slot] = crow
        self._shared[slot] = len(shared)
        self._reserved[slot] = reserve_pages
        self.reserved_total += reserve_pages
        return n_fresh

    def ensure_capacity(self, slot: int, last_pos: int) -> int:
        """Map global pages so ``slot`` can write through ``last_pos``.

        Called at window boundaries for the positions the next decode
        window will write; within the admission reservation (plus the
        by-reference pages) by construction, so the pop below can never
        find the free list empty.  Returns the number of pages appended
        (0 almost always — only boundary crossings grow the table).
        """
        need = last_pos // self.page_size + 1
        have = len(self._mapped[slot])
        if need <= have:
            return 0
        if need > self._reserved[slot] + self._shared[slot]:
            raise AssertionError(
                f"slot {slot} needs {need} pages beyond its reservation "
                f"of {self._reserved[slot]} (+{self._shared[slot]} shared)"
                " — admission under-reserved")
        pages = [self._free_pages.pop() for _ in range(need - have)]
        for pg in pages:
            self._refcount[pg] = 1
            self._owner[pg] = slot
        self.table = self._row_op(self.table,
                                  jnp.asarray(pages, jnp.int32),
                                  jnp.int32(slot), jnp.int32(have))
        self._mapped[slot].extend(pages)
        return len(pages)

    def advance_ring(self, slot: int, last_block: int) -> int:
        """Reclaim dead ring pages before the window writes
        ``last_block``.

        Every ring column about to be re-targeted (blocks
        ``(_lblock, last_block]``) holds a block that has fallen
        entirely behind the attention window — the ring is sized with
        one block of slack (``(R - 1) * page_size >= window + window
        tokens``), so its content can never be read again.  The old
        page is *freed to the pool* and the column remapped from the
        FIFO front (free-then-alloc: with an exactly-sized pool and
        every slot busy the free list may be empty until the free
        lands).  Returns the number of pages reclaimed."""
        if not self.local_ring or last_block <= self._lblock[slot]:
            return 0
        row = self._lrow[slot]
        swaps = 0
        for nb in range(self._lblock[slot] + 1, last_block + 1):
            col = nb % self.local_ring
            self._free_local.append(row[col])
            row[col] = self._free_local.popleft()
            swaps += 1
        self._lblock[slot] = last_block
        self.ltable = self._row_op(self.ltable,
                                   jnp.asarray(row, jnp.int32),
                                   jnp.int32(slot), jnp.int32(0))
        return swaps

    def make_writable(self, slot: int, logical_idx: int) -> bool:
        """Copy-on-write: give ``slot`` a private copy of its logical
        global page ``logical_idx`` if it is currently shared
        (refcount > 1).

        The divergent-append primitive: a holder about to write into a
        shared page copies it into a fresh page (one donated device
        copy + table entry update) and drops its reference to the
        shared original, which the other holders keep.  Grows this
        slot's reservation by the private page (and orphans the
        original if this slot owned it), so the free list stays
        underflow-safe.  Returns True iff a copy was made.
        """
        pg = self._mapped[slot][logical_idx]
        if self._refcount[pg] <= 1:
            return False
        own = self._owner[pg] == slot
        # The private page joins this slot's reservation (+1); an
        # owner-side CoW additionally orphans the original (+1).
        if not self.can_reserve(2 if own else 1):
            raise ValueError(
                f"cannot copy-on-write page {pg}: pool exhausted")
        new = self._free_pages.pop()
        self._refcount[pg] -= 1
        self._refcount[new] = 1
        self._owner[new] = slot
        self._reserved[slot] += 1
        self.reserved_total += 1
        if own:
            self._owner[pg] = None
            self._orphaned += 1
        else:
            self._shared[slot] -= 1
        self.pools, self.table = self._cow_op(
            self.pools, self.table, jnp.int32(pg), jnp.int32(new),
            jnp.int32(slot), jnp.int32(logical_idx))
        self._mapped[slot][logical_idx] = new
        return True

    def ensure_writable(self, slot: int, first_pos: int,
                        last_pos: int) -> int:
        """Copy-on-write every shared page overlapping the position
        range ``[first_pos, last_pos]`` that ``slot`` is about to write.
        Returns the number of pages copied (0 in the serve flow — the
        engine only writes past the full prompt pages sharing covers)."""
        cows = 0
        first = first_pos // self.page_size
        last = min(last_pos // self.page_size,
                   len(self._mapped[slot]) - 1)
        for j in range(first, last + 1):
            cows += bool(self.make_writable(slot, j))
        return cows

    def release(self, slot: int) -> List[int]:
        """Release every page class ``slot`` holds.

        Global pages decrement their refcounts, freeing only pages that
        drain to zero (shared pages survive for their other holders);
        ring pages all return to the FIFO free list; cross pages
        decrement their refcounts, with drained pages buffered for
        :meth:`drain_freed_cross`.  Every table row is pointed at its
        sink page so the released row's masked decode writes can never
        land in a page a later admission reuses.  Returns the physical
        *global* pages actually freed (the engine purges its prefix
        registry for them)."""
        freed = []
        for pg in self._mapped[slot]:
            self._refcount[pg] -= 1
            own = self._owner[pg]
            if own == slot:
                self._owner[pg] = None
                if self._refcount[pg] > 0:
                    self._orphaned += 1
            if self._refcount[pg] == 0:
                if own != slot:        # orphaned page just drained
                    self._orphaned -= 1
                freed.append(pg)
                self._free_pages.append(pg)
        self._free_pages.sort(reverse=True)
        self._mapped[slot] = []
        self.reserved_total -= self._reserved[slot]
        self._reserved[slot] = 0
        self._shared[slot] = 0
        self.table = self._clear_op(self.table, jnp.int32(slot),
                                    jnp.int32(self.sink))
        if self._lrow[slot]:
            self._free_local.extend(self._lrow[slot])
            self._lrow[slot] = []
            self._lblock[slot] = -1
            self.ltable = self._clear_op(self.ltable, jnp.int32(slot),
                                         jnp.int32(self.lsink))
        if self._cmapped[slot]:
            for pg in self._cmapped[slot]:
                self._cross_ref[pg] -= 1
                if self._cross_ref[pg] == 0:
                    self._free_cross.append(pg)
                    self._freed_cross.append(pg)
            self._free_cross.sort(reverse=True)
            self._cmapped[slot] = []
            self.ctable = self._clear_op(self.ctable, jnp.int32(slot),
                                         jnp.int32(self.csink))
        self._free_slots.append(slot)
        self._free_slots.sort(reverse=True)
        return freed

    def drain_freed_cross(self) -> List[int]:
        """Cross pages whose refcount drained since the last drain (the
        engine purges its encoder-feature registry for them)."""
        out, self._freed_cross = self._freed_cross, []
        return out

    def tables(self) -> Dict[str, jax.Array]:
        """The per-class page tables the decode window indirects
        through (fixed keys per engine — part of the jit structure)."""
        out = {"global": self.table}
        if self.ltable is not None:
            out["local"] = self.ltable
        if self.ctable is not None:
            out["cross"] = self.ctable
        return out

    def seize_pages(self, n: int) -> List[int]:
        """Fault injection: pull up to ``n`` free global pages out of
        circulation, holding them under a ghost reservation so
        ``can_reserve``/``_admit_cap`` see real pool pressure and the
        free-list underflow-safety invariant holds (the seizure is
        bounded by the *unreserved* headroom, never just the free
        count).  Returns the seized pages; :meth:`restore_pages`
        reverses the fault."""
        headroom = self.num_pages - self.reserved_total - self._orphaned
        take = max(0, min(n, headroom, len(self._free_pages)))
        seized = [self._free_pages.pop() for _ in range(take)]
        self.reserved_total += take
        return seized

    def restore_pages(self, pages: Sequence[int]) -> None:
        """Heal a :meth:`seize_pages` fault: drop the ghost reservation
        and return the pages to the free list."""
        self._free_pages.extend(pages)
        self._free_pages.sort(reverse=True)
        self.reserved_total -= len(pages)

    def reset(self) -> None:
        """Free every slot and page; pool buffers (and stale content —
        never attended, admission re-maps pages) are kept."""
        self._free_slots = list(range(self.max_slots - 1, -1, -1))
        self._free_pages = list(range(self.num_pages - 1, -1, -1))
        self._free_local = deque(range(self.num_local_pages))
        self._free_cross = list(range(self.num_cross_pages - 1, -1, -1))
        self._mapped = [[] for _ in range(self.max_slots)]
        self._lrow = [[] for _ in range(self.max_slots)]
        self._lblock = [-1] * self.max_slots
        self._cmapped = [[] for _ in range(self.max_slots)]
        self._cross_ref = [0] * self.num_cross_pages
        self._freed_cross = []
        self._reserved = [0] * self.max_slots
        self._shared = [0] * self.max_slots
        self._refcount = [0] * self.num_pages
        self._owner = [None] * self.num_pages
        self._orphaned = 0
        self.reserved_total = 0
        self.table = jnp.full((self.max_slots, self.max_pages_per_slot),
                              self.sink, jnp.int32)
        self.ltable = (jnp.full((self.max_slots, self.local_ring),
                                self.lsink, jnp.int32)
                       if self.local_ring else None)
        self.ctable = (jnp.full((self.max_slots, self.cross_pages),
                                self.csink, jnp.int32)
                       if self.cross_pages else None)
        if self._table_sharding is not None:
            self.table = jax.device_put(self.table, self._table_sharding)
            if self.ltable is not None:
                self.ltable = jax.device_put(self.ltable,
                                             self._table_sharding)
            if self.ctable is not None:
                self.ctable = jax.device_put(self.ctable,
                                             self._table_sharding)

    def resident_bytes(self) -> int:
        """Bytes of persistent paged storage: pools (incl. sink pages,
        recurrent slabs and, for int8 pools, the scale planes) + page
        tables.  0 only until the pools are shaped — engines preshape at
        construction, so the configured footprint is visible before any
        admission and survives :meth:`reset`."""
        if self.pools is None:
            return 0
        total = (sum(x.nbytes for x in jax.tree.leaves(self.pools))
                 + self.table.nbytes)
        if self.ltable is not None:
            total += self.ltable.nbytes
        if self.ctable is not None:
            total += self.ctable.nbytes
        return total


class PagedServeEngine(SlotServeEngine):
    """Ladder-locked serving over block-granular paged KV storage.

    Drop-in peer of :class:`~repro.serve.slot_engine.SlotServeEngine`
    (token-identical on every workload — rows are independent in both)
    whose cache footprint scales with the tokens actually *live*, not
    with ``max_batch x max_seq``: global layers hold their sequence's
    pages, sliding-window layers hold one fixed ring of pages with
    dead pages reclaimed as decode advances, recurrent layers hold one
    slab row, and enc-dec cross KV holds one shareable block.  Every
    registry architecture constructs and serves here.  ``num_pages``
    sizes the global pool; the default matches the dense engine's
    capacity, and the interesting deployments shrink it
    (``benchmarks/serve_bench.py``).  ``kv_quant="int8"`` stores the
    global pool quantized; ``prefix_sharing`` (default on, token-keyed,
    auto-disabled for enc-dec) maps page-aligned common prompt prefixes
    to shared refcounted physical pages.
    """

    def __init__(self, cfg: ModelConfig, params, *,
                 page_size: int = 16, num_pages: Optional[int] = None,
                 max_batch: int = 8, max_seq: int = 256,
                 kv_quant: Optional[str] = None,
                 prefix_sharing: bool = True, **kw):
        if CACHE_QUANT["enabled"]:
            raise NotImplementedError(
                "paged storage quantizes at the pool boundary "
                "(kv_quant='int8'), not via the dense CACHE_QUANT flag")
        if kv_quant not in POOL_QUANTS:
            raise ValueError(f"kv_quant={kv_quant!r} not in {POOL_QUANTS}")
        if page_size < 1 or page_size > max_seq:
            raise ValueError(f"page_size {page_size} not in [1, {max_seq}]")
        kinds = cfg.layer_kinds()
        self._has_global = any(k in (ATTN, BIDIR) for k in kinds)
        self._has_local = LOCAL in kinds
        self._has_slab = any(k in (RGLRU, WKV) for k in kinds)
        self._has_cross = bool(cfg.enc_dec)
        if self._has_cross and cfg.enc_frames <= 0:
            raise ValueError(
                f"{cfg.name} is enc-dec but enc_frames={cfg.enc_frames}; "
                "paged cross-attention needs a static encoder length")
        self.page_size = page_size
        self.kv_quant = kv_quant
        # Token-prefix sharing is sound only when K/V is a pure function
        # of the token prefix; enc-dec decoder K/V also depends on the
        # encoder output, so it shares cross pages (feature-keyed)
        # instead.
        self.prefix_sharing = (prefix_sharing and self._has_global
                               and not cfg.enc_dec)
        self.max_pages_per_slot = -(-max_seq // page_size)
        self.num_pages = (num_pages if num_pages is not None
                          else max_batch * self.max_pages_per_slot)
        # Ring sizing needs the decode-window length before
        # super().__init__ runs (it builds the cache): R * page_size
        # covers window + one decode window + one page of slack, so a
        # column is only ever re-targeted once its old block is fully
        # behind every read of the coming window.
        if kw.get("window") is None:
            kw["window"] = default_window()
        window_tokens = kw["window"]
        if self._has_local:
            w = min(cfg.sliding_window, max_seq)
            self.local_ring = -(-(w + window_tokens) // page_size) + 1
        else:
            self.local_ring = 0
        self.num_local_pages = max_batch * self.local_ring
        self.cross_pages = (-(-cfg.enc_frames // page_size)
                            if self._has_cross else 0)
        self.num_cross_pages = max_batch * self.cross_pages
        # token-prefix bytes -> physical page, and its reverse (purged
        # when pages drain back to the free list).
        self._prefix_registry: Dict[bytes, int] = {}
        self._page_key: Dict[int, bytes] = {}
        # encoder-feature bytes -> cross page block, and its reverse
        # (keyed on the block's first page).
        self._cross_registry: Dict[bytes, Tuple[int, ...]] = {}
        self._cross_key: Dict[int, bytes] = {}
        super().__init__(cfg, params, max_batch=max_batch, max_seq=max_seq,
                         **kw)
        # Page-aligned prefill caches are a storage invariant here, not
        # an optimization: an exact-length prefill cache cannot be
        # scattered into whole pages, so the bucketed path is mandatory
        # (reject at construction, not at the first admission).
        if not self._bucket_enabled:
            raise ValueError(
                "PagedServeEngine requires bucketed prefill (page-aligned "
                "cache capacities); prefill_bucketing=False or a "
                "non-bucketed prefill_fn cannot be paged")
        self._preshape_pools()

    # -- storage/decode hooks ------------------------------------------
    def _stats_extras(self) -> dict:
        extras = super()._stats_extras()
        extras.update({"page_admits": 0, "page_grows": 0,
                       "pages_mapped_peak": 0,
                       "pages_shared": 0, "page_cows": 0,
                       "window_pages_reclaimed": 0,
                       "local_ring_pages": getattr(self, "local_ring", 0),
                       "cross_admits": 0, "cross_shared": 0,
                       "pool_pages": self.num_pages,
                       "kv_pool": self.kv_quant or "f32"})
        return extras

    def _prefill_cache_len(self) -> Optional[int]:
        # None: the prefilled cache capacity equals the padded prompt
        # length (a page multiple via _bucket_len) — the admit scatter
        # maps exactly ceil(prompt / page) pages, not max_seq.
        return None

    def _default_decode_fn(self):
        wc = (min(self.cfg.sliding_window, self.max_seq)
              if self._has_local else None)
        return make_paged_decode_step(self.cfg, self.mesh, batch_axes=(),
                                      window_cap=wc)

    def _make_cache(self):
        table_sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            # Page tables replicated: every shard resolves every row's
            # logical -> physical mapping (pages are head-sharded, not
            # page-sharded, so indirection must be mesh-global).
            table_sharding = NamedSharding(self.mesh, P())
        return PagedKVCache(self.max_batch, self.num_pages, self.page_size,
                            self.max_pages_per_slot, quant=self.kv_quant,
                            sharding_fn=self._sharding_fn(),
                            table_sharding=table_sharding,
                            local_ring=self.local_ring,
                            num_local_pages=self.num_local_pages,
                            cross_pages=self.cross_pages,
                            num_cross_pages=self.num_cross_pages)

    def _rename_cache_tree(self, caches):
        """Kind-aware pool leaf names for a prefilled cache: sliding
        windows get ``lk``/``lv``, cross KV gets ``ck``/``cv``,
        recurrent slabs keep their names, and global attention stays
        ``k``/``v`` (the cache's generic rename maps it to
        ``pk``/``pv`` — kept there for direct-cache back-compat)."""
        def rename_block(c, kind):
            if kind == LOCAL:
                return {"lk": c["k"], "lv": c["v"]}
            return c

        out = []
        for cache, (pattern, _reps) in zip(caches,
                                           self.cfg.layer_groups()):
            grp = {}
            for i, kind in enumerate(pattern):
                c = cache[f"b{i}"]
                if self.cfg.enc_dec:
                    grp[f"b{i}"] = {
                        "self": rename_block(c["self"], kind),
                        "cross": {"ck": c["cross"]["k"],
                                  "cv": c["cross"]["v"]}}
                else:
                    grp[f"b{i}"] = rename_block(c, kind)
            out.append(grp)
        return out

    def _preshape_pools(self) -> None:
        """Shape the pools from the model's abstract cache structure so
        ``resident_bytes`` reports the configured footprint before the
        first admission (and across ``reset``)."""
        cfg, psz = self.cfg, self.page_size
        struct = jax.eval_shape(
            lambda: init_cache(cfg, 1, psz,
                               enc_len=cfg.enc_frames or None))
        self.cache.preshape(self._rename_cache_tree(struct))

    def _bucket_len(self, s: int) -> Optional[int]:
        # Page-multiple buckets instead of powers of two: prefill
        # compiles once per page count and admission maps exactly
        # ceil(prompt / page_size) pages — power-of-two padding would
        # map (and waste) pages for pad K/V.  Prompts beyond the engine
        # capacity fall back like the dense engine's (and fail
        # admission — a paged cache cannot exceed its table width).
        if s > self._bucket_cap:
            return None
        return -(-max(s, 1) // self.page_size) * self.page_size

    def reset(self) -> None:
        super().reset()
        self._prefix_registry.clear()
        self._page_key.clear()
        self._cross_registry.clear()
        self._cross_key.clear()

    def remesh(self, new_mesh) -> List[Request]:
        victims = super().remesh(new_mesh)
        # The rebuilt pools start empty: every registry entry points at
        # a page of the lost mesh's pools.
        self._prefix_registry.clear()
        self._page_key.clear()
        self._cross_registry.clear()
        self._cross_key.clear()
        self._preshape_pools()
        return victims

    # -- page accounting ------------------------------------------------
    def _pages_for(self, req: Request) -> int:
        """Worst-case *global* pages for ``req``: padded (effective)
        prompt plus its remaining decode budget, clamped to the
        ``max_seq`` stop rule.  For a preempted request the effective
        prompt has grown by its generated tokens while the remaining
        budget shrank equally, so resume reserves exactly the
        fresh-admission worst case — re-admission can never over-commit
        the pool.  Architectures with no global layer reserve zero
        pages (their storage is the fixed ring/slab/cross block)."""
        if not self._has_global:
            return 0
        k = len(req.generated)
        s = len(req.prompt) + max(k - 1, 0)
        blen = self._bucket_len(s) or s
        budget = max(1, req.max_new_tokens - max(k, 1))
        last = min(max(blen - 1, s + budget - 1), self.max_seq - 1)
        return last // self.page_size + 1

    def _cross_bytes_key(self, req: Request) -> bytes:
        return np.asarray(encoder_inputs(req, self.cfg)).tobytes()

    def _probe_shared(self, req: Request) -> List[int]:
        """Walk the prefix registry: physical pages for the longest
        chain of ``req``'s page-aligned token prefixes already resident.
        Causality makes page content a pure function of the token
        prefix through the page, so a registry hit is a content hit —
        including for a resume's effective prompt, whose generated tail
        was itself written from those very prefixes."""
        if not self.prefix_sharing:
            return []
        toks = effective_tokens(req)
        shared: List[int] = []
        for j in range(len(toks) // self.page_size):
            key = toks[:(j + 1) * self.page_size].tobytes()
            pg = self._prefix_registry.get(key)
            if pg is None:
                break
            if (self.cache.page_refcount(pg) < 1
                    or self._page_key.get(pg) != key):
                # Stale hit: the page drained (or was remapped) behind
                # the registry — e.g. the storage was reset without
                # engine.reset().  Mapping it would alias a free or
                # foreign page into this request, so drop the entry and
                # stop the chain here instead.
                self._prefix_registry.pop(key, None)
                if self._page_key.get(pg) == key:
                    self._page_key.pop(pg, None)
                break
            shared.append(pg)
        return shared

    def _admit_cap(self) -> Optional[int]:
        """Storage-budget constraint for the ladder sweep: live rows
        plus the prefix of waiting requests (backfilled first —
        admission order) whose worst-case reservations still fit every
        pool the architecture uses (global pages, local rings, cross
        blocks)."""
        cap = self._n_active()
        rem_g = (self.cache.num_pages - self.cache.reserved_total
                 - self.cache.orphaned_pages)
        rem_l = (self.cache.n_free_local // self.local_ring
                 if self._has_local else self.max_batch)
        rem_c = self.cache.n_free_cross if self._has_cross else 0
        waiting = [r for r, _, _ in self._backfilled] + list(self.queue)
        for req in waiting:
            if cap >= self.max_batch:
                break
            need_g = (self._pages_for(req) - len(self._probe_shared(req))
                      if self._has_global else 0)
            need_c = 0
            if self._has_cross and (self._cross_bytes_key(req)
                                    not in self._cross_registry):
                need_c = self.cross_pages
            if need_g > rem_g:
                break
            if self._has_local and rem_l < 1:
                break
            if need_c > rem_c:
                break
            cap += 1
            rem_g -= need_g
            rem_l -= 1 if self._has_local else 0
            rem_c -= need_c
        return cap

    def _can_admit(self, req: Request) -> bool:
        if self._has_global and not self.cache.can_reserve(
                self._pages_for(req) - len(self._probe_shared(req))):
            return False
        if (self._has_local
                and self.cache.n_free_local < self.local_ring):
            return False
        if self._has_cross:
            if (self._cross_bytes_key(req) not in self._cross_registry
                    and self.cache.n_free_cross < self.cross_pages):
                return False
        return True

    def _store_cache(self, req: Request, cache, slot: int) -> None:
        cache = self._rename_cache_tree(cache)
        shared = self._probe_shared(req) if self._has_global else []
        ckey = None
        cross_shared = None
        if self._has_cross:
            ckey = self._cross_bytes_key(req)
            blk = self._cross_registry.get(ckey)
            cross_shared = list(blk) if blk is not None else None
        last = len(effective_tokens(req)) - 1
        fresh = self.cache.admit(cache, slot,
                                 self._pages_for(req) - len(shared),
                                 shared_pages=shared, last_index=last,
                                 cross_shared=cross_shared)
        ext = self.stats["engine"]
        ext["page_admits"] += fresh
        ext["pages_shared"] += len(shared)
        if self._has_cross:
            if cross_shared is None:
                pages = tuple(self.cache.cross_pages_of(slot))
                self._cross_registry[ckey] = pages
                self._cross_key[pages[0]] = ckey
                ext["cross_admits"] += 1
            else:
                ext["cross_shared"] += 1
        self._note_pages_peak()
        if self.prefix_sharing:
            # Register this prompt's full pages (fresh ones only — a
            # shared page's key chain is already resident, and registry
            # keys always form prefix chains: a page-j key can only
            # outlive its page-(j-1) key if some holder maps page j
            # without page j-1, which chains never do).
            toks = effective_tokens(req)
            pages = self.cache.mapped_pages(slot)
            for j in range(len(toks) // self.page_size):
                key = toks[:(j + 1) * self.page_size].tobytes()
                if key not in self._prefix_registry:
                    self._prefix_registry[key] = pages[j]
                    self._page_key[pages[j]] = key

    def _release_slot(self, slot: int) -> None:
        for pg in self.cache.release(slot):
            key = self._page_key.pop(pg, None)
            if key is not None:
                self._prefix_registry.pop(key, None)
        for pg in self.cache.drain_freed_cross():
            key = self._cross_key.pop(pg, None)
            if key is not None:
                self._cross_registry.pop(key, None)

    def _note_pages_peak(self) -> None:
        mapped = self.cache.num_pages - self.cache.n_free_pages
        if mapped > self.stats["engine"]["pages_mapped_peak"]:
            self.stats["engine"]["pages_mapped_peak"] = mapped

    # -- window over the page pools --------------------------------------
    def _map_window(self, rung: int) -> None:
        # Map the pages this window can write (bounded by the per-slot
        # budget and max_seq, within each admission's reservation by
        # construction — the free list cannot underflow) and rotate the
        # local rings past dead blocks.  Shared pages never overlap
        # write positions in the serve flow (they cover full prompt
        # pages only), but ensure_writable keeps the invariant explicit.
        # Runs while the window before is still in flight: the mirrors
        # already stand where that window leaves the rows, and every
        # table or pool update here is ordered after it on the device.
        ext = self.stats["engine"]
        for slot in range(rung):
            if self._req[slot] is None:
                continue
            b = int(self._budget[slot])
            if b <= 0:
                continue
            first = int(self._pos[slot])
            last = min(first + min(self.window, b) - 1, self.max_seq - 1)
            if self._has_global:
                ext["page_grows"] += self.cache.ensure_capacity(slot, last)
                ext["page_cows"] += self.cache.ensure_writable(
                    slot, first, last)
            if self._has_local:
                ext["window_pages_reclaimed"] += self.cache.advance_ring(
                    slot, last // self.page_size)
        self._note_pages_peak()

    def _window_call(self, rung: int, toks, pos, budget):
        self.cache.pools, toks, pos, budget, out = self._window_fn(
            self.params, self.cache.pools, self.cache.tables(), toks, pos,
            budget, rung=rung)
        return toks, pos, budget, out

    def window_program_text(self, rung: int) -> str:
        """Compiled HLO text of the decode window at batch shape ``rung``
        over the current params, pools and page tables."""
        z = self._rows_to_device(np.zeros(self.max_batch, np.int32))
        return self._window_fn.lower(
            self.params, self.cache.pools, self.cache.tables(), z, z, z,
            rung=rung).compile().as_text()

    def _build_window_fn(self):
        decode_fn = self.decode_fn
        vocab = self.cfg.vocab_size
        max_seq = self.max_seq
        T = self.window

        def decode_window(params, pools, tables, toks, pos, budget, *,
                          rung):
            """T greedy tokens at batch shape ``rung``.

            Same carry discipline as the dense window (``toks``/``pos``/
            ``budget`` over every slot, the first ``rung`` advanced):
            page pools ride the carry full-size (pages are row-owned, so
            no rung slicing; donated), recurrent slabs are sliced to the
            rung's rows exactly like dense slot buffers and written back
            after the scan, and the per-class page tables are sliced to
            the rung's rows.  Frozen rows write their own (or, once
            released, the sink) page/slab row — never storage another
            row owns.
            """
            # Trace-time compile counter (see the dense window fn).
            self._window_traces += 1
            tbls = {k: jax.lax.slice_in_dim(t, 0, rung, axis=0)
                    for k, t in tables.items()}
            carry0 = _map_named(
                lambda n, b: (b if n in _POOL_LEAF_NAMES
                              else jax.lax.slice_in_dim(b, 0, rung,
                                                        axis=1)),
                pools)

            rows = (toks[:rung], pos[:rung], budget[:rung])
            (sub, *rows), out = jax.lax.scan(
                decode_body(lambda c, tk, ps: decode_fn(params, c, tbls,
                                                        tk, ps),
                            vocab, max_seq),
                (carry0, *rows), None, length=T)
            pools = _map_named(
                lambda n, b, s: (s if n in _POOL_LEAF_NAMES
                                 else jax.lax.dynamic_update_slice_in_dim(
                                     b, s, 0, axis=1)),
                pools, sub)
            pools = self._constrain_caches(pools)
            toks, pos, budget = self._constrain_rows(tuple(
                jax.lax.dynamic_update_slice_in_dim(full, r, 0, axis=0)
                for full, r in zip((toks, pos, budget), rows)))
            return pools, toks, pos, budget, out

        donate = () if jax.default_backend() == "cpu" else (1,)
        return jax.jit(decode_window, static_argnames=("rung",),
                       donate_argnums=donate)
