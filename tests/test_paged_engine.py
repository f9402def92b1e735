"""Paged KV storage: allocator invariants, compile stability, memory fit.

Three contracts of the block-granular cache
(:mod:`repro.serve.paged_engine`):

* **Allocator invariants** (hypothesis state machine over random
  admit/share-admit/grow/cow/release sequences on
  :class:`PagedKVCache`): every page's refcount equals the number of
  slots mapping it, ``free`` is exactly the refcount-0 pages at every
  step, no page is freed while a holder remains, release decrements
  (freeing only drained pages) and restores capacity, reservations
  plus orphaned pages never over-commit the pool, copy-on-write gives
  the writer a private copy while other holders keep the original, and
  a reused page serves its new owner's content — the page-granular
  extension of PR 4's slot-reuse regression.
* **Compile stability**: paged decode compiles at most once per
  ``SLAB_LADDER`` rung across >=3 batch shapes, and page-table growth
  (decode crossing page boundaries) writes entries into fixed-shape
  operands — it can never reshape-recompile anything.
* **Memory fit**: a long-context + many-short workload runs
  concurrently out of a pool a fraction of the dense slot engine's
  reservation — the over-provisioning the scale-in argument removes.
"""
from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import init_params
from repro.serve import make_engine, PagedKVCache, Request

# Small pool geometry: collisions and exhaustion happen often.
SLOTS, PAGES, PSZ, PMAX = 4, 10, 4, 6


def _fake_cache(n_pages: int, fill: float):
    """Single-request 'prefill cache' with recognizable content: cell
    (page p, offset o) of leaf k holds fill + p + o/10."""
    cap = n_pages * PSZ
    vals = (fill + np.repeat(np.arange(n_pages), PSZ)
            + np.tile(np.arange(PSZ), n_pages) / 10.0)
    leaf = jnp.asarray(vals, jnp.float32).reshape(1, 1, cap, 1, 1)
    return [{"b0": {"k": leaf, "v": leaf + 0.5}}]


def _check_invariants(cache: PagedKVCache, live: dict, owner: dict):
    holders = {}           # physical page -> number of slots mapping it
    for s in range(SLOTS):
        pages = cache.mapped_pages(s)
        # A slot never maps the same physical page twice.
        assert len(pages) == len(set(pages))
        for p in pages:
            holders[p] = holders.get(p, 0) + 1
    # Refcounts count holders exactly; free = drained pages only (no
    # page is freed while any holder remains, none leaks after).
    for p in range(PAGES):
        assert cache.page_refcount(p) == holders.get(p, 0), p
    assert cache.n_free_pages == PAGES - len(holders)
    # Orphans: occupied pages whose reserving owner released.
    assert cache.orphaned_pages == sum(
        1 for p in holders if owner.get(p) is None)
    # Reservations + orphans never over-commit the pool.
    assert cache.reserved_total == sum(v["reserve"] for v in live.values())
    assert cache.reserved_total + cache.orphaned_pages <= PAGES
    table = np.asarray(cache.table)
    for slot in range(SLOTS):
        pages = cache.mapped_pages(slot)
        # Device table mirrors the host mapping; tail entries sink.
        assert table[slot, :len(pages)].tolist() == pages
        assert (table[slot, len(pages):] == cache.sink).all()
        if slot not in live:
            assert pages == []
        else:
            assert cache.shared_pages_of(slot) == live[slot]["shared"]
    # Content: every *prompt* page still holds its descriptor's fill
    # pattern — reused pages serve the new owner, shared pages serve
    # every holder, and a CoW copy preserved what it copied.
    if cache.pools is not None:
        pool_k = np.asarray(jax.tree.leaves(cache.pools)[0])[0, :, :, 0, 0]
        for slot, v in live.items():
            for j, (fill, src_j) in enumerate(v["desc"]):
                want = fill + src_j + np.arange(PSZ) / 10.0
                got = pool_k[cache.mapped_pages(slot)[j]]
                np.testing.assert_allclose(got, want,
                                           err_msg=f"slot {slot} page {j}")


OPS = st.lists(
    st.tuples(st.sampled_from(["admit", "share", "grow", "cow", "release"]),
              st.integers(0, 7), st.integers(1, PMAX)),
    min_size=1, max_size=50)


class TestAllocatorStateMachine:
    @settings(max_examples=60, deadline=None)
    @given(ops=OPS)
    def test_page_pool_invariants(self, ops):
        """Random admit/share-admit/grow/cow/release programs against a
        shadow model; every step re-proves refcounts, orphan accounting,
        reservations, the device table mirror, and page contents."""
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        # slot -> {"desc": [(fill, src_page)] per prompt page,
        #          "reserve": int, "shared": int}
        live = {}
        owner = {}           # physical page -> reserving slot or None
        fill_counter = 100.0

        def admit(slot, n, reserve, shared_pages, desc):
            fresh_before = set(p for p in range(PAGES)
                               if cache.page_refcount(p) == 0)
            n_fresh = cache.admit(_fake_cache(n, desc[-1][0] if desc
                                              else 0.0), slot, reserve,
                                  shared_pages=shared_pages)
            assert n_fresh == n - len(shared_pages)
            for p in cache.mapped_pages(slot)[len(shared_pages):]:
                assert p in fresh_before   # fresh pages came from free
                owner[p] = slot

        for op, sel, size in ops:
            if op == "admit" and cache.n_free:
                n = min(size, 3)
                reserve = min(n + sel % 2, PMAX)
                if not cache.can_reserve(reserve):
                    assert (cache.num_pages - cache.reserved_total
                            - cache.orphaned_pages) < reserve
                    continue
                slot = cache.acquire()
                fill_counter += 100.0
                desc = [(fill_counter, j) for j in range(n)]
                admit(slot, n, reserve, (), desc)
                live[slot] = {"desc": desc, "reserve": reserve, "shared": 0}
            elif op == "share" and live and cache.n_free:
                # Admit a request mapping a live donor's leading prompt
                # pages by reference (the engine's prefix-sharing path).
                donor = sorted(live)[sel % len(live)]
                n_donor = len(live[donor]["desc"])
                if not n_donor:
                    continue
                k = min(size, n_donor)
                n = min(k + sel % 2, PMAX)       # k shared + maybe fresh
                reserve = n - k
                if not cache.can_reserve(reserve):
                    continue
                shared = cache.mapped_pages(donor)[:k]
                refs_before = [cache.page_refcount(p) for p in shared]
                slot = cache.acquire()
                fill_counter += 100.0
                desc = (live[donor]["desc"][:k]
                        + [(fill_counter, j) for j in range(k, n)])
                admit(slot, n, reserve, shared, desc)
                for p, r in zip(shared, refs_before):
                    assert cache.page_refcount(p) == r + 1
                live[slot] = {"desc": desc, "reserve": reserve, "shared": k}
            elif op == "grow" and live:
                slot = sorted(live)[sel % len(live)]
                bound = live[slot]["reserve"] + live[slot]["shared"]
                # Any position within reservation + shared is mappable.
                last = min(size, bound) * PSZ - 1
                grown = cache.ensure_capacity(slot, last)
                assert len(cache.mapped_pages(slot)) >= last // PSZ + 1
                for p in cache.mapped_pages(slot):
                    owner.setdefault(p, slot)
                assert grown >= 0
            elif op == "cow" and live:
                slot = sorted(live)[sel % len(live)]
                pages = cache.mapped_pages(slot)
                if not pages:
                    continue
                j = sel % len(pages)
                old = pages[j]
                refc = cache.page_refcount(old)
                if refc > 1 and not cache.can_reserve(2):
                    continue           # pool too tight to copy safely
                copied = cache.make_writable(slot, j)
                assert copied == (refc > 1)
                if copied:
                    new = cache.mapped_pages(slot)[j]
                    assert new != old and cache.page_refcount(new) == 1
                    assert cache.page_refcount(old) == refc - 1
                    if owner.get(old) == slot:
                        owner[old] = None       # original orphaned
                    else:
                        live[slot]["shared"] -= 1
                    owner[new] = slot
                    live[slot]["reserve"] += 1
            elif op == "release" and live:
                slot = sorted(live)[sel % len(live)]
                before = cache.n_free_pages
                held = cache.mapped_pages(slot)
                drained = [p for p in held if cache.page_refcount(p) == 1]
                freed = cache.release(slot)
                # Exactly the drained pages were freed; shared survive.
                assert sorted(freed) == sorted(drained)
                assert cache.n_free_pages == before + len(drained)
                for p in held:
                    if owner.get(p) == slot:
                        owner[p] = None
                for p in freed:
                    owner.pop(p, None)
                del live[slot]
            _check_invariants(cache, live, owner)
        for slot in sorted(live):
            cache.release(slot)
        # Full capacity restored, nothing leaked.
        assert cache.n_free_pages == PAGES
        assert cache.reserved_total == 0
        assert cache.orphaned_pages == 0
        assert cache.n_free == SLOTS

    def test_admit_rejects_over_reservation(self):
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        slot = cache.acquire()
        cache.admit(_fake_cache(2, 1.0), slot, PAGES)  # whole pool
        assert not cache.can_reserve(1)
        with pytest.raises(ValueError):
            cache.admit(_fake_cache(1, 2.0), cache.acquire(), 1)

    def test_grow_beyond_reservation_is_a_bug(self):
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        slot = cache.acquire()
        cache.admit(_fake_cache(1, 1.0), slot, 2)
        with pytest.raises(AssertionError):
            cache.ensure_capacity(slot, 3 * PSZ - 1)

    def test_pool_must_fit_one_full_request(self):
        with pytest.raises(ValueError):
            PagedKVCache(SLOTS, PMAX - 1, PSZ, PMAX)

    def test_shared_page_must_be_live(self):
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        slot = cache.acquire()
        with pytest.raises(ValueError):
            cache.admit(_fake_cache(2, 1.0), slot, 1, shared_pages=[3])


class TestCopyOnWrite:
    def _admit_pair(self, cache):
        """Slot a owns 2 pages; slot b maps both by reference."""
        a = cache.acquire()
        cache.admit(_fake_cache(2, 100.0), a, 2)
        b = cache.acquire()
        cache.admit(_fake_cache(2, 999.0), b, 0,
                    shared_pages=cache.mapped_pages(a))
        return a, b

    def test_divergent_append_copies_for_the_writer_only(self):
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        a, b = self._admit_pair(cache)
        pg = cache.mapped_pages(a)[1]
        assert cache.page_refcount(pg) == 2
        assert cache.make_writable(b, 1)        # sharer-side CoW
        new = cache.mapped_pages(b)[1]
        assert new != pg
        assert cache.page_refcount(pg) == 1
        assert cache.page_refcount(new) == 1
        assert cache.shared_pages_of(b) == 1    # page 0 still shared
        pool_k = np.asarray(jax.tree.leaves(cache.pools)[0])[0, :, :, 0, 0]
        # The copy preserved the shared content (slot a's fill)...
        np.testing.assert_allclose(pool_k[new], pool_k[pg])
        # ...and diverging the copy never touches the original.
        cache.pools = jax.tree.map(lambda x: x.at[:, new].set(-1.0),
                                   cache.pools)
        pool_k = np.asarray(jax.tree.leaves(cache.pools)[0])[0, :, :, 0, 0]
        np.testing.assert_allclose(pool_k[pg],
                                   100.0 + 1 + np.arange(PSZ) / 10.0)
        # Idempotent: the private page never copies again.
        assert not cache.make_writable(b, 1)

    def test_owner_side_cow_orphans_the_original(self):
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        a, b = self._admit_pair(cache)
        pg = cache.mapped_pages(a)[0]
        assert cache.make_writable(a, 0)        # writer owns the page
        assert cache.mapped_pages(a)[0] != pg
        assert cache.page_refcount(pg) == 1     # b still holds it
        assert cache.orphaned_pages == 1        # charged to nobody
        freed = cache.release(b)
        assert pg in freed                      # drained with b
        assert cache.orphaned_pages == 0

    def test_release_keeps_shared_pages_for_survivors(self):
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        a, b = self._admit_pair(cache)
        shared = cache.mapped_pages(a)
        assert cache.release(a) == []           # b holds every page
        assert cache.orphaned_pages == 2
        assert cache.n_free_pages == PAGES - 2
        pool_k = np.asarray(jax.tree.leaves(cache.pools)[0])[0, :, :, 0, 0]
        np.testing.assert_allclose(pool_k[shared[0]],
                                   100.0 + np.arange(PSZ) / 10.0)
        assert sorted(cache.release(b)) == sorted(shared)
        assert cache.n_free_pages == PAGES

    def test_cow_respects_pool_exhaustion(self):
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        a, b = self._admit_pair(cache)
        c = cache.acquire()
        cache.admit(_fake_cache(2, 300.0), c, PAGES - 2)  # rest of pool
        with pytest.raises(ValueError):
            cache.make_writable(b, 0)


class TestQuantPool:
    def test_int8_pool_layout_and_bytes(self):
        f32 = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        q = PagedKVCache(SLOTS, PAGES, PSZ, PMAX, quant="int8")
        for cache in (f32, q):
            slot = cache.acquire()
            cache.admit(_fake_cache(2, 1.0), slot, 2)
        layer = q.pools[0]["b0"]
        assert set(layer) == {"pk", "pk_s", "pv", "pv_s"}
        assert layer["pk"].dtype == jnp.int8
        assert layer["pk_s"].dtype == jnp.bfloat16
        assert layer["pk_s"].shape == layer["pk"].shape[:-1] + (1,)
        # int8 values + bf16 scales: (1 + 2/hd) bytes/elem vs 4 — well
        # under the 0.35x gate headroom at real head dims; the fake
        # cache's hd=1 still shrinks to 3/4 (scale planes dominate
        # there, and the shared page table is identical in both).
        q_pool = q.resident_bytes() - q.table.nbytes
        f_pool = f32.resident_bytes() - f32.table.nbytes
        assert q_pool == 0.75 * f_pool, (q_pool, f_pool)
        assert q.quant == "int8"

    def test_int8_roundtrip_matches_dense_quantizer(self):
        """Pool cells dequantize to what attention._quant_kv would
        produce — admitted and decoded tokens share one numeric."""
        from repro.models.attention import _dequant_kv, _quant_kv
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX, quant="int8")
        slot = cache.acquire()
        src = _fake_cache(3, 42.0)
        cache.admit(src, slot, 3)
        pages = cache.mapped_pages(slot)
        layer = cache.pools[0]["b0"]
        got = np.asarray(
            _dequant_kv(layer["pk"], layer["pk_s"], jnp.float32)
        )[0, pages].reshape(1, 1, 3 * PSZ, 1, 1)
        want = np.asarray(
            _dequant_kv(*_quant_kv(src[0]["b0"]["k"]), jnp.float32))
        np.testing.assert_allclose(got, want)

    def test_rejects_unknown_quant(self):
        with pytest.raises(ValueError):
            PagedKVCache(SLOTS, PAGES, PSZ, PMAX, quant="fp8")


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("yi-6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(lens, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=s).astype(np.int32) for s in lens]


def _run(engine, prompts, budgets, max_steps=2000):
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        engine.submit(Request(rid=i, prompt=p, max_new_tokens=b))
    done = engine.run(max_steps=max_steps)
    return {c.rid: c.tokens for c in done}


class TestPipelinePageReuse:
    def test_freed_page_reused_by_the_next_admission(self, setup):
        """Row A finishes in window j and rides window j+1 frozen, its
        last write landing in its own page; j's read-back frees that
        page while j+1 is in flight, and the very next admission (B,
        blocked on the pool until then) drains j+1 and scatters its
        prompt into the page.  Every stream equals the sequential
        engine's: the drain orders B's writes after A's frozen one."""
        cfg, params = setup
        pa, pc, pb = _prompts([7, 6, 9], cfg.vocab_size, seed=8)
        budgets = {0: 5, 1: 30, 2: 6}
        seq = make_engine(cfg, params, kind="sequential", max_slots=1,
                          max_seq=56)
        want = _run(seq, [pa, pc, pb], [budgets[i] for i in range(3)])
        # A reserves 2 pages, C 5: B's 2 fit only in A's, once freed.
        eng = make_engine(cfg, params, kind="paged", max_slots=3,
                          max_seq=56, page_size=8, num_pages=7, window=1)
        eng.submit(Request(rid=0, prompt=pa, max_new_tokens=budgets[0]))
        eng.submit(Request(rid=1, prompt=pc, max_new_tokens=budgets[1]))
        fin, a_pages, b_at = [], [], None
        for step in range(200):
            if step == 1:
                eng.submit(Request(rid=2, prompt=pb,
                                   max_new_tokens=budgets[2]))
                assert not eng._can_admit(eng.queue[0])   # truly blocked
            if eng._req[0] is not None and eng._req[0].rid == 0:
                a_pages = eng.cache.mapped_pages(0)
            in_flight = eng._inflight is not None
            drains = eng.stats["engine"]["pipeline_drains"]
            if not eng.step(fin):
                break
            if b_at is None and any(r is not None and r.rid == 2
                                    for r in eng._req):
                b_at = step
                slot = next(s for s, r in enumerate(eng._req)
                            if r is not None and r.rid == 2)
                assert in_flight
                assert eng.stats["engine"]["pipeline_drains"] == drains + 1
                assert set(a_pages) & set(eng.cache.mapped_pages(slot))
        assert b_at is not None and len(a_pages) == 2
        assert {r.rid: tuple(r.generated) for r in fin} == want
        assert eng.cache.n_free_pages == eng.cache.num_pages

    def test_no_eviction_when_the_drain_frees_the_room(self, setup):
        """An interactive arrival blocked on the pool while the window in
        flight holds a batch row's last step: the preemption path drains
        first, the row's release makes the room, and nobody is evicted.
        Every stream equals the sequential engine's."""
        cfg, params = setup
        pa, pc, pb = _prompts([7, 6, 9], cfg.vocab_size, seed=8)
        budgets = {0: 5, 1: 30, 2: 6}
        seq = make_engine(cfg, params, kind="sequential", max_slots=1,
                          max_seq=56)
        want = _run(seq, [pa, pc, pb], [budgets[i] for i in range(3)])
        eng = make_engine(cfg, params, kind="paged", max_slots=3,
                          max_seq=56, page_size=8, num_pages=7, window=1)
        eng.submit(Request(rid=0, prompt=pa, max_new_tokens=budgets[0]))
        eng.submit(Request(rid=1, prompt=pc, max_new_tokens=budgets[1]))
        fin = []
        for _ in range(50):          # until A's last step is in flight
            eng.step(fin)
            if eng._budget[0] == 0:
                break
        assert eng._inflight is not None and eng._req[0].rid == 0
        eng.submit(Request(rid=2, prompt=pb, max_new_tokens=budgets[2],
                           klass="interactive"))
        assert not eng._can_admit(eng.queue[0])       # truly blocked
        drains = eng.stats["engine"]["pipeline_drains"]
        eng.step(fin)
        assert eng._req[0] is not None and eng._req[0].rid == 2
        assert eng.stats["engine"]["pipeline_drains"] == drains + 1
        for _ in range(200):
            if not eng.step(fin):
                break
        assert eng.stats["engine"]["preemptions"] == 0
        assert {r.rid: tuple(r.generated) for r in fin} == want


class TestPagedCompileStability:
    def test_one_compile_per_rung_with_page_growth(self, setup):
        """>=3 rungs in one serve *and* budgets long enough that rows
        cross page boundaries mid-decode: the decode window still
        compiles at most once per distinct rung — table growth writes
        entries into fixed-shape operands, never reshapes them."""
        cfg, params = setup
        prompts = _prompts([6, 9, 5, 7, 11, 6], cfg.vocab_size)
        budgets = [14, 9, 2, 2, 2, 2]   # rid 0 crosses pages 8 and 16
        eng = make_engine(cfg, params, kind="paged", max_slots=4,
                          max_seq=64, window=2, page_size=8)
        tokens = _run(eng, prompts, budgets)
        assert len(tokens) == 6
        assert eng.stats["engine"]["page_grows"] > 0   # boundary crossings happened
        rungs = eng.stats["engine"]["rungs"]
        assert len(set(rungs)) >= 3, rungs
        compiles = eng.stats["decode_compiles"]
        if compiles is None:
            pytest.skip("jit compile-cache counter unavailable")
        assert compiles <= len(set(rungs))
        # Steady state: same shapes, zero new compiles, same tokens.
        eng.reset()
        tokens2 = _run(eng, prompts, budgets)
        assert eng.stats["decode_compiles"] == compiles
        assert tokens2 == tokens

    def test_compile_counter_trace_fallback(self, setup, monkeypatch):
        """If jax's private jit-cache API vanishes, decode_compiles
        falls back to the engine's trace counter instead of None — the
        bench gate rows can never silently degrade to a passing
        sentinel."""
        import repro.serve.slot_engine as se
        monkeypatch.setattr(se, "jit_cache_entries", lambda fn: None)
        cfg, params = setup
        eng = make_engine(cfg, params, kind="paged", max_slots=2,
                          max_seq=64, window=2, page_size=8)
        _run(eng, _prompts([5, 9], cfg.vocab_size), [3, 3])
        assert eng.stats["decode_compiles"] == eng._window_traces
        assert eng.stats["decode_compiles"] >= 1

    def test_window_program_text_leaves_engine_untouched(self, setup):
        """The compiled decode window's text is readable at any rung
        without running it: the pools are not donated and a later serve
        emits the same tokens."""
        cfg, params = setup
        eng = make_engine(cfg, params, kind="paged", max_slots=2,
                          max_seq=64, window=2, page_size=8)
        prompts = _prompts([5, 9], cfg.vocab_size)
        want = _run(eng, prompts, [3, 3])
        eng.reset()
        text = eng.window_program_text(eng.rungs[-1])
        assert "ENTRY" in text and "while" in text   # the T-step scan
        assert _run(eng, prompts, [3, 3]) == want

    def test_prefill_compiles_once_per_page_count(self, setup):
        """Paged prompts bucket to page multiples: one prefill
        compilation per ceil(len/page) value, not per length."""
        from repro.serve.slot_engine import jit_cache_entries
        cfg, params = setup
        eng = make_engine(cfg, params, kind="paged", max_slots=2,
                          max_seq=64, window=2, page_size=8)
        prompts = _prompts([5, 6, 7, 8, 9, 12], cfg.vocab_size)
        _run(eng, prompts, [3] * 6)
        # lens 5-8 share the 1-page bucket; 9 and 12 the 2-page bucket.
        assert eng.stats["engine"]["prefill_bucket_misses"] == 2
        assert eng.stats["engine"]["prefill_bucket_hits"] == 4
        assert jit_cache_entries(eng.prefill_fn) in (2, None)


class TestMemoryFootprint:
    def test_long_context_mix_fits_smaller_pool(self, setup):
        """One long-context request + short tail served concurrently
        out of a pool the dense engine's worst-case reservation could
        not even hold two slots of — at identical tokens."""
        cfg, params = setup
        lens = [40, 6, 9, 5, 7, 12]
        budgets = [8, 4, 5, 3, 6, 4]
        prompts = _prompts(lens, cfg.vocab_size, seed=3)
        slot = make_engine(cfg, params, kind="slot", max_slots=4,
                           max_seq=64, window=4)
        want = _run(slot, prompts, budgets)
        # 12 pages of 8 tokens; the dense equivalent is 4 slots x 8
        # pages = 32.  Two full-length requests would already need 16.
        eng = make_engine(cfg, params, kind="paged", max_slots=4,
                          max_seq=64, window=4, page_size=8, num_pages=12)
        got = _run(eng, prompts, budgets)
        assert got == want
        # Genuinely concurrent (dense storage at this byte budget could
        # hold at most one max_seq slot)...
        assert max(eng.stats["engine"]["rungs"]) >= 2
        assert eng.cache.num_pages < 2 * eng.cache.max_pages_per_slot
        # ...and genuinely smaller than the dense engine's residency.
        dense = slot.cache.resident_bytes()
        paged = eng.cache.resident_bytes()
        assert paged < 0.6 * dense, (paged, dense)

    def test_rejects_unsupported_configs(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError):    # exact-length caches can't page
            make_engine(cfg, params, kind="paged", max_slots=2, max_seq=32,
                        buckets="off")
        from repro.models.attention import set_kv_cache_quant
        cfg, params = setup
        set_kv_cache_quant(True)
        try:
            with pytest.raises(NotImplementedError):
                make_engine(cfg, params, kind="paged", max_slots=2,
                            max_seq=32)
        finally:
            set_kv_cache_quant(False)
        with pytest.raises(ValueError):    # pool quant is int8-or-f32
            make_engine(cfg, params, kind="paged", max_slots=2, max_seq=32,
                        kv_quant="fp8")


class TestPrefixSharing:
    def test_common_preamble_dedups_physical_pages(self, setup):
        """Requests sharing a page-aligned system prompt map the same
        physical pages (admission refcounts, not copies), emit the same
        tokens as without sharing, and the registry drains with the
        pool."""
        cfg, params = setup
        rng = np.random.default_rng(11)
        preamble = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
        prompts = [np.concatenate(
            [preamble, rng.integers(0, cfg.vocab_size, size=ext)
             .astype(np.int32)]) for ext in (3, 7, 0, 5)]
        budgets = [5, 4, 6, 3]

        def build(**kw):
            return make_engine(cfg, params, kind="paged", max_slots=4,
                               max_seq=64, window=4, page_size=8, **kw)

        base = build(prefix_sharing=False)
        want = _run(base, prompts, budgets)
        eng = build()
        got = _run(eng, prompts, budgets)
        assert got == want
        # 2 preamble pages x 3 follower requests mapped by reference
        # (admission order can vary; every follower shares >= the
        # preamble) and the fresh-page count shrinks by exactly the
        # shared count.
        assert eng.stats["engine"]["pages_shared"] >= 6
        assert (eng.stats["engine"]["page_admits"] + eng.stats["engine"]["pages_shared"]
                == base.stats["engine"]["page_admits"])
        assert eng.stats["engine"]["page_cows"] == 0   # writes start past prompts
        # Peak residency: sharing strictly fewer pages mapped at once.
        assert (eng.stats["engine"]["pages_mapped_peak"]
                < base.stats["engine"]["pages_mapped_peak"])
        # Everything drains: pool full, registry empty, nothing orphaned.
        assert eng.cache.n_free_pages == eng.cache.num_pages
        assert eng.cache.orphaned_pages == 0
        assert not eng._prefix_registry and not eng._page_key

    def test_sharing_feeds_admission_capacity(self, setup):
        """A pool that cannot hold two worst-case requests exclusively
        still serves identical-prompt requests concurrently — the
        shared pages don't charge the reservation twice."""
        cfg, params = setup
        rng = np.random.default_rng(5)
        prompt = rng.integers(0, cfg.vocab_size, size=24).astype(np.int32)
        prompts = [prompt, prompt.copy()]
        # Long enough that the first request is still decoding when the
        # second's admission re-probes the (now populated) registry.
        budgets = [6, 6]
        # Worst case per request: ceil((24 + 3) / 8) = 4 pages; pool of
        # 6 fits both only because the 3 full prompt pages are shared.
        eng = make_engine(cfg, params, kind="paged", max_slots=2,
                          max_seq=32, window=4, page_size=8, num_pages=6)
        got = _run(eng, prompts, budgets)
        noshare = make_engine(cfg, params, kind="paged", max_slots=2,
                              max_seq=32, window=4, page_size=8,
                              num_pages=6, prefix_sharing=False)
        want = _run(noshare, prompts, budgets)
        assert got == want
        assert max(eng.stats["engine"]["rungs"]) == 2       # truly concurrent
        assert max(noshare.stats["engine"]["rungs"]) == 1   # serialized without
        assert eng.stats["engine"]["pages_shared"] == 3


class TestResetLifecycle:
    """reset() must return the engine to a like-new state: pool, prefix
    registry, and orphan accounting all purged."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg = smoke_config("yi-6b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        return cfg, params

    def test_serve_reset_serve_under_pool_pressure(self, setup):
        """Serve a sharing-heavy workload that nearly fills the pool,
        reset, then serve it again: the second pass must emit identical
        tokens and identical page accounting, with no leaked pages or
        stale registry entries carried across the reset."""
        cfg, params = setup
        rng = np.random.default_rng(23)
        preamble = rng.integers(0, cfg.vocab_size, size=16).astype(np.int32)
        prompts = [np.concatenate(
            [preamble, rng.integers(0, cfg.vocab_size, size=ext)
             .astype(np.int32)]) for ext in (2, 5, 0)]
        budgets = [5, 4, 6]
        # Tight pool: worst case per request is 4 pages; 8 pages only
        # fit three concurrent requests because the preamble is shared.
        eng = make_engine(cfg, params, kind="paged", max_slots=3,
                          max_seq=32, window=4, page_size=8, num_pages=8)
        first = _run(eng, prompts, budgets)
        snap = dict(eng.stats["engine"])
        assert snap["pages_shared"] > 0          # pressure test is real

        eng.reset()
        assert eng.cache.n_free_pages == eng.cache.num_pages
        assert eng.cache.orphaned_pages == 0
        assert not eng._prefix_registry and not eng._page_key
        assert eng.stats["engine"]["page_admits"] == 0

        second = _run(eng, prompts, budgets)
        assert second == first
        for key in ("page_admits", "pages_shared", "page_grows",
                    "pages_mapped_peak"):
            assert eng.stats["engine"][key] == snap[key], key
        assert eng.cache.n_free_pages == eng.cache.num_pages

    def test_registry_desync_drops_stale_entries(self, setup):
        """If storage drains behind the engine's back, the prefix
        registry points at recycled pages.  _probe_shared must detect
        the desync (refcount/key mismatch), drop the stale entries, and
        serve correct tokens instead of mapping garbage."""
        cfg, params = setup
        rng = np.random.default_rng(31)
        prompt = rng.integers(0, cfg.vocab_size, size=24).astype(np.int32)
        eng = make_engine(cfg, params, kind="paged", max_slots=2,
                          max_seq=32, window=4, page_size=8, num_pages=8)
        want = _run(eng, [prompt], [4])
        # Forge the post-desync state: registry entries for this exact
        # prompt pointing at pages that already drained (refcount 0) —
        # what a storage-level reset without engine.reset() leaves
        # behind.  A naive probe would map these free pages as shared
        # prefix and alias garbage into the request.
        toks = np.asarray(prompt, np.int32)
        for j in range(len(toks) // eng.page_size):
            key = toks[:(j + 1) * eng.page_size].tobytes()
            eng._prefix_registry[key] = j
            eng._page_key[j] = key
        assert eng._prefix_registry               # the hazard is armed
        got = _run(eng, [prompt.copy()], [4])
        assert got == want
        assert eng.stats["engine"]["pages_shared"] == 0   # no bogus sharing
        # Stale entries were evicted; any survivors point at live pages
        # whose reverse mapping agrees.
        for key, pg in eng._prefix_registry.items():
            assert eng.cache.page_refcount(pg) >= 1
            assert eng._page_key.get(pg) == key


def _fake_local_cache(cap: int, fill: float):
    """Single-request prefill cache with only sliding-window leaves:
    dense cell c of lk holds fill + c."""
    vals = fill + np.arange(cap, dtype=np.float32)
    leaf = jnp.asarray(vals, jnp.float32).reshape(1, 1, cap, 1, 1)
    return [{"b0": {"lk": leaf, "lv": leaf + 0.5}}]


class TestLocalRingAllocator:
    """White-box local-ring lifecycle: admission maps one fixed ring,
    advance_ring frees dead columns back to the pool (FIFO — reclaimed
    pages transit the whole free list before reuse), and release
    returns everything."""

    def test_ring_admit_regather_and_sink(self):
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX,
                             local_ring=3, num_local_pages=12)
        slot = cache.acquire()
        # One-page prompt, last real token at dense cell 2.
        cache.admit(_fake_local_cache(PSZ, 100.0), slot, 0, last_index=2)
        assert cache.n_free_local == 12 - 3
        row = cache.local_pages_of(slot)
        assert len(row) == 3 and len(set(row)) == 3
        ltable = np.asarray(cache.ltable)
        assert ltable[slot].tolist() == row
        for s in range(SLOTS):
            if s != slot:
                assert (ltable[s] == cache.lsink).all()
        # Ring cell c of column 0 holds dense cell c (identity layout
        # when the prompt fits) up to the last real token; cells ahead
        # of it — and the whole un-decoded columns — are zeroed, not
        # garbage (decode writes each cell before any read of it).
        lk = np.asarray(jax.tree.leaves(cache.pools)[0])[0, :, :, 0, 0]
        np.testing.assert_allclose(
            lk[row[0]], np.where(np.arange(PSZ) <= 2,
                                 100.0 + np.arange(PSZ), 0.0))
        np.testing.assert_allclose(lk[row[1]], 0.0)
        np.testing.assert_allclose(lk[row[2]], 0.0)

    def test_advance_ring_rotates_through_free_list(self):
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX,
                             local_ring=3, num_local_pages=12)
        slot = cache.acquire()
        cache.admit(_fake_local_cache(PSZ, 1.0), slot, 0, last_index=2)
        row0 = cache.local_pages_of(slot)
        free0 = list(cache._free_local)
        # Decode crosses two block boundaries: columns for blocks 1 and
        # 2 retire their pages and remap from the FIFO front.
        assert cache.advance_ring(slot, 2) == 2
        row1 = cache.local_pages_of(slot)
        # Column 0 (still inside the window span) kept its page; the
        # re-targeted columns took the two oldest free pages, and the
        # freed pages went to the *back* of the free list.
        assert row1[0] == row0[0]
        assert row1[1:] == free0[:2]
        assert list(cache._free_local)[-2:] == [row0[1], row0[2]]
        # Idempotent: the same block advances nothing twice.
        assert cache.advance_ring(slot, 2) == 0
        # Conservation at every step: rings + free list == the pool.
        held = [p for s in range(SLOTS) for p in cache.local_pages_of(s)]
        assert sorted(held + list(cache._free_local)) == list(range(12))
        # Wrap-around: far-future block reuses column (block % ring).
        assert cache.advance_ring(slot, 5) == 3
        assert np.asarray(cache.ltable)[slot].tolist() == \
            cache.local_pages_of(slot)
        cache.release(slot)
        assert cache.n_free_local == 12
        assert (np.asarray(cache.ltable)[slot] == cache.lsink).all()

    def test_exact_pool_self_swap_is_safe(self):
        """With an exactly-sized pool fully held, advance_ring's
        free-then-alloc hands the column its own page back — a no-op
        swap that still counts as a reclaim and never underflows."""
        cache = PagedKVCache(1, PAGES, PSZ, PMAX,
                             local_ring=3, num_local_pages=3)
        slot = cache.acquire()
        cache.admit(_fake_local_cache(PSZ, 1.0), slot, 0, last_index=2)
        assert cache.n_free_local == 0
        row0 = cache.local_pages_of(slot)
        assert cache.advance_ring(slot, 1) == 1
        assert cache.local_pages_of(slot) == row0   # self-swap
        assert cache.n_free_local == 0


class TestResidentBytesPreshape:
    """resident_bytes satellite: engines report the configured pool
    footprint from construction (not 0 until the first admission), and
    reset() preserves it."""

    @pytest.fixture(scope="class")
    def setup(self):
        cfg = smoke_config("yi-6b")
        params = init_params(cfg, jax.random.PRNGKey(0))
        return cfg, params

    def test_engine_reports_footprint_before_first_admission(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, kind="paged", max_slots=2,
                          max_seq=32, window=2, page_size=8)
        configured = eng.cache.resident_bytes()
        assert configured > 0
        got = _run(eng, _prompts([6, 9], cfg.vocab_size), [3, 3])
        assert len(got) == 2
        # Admission/decode never changes the footprint (pools are
        # preallocated; tables are fixed-shape).
        assert eng.cache.resident_bytes() == configured
        eng.reset()
        assert eng.cache.resident_bytes() == configured

    def test_quantized_pool_preshape_matches_lazy(self, setup):
        cfg, params = setup
        eng = make_engine(cfg, params, kind="paged", max_slots=2,
                          max_seq=32, window=2, page_size=8,
                          kv_quant="int8")
        configured = eng.cache.resident_bytes()
        assert configured > 0
        _run(eng, _prompts([6], cfg.vocab_size), [3])
        assert eng.cache.resident_bytes() == configured

    def test_direct_cache_stays_lazy(self):
        """Back-compat: a directly constructed cache (no engine, no
        preshape) still reports 0 until its first admission shapes the
        pools."""
        cache = PagedKVCache(SLOTS, PAGES, PSZ, PMAX)
        assert cache.resident_bytes() == 0
        slot = cache.acquire()
        cache.admit(_fake_cache(2, 1.0), slot, 3)
        assert cache.resident_bytes() > 0
