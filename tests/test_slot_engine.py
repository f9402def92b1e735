"""Slot-based serving fast path: recompile stability, slot lifecycle,
bucketed prefill, and token equivalence with the sequential engine.

Covers the acceptance contract of the ladder-locked hot path:

* a mixed serve passing through >=3 batch shapes triggers at most one
  decode compile per ladder rung (counted via the jit compile cache);
* slots are reused after release with no stale-cache token leakage
  (admission overwrites the slot's full capacity);
* per-slot positions: heterogeneous prompt lengths decode exactly as
  their single-request serves (the legacy engine forced every row to
  ``max(positions)``);
* bucketed prefill pads to power-of-two shapes without changing tokens,
  and records hit/miss stats;
* ``choose_decode_batch``'s ladder sweep is memoized per (cfg, rung).
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.models import init_params
from repro.serve import make_engine, Request
from repro.serve.policy import SchedulingPolicy


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("yi-6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(lens, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=s).astype(np.int32) for s in lens]


def _run(engine, prompts, budgets, max_steps=800):
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        engine.submit(Request(rid=i, prompt=p, max_new_tokens=b))
    done = engine.run(max_steps=max_steps)
    return {c.rid: c.tokens for c in done}


class TestCompileStability:
    def test_one_compile_per_rung_across_batch_transitions(self, setup):
        """>=3 distinct batch shapes in one serve; decode compiles stay
        bounded by the number of distinct ladder rungs."""
        cfg, params = setup
        prompts = _prompts([6, 9, 5, 7, 11, 6], cfg.vocab_size)
        # Slots 0/1 hold the long-lived requests; the short tail cycles
        # through slots 2/3, so the serve drains rung 4 -> 2 -> 1.
        budgets = [14, 9, 2, 2, 2, 2]
        eng = make_engine(cfg, params, kind="slot", max_slots=4,
                          max_seq=64, window=2)
        tokens = _run(eng, prompts, budgets)
        assert len(tokens) == 6
        rungs = eng.stats["engine"]["rungs"]
        # The serve really exercised multiple ladder shapes...
        assert len(set(rungs)) >= 3, rungs
        # ...and compiled the window at most once per distinct rung.
        compiles = eng.stats["decode_compiles"]
        if compiles is None:            # jax without _cache_size
            pytest.skip("jit compile-cache counter unavailable")
        assert compiles <= len(set(rungs))
        # Steady state: re-serving the same shapes compiles nothing new.
        tokens2 = _run(eng, prompts, budgets)
        assert eng.stats["decode_compiles"] == compiles
        assert tokens2 == tokens  # deterministic greedy decode

    def test_prefill_bucket_hits(self, setup):
        """Prompts sharing a power-of-two bucket reuse one prefill
        compilation; stats record the hit/miss split."""
        cfg, params = setup
        eng = make_engine(cfg, params, kind="slot", max_slots=2,
                          max_seq=64, window=2)
        prompts = _prompts([5, 6, 7, 8], cfg.vocab_size)
        _run(eng, prompts, [3, 3, 3, 3])
        # All four prompts pad to the same 8-token bucket.
        assert eng.stats["engine"]["prefill_bucket_misses"] == 1
        assert eng.stats["engine"]["prefill_bucket_hits"] == 3
        from repro.serve.slot_engine import jit_cache_entries
        assert jit_cache_entries(eng.prefill_fn) in (1, None)


class TestSlotLifecycle:
    def test_slot_reused_after_release_no_stale_tokens(self, setup):
        """A slot freed by a finished request serves the next request
        with exactly the tokens a fresh engine would produce."""
        cfg, params = setup
        pa, pb = _prompts([13, 6], cfg.vocab_size, seed=3)
        eng = make_engine(cfg, params, kind="slot", max_slots=1,
                          max_seq=64, window=2)
        eng.submit(Request(rid=0, prompt=pa, max_new_tokens=6))
        eng.submit(Request(rid=1, prompt=pb, max_new_tokens=5))
        tokens = {c.rid: c.tokens for c in eng.run(200)}
        # One slot, two requests: it was reused.
        assert eng.stats["engine"]["slot_admits"] == 2
        assert eng.stats["engine"]["slot_releases"] == 2
        fresh = make_engine(cfg, params, kind="slot", max_slots=1,
                            max_seq=64, window=2)
        fresh.submit(Request(rid=1, prompt=pb, max_new_tokens=5))
        alone = {c.rid: c.tokens for c in fresh.run(200)}
        assert tokens[1] == alone[1]

    def test_free_list_prefers_lowest_slot(self):
        from repro.serve import SlotKVCache
        c = SlotKVCache(4)
        assert [c.acquire() for _ in range(3)] == [0, 1, 2]
        c.release(1)
        c.release(0)
        assert c.acquire() == 0
        assert c.acquire() == 1
        assert c.acquire() == 3
        assert c.n_free == 0

    def test_per_slot_positions_match_singleton_serves(self, setup):
        """Heterogeneous prompt lengths: each request's tokens equal its
        single-request serve — short rows never attend past their own
        length (per-slot positions, not max(positions))."""
        cfg, params = setup
        lens = [6, 13, 21, 9]
        prompts = _prompts(lens, cfg.vocab_size, seed=5)
        budgets = [4, 3, 5, 4]
        eng = make_engine(cfg, params, kind="slot", max_slots=4,
                          max_seq=64, window=3)
        batched = _run(eng, prompts, budgets)
        alone = {}
        for i in range(len(lens)):
            single = make_engine(cfg, params, kind="slot", max_slots=1,
                                 max_seq=64, window=3)
            single.submit(Request(rid=i, prompt=prompts[i],
                                  max_new_tokens=budgets[i]))
            alone.update({c.rid: c.tokens for c in single.run(200)})
        assert batched == alone


class TestEquivalenceWithLegacyEngine:
    def test_tokens_match_legacy_uniform_lengths(self, setup):
        """Same workload, same tokens as ServeEngine (the pre-slot
        baseline), including the max_new_tokens=1 edge (legacy always
        decodes at least one token past the prefill token)."""
        cfg, params = setup
        prompts = _prompts([6] * 5, cfg.vocab_size, seed=1)
        budgets = [3, 1, 4, 2, 3]
        legacy = make_engine(cfg, params, kind="sequential", max_slots=2,
                             max_seq=64)
        want = _run(legacy, prompts, budgets)
        slot = make_engine(cfg, params, kind="slot", max_slots=2,
                           max_seq=64, window=4)
        got = _run(slot, prompts, budgets)
        assert got == want
        assert all(len(t) == max(b, 2)
                   for t, b in zip((got[i] for i in range(5)), budgets))


class TestChooseDecodeBatchCache:
    def test_ladder_sweep_memoized(self):
        from unittest import mock

        from repro.serve.engine import _rung_cycles, choose_decode_batch
        cfg = get_config("qwen2.5-0.5b")
        b1 = choose_decode_batch(19, cfg, 128)
        info0 = _rung_cycles.cache_info()
        # A warm call must not re-run the simulator at all.
        with mock.patch("repro.serve.engine.simulate_workload",
                        side_effect=AssertionError("simulator re-ran")):
            b2 = choose_decode_batch(19, cfg, 128)
        assert b1 == b2
        assert _rung_cycles.cache_info().hits > info0.hits


class TestWindowedPromptBuckets:
    """Satellite regressions for sliding-window prompt bucketing: long
    prompts on LOCAL configs bucket like any other (the rolled-ring
    prefill layout), and the fallback counter is distinct from a
    first-seen bucket miss."""

    @pytest.fixture(scope="class")
    def gemma(self):
        cfg = smoke_config("gemma3-1b")   # LOCAL x5 + ATTN, window 16
        params = init_params(cfg, jax.random.PRNGKey(0))
        return cfg, params

    def test_long_prompts_zero_post_warmup_compiles(self, gemma):
        """Prompts longer than the sliding window used to fall off the
        bucketed path (one exact-length compile per unique length);
        they now bucket to 2^k clamped to max_seq, so a warmed engine
        serves varied long prompts with zero prefill or decode
        compiles."""
        cfg, params = gemma
        eng = make_engine(cfg, params, kind="slot", max_slots=4,
                          max_seq=64, window=4)
        eng.warmup()
        assert cfg.sliding_window < 64   # the prompts must cross it
        lens = [17, 20, 23, 24, 31, 33, 40, 47]
        prompts = _prompts(lens, cfg.vocab_size, seed=7)
        tokens = _run(eng, prompts, [4] * len(lens))
        assert len(tokens) == len(lens)
        ext = eng.stats["engine"]
        # Every prompt landed in a warmup-enumerated bucket: no
        # first-seen misses, no exact-length fallbacks, no compiles.
        assert ext["prefill_bucket_fallbacks"] == 0
        assert ext["prefill_bucket_misses"] == 0
        assert ext["prefill_bucket_hits"] == len(lens)
        assert eng.stats["decode_compiles"] == 0

    def test_long_prompts_match_singleton_serves(self, gemma):
        """The rolled-ring bucket layout is token-exact: batched long
        prompts equal their single-request serves."""
        cfg, params = gemma
        lens = [17, 25, 33]
        prompts = _prompts(lens, cfg.vocab_size, seed=11)
        budgets = [6, 4, 5]
        eng = make_engine(cfg, params, kind="slot", max_slots=3,
                          max_seq=64, window=3)
        batched = _run(eng, prompts, budgets)
        alone = {}
        for i in range(len(lens)):
            single = make_engine(cfg, params, kind="slot", max_slots=1,
                                 max_seq=64, window=3)
            single.submit(Request(rid=i, prompt=prompts[i],
                                  max_new_tokens=budgets[i]))
            alone.update({c.rid: c.tokens for c in single.run(200)})
        assert batched == alone

    def test_fallbacks_counted_separately_from_misses(self, setup):
        """Only prompts longer than the engine capacity fall back to
        exact-length prefill; the counter is split from first-seen
        bucket misses so capacity tuning can tell 'compiles once, then
        hits' from 'compiles every time'."""
        cfg, params = setup
        eng = make_engine(cfg, params, kind="slot", max_slots=2,
                          max_seq=32, window=2)
        lens = [9, 12, 40, 45]   # 9/12 share the 16-bucket; 40/45 > cap
        tokens = _run(eng, _prompts(lens, cfg.vocab_size, seed=2),
                      [3, 3, 2, 2])
        assert len(tokens) == 4
        ext = eng.stats["engine"]
        assert ext["prefill_bucket_misses"] == 1
        assert ext["prefill_bucket_hits"] == 1
        assert ext["prefill_bucket_fallbacks"] == 2


def _script(eng, arrivals, actions=None, max_steps=400):
    """Drive ``eng.step()`` by hand: ``arrivals`` maps a step number to
    the requests submitted before it, ``actions`` to a call on the
    engine made before it.  Returns the finished requests by rid."""
    fin, pending, actions = [], dict(arrivals), dict(actions or {})
    for step in range(max_steps):
        for req in pending.pop(step, []):
            eng.submit(req)
        if step in actions:
            actions.pop(step)(eng)
        if not eng.step(fin) and not pending and not actions:
            break
    return {r.rid: r for r in fin}


# name -> (max_seq, [(arrival step, prompt len, budget)], {step: action})
PIPELINE_CASES = {
    # Rows join a running engine: each admission drains the window in
    # flight, and decode goes on overlapped.
    "admitted_midstream": (64, [(0, 6, 20), (3, 9, 6), (5, 5, 8)], {}),
    # Staggered budgets: every row finishes in a window whose successor
    # is already in flight, and rides it frozen.
    "finishing_in_flight": (64, [(0, 7, 3), (0, 5, 5), (0, 9, 7),
                                 (0, 6, 9)], {}),
    "preempt_resume": (64, [(0, 9, 14), (0, 17, 12), (0, 6, 20)],
                       {1: lambda e: e.preempt(1), 2: lambda e: e.preempt(2)}),
    "cancel": (64, [(0, 9, 12), (0, 5, 20), (1, 7, 6)],
               {1: lambda e: e.cancel(1)}),
    # The prompt leaves room for fewer tokens than its budget: the row
    # stops at max_seq - 1, on the host's mirror as on the device.
    "max_seq": (32, [(0, 26, 20), (0, 7, 30), (2, 5, 4)], {}),
}


@pytest.fixture(scope="module")
def pipeline_refs(setup):
    """Each case's requests served one at a time by the sequential
    engine: no window, no pipeline, no batch."""
    cfg, params = setup
    refs = {}
    for name, (max_seq, reqs, _acts) in PIPELINE_CASES.items():
        prompts = _prompts([n for _, n, _ in reqs], cfg.vocab_size, seed=9)
        seq = make_engine(cfg, params, kind="sequential", max_slots=1,
                          max_seq=max_seq)
        refs[name] = _run(seq, prompts, [b for _, _, b in reqs])
    return refs


class _TargetOne(SchedulingPolicy):
    """The default policy with a ladder target of one row."""

    def ladder_target(self, *args, **kwargs) -> int:
        return 1


class TestWindowPipeline:
    """The two-deep window pipeline (one window queued on the device
    while the host reads the last one back) emits the tokens of the
    sequential engine in every case that changes the row set while a
    window is in flight, on both slot engine kinds; window 8 is a second
    window length through the same pipeline."""

    @pytest.mark.parametrize("case", sorted(PIPELINE_CASES))
    @pytest.mark.parametrize("kind", ["slot", "paged"])
    @pytest.mark.parametrize("window", [1, 8])
    def test_tokens_match_the_sequential_engine(self, setup, pipeline_refs,
                                                kind, case, window):
        cfg, params = setup
        max_seq, reqs, actions = PIPELINE_CASES[case]
        prompts = _prompts([n for _, n, _ in reqs], cfg.vocab_size, seed=9)
        kw = {"page_size": 8} if kind == "paged" else {}
        eng = make_engine(cfg, params, kind=kind, max_slots=4,
                          max_seq=max_seq, window=window, **kw)
        arrivals = {}
        for rid, ((at, _n, budget), p) in enumerate(zip(reqs, prompts)):
            arrivals.setdefault(at, []).append(
                Request(rid=rid, prompt=p, max_new_tokens=budget))
        got = _script(eng, arrivals, actions)
        want = pipeline_refs[case]
        assert sorted(got) == sorted(want)
        for rid, req in got.items():
            if req.finish_reason == "cancelled":
                assert 1 <= len(req.generated) < len(want[rid])
                assert tuple(req.generated) == want[rid][:len(req.generated)]
            else:
                assert tuple(req.generated) == want[rid], rid
        ext = eng.stats["engine"]
        assert eng._inflight is None and eng._n_active() == 0
        # Every window dispatched with none in flight ends in one drain.
        assert ext["windows"] - ext["windows_overlapped"] \
            == ext["pipeline_drains"] >= 1
        if window == 1:
            assert ext["windows_overlapped"] >= 1
        assert ext["slot_admits"] == ext["slot_releases"] \
            == len(reqs) + ext["preemptions"]
        if case == "preempt_resume":
            assert ext["preemptions"] >= 2
        if case == "cancel":
            assert ext["cancelled"] == 1
        if case == "max_seq":
            assert len(got[0].generated) == max_seq - 26 < 20

    def test_a_finishing_row_keeps_its_slot_until_read_back(self, setup):
        """A row whose budget runs out in window j is frozen (budget 0)
        in window j+1 and keeps its slot until j is read back; a drain
        then reads back j+1 and the mirrors match the device."""
        cfg, params = setup
        p0, p1 = _prompts([6, 8], cfg.vocab_size, seed=4)
        eng = make_engine(cfg, params, kind="slot", max_slots=2,
                          max_seq=64, window=1)
        eng.submit(Request(rid=0, prompt=p0, max_new_tokens=3))
        eng.submit(Request(rid=1, prompt=p1, max_new_tokens=9))
        fin = []
        eng.step(fin)        # window 0 dispatched; nothing read back
        assert eng._inflight.idx == 0 and not fin
        eng.step(fin)        # window 1 in flight, window 0 read back
        assert eng._inflight.idx == 1 and eng._budget[0] == 0
        assert eng._req[0] is not None and eng._req[0].rid == 0
        eng.step(fin)        # rid 0's last token comes with window 1
        assert [r.rid for r in fin] == [0] and eng._req[0] is None
        eng.drain_pipeline("test")     # reads window 2 back: mirrors hold
        assert eng._inflight is None
        assert eng.stats["engine"]["pipeline_drains"] == 1
        assert (eng._pos[1], eng._budget[1]) == (8 + 3, 8 - 3)
        assert len(eng._req[1].generated) == 1 + 3

    def test_a_row_held_back_by_the_ladder_target_drains_nothing(self,
                                                                 setup):
        """A waiting row that admission would not take (the ladder
        target is reached) leaves the window in flight alone: decode
        goes on overlapped until a row finishes and the waiting one
        joins an idle engine."""
        cfg, params = setup
        p0, p1 = _prompts([6, 8], cfg.vocab_size, seed=5)
        seq = make_engine(cfg, params, kind="sequential", max_slots=1,
                          max_seq=64)
        want = _run(seq, [p0, p1], [12, 6])
        eng = make_engine(cfg, params, kind="slot", max_slots=4,
                          max_seq=64, window=1, policy=_TargetOne())
        eng.submit(Request(rid=0, prompt=p0, max_new_tokens=12))
        eng.submit(Request(rid=1, prompt=p1, max_new_tokens=6))
        fin = []
        eng.step(fin)
        assert eng._n_active() == 1 and len(eng.queue) == 1
        for _ in range(8):
            eng.step(fin)
        ext = eng.stats["engine"]
        assert ext["pipeline_drains"] == 0 and len(eng.queue) == 1
        assert ext["windows_overlapped"] == ext["windows"] - 1 == 8
        got = _script(eng, {})
        got.update({r.rid: r for r in fin})
        assert {rid: tuple(r.generated) for rid, r in got.items()} == want

