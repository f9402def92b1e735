"""Overload robustness: SLO admission classes, preemption, deadlines,
cancellation, backpressure, and the seeded fault-injection harness.

The contract under test is graceful degradation with zero corruption:

* an interactive arrival into a saturated page pool is admitted by
  preempting a batch-class resident instead of stalling behind the
  drain, and the evictee resumes **token-identically** (re-prefill of
  ``prompt + generated[:-1]``, decode on);
* every lifecycle exit — ``cancel()``, deadline expiry, load-shed
  rejection — resolves its handle with a typed reason and releases all
  engine storage (slots, pages, reservations, prefix registry);
* a seeded :class:`~repro.serve.faults.FaultPlan` (the
  ``REPRO_FAULT_SEED`` CI axis) can batter the frontend with allocator
  exhaustion, preemption storms, stragglers, cancels, expiries, and
  raising callbacks — and afterwards every handle is resolved, the
  allocator is drained to zero leaks, and every surviving stream equals
  the unfaulted serve.
"""
import os
import time

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import init_params
from repro.serve import (FaultEvent, FaultPlan, KLASS_BATCH,
                         KLASS_INTERACTIVE, make_engine, RejectedError,
                         Request, SchedulingPolicy, ServeFrontend,
                         validate_stats)

MAX_SLOTS = 4
MAX_SEQ = 64
WINDOW = 4
PSZ = 8
SMALL_POOL = 8   # two mid-size residents exhaust it
FAULT_SEED = int(os.environ.get("REPRO_FAULT_SEED", "0"))


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("yi-6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _make(setup, *, num_pages=None, **kw):
    cfg, params = setup
    return make_engine(cfg, params, kind="paged", max_slots=MAX_SLOTS,
                      max_seq=MAX_SEQ, window=WINDOW, page_size=PSZ,
                      num_pages=num_pages, **kw)


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32)
            for s in lens]


@pytest.fixture(scope="module")
def reference(setup):
    """rid -> expected tokens, served one at a time on an unpressured
    engine (per-request streams are arrival/batch-invariant, so this is
    the ground truth for every overload scenario)."""
    cfg, _ = setup
    eng = _make(setup)

    def tokens_for(prompts, budgets):
        want = {}
        for rid, (p, b) in enumerate(zip(prompts, budgets)):
            eng.reset()
            eng.submit(Request(rid=rid, prompt=p, max_new_tokens=b))
            want[rid] = eng.run(max_steps=4096)[0].tokens
        return want
    return tokens_for


class TestPreemption:
    def test_interactive_admitted_by_preempting_batch(self, setup,
                                                      reference):
        """The headline scenario: batch load saturates the page pool,
        then an interactive request arrives.  It must be admitted via
        preemption (not stall until the batch drains), and every stream
        — the evictee's resumed one included — must equal the
        unpressured serve."""
        cfg, _ = setup
        prompts = _prompts(cfg, [9, 17, 15, 7], seed=0)
        budgets = [12, 10, 12, 6]
        want = reference(prompts, budgets)

        eng = _make(setup, num_pages=SMALL_POOL)
        for rid in range(3):     # saturating batch load
            eng.submit(Request(rid=rid, prompt=prompts[rid],
                               max_new_tokens=budgets[rid]))
        fin, steps, admitted_at = [], 0, None
        while eng.step(fin) and steps < 400:
            steps += 1
            if steps == 1:
                assert eng.cache.n_free_pages < PSZ  # genuinely full
                eng.submit(Request(rid=3, prompt=prompts[3],
                                   max_new_tokens=budgets[3],
                                   klass=KLASS_INTERACTIVE))
            if admitted_at is None and any(
                    r is not None and r.rid == 3 for r in eng._req):
                admitted_at = steps
        got = {r.rid: tuple(r.generated) for r in fin}
        assert got == want
        ext = eng.stats["engine"]
        assert ext["preemptions"] >= 1
        # No admit stall: the interactive request was resident within a
        # couple of windows of arriving, not after the batch drained.
        assert admitted_at is not None and admitted_at <= 3
        # Accounting reconciles: every preemption is one extra
        # admit/release pair on top of the workload's own.
        assert ext["slot_admits"] == len(prompts) + ext["preemptions"]
        assert ext["slot_admits"] == ext["slot_releases"]
        preempted = [r for r in fin if r.preemptions > 0]
        assert preempted, "a batch resident should have been evicted"
        # Pool fully drained: no leaked pages/reservations/registry.
        assert eng.cache.n_free_pages == eng.cache.num_pages
        assert eng.cache.reserved_total == 0
        assert eng.cache.orphaned_pages == 0
        assert not eng._prefix_registry and not eng._page_key
        validate_stats(eng.stats)

    def test_policy_off_never_preempts(self, setup, reference):
        """The no-policy baseline (class_priority and preemption off)
        serves the same workload FIFO with zero preemptions — the knob
        the SLO bench measures against."""
        cfg, _ = setup
        prompts = _prompts(cfg, [9, 17, 15, 7], seed=0)
        budgets = [12, 10, 12, 6]
        want = reference(prompts, budgets)
        eng = _make(setup, num_pages=SMALL_POOL,
                    policy=SchedulingPolicy(class_priority=False,
                                            preemption=False))
        for rid in range(4):
            eng.submit(Request(rid=rid, prompt=prompts[rid],
                               max_new_tokens=budgets[rid],
                               klass=(KLASS_INTERACTIVE if rid == 3
                                      else KLASS_BATCH)))
        got = {c.rid: c.tokens for c in eng.run(max_steps=4096)}
        assert got == want
        assert eng.stats["engine"]["preemptions"] == 0

    def test_preempt_storm_token_identical(self, setup, reference):
        """Forced evictions at arbitrary points (the fault-injection
        surface) never perturb a stream: preempt+resume is invisible in
        tokens and the accounting stays reconciled."""
        cfg, _ = setup
        prompts = _prompts(cfg, [9, 17, 15, 8], seed=2)
        budgets = [9, 8, 10, 7]
        want = reference(prompts, budgets)
        eng = _make(setup)
        for rid, (p, b) in enumerate(zip(prompts, budgets)):
            eng.submit(Request(rid=rid, prompt=p, max_new_tokens=b))
        fin, steps, stormed = [], 0, 0
        while eng.step(fin) and steps < 400:
            steps += 1
            if steps in (1, 3):
                stormed += eng.preempt(2)
        assert stormed >= 2
        got = {r.rid: tuple(r.generated) for r in fin}
        assert got == want
        ext = eng.stats["engine"]
        assert ext["preemptions"] == stormed
        assert ext["slot_admits"] == len(prompts) + stormed
        assert ext["slot_admits"] == ext["slot_releases"]
        assert eng.cache.n_free_pages == eng.cache.num_pages

    def test_steady_state_no_compiles_across_preempt_cycles(self, setup):
        """Preempt/re-admit cycles reuse the warmed (rung, bucket)
        entry points: a resume's effective prompt lands in the same
        bucketed prefill family, so ``decode_compiles`` stays 0."""
        cfg, _ = setup
        eng = _make(setup)
        eng.warmup(max_prompt_len=32)
        prompts = _prompts(cfg, [9, 17, 15, 8], seed=3)
        for rid, p in enumerate(prompts):
            eng.submit(Request(rid=rid, prompt=p, max_new_tokens=14))
        fin, steps = [], 0
        while eng.step(fin) and steps < 400:
            steps += 1
            if steps in (1, 2):
                eng.preempt(1)
        assert eng.stats["engine"]["preemptions"] >= 2
        assert len(fin) == len(prompts)
        assert eng.stats["decode_compiles"] == 0


class TestCancellation:
    def test_cancel_under_pool_pressure_unblocks_admit(self, setup):
        """A queued request blocked on an exhausted pool must be
        admitted the moment a resident's cancellation releases its
        pages — cancellation is load relief, not just early exit."""
        cfg, _ = setup
        prompts = _prompts(cfg, [17, 15, 9], seed=4)
        eng = _make(setup, num_pages=SMALL_POOL,
                    policy=SchedulingPolicy(preemption=False))
        for rid in range(2):    # two residents exhaust the 8-page pool
            eng.submit(Request(rid=rid, prompt=prompts[rid],
                               max_new_tokens=30))
        fin, steps = [], 0
        waiter_done_at = None
        while eng.step(fin) and steps < 400:
            steps += 1
            if steps == 1:
                eng.submit(Request(rid=2, prompt=prompts[2],
                                   max_new_tokens=4))
                assert not eng._can_admit(eng.queue[0])  # truly blocked
            if steps == 2:
                assert eng.cancel(0)    # release resident 0's pages
            if waiter_done_at is None and any(r.rid == 2 for r in fin):
                waiter_done_at = steps
        # Finished right after the cancel — decades before the 30-token
        # residents would have drained the pool on their own.
        assert waiter_done_at is not None and waiter_done_at <= 4
        by = {r.rid: r for r in fin}
        assert 2 in by and len(by[2].generated) == 4
        assert by[0].finish_reason == "cancelled"
        assert eng.stats["engine"]["cancelled"] == 1
        assert eng.cache.n_free_pages == eng.cache.num_pages
        assert eng.cache.reserved_total == 0

    def test_handle_cancel_resolves_and_keeps_tokens(self, setup):
        cfg, _ = setup
        prompts = _prompts(cfg, [9, 17, 15], seed=5)
        fe = ServeFrontend(_make(setup))
        hs = [fe.submit(p, 40) for p in prompts]
        # Wait for first delivery so the cancel lands mid-flight.
        t0 = time.time()
        while not hs[1].tokens and time.time() - t0 < 60:
            time.sleep(0.01)
        assert hs[1].cancel()
        done = {c.rid: c for c in fe.drain(timeout=120)}
        fe.shutdown()
        assert done[1].finish_reason == "cancelled"
        assert tuple(hs[1].tokens) == done[1].tokens  # delivered kept
        assert 1 <= len(done[1].tokens) < 40
        for rid in (0, 2):
            assert done[rid].finish_reason == "length"
            assert len(done[rid].tokens) == 40
        assert hs[1].cancel() is False        # already resolved
        assert fe.stats["engine"]["cancelled"] == 1


class TestDeadlines:
    def test_midflight_deadline_resolves_with_partial_stream(self, setup):
        cfg, _ = setup
        fe = ServeFrontend(_make(setup))
        h = fe.submit(_prompts(cfg, [9], seed=6)[0], 10_000, deadline=1.0)
        c = h.result(timeout=120)
        fe.shutdown()
        assert c.finish_reason == "deadline"
        assert 1 <= len(c.tokens) < 10_000

    def test_queued_deadline_expires_without_touching_engine(self, setup):
        """A deadline that lapses while the request is still queued
        resolves at intake — the engine never sees it."""
        cfg, _ = setup
        prompts = _prompts(cfg, [9] * 5, seed=7)
        eng = _make(setup)
        fe = ServeFrontend(eng)
        # Exhaust admission capacity so the dead-on-arrival submit is
        # deferred at intake rather than admitted.
        for p in prompts[:4]:
            fe.submit(p, 30)
        h = fe.submit(prompts[4], 5, deadline=1e-4)
        time.sleep(0.01)
        c = h.result(timeout=120)
        done = fe.drain(timeout=120)
        fe.shutdown()
        assert c.finish_reason == "deadline" and c.tokens == ()
        assert len(done) == 5
        assert eng.stats["engine"]["cancelled"] == 0

    def test_submit_validation(self, setup):
        fe = ServeFrontend(_make(setup))
        with pytest.raises(ValueError):
            fe.submit([1, 2], 4, deadline=0.0)
        with pytest.raises(ValueError):
            fe.submit([1, 2], 4, klass="realtime")
        fe.shutdown(drain=False)


class TestBackpressure:
    def test_rejection_then_clean_drain(self, setup):
        """Over-limit submits shed load with a typed, retryable error;
        everything actually accepted still serves to completion."""
        cfg, _ = setup
        prompts = _prompts(cfg, [9] * 12, seed=8)
        fe = ServeFrontend(_make(setup), max_queued=2)
        accepted, nrej = [], 0
        for p in prompts:
            try:
                accepted.append(fe.submit(p, 6))
            except RejectedError as e:
                nrej += 1
                assert e.retry_after > 0
        assert nrej >= 1
        done = fe.drain(timeout=120)
        m = fe.metrics()
        fe.shutdown()
        assert len(done) == len(accepted)
        assert all(c.finish_reason == "length" for c in done)
        assert m["rejected"] == nrej
        assert m["submitted"] == len(accepted)
        # Backlog cleared: a post-drain submit is accepted again.
        fe2 = ServeFrontend(_make(setup), max_queued=2)
        c = fe2.submit(prompts[0], 3).result(timeout=120)
        fe2.shutdown()
        assert len(c.tokens) == 3


class TestChaos:
    """The seeded fault-injection suite (CI pins ``REPRO_FAULT_SEED``;
    the nightly matrix sweeps it)."""

    def test_seeded_storm_resolves_everything_zero_leaks(self, setup,
                                                         reference):
        cfg, _ = setup
        lens = [9, 17, 15, 7, 8, 12]
        budgets = [12] * len(lens)
        prompts = _prompts(cfg, lens, seed=9)
        want = reference(prompts, budgets)

        plan = FaultPlan.random(FAULT_SEED, n_events=10, horizon=24)
        eng = _make(setup, num_pages=10)
        fe = ServeFrontend(eng, fault_plan=plan)
        hs = [fe.submit(p, b) for p, b in zip(prompts, budgets)]
        done = fe.drain(timeout=300)
        fe.shutdown()

        # 1. Every handle resolved, with a schema finish reason.
        assert len(done) == len(hs) and all(h.done for h in hs)
        for c in done:
            assert c.finish_reason in ("length", "cancelled", "deadline")
        # 2. Zero leaked storage of any kind.
        assert eng.cache.n_free == eng.max_batch
        assert eng.cache.n_free_pages == eng.cache.num_pages
        assert eng.cache.reserved_total == 0
        assert eng.cache.orphaned_pages == 0
        assert not eng._prefix_registry and not eng._page_key
        # 3. Survivors are token-identical to the unfaulted serve
        #    (lifecycle exits truncate by design; nothing else may).
        for c in done:
            if c.finish_reason == "length":
                assert c.tokens == want[c.rid], c.rid
            else:
                assert c.tokens == want[c.rid][:len(c.tokens)], c.rid
        # 4. The storm actually happened and was recorded.
        assert fe.metrics()["faults"] == len(fe.fault_log)
        assert fe.fault_log
        validate_stats(eng.stats)

    def test_handcrafted_storm_hits_every_fault_kind(self, setup,
                                                     reference):
        """A pinned plan exercising all seven kinds in one serve —
        including the straggler path into the PR-8 watchdog."""
        from repro.distributed.fault import StragglerWatchdog
        cfg, _ = setup
        lens = [9, 17, 15, 7, 8]
        budgets = [14] * len(lens)
        prompts = _prompts(cfg, lens, seed=10)
        want = reference(prompts, budgets)
        plan = FaultPlan(events=(
            FaultEvent(1, "exhaust_pages", 3),
            FaultEvent(2, "preempt", 2),
            FaultEvent(2, "raise_callback"),
            FaultEvent(3, "cancel"),
            FaultEvent(3, "straggler", 2),
            FaultEvent(4, "expire"),
            FaultEvent(5, "heal_pages"),
        ))
        eng = _make(setup, num_pages=12)
        wd = StragglerWatchdog(threshold=3.0)
        fe = ServeFrontend(eng, fault_plan=plan, watchdog=wd)
        errs = []
        # The plan's cycles assume all five arrive in one intake flush:
        # holding the engine lock keeps the scheduler from flushing
        # until every submit (which never takes that lock) has returned.
        with fe._mutex:
            hs = [fe.submit(p, b,
                            on_token=(lambda t: None) if i else None)
                  for i, (p, b) in enumerate(zip(prompts, budgets))]
        done = {c.rid: c for c in fe.drain(timeout=300)}
        fe.shutdown()
        fired = {k for _, k, n in fe.fault_log if n > 0}
        assert {"exhaust_pages", "preempt", "cancel", "expire",
                "straggler", "heal_pages", "raise_callback"} <= fired
        # The raising callback was quarantined on exactly one handle.
        assert sum(1 for h in hs
                   if isinstance(h.callback_error, RuntimeError)) == 1
        # The inflated window tripped the watchdog.
        assert len(wd.flagged) >= 1
        # Lifecycle exits happened; survivors identical; zero leaks.
        reasons = {c.finish_reason for c in done.values()}
        assert "cancelled" in reasons and "deadline" in reasons
        for rid, c in done.items():
            if c.finish_reason == "length":
                assert c.tokens == want[rid]
        assert eng.stats["engine"]["preemptions"] >= 2
        assert eng.cache.n_free_pages == eng.cache.num_pages
        assert eng.cache.reserved_total == 0

    def test_fault_plans_are_deterministic(self):
        a = FaultPlan.random(123, n_events=16, horizon=64)
        b = FaultPlan.random(123, n_events=16, horizon=64)
        assert a == b
        assert a != FaultPlan.random(124, n_events=16, horizon=64)
        # Every seizure has a later heal, so plans always drain.
        for ev in a.events:
            if ev.kind == "exhaust_pages":
                assert any(h.kind == "heal_pages" and h.step > ev.step
                           for h in a.events)
        assert a.events_at(a.horizon)
        with pytest.raises(ValueError):
            FaultEvent(0, "meteor_strike")


class TestPolicyUnit:
    """Pure policy-layer behavior (no engines, no jax)."""

    def _req(self, rid, klass=None, gen=0):
        r = Request(rid=rid, prompt=np.arange(4, dtype=np.int32),
                    max_new_tokens=8, klass=klass)
        r.generated = list(range(gen))
        return r

    def test_enqueue_orders_interactive_first(self):
        from collections import deque
        pol = SchedulingPolicy()
        q = deque()
        for rid, k in enumerate(["batch", "batch", "interactive",
                                 "batch", "interactive"]):
            pol.enqueue(q, self._req(rid, k))
        assert [r.rid for r in q] == [2, 4, 0, 1, 3]
        # FIFO within each class; policy off degrades to pure FIFO.
        q2 = deque()
        off = SchedulingPolicy(class_priority=False)
        for rid, k in enumerate(["batch", "interactive", "batch"]):
            off.enqueue(q2, self._req(rid, k))
        assert [r.rid for r in q2] == [0, 1, 2]

    def test_requeue_puts_victim_at_class_front(self):
        from collections import deque
        pol = SchedulingPolicy()
        q = deque()
        for rid, k in enumerate(["interactive", "batch", "batch"]):
            pol.enqueue(q, self._req(rid, k))
        pol.requeue(q, self._req(9, "batch", gen=3))
        assert [r.rid for r in q] == [0, 9, 1, 2]

    def test_choose_victim_least_progress_batch_only(self):
        pol = SchedulingPolicy()
        resident = [(0, self._req(0, "interactive", gen=1)),
                    (1, self._req(1, "batch", gen=5)),
                    (2, self._req(2, "batch", gen=2)),
                    (3, self._req(3, "batch", gen=2))]
        slot, req = pol.choose_victim(resident)
        assert (slot, req.rid) == (3, 3)   # least progress, ties high slot
        assert pol.choose_victim([resident[0]]) is None  # never interactive
        assert SchedulingPolicy(preemption=False).choose_victim(
            resident) is None

    def test_ladder_floor_covers_interactive(self, setup):
        cfg, _ = setup
        pol = SchedulingPolicy()
        # Interactive backlog lifts the rung to cover it, capped by the
        # admit budget; with no interactive there is no floor.
        base = pol.ladder_target(2, 0, cfg, 8)
        assert pol.ladder_target(2, 2, cfg, 8) >= 2
        # The floor never outruns the storage admit cap — a blocked
        # interactive admission goes through preemption, not the rung.
        assert pol.ladder_target(2, 2, cfg, 8, admit_cap=1) == 1
        off = SchedulingPolicy(class_priority=False)
        assert off.ladder_target(2, 2, cfg, 8) == base
