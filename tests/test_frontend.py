"""Online request-lifecycle frontend (``repro.serve.frontend``).

The headline claim is *serving-path transparency*: the frontend's
asynchronous intake, coalesced batched prefills, and window-boundary
scheduling must emit exactly the tokens of the offline ``run()`` on the
same requests — and after :meth:`ServeFrontend.warmup`, serve them with
zero decode compiles.  The supporting contracts: per-request streaming
order (tokens in generation order, then the Completion), drain blocking
on inflight work, abortive shutdown resolving every handle, and the
batched multi-prompt prefill being bitwise the single-prompt prefill
per row (the invariant the identity claim stands on).  The frontend's
locking: a caller's ``submit`` and ``cancel`` and the emit thread's
delivery never wait for the scheduler's decode window, and concurrent
submitters get unique rids and the offline tokens.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import init_params
from repro.serve import (make_engine, RejectedError, Request, ServeFrontend,
                         validate_stats)
from repro.serve.api import now

MAX_SLOTS = 4
MAX_SEQ = 64
WINDOW = 4


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("yi-6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _make(setup, kind="slot", **kw):
    cfg, params = setup
    return make_engine(cfg, params, kind=kind, max_slots=MAX_SLOTS,
                       max_seq=MAX_SEQ, window=WINDOW, **kw)


def _workload(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=s).astype(np.int32)
            for s in lens]


def _offline(setup, prompts, budgets, kind="slot", **kw):
    eng = _make(setup, kind=kind, **kw)
    for i, (p, b) in enumerate(zip(prompts, budgets)):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=b))
    return {c.rid: c.tokens for c in eng.run(max_steps=4096)}


class _GatedEngine:
    """A stub engine whose ``step()`` blocks on ``gate`` while a request
    with a rid in ``hold`` is active, so the frontend's scheduler sits
    inside a decode window holding the engine lock.  Each step gives
    every active request one token (its rid)."""

    max_batch = MAX_SLOTS

    def __init__(self, hold):
        self.hold = set(hold)
        self.gate = threading.Event()
        self.in_step = threading.Event()
        self.queue, self._backfilled, self.active = [], [], []

    def _n_active(self):
        return len(self.active)

    def submit(self, req):
        req.admitted_at = now()
        self.queue.append(req)

    def cancel(self, rid):
        self.active = [r for r in self.active if r.rid != rid]

    def step(self, finished):
        self.active += self.queue
        self.queue = []
        if not self.active:
            return 0
        if self.hold & {r.rid for r in self.active}:
            self.in_step.set()
            self.gate.wait(30)
        for r in self.active:
            if r.first_token_at is None:
                r.first_token_at = now()
            r.generated.append(r.rid)
            r.done = len(r.generated) >= r.max_new_tokens
        self.active = [r for r in self.active if not r.done]
        return 1


class TestLocking:
    def test_submit_cancel_and_delivery_never_wait_on_a_step(self):
        """While the scheduler is inside a step: a completion already in
        the backlog is delivered, ``submit`` returns at once with a
        near-zero lock wait, ``max_queued`` still counts what waits, and
        ``cancel`` returns.  Released, every request resolves."""
        eng = _GatedEngine(hold={1})
        fe = ServeFrontend(eng, max_queued=2)
        a_token = threading.Event()

        def a_cb(_tok):
            # Holds the emit thread until the step blocks, so that A's
            # completion waits in the backlog behind this token.
            a_token.set()
            eng.in_step.wait(30)

        try:
            a = fe.submit([1, 2, 3], 1, on_token=a_cb)          # rid 0
            assert a_token.wait(30)
            fe.submit([4, 5], 2)                 # rid 1: its step blocks
            assert eng.in_step.wait(30)
            assert a.result(timeout=5).finish_reason == "length"
            waiting = []
            for prompt in ([6], [7]):                          # rids 2, 3
                t0 = time.perf_counter()
                waiting.append(fe.submit(prompt, 1))
                assert time.perf_counter() - t0 < 0.05
            assert all(h.lock_wait_s < 0.01 for h in waiting)
            with pytest.raises(RejectedError):
                fe.submit([8], 1)                # two wait for admission
            assert waiting[1].cancel()
            assert not eng.gate.is_set()         # all inside the one step
        finally:
            eng.gate.set()
        done = fe.drain(timeout=30)
        fe.shutdown()
        assert {c.rid: c.finish_reason for c in done} == {
            0: "length", 1: "length", 2: "length", 3: "cancelled"}
        assert {c.rid: c.tokens for c in done}[1] == (1, 1)
        assert fe.metrics()["rejected"] == 1


class TestLifecycle:
    def test_out_of_order_arrivals_match_offline(self, setup):
        """Mixed bucket lengths submitted online — intake coalescing
        sorts and batches them, yet every stream equals the offline
        serve of the same requests in the same rid order."""
        cfg, _ = setup
        lens = [5, 17, 9, 3, 23, 8]
        budgets = [4, 2, 6, 3, 5, 4]
        prompts = _workload(cfg, lens, seed=1)
        want = _offline(setup, prompts, budgets)

        fe = ServeFrontend(_make(setup))
        handles = [fe.submit(p, b) for p, b in zip(prompts, budgets)]
        done = fe.drain(timeout=120)
        fe.shutdown()
        assert {c.rid: c.tokens for c in done} == want
        # Handles stream the same tokens their completions report.
        for h, c in zip(handles, sorted(done, key=lambda c: c.rid)):
            assert h.rid == c.rid
            assert tuple(h.tokens) == c.tokens
            assert h.done and h.result(timeout=1) == c
            assert c.finish_reason == "length"
        validate_stats(fe.stats)

    @pytest.mark.parametrize("lifecycle", ["complete", "cancel_and_deadline"])
    def test_concurrent_submitters_match_offline(self, setup, lifecycle):
        """Eight threads submit at once (in one case cancelling some
        requests and giving others a 0.1 ms deadline): every rid is
        unique, every handle resolves, and every stream is the offline
        serve's, or a prefix of it where a cancel or deadline cut it."""
        cfg, _ = setup
        n_threads, per_thread = 8, 2
        n = n_threads * per_thread
        rng = np.random.default_rng(17)
        lens = [int(x) for x in rng.integers(3, 24, size=n)]
        budgets = [int(b) for b in rng.integers(2, 7, size=n)]
        prompts = _workload(cfg, lens, seed=17)
        want = _offline(setup, prompts, budgets, kind="paged", page_size=8)
        cut = lifecycle == "cancel_and_deadline"

        fe = ServeFrontend(_make(setup, kind="paged", page_size=8))
        barrier = threading.Barrier(n_threads)
        handles, errors = {}, []

        def client(t):
            try:
                barrier.wait(timeout=30)
                for j in range(t * per_thread, (t + 1) * per_thread):
                    h = fe.submit(prompts[j], budgets[j],
                                  deadline=1e-4 if cut and j % 4 == 1
                                  else None)
                    if cut and j % 4 == 3:
                        h.cancel()
                    handles[j] = h
            except BaseException as e:  # noqa: B036 - reported below
                errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        done = fe.drain(timeout=120)
        fe.shutdown()
        assert not errors
        assert len(handles) == len(done) == n
        rids = sorted(h.rid for h in handles.values())
        assert rids == sorted(c.rid for c in done) == list(range(n))
        reasons = set()
        for j, h in handles.items():
            c = h.result(timeout=1)
            reasons.add(c.finish_reason)
            assert tuple(h.tokens) == c.tokens
            if c.finish_reason == "length":
                assert c.tokens == want[j]
            else:
                assert cut and c.finish_reason == (
                    "deadline" if j % 4 == 1 else "cancelled")
                assert c.tokens == want[j][:len(c.tokens)]
        assert (reasons != {"length"}) == cut

    def test_paged_engine_served_identically(self, setup):
        cfg, _ = setup
        prompts = _workload(cfg, [7, 8, 9, 16, 12], seed=3)
        budgets = [3, 5, 2, 4, 6]
        want = _offline(setup, prompts, budgets, kind="paged",
                        page_size=8)
        with ServeFrontend(_make(setup, kind="paged", page_size=8)) as fe:
            for p, b in zip(prompts, budgets):
                fe.submit(p, b)
            done = fe.drain(timeout=120)
        assert {c.rid: c.tokens for c in done} == want

    def test_callback_ordering_per_request(self, setup):
        """on_token callbacks fire once per token in generation order,
        all before the completion resolves; a raising callback is
        quarantined on the handle without disturbing the serve."""
        cfg, _ = setup
        prompts = _workload(cfg, [6, 6, 11], seed=5)
        streams = {i: [] for i in range(3)}
        order_ok = {}
        # A token can arrive before the last submit returns; the callback
        # reads `handles`, so it waits until the list exists.
        submitted = threading.Event()

        def cb(rid):
            def _cb(tok):
                submitted.wait(timeout=60)
                if rid == 2:
                    raise RuntimeError("user callback exploded")
                streams[rid].append(tok)
                order_ok[rid] = not handles[rid].done
            return _cb

        fe = ServeFrontend(_make(setup))
        handles = [fe.submit(p, 5, on_token=cb(i))
                   for i, p in enumerate(prompts)]
        submitted.set()
        done = {c.rid: c for c in fe.drain(timeout=120)}
        fe.shutdown()
        for rid in (0, 1):
            assert tuple(streams[rid]) == done[rid].tokens
            assert order_ok[rid]            # tokens preceded completion
            assert handles[rid].callback_error is None
        # rid 2: first delivery raised; stream still completes intact.
        assert isinstance(handles[2].callback_error, RuntimeError)
        assert len(done[2].tokens) == 5

    def test_drain_blocks_on_inflight(self, setup):
        cfg, _ = setup
        prompts = _workload(cfg, [8, 8, 8, 8, 8, 8], seed=7)
        fe = ServeFrontend(_make(setup))
        for p in prompts:
            fe.submit(p, 12)
        done = fe.drain(timeout=120)        # called with work inflight
        assert len(done) == len(prompts)
        assert all(c.n_tokens == 12 for c in done)
        m = fe.metrics()
        assert m["completed"] == m["submitted"] == len(prompts)
        assert m["inflight"] == 0
        assert len(m["ttft"]) == len(prompts)
        assert all(t >= 0 for t in m["ttft"] + m["tpot"])
        fe.shutdown()

    def test_abortive_shutdown_resolves_handles(self, setup):
        cfg, _ = setup
        prompts = _workload(cfg, [8] * 6, seed=9)
        fe = ServeFrontend(_make(setup))
        handles = [fe.submit(p, 40) for p in prompts]
        fe.shutdown(drain=False)
        for h in handles:
            c = h.result(timeout=30)
            assert c.finish_reason in ("aborted", "length")
        assert any(h.result(timeout=0).finish_reason == "aborted"
                   for h in handles)
        with pytest.raises(RuntimeError):
            fe.submit(prompts[0], 1)


class TestWarmServing:
    def test_warmup_then_serve_zero_compiles(self, setup):
        """After AOT warmup the whole online path — coalesced batched
        prefills included — runs without a single decode compile."""
        cfg, _ = setup
        fe = ServeFrontend(_make(setup))
        fe.warmup(max_prompt_len=24)
        prompts = _workload(cfg, [5, 17, 9, 3, 23, 8, 16, 12], seed=11)
        for p in prompts:
            fe.submit(p, 6)
        done = fe.drain(timeout=120)
        stats = fe.stats
        fe.shutdown()
        assert len(done) == len(prompts)
        assert stats["decode_compiles"] == 0
        # Bursty arrivals really coalesced: some admission cycle batched
        # several same-bucket prompts into one prefill call.
        assert stats["engine"]["prefill_batched_reqs"] > 0
        assert fe.coalesced_prefills > 0

    def test_poisson_smoke_token_identical(self, setup):
        """Seeded Poisson arrivals (the serve_bench generator shape):
        whatever interleaving the arrival process produces, the streams
        equal the offline serve."""
        cfg, _ = setup
        rng = np.random.default_rng(13)
        lens = [int(x) for x in rng.integers(3, 24, size=8)]
        budgets = [int(b) for b in rng.integers(2, 7, size=8)]
        gaps = rng.exponential(scale=0.004, size=8)
        prompts = _workload(cfg, lens, seed=13)
        want = _offline(setup, prompts, budgets)

        fe = ServeFrontend(_make(setup))
        fe.warmup(max_prompt_len=24)
        stop = threading.Event()
        for p, b, g in zip(prompts, budgets, gaps):
            stop.wait(g)                   # Poisson inter-arrival gap
            fe.submit(p, b)
        done = fe.drain(timeout=120)
        stats = fe.stats
        fe.shutdown()
        assert {c.rid: c.tokens for c in done} == want
        assert stats["decode_compiles"] == 0


def _wait_tokens(handle, n, timeout=60.0):
    t0 = time.time()
    while len(handle.tokens) < n:
        assert time.time() - t0 < timeout, (handle.rid, handle.tokens)
        time.sleep(0.002)


class TestWindowPipeline:
    """The scheduler over the engine's two-deep window pipeline, counted
    in engine windows, not time, at one decode step per window (the
    default on an accelerator)."""

    def test_an_arrival_prefills_within_two_windows(self, setup):
        """A request submitted while a window is in flight has its
        prefill dispatched before at most two further decode windows,
        and its stream is the offline one."""
        cfg, params = setup
        prompts = _workload(cfg, [9, 6], seed=21)
        ref = make_engine(cfg, params, kind="paged", max_slots=MAX_SLOTS,
                          max_seq=256, window=8)
        for i, (p, n) in enumerate(zip(prompts, [120, 8])):
            ref.submit(Request(rid=i, prompt=p, max_new_tokens=n))
        want = {c.rid: c.tokens for c in ref.run(max_steps=4096)}
        eng = make_engine(cfg, params, kind="paged", max_slots=MAX_SLOTS,
                          max_seq=256, window=1)
        at_prefill = []
        prefill = eng.prefill_batch

        def counted(reqs):
            at_prefill.append(eng.stats["decode_steps"])
            prefill(reqs)

        eng.prefill_batch = counted
        fe = ServeFrontend(eng)
        a = fe.submit(prompts[0], 120)
        _wait_tokens(a, 5)
        assert eng._inflight is not None       # a window is in flight
        submitted_at = eng.stats["decode_steps"]
        b = fe.submit(prompts[1], 8)
        done = {c.rid: c.tokens for c in fe.drain(timeout=120)}
        fe.shutdown()
        assert done == want
        assert len(at_prefill) == 2
        assert at_prefill[1] - submitted_at <= 2
        assert b.admitted_at is not None

    def test_overlap_and_drain_counters_on_a_scripted_run(self, setup):
        """A joins an idle engine (nothing to drain), B joins it running
        (one drain), A is cancelled (one drain), B runs dry (one drain):
        three drains, and every other window overlapped the one before."""
        cfg, params = setup
        prompts = _workload(cfg, [9, 6], seed=22)
        eng = make_engine(cfg, params, kind="paged", max_slots=MAX_SLOTS,
                          max_seq=256, window=1)
        fe = ServeFrontend(eng)
        a = fe.submit(prompts[0], 200)
        _wait_tokens(a, 3)
        b = fe.submit(prompts[1], 30)
        _wait_tokens(b, 3)
        assert a.cancel()
        assert a.result(timeout=60).finish_reason == "cancelled"
        assert b.result(timeout=60).finish_reason == "length"
        stats = fe.stats
        fe.shutdown()
        ext = stats["engine"]
        assert (ext["slot_admits"], ext["cancelled"]) == (2, 1)
        assert ext["pipeline_drains"] == 3
        assert ext["windows_overlapped"] == ext["windows"] - 3
        assert ext["windows_overlapped"] > 0.8 * ext["windows"]


class TestBatchedPrefillIdentity:
    def test_batched_rows_bitwise_equal_single(self, setup):
        """The coalesced multi-prompt prefill is bitwise the
        single-prompt prefill per row — logits-derived first token and
        every parked cache leaf — so coalescing can never perturb a
        stream."""
        cfg, _ = setup
        prompts = _workload(cfg, [5, 7, 3], seed=15)
        reqs_b = [Request(rid=i, prompt=p, max_new_tokens=4)
                  for i, p in enumerate(prompts)]
        reqs_s = [Request(rid=i, prompt=p.copy(), max_new_tokens=4)
                  for i, p in enumerate(prompts)]

        batched = _make(setup)
        batched.prefill_batch(reqs_b)      # one (rung=4, bucket=8) call
        assert batched.stats["engine"]["prefill_batches"] == 1
        assert batched.stats["engine"]["prefill_batched_reqs"] == 3

        single = _make(setup)
        for r in reqs_s:
            single._backfill_one(r)

        assert len(batched._backfilled) == len(single._backfilled) == 3
        for (rb, cb, pb), (rs, cs, ps) in zip(batched._backfilled,
                                              single._backfilled):
            assert rb.generated == rs.generated   # argmax of row logits
            assert pb == ps
            leaves_b = jax.tree.leaves(cb)
            leaves_s = jax.tree.leaves(cs)
            assert len(leaves_b) == len(leaves_s)
            for lb, ls in zip(leaves_b, leaves_s):
                np.testing.assert_array_equal(np.asarray(lb),
                                              np.asarray(ls))
