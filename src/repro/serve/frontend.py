"""Online request-lifecycle frontend: async intake over the ladder loop.

The engines' ``run()`` is an offline host loop — every request must be
queued up front, and results only exist when the whole queue drains.
This module adds the arrival-rate axis the ROADMAP's serving story
needs: an always-on service wrapping one engine, with

* **Thread-safe intake**: :meth:`ServeFrontend.submit` can be called
  from any thread at any time; it returns a :class:`RequestHandle`
  immediately (streaming token list, completion event, optional
  per-token callback) and parks the request on an intake queue.

* **Window-boundary scheduling**: a single scheduler thread owns the
  engine.  Each cycle it (1) admits arrivals up to the engine's free
  capacity, *coalescing same-bucket prompts into one batched
  multi-prompt prefill-insert per bucket*
  (:meth:`~repro.serve.slot_engine.SlotServeEngine.prefill_batch`) so a
  burst of k arrivals costs one ``(rung, bucket)`` prefill call instead
  of k, (2) drives one engine ``step()`` — it dispatches the next decode
  window and reads back the one before, so the chip holds a window
  while the host does the rest of the cycle — and (3) flushes every
  newly generated token onto a backlog queue.  A prefill dispatched in
  (1) queues behind at most the one window in flight.  The engine is
  never touched off this thread, so the engines stay single-threaded
  internally.

* **Two locks**: the *engine lock* (``_mutex``) is held by the
  scheduler through all engine work — each prefill, decode window,
  emit, reap, fault and remesh — and by :meth:`warmup` and
  :attr:`stats`, which read the engine.  The *bookkeeping lock*
  (``_books``) guards only frontend state: rid allocation, the handles,
  the per-request lock waits, filed cancels, completions, the rejection
  count and the number of requests waiting for admission.  It is never
  held across engine or device work, and no thread takes the engine
  lock while holding it.  :meth:`submit`, cancellation,
  :meth:`drain`, :meth:`metrics` and the emit thread take only the
  bookkeeping lock, so none of them waits out a decode window.

* **Detokenize/emit thread**: a second thread drains the backlog into
  per-request delivery — appending to the handle's token stream and
  invoking its callback in strict per-request order (tokens, then the
  :class:`~repro.serve.api.Completion`).  Decode windows never block on
  user callbacks.

* **Graceful drain/shutdown**: :meth:`drain` blocks until everything
  in flight has completed; :meth:`shutdown` drains (or aborts, when
  ``drain=False`` — inflight handles resolve with
  ``finish_reason="aborted"``) and joins both threads.

* **AOT warmup**: :meth:`warmup` pre-compiles every ``(rung, bucket)``
  prefill and decode-window entry point via the engine's
  :meth:`~repro.serve.slot_engine.SlotServeEngine.warmup`, so steady
  state serves with ``stats["decode_compiles"] == 0`` — the serving
  loop is exactly as compile-stable online as offline.

* **Fault recovery** (mesh-aware engines): pass a
  :class:`~repro.distributed.fault.StragglerWatchdog` and a
  ``device_probe`` callable and the scheduler times every decode window
  into the watchdog; a flagged straggler (and, cheaply, every cycle)
  re-probes the device set, and a shrunk probe triggers
  :func:`~repro.distributed.fault.plan_elastic_mesh` + the engine's
  ``remesh()``: victims are released back to the queue and re-prefilled
  on the rebuilt mesh instead of crashing the serve.  Greedy decoding
  regenerates the identical prefix, so the emit dedup
  (``req.generated[n:]``) resumes every interrupted stream seamlessly.

Token identity: the slot/paged engines' rows are batch-invariant and
their batched prefill is bitwise the single-prompt prefill per row, so
the frontend's reordered, coalesced admission produces exactly the
tokens of the offline ``run()`` on the same requests (pinned in
``tests/test_frontend.py``).  The sequential engine is served too, but
its mixed-length batches are not batch-invariant — no identity claim.

TTFT/TPOT here are *user-observed*: stamped at emission by the emit
thread (submission -> first delivered token; mean gap thereafter), not
at the engine's internal prefill, so queueing delay under load is part
of the number — that is the point of the Poisson rows in
``benchmarks/serve_bench.py``.  Every stamp is on
:func:`repro.serve.api.now`; each request's handle carries its
lifecycle (:class:`RequestHandle`), and the service's spans go to the
profiler's host tracer (:mod:`repro.serve.spans`).
"""
from __future__ import annotations

import copy
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.serve.api import (Completion, FINISH_ABORTED, FINISH_CANCELLED,
                             FINISH_DEADLINE, FINISH_LENGTH, FINISH_MAX_SEQ,
                             now)
from repro.serve.engine import Request
from repro.serve.policy import KLASSES, RejectedError
from repro.serve.spans import span

_SHUTDOWN = object()


class _Done:
    """Backlog sentinel: all of ``req``'s tokens precede it in the
    backlog, so delivery order per request is tokens-then-completion.
    ``reason`` pins a lifecycle exit (abort/cancel/deadline); ``None``
    means a natural finish, classified by budget accounting."""

    def __init__(self, req: Request, aborted: bool = False,
                 reason: Optional[str] = None):
        self.req = req
        self.reason = reason or (FINISH_ABORTED if aborted else None)


class RequestHandle:
    """Streaming view of one in-flight request.

    ``tokens`` snapshots the delivered stream so far; ``result()``
    blocks for the :class:`~repro.serve.api.Completion`.  The
    ``on_token`` callback (if given) runs on the emit thread, once per
    token, in generation order; a raising callback never disturbs the
    serve (the exception is kept on ``callback_error``).

    Lifecycle stamps, seconds on :func:`repro.serve.api.now`, each set
    where the work happens (``None`` until then): ``submitted_at``
    (entry to ``submit``), ``queued_at`` (``submit`` hands the request
    to the scheduler), ``admitted_at`` (the scheduler takes it into a
    prefill call), ``first_token_at`` (its first token is on the host),
    ``first_emitted_at`` (its first token is delivered), ``done_at``
    (its completion is delivered); and ``lock_wait_s``, the time
    ``submit`` waited for the frontend's bookkeeping lock (never held
    across engine work, so this is not the wait for a decode window).
    """

    def __init__(self, rid: int, max_new_tokens: int,
                 on_token: Optional[Callable[[int], None]] = None):
        self.rid = rid
        self.max_new_tokens = max_new_tokens
        self.submitted_at = now()
        self.queued_at: Optional[float] = None
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.first_emitted_at: Optional[float] = None
        self.done_at: Optional[float] = None
        self.lock_wait_s = 0.0
        self.callback_error: Optional[BaseException] = None
        self._on_token = on_token
        self._tokens: List[int] = []
        self._lock = threading.Lock()
        self._done = threading.Event()
        self._completion: Optional[Completion] = None
        # Wired by ServeFrontend.submit; standalone handles can't cancel.
        self._cancel_cb: Optional[Callable[[int], None]] = None

    @property
    def tokens(self) -> List[int]:
        with self._lock:
            return list(self._tokens)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: Optional[float] = None) -> Completion:
        """Block until the request completes; returns its Completion."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.rid} still in flight")
        return self._completion

    def cancel(self) -> bool:
        """Request mid-flight cancellation: the scheduler releases the
        engine resources (slot/pages) at its next cycle and the handle
        resolves with ``finish_reason="cancelled"`` (tokens delivered so
        far are kept).  Returns False if already done (or the handle is
        not attached to a frontend); True once the cancel is filed —
        resolution is asynchronous, ``result()`` observes it."""
        if self._done.is_set() or self._cancel_cb is None:
            return False
        self._cancel_cb(self.rid)
        return True

    # Emit-thread side ---------------------------------------------------
    def _deliver(self, toks: Sequence[int]) -> None:
        for t in toks:
            if self.first_emitted_at is None:
                self.first_emitted_at = now()
            with self._lock:
                self._tokens.append(t)
            if self._on_token is not None:
                try:
                    self._on_token(t)
                except BaseException as e:  # noqa: B036 - user callback
                    self.callback_error = e
                    self._on_token = None

    def _finish(self, completion: Completion) -> None:
        self._completion = completion
        self._done.set()


class ServeFrontend:
    """Always-on serving service over one engine (see module docs).

    Threads start lazily at the first :meth:`submit` (or explicitly via
    :meth:`start`); the instance is a context manager whose exit drains
    and shuts down.  Only the scheduler thread ever touches the engine.
    It holds the engine lock ``_mutex`` through engine work; callers and
    the emit thread take only the bookkeeping lock ``_books``, which
    guards frontend state and is never held across engine work.  Lock
    order: ``_books`` may be taken while ``_mutex`` is held, never the
    reverse.  :meth:`metrics` can be read at any time without waiting on
    the engine; :attr:`stats` reads the engine, so it waits for the
    engine lock.
    """

    def __init__(self, engine, *, idle_wait: float = 0.002,
                 watchdog=None, device_probe=None, min_data: int = 1,
                 max_queued: Optional[int] = None, fault_plan=None):
        self.engine = engine
        self.idle_wait = idle_wait
        # Fault recovery (mesh-aware engines only): `watchdog` is a
        # StragglerWatchdog fed with per-window step times; `device_probe`
        # returns the currently-healthy device list (tests shrink a fake
        # set via repro.distributed.fault.simulate_failure).
        self.watchdog = watchdog
        self.device_probe = device_probe
        self.min_data = min_data
        self.remeshes = 0
        # Overload robustness: `max_queued` bounds the not-yet-admitted
        # backlog (over-limit submits raise RejectedError — typed load
        # shedding, never a silent drop); `fault_plan` is a
        # repro.serve.faults.FaultPlan injected at scheduler-cycle
        # granularity (chaos testing).
        self.max_queued = max_queued
        self.fault_plan = fault_plan
        self.fault_log: List[Tuple[int, str, int]] = []
        self._cycle = 0
        self._seized_pages: List[int] = []
        self._slow_next = 0.0          # straggler-fault dt inflation
        self._fault_cursor = -1        # last cycle whose faults fired
        self._healthy_n: Optional[int] = None
        self._step_idx = 0
        self._intake: "queue.Queue" = queue.Queue()
        self._backlog: "queue.Queue" = queue.Queue()
        self._mutex = threading.Lock()      # engine lock: engine work
        self._books = threading.Lock()      # bookkeeping lock (below)
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._abort = threading.Event()
        # rid -> (req, handle, n_emitted); scheduler thread only.
        self._tracked: Dict[int, List[Any]] = {}
        # Scheduler thread only, under the engine lock: seconds from
        # ``queued_at`` to ``admitted_at``, in the order first tokens came.
        self._admit_waits: List[float] = []
        # Under ``_books``: -------------------------------------------
        self.rejected = 0
        self._cancels: set = set()
        self._handles: List[RequestHandle] = []
        self._completions: List[Completion] = []
        self._next_rid = 0
        # Seconds each ``submit`` waited for ``_books``, one per request.
        self._lock_waits: List[float] = []
        # Requests accepted and not yet admitted, resolved or aborted:
        # in the intake queue, deferred, or in the scheduler's hands
        # mid-flush.  ``max_queued`` bounds it.
        self._waiting = 0
        # Admitted-capacity overflow (batch-class only — interactive
        # arrivals bypass the capacity cap so preemption can serve
        # them); changed by the scheduler thread only.
        self._deferred: List[Tuple[Request, RequestHandle]] = []
        self._started = False
        self._scheduler_t: Optional[threading.Thread] = None
        self._emitter_t: Optional[threading.Thread] = None
        self.coalesced_prefills = 0          # batched-prefill admissions

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "ServeFrontend":
        if self._started:
            return self
        with self._books:
            # Concurrent first submits must start one scheduler, not two.
            if self._started:
                return self
            self._started = True
            self._scheduler_t = threading.Thread(target=self._scheduler,
                                                 name="serve-scheduler",
                                                 daemon=True)
            self._emitter_t = threading.Thread(target=self._emitter,
                                               name="serve-emit",
                                               daemon=True)
            self._scheduler_t.start()
            self._emitter_t.start()
        return self

    def __enter__(self) -> "ServeFrontend":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=exc == (None, None, None))

    def warmup(self, max_prompt_len: Optional[int] = None,
               rungs: Optional[Sequence[int]] = None) -> None:
        """AOT-compile every serving entry point before taking load
        (engines without a ``warmup`` hook — the sequential engine —
        no-op; their compile stability is per batch shape)."""
        with self._mutex:
            if hasattr(self.engine, "warmup"):
                self.engine.warmup(max_prompt_len, rungs=rungs)

    def submit(self, prompt, max_new_tokens: int, *,
               rid: Optional[int] = None,
               on_token: Optional[Callable[[int], None]] = None,
               klass: Optional[str] = None,
               deadline: Optional[float] = None) -> RequestHandle:
        """Enqueue one request; returns its streaming handle at once.

        ``klass`` is the admission class (``"interactive"`` |
        ``"batch"``; ``None`` defers to the engine default) and
        ``deadline`` a per-request timeout in seconds from now — an
        expired request is released wherever it is (queued, deferred, or
        decoding) and resolves with ``finish_reason="deadline"``.  With
        ``max_queued`` set, a full backlog raises
        :class:`~repro.serve.policy.RejectedError` instead of queueing
        unboundedly.
        """
        submitted_at = now()
        with span("serve.submit") as sp:
            if self._stop.is_set():
                raise RuntimeError("frontend is shut down")
            if klass is not None and klass not in KLASSES:
                raise ValueError(f"klass={klass!r} not in {KLASSES}")
            if deadline is not None and deadline <= 0:
                raise ValueError(f"deadline={deadline} must be > 0 seconds")
            prompt = np.asarray(prompt, np.int32)
            self.start()
            t0 = now()
            with span("serve.submit.lock") as lsp:
                self._books.acquire()
                # With ``rid`` None, the id the request is about to take.
                lsp.set_metadata(rid=self._next_rid if rid is None else rid)
            waited = now() - t0
            try:
                if self.max_queued is not None \
                        and self._waiting >= self.max_queued:
                    self.rejected += 1
                    raise RejectedError(
                        f"intake full ({self._waiting} >= max_queued="
                        f"{self.max_queued})",
                        retry_after=max(4 * self.idle_wait,
                                        0.01 * self._waiting))
                if rid is None:
                    rid = self._next_rid
                self._next_rid = max(self._next_rid, rid) + 1
                handle = RequestHandle(rid, max_new_tokens, on_token)
                handle.submitted_at = submitted_at
                handle._cancel_cb = self._file_cancel
                handle.lock_wait_s = waited
                req = Request(rid=rid, prompt=prompt,
                              max_new_tokens=max_new_tokens,
                              arrived=submitted_at, klass=klass,
                              deadline=None if deadline is None
                              else submitted_at + deadline)
                self._lock_waits.append(waited)
                self._handles.append(handle)
                self._waiting += 1
                handle.queued_at = now()
                self._intake.put((req, handle))
            finally:
                self._books.release()
            sp.set_metadata(rid=rid)
            self._wake.set()
            return handle

    def _file_cancel(self, rid: int) -> None:
        """File a cancellation (any thread); the scheduler reaps it at
        its next cycle."""
        with self._books:
            self._cancels.add(rid)
        self._wake.set()

    def drain(self, timeout: Optional[float] = None) -> List[Completion]:
        """Block until every submitted request has completed; returns
        all completions so far in submission order."""
        deadline = None if timeout is None else now() + timeout
        with self._books:
            pending = list(self._handles)
        for h in pending:
            left = None if deadline is None else deadline - now()
            if not h._done.wait(left if left is None else max(left, 0)):
                raise TimeoutError(
                    f"drain timed out with request {h.rid} in flight")
        with self._books:
            return list(self._completions)

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop the service.  ``drain=True`` finishes inflight work
        first; ``drain=False`` aborts it (handles resolve with
        ``finish_reason="aborted"``).  Idempotent; joins both threads."""
        if self._started and drain and not self._stop.is_set():
            self.drain(timeout)
        if not drain:
            self._abort.set()
        self._stop.set()
        self._wake.set()
        if not self._started:
            return
        self._scheduler_t.join(timeout=30)
        self._backlog.put(_SHUTDOWN)
        self._emitter_t.join(timeout=30)

    # -- scheduler thread ------------------------------------------------
    def _free_capacity(self) -> int:
        eng = self.engine
        active = eng._n_active() if hasattr(eng, "_n_active") else 0
        inflight = active + len(eng.queue) + len(eng._backfilled)
        return max(eng.max_batch - inflight, 0)

    def _intake_flush(self) -> bool:
        """Admit arrivals up to the engine's free capacity, coalescing
        same-bucket prompts into one batched prefill-insert each.

        Interactive arrivals bypass the capacity cap — under saturation
        they must reach the engine's queue, where the scheduling policy
        admits them (preempting batch work if the pool is full); batch
        arrivals beyond capacity defer to a later cycle.  Entries
        cancelled or deadline-expired before admission resolve here
        without ever touching the engine.
        """
        with span("serve.intake") as sp:
            eng = self.engine
            with self._mutex:
                cap = self._free_capacity()
            with self._books:
                pending = self._deferred
                self._deferred = []
                cancels = set(self._cancels)
                while True:
                    try:
                        pending.append(self._intake.get_nowait())
                    except queue.Empty:
                        break
            if not pending:
                sp.set_metadata(admitted=0, deferred=0)
                return False
            policy = getattr(eng, "policy", None)
            t = now()
            admit: List[Tuple[Request, RequestHandle]] = []
            defer: List[Tuple[Request, RequestHandle]] = []
            resolved: List[Tuple[Request, RequestHandle, str]] = []
            n_batch = 0
            for req, handle in pending:
                if req.klass is None:
                    # prefill_batch skips engine.submit(), so the engine
                    # default class is stamped here.
                    req.klass = getattr(eng, "default_klass", None)
                if req.rid in cancels:
                    resolved.append((req, handle, FINISH_CANCELLED))
                elif req.deadline is not None and t >= req.deadline:
                    resolved.append((req, handle, FINISH_DEADLINE))
                elif policy is not None and policy.class_priority \
                        and policy.is_interactive(req):
                    admit.append((req, handle))
                elif n_batch < cap:
                    admit.append((req, handle))
                    n_batch += 1
                else:
                    defer.append((req, handle))
            sp.set_metadata(admitted=len(admit), deferred=len(defer))
            with self._books:
                for req, handle, reason in resolved:
                    req.done = True
                    req.finish_reason = reason
                    self._cancels.discard(req.rid)
                    self._backlog.put((handle, _Done(req, reason=reason)))
                self._deferred = defer + self._deferred
                self._waiting -= len(admit) + len(resolved)
            if admit:
                with self._mutex:
                    for req, handle in admit:
                        self._tracked[req.rid] = [req, handle, 0]
                    if hasattr(eng, "prefill_batch"):
                        # Same-bucket arrivals prefill as one batched call;
                        # the rows park decode-ready in the engine's
                        # backfill queue and the next window admits them in
                        # policy order.
                        key = (lambda item:
                               eng._bucket_len(len(item[0].prompt))
                               or len(item[0].prompt))
                        ordered = sorted(admit, key=key)
                        eng.prefill_batch([req for req, _ in ordered])
                        self.coalesced_prefills += 1
                    else:
                        for req, _ in admit:
                            eng.submit(req)
                    # The engines' submit() stamps arrival at queue time;
                    # restore the true submission stamps.
                    for req, handle in admit:
                        req.arrived = handle.submitted_at
                    self._emit_new()
            return bool(admit) or bool(resolved)

    def _emit_new(self) -> None:
        """Push every not-yet-emitted token to the backlog (called with
        the engine lock held, scheduler thread only).  A request's first
        push copies its engine stamps to its handle."""
        with span("serve.emit") as sp:
            pushed = 0
            finished: List[int] = []
            for rid in list(self._tracked):
                req, handle, n = self._tracked[rid]
                fresh = req.generated[n:]
                if fresh:
                    if handle.first_token_at is None:
                        self._copy_engine_stamps(req, handle)
                    self._backlog.put((handle, list(fresh)))
                    self._tracked[rid][2] = n + len(fresh)
                    pushed += len(fresh)
                if req.done:
                    self._backlog.put((handle, _Done(req)))
                    finished.append(rid)
                    del self._tracked[rid]
            if finished:
                with self._books:
                    self._cancels.difference_update(finished)
            sp.set_metadata(tokens=pushed)

    def _copy_engine_stamps(self, req: Request,
                            handle: RequestHandle) -> None:
        handle.admitted_at = req.admitted_at
        handle.first_token_at = req.first_token_at
        if req.admitted_at is not None:
            self._admit_waits.append(req.admitted_at - handle.queued_at)

    def _reap(self) -> int:
        """Resolve filed cancellations and expired deadlines for admitted
        requests (engine lock held): the engine releases the slot/pages,
        any already-generated tokens flush, the handle resolves with the
        lifecycle reason.  Pre-admission entries resolve at intake flush
        instead.  Returns the number of requests reaped."""
        t = now()
        victims: List[Tuple[int, str]] = []
        with self._books:
            for rid, (req, _handle, _n) in self._tracked.items():
                if rid in self._cancels:
                    victims.append((rid, FINISH_CANCELLED))
                elif req.deadline is not None and t >= req.deadline:
                    victims.append((rid, FINISH_DEADLINE))
            self._cancels.difference_update(rid for rid, _ in victims)
        for rid, reason in victims:
            req, handle, n = self._tracked.pop(rid)
            if hasattr(self.engine, "cancel"):
                self.engine.cancel(rid)
            req.done = True
            req.finish_reason = reason
            fresh = req.generated[n:]
            if fresh:
                self._backlog.put((handle, list(fresh)))
            self._backlog.put((handle, _Done(req, reason=reason)))
        return len(victims)

    def _apply_faults(self) -> None:
        """Fire this cycle's scheduled fault events (engine lock held).
        The cursor makes each cycle's events one-shot: the fault clock
        only advances on productive cycles, and idle scheduler spins must
        not replay the current cycle's storm."""
        if self.fault_plan is None or self._cycle == self._fault_cursor:
            return
        self._fault_cursor = self._cycle
        events = self.fault_plan.events_at(self._cycle)
        if events:
            self._drain_engine("fault")
        for ev in events:
            self._apply_fault(ev)

    def _drain_engine(self, reason: str) -> None:
        """Read back the engine's window in flight (engine lock held);
        engines without a window pipeline have none."""
        drain = getattr(self.engine, "drain_pipeline", None)
        if drain is not None:
            drain(reason)

    def _apply_fault(self, ev) -> None:
        eng = self.engine
        did = 0
        if ev.kind == "exhaust_pages":
            cache = getattr(eng, "cache", None)
            if hasattr(cache, "seize_pages"):
                seized = cache.seize_pages(ev.arg)
                self._seized_pages.extend(seized)
                did = len(seized)
        elif ev.kind == "heal_pages":
            cache = getattr(eng, "cache", None)
            if self._seized_pages and hasattr(cache, "restore_pages"):
                did = len(self._seized_pages)
                cache.restore_pages(self._seized_pages)
                self._seized_pages = []
        elif ev.kind == "preempt":
            if hasattr(eng, "preempt"):
                did = eng.preempt(ev.arg)
        elif ev.kind == "straggler":
            # Surfaces at the next consumed window as an inflated step
            # time fed to the watchdog (the PR-8 straggler path).
            self._slow_next += 10.0 * ev.arg
            did = ev.arg
        elif ev.kind in ("cancel", "expire"):
            if self._tracked:
                rid = min(self._tracked)
                if ev.kind == "cancel":
                    with self._books:
                        self._cancels.add(rid)
                else:
                    self._tracked[rid][0].deadline = now()
                did = 1
        elif ev.kind == "raise_callback":
            if self._tracked:
                rid = min(self._tracked)
                handle = self._tracked[rid][1]

                def _boom(_tok, _rid=rid):
                    raise RuntimeError(
                        f"injected callback fault (rid {_rid})")
                handle._on_token = _boom
                did = 1
        self.fault_log.append((self._cycle, ev.kind, did))

    def _scheduler(self) -> None:
        finished: List[Request] = []
        while True:
            if self._abort.is_set():
                break
            with span("serve.cycle", cycle=self._cycle):
                moved = self._intake_flush()
                with self._mutex:
                    self._apply_faults()
                    reaped = self._reap()
                    self._check_devices()
                    t0 = time.perf_counter()
                    consumed = self.engine.step(finished)
                    dt = time.perf_counter() - t0
                    if self.watchdog is not None and consumed:
                        dt += self._slow_next
                        self._slow_next = 0.0
                        if self.watchdog.observe(self._step_idx, dt):
                            # A stalled window is how a lost shard shows up
                            # from inside the host loop — re-probe at once.
                            self._check_devices()
                        self._step_idx += 1
                    self._emit_new()
                    finished.clear()
                    if consumed or moved or reaped:
                        # The fault clock ticks on productive cycles only,
                        # so a plan replays identically regardless of how
                        # long the scheduler idles between work.
                        self._cycle += 1
                if self._stop.is_set() and not consumed and not moved \
                        and not reaped and self._intake.empty() \
                        and not self._deferred:
                    break
                if not moved and not consumed and not reaped:
                    with span("serve.idle"):
                        self._wake.wait(self.idle_wait)
                    self._wake.clear()
        with self._mutex:
            self._drain_engine("shutdown")
            self._heal_seized()
        if self._abort.is_set():
            self._abort_inflight()

    def _heal_seized(self) -> None:
        """Return any still-seized pages at scheduler exit (engine lock
        held): the injector ghosts pool capacity, it never leaks it — a
        plan whose ``heal_pages`` cycle was never reached must not leave
        the pool short after shutdown."""
        if not self._seized_pages:
            return
        cache = getattr(self.engine, "cache", None)
        if hasattr(cache, "restore_pages"):
            self.fault_log.append(
                (self._cycle, "heal_pages", len(self._seized_pages)))
            cache.restore_pages(self._seized_pages)
            self._seized_pages = []

    # -- fault recovery --------------------------------------------------
    def _check_devices(self) -> None:
        """Probe device health (engine lock held, scheduler thread
        only); a shrunk probe triggers elastic recovery."""
        if self.device_probe is None:
            return
        healthy = list(self.device_probe())
        if self._healthy_n is not None and len(healthy) < self._healthy_n:
            self._recover(healthy)
        self._healthy_n = len(healthy)

    def _recover(self, healthy) -> None:
        """Rebuild the engine's mesh on the surviving devices and release
        the victims for re-prefill (engine lock held).

        The model axis is kept when it still fits and halved otherwise
        (param sharding must stay divisible); the data axis absorbs the
        rest.  Interrupted requests keep their handles: ``remesh()``
        clears their generated streams and greedy decoding regenerates
        the same prefix, so ``_emit_new``'s per-request counters skip the
        already-delivered tokens automatically.
        """
        from repro.distributed.fault import plan_elastic_mesh
        eng = self.engine
        if getattr(eng, "mesh", None) is None or not hasattr(eng, "remesh"):
            return
        mp = eng.mesh.shape.get("model", 1)
        plan = None
        while mp >= 1:
            plan = plan_elastic_mesh(len(healthy), model_parallel=mp,
                                     min_data=self.min_data)
            if plan is not None:
                break
            mp //= 2
        if plan is None:
            return      # nothing serveable left; keep limping, don't crash
        from jax.sharding import Mesh
        d, mp = plan
        mesh = Mesh(np.asarray(healthy[:d * mp]).reshape(d, mp),
                    ("data", "model"))
        eng.remesh(mesh)
        self.remeshes += 1

    def _abort_inflight(self) -> None:
        with self._mutex:
            leftovers = list(self._tracked.values())
            self._tracked.clear()
        with self._books:
            leftovers.extend([req, handle, 0]
                             for req, handle in self._deferred)
            self._deferred = []
            while True:
                try:
                    req, handle = self._intake.get_nowait()
                except queue.Empty:
                    break
                leftovers.append([req, handle, 0])
            self._waiting = 0
        for req, handle, _n in leftovers:
            self._backlog.put((handle, _Done(req, aborted=True)))

    # -- emit thread -----------------------------------------------------
    def _emitter(self) -> None:
        while True:
            item = self._backlog.get()
            if item is _SHUTDOWN:
                break
            handle, payload = item
            with span("serve.deliver", rid=handle.rid):
                if isinstance(payload, _Done):
                    completion = self._completion_for(payload, handle)
                    with self._books:
                        self._completions.append(completion)
                    handle._finish(completion)
                else:
                    handle._deliver(payload)

    def _completion_for(self, done: _Done, handle: RequestHandle
                        ) -> Completion:
        req = done.req
        n = len(req.generated)
        first = (handle.submitted_at if handle.first_emitted_at is None
                 else handle.first_emitted_at)
        handle.done_at = now()
        reason = done.reason or getattr(req, "finish_reason", None)
        if reason is None:
            reason = (FINISH_LENGTH if n >= req.max_new_tokens
                      else FINISH_MAX_SEQ)
        return Completion(
            rid=req.rid, tokens=tuple(req.generated),
            ttft=max(0.0, first - handle.submitted_at),
            tpot=(max(0.0, (handle.done_at - first) / (n - 1)) if n > 1
                  else 0.0),
            finish_reason=reason)

    # -- observability ---------------------------------------------------
    @property
    def stats(self) -> Dict[str, Any]:
        """Snapshot of the wrapped engine's stats (shared schema), with
        the frontend's own per-request counters beside the engine's
        extras under ``stats["engine"]["frontend"]``: ``submit_lock_s``
        (each request's ``lock_wait_s``, in submission order) and
        ``admit_wait_s`` (``admitted_at - queued_at``, in the order the
        first tokens came).  Reading the engine, it waits for the engine
        lock."""
        with self._mutex:
            out = copy.deepcopy(self.engine.stats)
            with self._books:
                lock_waits = list(self._lock_waits)
            out["engine"]["frontend"] = {
                "submit_lock_s": lock_waits,
                "admit_wait_s": list(self._admit_waits)}
            return out

    def metrics(self) -> Dict[str, Any]:
        """Frontend-level service metrics (user-observed latency); takes
        only the bookkeeping lock, so it never waits out engine work."""
        with self._books:
            comps = list(self._completions)
            return {
                "submitted": len(self._handles),
                "completed": len(comps),
                "inflight": len(self._handles) - len(comps),
                "coalesced_prefills": self.coalesced_prefills,
                "remeshes": self.remeshes,
                "rejected": self.rejected,
                "deferred": len(self._deferred),
                "faults": len(self.fault_log),
                "finish_reasons": {
                    r: sum(1 for c in comps if c.finish_reason == r)
                    for r in sorted({c.finish_reason for c in comps})},
                "stragglers": (len(self.watchdog.flagged)
                               if self.watchdog is not None else 0),
                "ttft": [c.ttft for c in comps],
                "tpot": [c.tpot for c in comps if c.n_tokens > 1],
            }
