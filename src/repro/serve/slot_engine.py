"""Slot-based serving fast path: ladder-locked decode with zero steady-state
recompiles and an on-device multi-token loop.

:class:`~repro.serve.engine.ServeEngine` proves the *policy* (§4.3 ladder
quantization, multi-tenant co-scheduling) but undoes the win at the system
level: every admission re-concatenates the KV cache, every batch shrink
re-jits ``decode_fn`` at a new batch size, and every token round-trips to
the host for the argmax.  This module rebuilds the decode hot path so the
serving loop is as ladder-shaped as the kernels:

* **Persistent slot cache** (:class:`SlotKVCache`): KV/recurrent caches
  live in fixed ``(layers, max_batch, max_seq, ...)`` buffers.  A request
  is *assigned* a slot at admission (one jitted donated
  ``dynamic_update_slice`` writes its prefilled cache in) and *releases*
  it when done — no per-step ``jnp.concatenate``, no gather-shrink.
  Slot reuse is safe because admission overwrites the slot's full
  sequence capacity.

* **Fixed-shape ladder decode**: the decode window always runs at a
  ``SLAB_LADDER`` rung (the smallest rung covering the highest live
  slot), with per-slot budgets masking holes and finished rows.  After
  one warmup compile per rung there are zero recompiles for the rest of
  the serve — ``stats["decode_compiles"]`` tracks the jit cache.

* **On-device decode window**: ``lax.scan`` over ``window`` tokens
  (:func:`default_window` unless given) with on-device greedy argmax,
  per-slot positions (short requests never attend past their own
  length — the legacy engine forced ``pos = max(positions)`` on every
  row), per-slot done flags, and donated cache buffers; co-exec
  prefill backfill runs at window boundaries.

* **Two-deep window pipeline**: each step dispatches window *j+1*
  straight from window *j*'s device outputs, then reads *j* back, so
  the chip holds the next window while the host posts the last one.
  The host mirrors of positions and budgets advance at dispatch by the
  scan body's own rule, so page mapping sees exactly what the window in
  flight will write.  Anything that changes the row set or storage
  (admission, preemption, cancellation, remesh, reset, injected
  faults) first *drains* the pipeline: it reads the window in flight
  back, and the next window starts from the host mirrors.  A row that
  finishes in *j* rides *j+1* frozen (budget 0) and releases its
  storage at *j*'s read-back.

* **Bucketed prefill**: prompts pad to power-of-two buckets
  (:func:`repro.serve.serve_step.make_bucketed_prefill_step`) so
  ``prefill_fn`` compiles once per bucket, not once per unique prompt
  length.  Bucketing is exact for *every* registry architecture: causal
  masking hides trailing pads from attention (per-slot decode masks keep
  their cache cells invisible), sliding-window layers lay buckets longer
  than their ring capacity via a rolled-ring gather at each row's real
  last token, recurrent (RG-LRU/RWKV) prefills freeze their carried
  state at the real last token, MoE routing masks pads out of the
  capacity cumsum with an exact dynamic threshold, and enc-dec decoder
  pads are causal like any other.  Buckets clamp to ``max_seq``; only
  prompts *longer* than the engine capacity fall back to exact-length
  prefill (counted separately as ``prefill_bucket_fallbacks``).

Token equivalence: in the slot engine, rows are fully independent — a
request's tokens equal its single-request serve regardless of batch
composition (tested against singleton serves in
``tests/test_slot_engine.py``).  On *uniform-length* workloads the
sequential engine computes the same thing, so the two are
token-identical (``tests/test_coexec.py``, with and without
``coexec_backend``).  On mixed-length batches the sequential engine is
the one that deviates from the singleton reference — it forces every
row to ``pos = max(positions)``, attending zero-K/V gap slots — which
is exactly the inefficiency per-slot positions remove; greedy argmax
still agrees on the tested workloads, but only the slot engine's
outputs are batch-invariant by construction.
"""
from __future__ import annotations

from collections import deque
from typing import (Any, Callable, Deque, List, NamedTuple, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import (cache_specs, param_specs, to_named)
from repro.serve.api import completion_of, Completion, FINISH_CANCELLED, now
from repro.serve.engine import (effective_tokens, encoder_inputs,
                                init_serve_stats, note_first_token,
                                record_step_packing, Request, SLAB_LADDER)
from repro.serve.policy import KLASS_BATCH, SchedulingPolicy
from repro.serve.serve_step import (make_bucketed_prefill_step,
                                    make_decode_step)
from repro.serve.spans import span

PyTree = Any

_MIN_BUCKET = 8


def jit_cache_entries(fn) -> Optional[int]:
    """Compiled-variant count of a jitted callable, or None.

    ``_cache_size`` is a private jax API; if a future jax drops it the
    compile-count *stats* degrade to None but serving keeps working
    (tests skip the exact-count assertions in that case; the engines'
    ``decode_compiles`` stat falls back to the trace counter below, so
    the bench-gate rows stay meaningful).
    """
    size = getattr(fn, "_cache_size", None)
    return size() if callable(size) else None


def default_window() -> int:
    """Decode steps per window when none is given: one on an
    accelerator, where a served model's decode step outlasts the host's
    work at a window boundary, so the window queued behind the running
    one keeps the device busy; eight on the CPU backend, where a step is
    far shorter than that work and a one-step window would leave the
    device waiting on the host for most of each step."""
    return 8 if jax.default_backend() == "cpu" else 1


def decode_body(step: Callable, vocab: int, max_seq: int):
    """One greedy decode step over ``(cache, toks, pos, budget)`` — the
    scan body of both engines' windows, around ``step(cache, toks, pos)
    -> (logits, cache)``.  :func:`advance_rows` is its host twin for
    ``pos``/``budget``; the two must agree."""

    def body(carry, _):
        c, tk, ps, bd = carry
        logits, c = step(c, tk[:, None], ps)
        nxt = jnp.argmax(logits[:, -1, :vocab], axis=-1).astype(jnp.int32)
        live = bd > 0
        emit = jnp.where(live, nxt, -1)
        tk = jnp.where(live, nxt, tk)
        ps = jnp.where(live, ps + 1, ps)
        bd = jnp.where(live, bd - 1, bd)
        bd = jnp.where(ps >= max_seq - 1, 0, bd)
        return (c, tk, ps, bd), emit

    return body


def advance_rows(pos: np.ndarray, budget: np.ndarray, steps: int,
                 max_seq: int) -> None:
    """Advance host ``pos``/``budget`` rows in place by ``steps`` decode
    steps, by the scan body's rule: a live row (budget > 0) moves one
    position and spends one token, and a row reaching ``max_seq - 1``
    stops."""
    for _ in range(steps):
        live = budget > 0
        pos += live
        budget -= live
        budget[pos >= max_seq - 1] = 0


class _Window(NamedTuple):
    """A decode window dispatched and not yet read back: its index and
    rung, its device outputs (``toks``/``pos``/``budget`` over every
    slot, ``out`` of shape ``(T, rung)``), and the host mirrors of
    ``pos``/``budget`` as they stand after it."""
    idx: int
    rung: int
    toks: Any
    pos: Any
    budget: Any
    out: Any
    pos_after: np.ndarray
    budget_after: np.ndarray


class SlotKVCache:
    """Fixed slot buffers + free list for the persistent serving cache.

    Buffers are allocated lazily from the first prefilled cache (so the
    structure matches whatever the model's prefill emits — attention KV,
    recurrent states, quantized caches) with the batch axis widened to
    ``max_slots``.  ``write`` is a single jitted donated update, so slot
    admission costs one dynamic-slice store, never a concatenate.
    """

    def __init__(self, max_slots: int, sharding_fn=None):
        self.max_slots = max_slots
        self.buffers: Optional[List[PyTree]] = None
        self._free = list(range(max_slots - 1, -1, -1))  # pop() -> lowest
        # Mesh-aware engines inject ``sharding_fn(tree) -> tree of
        # NamedSharding``; buffers are committed to those shardings at
        # allocation AND every jitted update re-constrains its output,
        # so the window jit always sees one stable input sharding (a
        # drift would change the compile key — one silent recompile per
        # window, exactly what the ladder exists to prevent).
        self._sharding_fn = sharding_fn
        donate = () if jax.default_backend() == "cpu" else (0,)

        def write_op(bufs, new, slot):
            out = jax.tree.map(
                lambda b, n: jax.lax.dynamic_update_slice_in_dim(
                    b, n, slot, axis=1), bufs, new)
            if sharding_fn is not None:
                out = jax.lax.with_sharding_constraint(out, sharding_fn(out))
            return out

        self._write = jax.jit(write_op, donate_argnums=donate)

    @property
    def n_free(self) -> int:
        return len(self._free)

    def acquire(self) -> int:
        """Claim the lowest free slot (keeps live slots packed at the
        front, so the ladder rung stays minimal)."""
        return self._free.pop()

    def release(self, slot: int) -> None:
        """Return a slot to the free list.  The stale cache content is
        left in place — the next admission overwrites the slot's full
        sequence capacity, so no tokens can leak across requests."""
        self._free.append(slot)
        self._free.sort(reverse=True)

    def reset(self) -> None:
        """Free every slot; allocated device buffers (and their stale
        content — overwritten at admission) are kept."""
        self._free = list(range(self.max_slots - 1, -1, -1))

    def resident_bytes(self) -> int:
        """Total bytes of the persistent cache storage (0 until the
        first admission shapes the buffers)."""
        if self.buffers is None:
            return 0
        return sum(x.nbytes for x in jax.tree.leaves(self.buffers))

    def write(self, prefill_cache: List[PyTree], slot: int) -> None:
        """Store a single-request prefilled cache into ``slot``."""
        if self.buffers is None:
            self.buffers = jax.tree.map(
                lambda x: jnp.zeros(
                    x.shape[:1] + (self.max_slots,) + x.shape[2:], x.dtype),
                prefill_cache)
            if self._sharding_fn is not None:
                self.buffers = jax.device_put(
                    self.buffers, self._sharding_fn(self.buffers))
        self.buffers = self._write(self.buffers, prefill_cache,
                                   jnp.int32(slot))


class SlotServeEngine:
    """Ladder-locked continuous batching over a persistent slot cache.

    Drop-in peer of :class:`~repro.serve.engine.ServeEngine` (same
    ``submit``/``run``/``stats`` surface, token-identical outputs) whose
    hot path is compile-stable: decode runs at fixed ``SLAB_LADDER``
    rungs over slot buffers, generating ``window`` tokens per program,
    with the next window queued on the device while the host reads the
    last one back (module docs).
    """

    def __init__(self, cfg: ModelConfig, params, *,
                 prefill_fn: Optional[Callable] = None,
                 decode_fn: Optional[Callable] = None,
                 cache_init_fn: Optional[Callable] = None,
                 max_batch: int = 8, max_seq: int = 256,
                 window: Optional[int] = None,
                 ladder: Optional[Sequence[int]] = None,
                 multi_tenant: bool = True,
                 prefill_bucketing: bool = True,
                 prefill_is_bucketed: Optional[bool] = None,
                 expert_backend: Optional[str] = None,
                 coexec_backend: Optional[str] = None,
                 mesh=None, policy: Optional[SchedulingPolicy] = None,
                 default_klass: str = KLASS_BATCH):
        del cache_init_fn  # slot buffers are shaped from the first prefill
        self.cfg = cfg
        self.policy = policy or SchedulingPolicy()
        self.default_klass = default_klass
        if mesh is not None and (prefill_fn is not None
                                 or decode_fn is not None):
            raise ValueError(
                "mesh-aware engines build their own sharded serve steps; "
                "injected prefill_fn/decode_fn cannot be re-sharded on "
                "remesh — drop them or drop mesh=")
        self.mesh = mesh
        # Host-side master copy: remesh() re-commits it to the surviving
        # devices, so recovery never reads back a sharded array that may
        # have lost a shard.
        self._host_params = params
        if mesh is not None:
            params = jax.device_put(
                params, to_named(param_specs(params, cfg, mesh, fsdp=False),
                                 mesh))
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.window = default_window() if window is None else window
        self.multi_tenant = multi_tenant
        self.stats = init_serve_stats(coexec_backend, expert_backend)
        self.stats["engine"].update(self._stats_extras())
        self.coexec_backend = coexec_backend
        self._expert_backend = expert_backend

        # Ladder rungs available at this engine's max_batch; decode only
        # ever compiles at these batch shapes.
        source = SLAB_LADDER if ladder is None else tuple(ladder)
        rungs = sorted({b for b in source if b <= max_batch}
                       | {max_batch})
        self.rungs: Tuple[int, ...] = tuple(rungs)

        # Bucketed prefill is exact for every layer family (module doc),
        # so the only gate left is an injected prefill_fn that cannot
        # take a last_index.
        if prefill_fn is None:
            self._bucket_enabled = prefill_bucketing
            self._prefill_needs_index = True
            self.prefill_fn = jax.jit(self._make_prefill_step())
        else:
            self.prefill_fn = prefill_fn
            self._prefill_needs_index = bool(prefill_is_bucketed)
            self._bucket_enabled = (prefill_bucketing
                                    and self._prefill_needs_index)
        # Buckets clamp to the engine capacity; sliding-window layers
        # whose ring is shorter than a bucket lay the last ring-capacity
        # tokens via the rolled-ring prefill layout, so the clamp no
        # longer shrinks to the window.
        self._bucket_cap = max_seq
        self._seen_buckets: set = set()

        # Batched multi-prompt prefill needs the builtin bucketed step
        # (vector last_index); injected prefill_fns opt in by setting
        # this attribute after construction.  MoE stays serial: routing
        # capacity couples batch rows, so a coalesced group would not be
        # row-identical to singleton prefills.
        self._batch_prefill = (self._bucket_enabled and prefill_fn is None
                               and cfg.moe is None)

        self.decode_fn = decode_fn or self._default_decode_fn()
        self._window_traces = 0     # re-trace count; see _build_window_fn
        # decode compiles reported relative to this base — warmup() sets
        # it to the post-warmup count so steady state reads 0.
        self._compile_base = 0
        self._window_fn = self._build_window_fn()

        self.cache = self._make_cache()
        # Per-slot host state (mirrors the device-side window carries).
        self._req: List[Optional[Request]] = [None] * max_batch
        # ``_pos``/``_budget`` run one window ahead of the last read-back:
        # they advance when a window is dispatched.  ``_tok`` is exact
        # whenever nothing is in flight, the only time it is read.
        self._tok = np.zeros(max_batch, np.int32)
        self._pos = np.zeros(max_batch, np.int32)
        self._budget = np.zeros(max_batch, np.int32)
        self._inflight: Optional[_Window] = None

        self.queue: Deque[Request] = deque()
        self._backfilled: Deque[Tuple[Request, Any, int]] = deque()
        # Finished (or cancelled) outside a step, awaiting delivery via
        # the next step()'s ``finished`` list (keeps run()'s
        # one-completion-per-request contract).
        self._finished: List[Request] = []

    # Subclass hooks (the paged engine swaps storage + decode step but
    # keeps the ladder/window/admission policy).
    def _stats_extras(self) -> dict:
        """Engine-specific keys, namespaced under ``stats["engine"]``
        (the top level is exactly the shared schema of
        ``repro.serve.api.STATS_KEYS``)."""
        return {
            "windows": 0, "rungs": [],
            "prefill_bucket_hits": 0, "prefill_bucket_misses": 0,
            "prefill_bucket_fallbacks": 0,
            "prefill_batches": 0, "prefill_batched_reqs": 0,
            "slot_admits": 0, "slot_releases": 0,
            "preemptions": 0, "cancelled": 0,
            "remeshes": 0,
            # Windows dispatched while another was in flight, and forced
            # read-backs of the window in flight (``drain_pipeline``).
            "windows_overlapped": 0, "pipeline_drains": 0,
        }

    def _prefill_cache_len(self) -> Optional[int]:
        """Sequence capacity of a single-request prefilled cache (the
        dense slot engine prefills straight into slot shape)."""
        return self.max_seq

    def _make_prefill_step(self):
        # batch_axes=() on a mesh: the prefill batch dim is a slot
        # group, not a data-parallel batch — rows stay replicated over
        # "data" and shard only activations/heads over "model".
        return make_bucketed_prefill_step(
            self.cfg, self.mesh, cache_len=self._prefill_cache_len(),
            batch_axes=())

    def _default_decode_fn(self):
        return make_decode_step(self.cfg, self.mesh, batch_axes=())

    def _make_cache(self):
        return SlotKVCache(self.max_batch, sharding_fn=self._sharding_fn())

    # ------------------------------------------------------------------
    # Mesh plumbing (no-ops on single-device engines)
    # ------------------------------------------------------------------
    def _sharding_fn(self):
        """``tree -> tree of NamedSharding`` from the canonical
        :func:`repro.distributed.sharding.cache_specs` rules, or None
        when single-device."""
        if self.mesh is None:
            return None

        def fn(tree):
            return to_named(cache_specs(tree, self.cfg, self.mesh,
                                        batch_axes=()), self.mesh)
        return fn

    def _constrain_caches(self, tree):
        """Pin a jitted window's cache outputs to the allocation-time
        shardings so input and output shardings agree across windows."""
        fn = self._sharding_fn()
        if fn is None:
            return tree
        return jax.lax.with_sharding_constraint(tree, fn(tree))

    def _row_sharding(self):
        """Replicated sharding of the per-slot ``toks``/``pos``/``budget``
        rows on a mesh (None when single-device).  A window fed from the
        host and one fed from the last window's outputs then share one
        input sharding, and so one compiled program."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P())

    def _rows_to_device(self, rows: np.ndarray):
        # A copy: the CPU backend may alias host memory, and the mirrors
        # advance in place while the window is still queued.
        rows = np.array(rows)
        sharding = self._row_sharding()
        if sharding is None:
            return jnp.asarray(rows)
        return jax.device_put(rows, sharding)

    def _constrain_rows(self, rows):
        sharding = self._row_sharding()
        if sharding is None:
            return rows
        return jax.lax.with_sharding_constraint(rows, sharding)

    def reset(self) -> None:
        """Clear all serving state for a fresh serve on the same engine.

        Jitted functions keep their compile caches and the cache keeps
        its device buffers, so a long-lived engine (or a fuzz harness
        running many workloads) compiles once per shape, not per serve.
        """
        self.drain_pipeline("reset")
        self.queue.clear()
        self._backfilled.clear()
        self._finished.clear()
        self._req = [None] * self.max_batch
        self._tok[:] = 0
        self._pos[:] = 0
        self._budget[:] = 0
        self.cache.reset()
        self.stats = init_serve_stats(self.coexec_backend,
                                      self._expert_backend)
        self.stats["engine"].update(self._stats_extras())

    def remesh(self, new_mesh) -> List[Request]:
        """Rebuild every device-side structure on ``new_mesh`` and
        re-queue the in-flight victims for re-prefill.

        The lost-shard recovery path (wired into
        :class:`repro.serve.frontend.ServeFrontend` via
        ``distributed/fault.py``): when the healthy device set shrinks,
        the old mesh's arrays are unusable, so every resident request is
        *released* — its generated tokens cleared, the request pushed
        back to the queue head in admission order — and params, serve
        steps, window jits, and cache storage are rebuilt against the
        survivors' mesh.  Greedy decode is deterministic, so each victim
        regenerates its identical token prefix and streams resume
        seamlessly (the frontend emits ``generated[n_emitted:]``, which
        simply stays empty until the re-serve passes the old
        watermark).  Returns the victims for observability.
        """
        if self.mesh is None:
            raise ValueError("remesh requires a mesh-aware engine "
                             "(construct with mesh=...)")
        self.drain_pipeline("remesh")
        victims: List[Request] = []
        for slot in range(self.max_batch):
            if self._req[slot] is not None:
                victims.append(self._req[slot])
                self._req[slot] = None
        victims.extend(req for req, _cache, _pos in self._backfilled)
        self._backfilled.clear()
        for req in victims:
            req.generated = []
            req.done = False
            req.finished_at = None
        for req in reversed(victims):
            self.queue.appendleft(req)
        self._tok[:] = 0
        self._pos[:] = 0
        self._budget[:] = 0

        self.mesh = new_mesh
        self.params = jax.device_put(
            self._host_params,
            to_named(param_specs(self._host_params, self.cfg, new_mesh,
                                 fsdp=False), new_mesh))
        self.prefill_fn = jax.jit(self._make_prefill_step())
        self._seen_buckets.clear()
        self.decode_fn = self._default_decode_fn()
        self._window_traces = 0
        self._compile_base = 0
        self._window_fn = self._build_window_fn()
        self.cache = self._make_cache()
        self.stats["engine"]["remeshes"] += 1
        return victims

    # ------------------------------------------------------------------
    # Jitted multi-token decode window
    # ------------------------------------------------------------------
    def _build_window_fn(self):
        decode_fn = self.decode_fn
        vocab = self.cfg.vocab_size
        max_seq = self.max_seq
        T = self.window

        def decode_window(params, caches, toks, pos, budget, *, rung):
            """T greedy tokens at batch shape ``rung``.

            toks/pos/budget: (max_batch,) int32 — last emitted token,
            next write position, and remaining token budget per slot;
            the window reads and advances the first ``rung`` rows and
            passes the rest through, so its outputs feed the next window
            at any rung.  Rows with budget <= 0 (holes, finished
            requests) stay frozen and emit -1; their attention output is
            computed but discarded, and their (deterministic,
            value-stable) cache writes land in slots that are either
            released or fully overwritten at the next admission.
            """
            # Executes at trace time only: a jax-version-proof compile
            # counter backing the jit-cache one (tracing == compiling
            # for a fresh (rung,) signature; cache hits skip the body).
            self._window_traces += 1
            sub = jax.tree.map(
                lambda x: jax.lax.slice_in_dim(x, 0, rung, axis=1), caches)
            rows = (toks[:rung], pos[:rung], budget[:rung])
            (sub, *rows), out = jax.lax.scan(
                decode_body(lambda c, tk, ps: decode_fn(params, c, tk, ps),
                            vocab, max_seq),
                (sub, *rows), None, length=T)
            caches = jax.tree.map(
                lambda full, s: jax.lax.dynamic_update_slice_in_dim(
                    full, s, 0, axis=1), caches, sub)
            caches = self._constrain_caches(caches)
            toks, pos, budget = self._constrain_rows(tuple(
                jax.lax.dynamic_update_slice_in_dim(full, r, 0, axis=0)
                for full, r in zip((toks, pos, budget), rows)))
            return caches, toks, pos, budget, out

        donate = () if jax.default_backend() == "cpu" else (1,)
        return jax.jit(decode_window, static_argnames=("rung",),
                       donate_argnums=donate)

    # ------------------------------------------------------------------
    # Prefill (bucketed) + admission
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Enqueue a request in admission-class order (interactive ahead
        of the first batch entry; FIFO within each class)."""
        req.arrived = now()
        if req.klass is None:
            req.klass = self.default_klass
        self.policy.enqueue(self.queue, req)

    def _bucket_len(self, s: int) -> Optional[int]:
        """Prefill shape bucket for an ``s``-token prompt, or None when
        the prompt exceeds the engine capacity (exact-length fallback —
        the ``prefill_bucket_fallbacks`` counter).  Buckets clamp to
        ``_bucket_cap`` so every servable prompt lands in a finite,
        warmup-enumerable bucket set."""
        if s > self._bucket_cap:
            return None
        b = _MIN_BUCKET
        while b < s:
            b *= 2
        return min(b, self._bucket_cap)

    def _prefill_one(self, req: Request):
        # A preempted request resumes by re-prefilling every token it
        # ever wrote (prompt + generated[:-1]) and re-entering decode at
        # the released position — token-identical to an unpreempted
        # serve (see repro.serve.engine.effective_tokens).  The first
        # token was already sampled and stamped, so resume skips both.
        toks = effective_tokens(req)
        resume = bool(req.generated)
        s = len(toks)
        b = self._bucket_len(s) if self._bucket_enabled else None
        with span("serve.prefill", rids=req.rid, rows=1, bucket=b or s):
            if req.admitted_at is None:
                req.admitted_at = now()
            if self._bucket_enabled:
                if b is not None:
                    if b in self._seen_buckets:
                        self.stats["engine"]["prefill_bucket_hits"] += 1
                    else:
                        self._seen_buckets.add(b)
                        self.stats["engine"]["prefill_bucket_misses"] += 1
                    padded = np.zeros(b, np.int32)
                    padded[:s] = toks
                    tokens = padded[None]
                else:
                    # Prompt exceeds the engine capacity: exact-length
                    # fallback, distinct from a first-seen bucket (misses
                    # compile once and then hit; fallbacks compile per
                    # unique length every time).
                    self.stats["engine"]["prefill_bucket_fallbacks"] += 1
                    tokens = np.asarray(toks[None], np.int32)
                batch = {"tokens": jnp.asarray(tokens),
                         "last_index": jnp.int32(s - 1)}
            else:
                batch = {"tokens": jnp.asarray(toks[None], jnp.int32)}
                if self._prefill_needs_index:
                    batch["last_index"] = jnp.int32(s - 1)
            enc = encoder_inputs(req, self.cfg)
            if enc is not None:
                batch["frontend_embeds"] = jnp.asarray(enc[None])
            logits, cache = self.prefill_fn(self.params, batch)
            if not resume:
                note_first_token(req, logits, self.cfg.vocab_size, self.stats)
        return cache, s

    def _backfill_one(self, req: Request) -> None:
        """One deferred (co-scheduled) prefill at a window boundary; the
        request parks decode-ready for the next admission."""
        cache, pos = self._prefill_one(req)
        self._backfilled.append((req, cache, pos))
        self.stats["backfilled"] += 1

    def _n_active(self) -> int:
        return sum(r is not None for r in self._req)

    def _admit_cap(self) -> Optional[int]:
        """Upper bound on resident requests (None = slots only).  The
        paged engine returns live rows + what the page pool can still
        reserve, so the ladder sweep can't target a rung the pool
        cannot back."""
        return None

    def _can_admit(self, req: Request) -> bool:
        """Storage-level admission check for the next candidate (the
        dense slot engine only needs a free slot, already guaranteed by
        the loop condition)."""
        return True

    def _store_cache(self, req: Request, cache, slot: int) -> None:
        """Move a single-request prefilled cache into persistent
        storage for ``slot``."""
        self.cache.write(cache, slot)

    def _admit(self) -> None:
        """Fill free slots up to the SISA ladder target.

        Backfilled requests are admitted first (their prefill already
        ran — re-running it would double-book its GEMMs against the
        ladder), then fresh queue requests are prefilled into slots.
        With ``class_priority`` an interactive head is admitted even
        past the ladder target (up to ``max_batch``), and with
        ``preemption`` a storage-blocked interactive admission evicts a
        batch-class resident (:meth:`_preempt_slot`) instead of
        stalling behind the pool — ``_admit_cap`` exhaustion degrades
        gracefully rather than walling off interactive traffic.

        A row joins only with no window in flight: the pipeline drains
        as a slot is taken.
        """
        waiting = [r for r, _, _ in self._backfilled] + list(self.queue)
        n_live = self._n_active() + len(waiting)
        if n_live == 0:
            return
        n_inter = sum(1 for r in waiting if self.policy.is_interactive(r))
        target = self.policy.ladder_target(
            n_live, n_inter, self.cfg, self.max_batch,
            admit_cap=self._admit_cap())
        self.stats["batches"].append(min(target, n_live))
        # Termination: every pass either admits (shrinks the waiting
        # set) or preempts (shrinks the batch-class residents), both
        # finite; the guard is a belt against invariant bugs only.
        guard = 2 * (self.max_batch + n_live) + 4
        while (self._backfilled or self.queue) and guard > 0:
            guard -= 1
            src, idx, head = self._next_candidate()
            boost = (self.policy.class_priority
                     and self.policy.is_interactive(head))
            if self._n_active() >= (self.max_batch if boost else target):
                break
            if not self.cache.n_free or not self._can_admit(head):
                if not (boost and self._preempt_for(head)):
                    break
                continue
            if src == "backfilled":
                req, cache, pos = self._backfilled[idx]
                del self._backfilled[idx]
            else:
                req = self.queue[idx]
                del self.queue[idx]
                cache, pos = self._prefill_one(req)
            self.drain_pipeline("admit")
            slot = self.cache.acquire()
            self._store_cache(req, cache, slot)
            self._req[slot] = req
            self._tok[slot] = req.generated[-1]
            self._pos[slot] = pos
            # generated already holds the prefill token; match the
            # sequential engine's stop rule (>= max_new_tokens after at
            # least one decode step).
            self._budget[slot] = max(1, req.max_new_tokens
                                     - len(req.generated))
            self.stats["engine"]["slot_admits"] += 1

    def _next_candidate(self):
        """Admission candidate in policy order: the first interactive
        entry anywhere (backfilled ahead of queued — its prefill already
        ran), else the backfilled head, else the queue head.  Without
        this, one pool-blocked batch head at the backfill front would
        wall off every interactive arrival behind it — the exact stall
        the policy layer exists to remove."""
        if self.policy.class_priority:
            for i, (r, _c, _p) in enumerate(self._backfilled):
                if self.policy.is_interactive(r):
                    return "backfilled", i, r
            for i, r in enumerate(self.queue):
                if self.policy.is_interactive(r):
                    return "queue", i, r
        if self._backfilled:
            return "backfilled", 0, self._backfilled[0][0]
        return "queue", 0, self.queue[0]

    # ------------------------------------------------------------------
    # Preemption + cancellation (overload robustness)
    # ------------------------------------------------------------------
    def _preempt_for(self, head: Request) -> bool:
        """Evict one batch-class resident to unblock ``head``'s
        admission; returns True iff a victim was preempted or the drain
        before it made room."""
        if not self.policy.preemption:
            return False
        if self._inflight is not None:
            # Rows finishing in the window in flight may free the room:
            # read it back, and evict only if they did not.
            self.drain_pipeline("preempt")
            if self.cache.n_free and self._can_admit(head):
                return True
        resident = [(s, r) for s, r in enumerate(self._req)
                    if r is not None]
        victim = self.policy.choose_victim(resident)
        if victim is None:
            return False
        self._preempt_slot(*victim)
        return True

    def _preempt_slot(self, slot: int, req: Request) -> None:
        """Release ``slot``'s storage and requeue its request for a
        deterministic resume: the re-admit prefills
        ``prompt + generated[:-1]`` and decodes on, token-identical to
        an unpreempted serve (pinned in the differential harness)."""
        self._req[slot] = None
        self._budget[slot] = 0
        self._release_slot(slot)
        self.stats["engine"]["slot_releases"] += 1
        self.stats["engine"]["preemptions"] += 1
        req.preemptions += 1
        self.policy.requeue(self.queue, req)

    def preempt(self, n: int = 1) -> int:
        """Forcibly evict up to ``n`` residents (the fault-injection
        storm): policy victim choice first, then — the policy only ever
        names batch-class victims — any remaining resident by lowest
        progress.  Returns the number actually preempted."""
        self.drain_pipeline("preempt")
        count = 0
        for _ in range(n):
            resident = [(s, r) for s, r in enumerate(self._req)
                        if r is not None]
            victim = self.policy.choose_victim(resident)
            if victim is None and resident:
                victim = min(resident,
                             key=lambda sr: (len(sr[1].generated), -sr[0]))
            if victim is None:
                break
            self._preempt_slot(*victim)
            count += 1
        return count

    def cancel(self, rid: int) -> bool:
        """Release a request mid-flight.  A resident request frees its
        slot (and, on the paged engine, its pages) immediately — a
        waiting admission can proceed this very step; queued/backfilled
        entries are dropped.  Marks the request done with
        ``finish_reason="cancelled"``; returns True iff found (a
        resident that the drain before its release finishes is done, not
        found)."""
        if any(r is not None and r.rid == rid for r in self._req):
            self.drain_pipeline("cancel")
        for slot, req in enumerate(self._req):
            if req is not None and req.rid == rid:
                self._req[slot] = None
                self._budget[slot] = 0
                self._release_slot(slot)
                self.stats["engine"]["slot_releases"] += 1
                break
        else:
            for item in list(self._backfilled):
                if item[0].rid == rid:
                    self._backfilled.remove(item)
                    req = item[0]
                    break
            else:
                for req in list(self.queue):
                    if req.rid == rid:
                        self.queue.remove(req)
                        break
                else:
                    return False
        req.done = True
        req.finish_reason = FINISH_CANCELLED
        req.finished_at = now()
        self._finished.append(req)
        self.stats["engine"]["cancelled"] += 1
        return True

    def _current_rung(self) -> int:
        """Smallest ladder rung covering the highest row with budget
        left (0 when none has): rows that finish in the window in flight
        are not decoded again."""
        highest = max((i + 1 for i, r in enumerate(self._req)
                       if r is not None and self._budget[i] > 0),
                      default=0)
        if highest == 0:
            return 0
        return next(r for r in self.rungs if r >= highest)

    # ------------------------------------------------------------------
    # Serve loop
    # ------------------------------------------------------------------
    def _release_slot(self, slot: int) -> None:
        """Return a finished request's storage (hook: the paged engine
        also retires freed physical pages from its prefix registry)."""
        self.cache.release(slot)

    def _map_window(self, rung: int) -> None:
        """Make storage ready for the window at ``rung`` (hook: the
        paged engine maps the pages the window can write)."""

    def _window_call(self, rung: int, toks, pos, budget):
        """Invoke the jitted window at ``rung`` (storage-specific)."""
        self.cache.buffers, toks, pos, budget, out = self._window_fn(
            self.params, self.cache.buffers, toks, pos, budget, rung=rung)
        return toks, pos, budget, out

    def _dispatch(self, rung: int) -> Optional[_Window]:
        """Dispatch the next window at ``rung`` and return the one it
        overlaps (still in flight, not yet read back), if any.

        With a window in flight the new one takes that window's device
        outputs as its inputs (no host copy, no wait); otherwise the
        host mirrors, which are exact then.  The mirrors advance here,
        at dispatch, so the next window's ``_map_window`` sees where this
        one leaves the rows before it is read back."""
        prev = self._inflight
        idx = self.stats["engine"]["windows"]
        with span("serve.window.prep", rung=rung, window=idx):
            self._map_window(rung)
            if prev is None:
                toks, pos, budget = (self._rows_to_device(a) for a in
                                     (self._tok, self._pos, self._budget))
            else:
                toks, pos, budget = prev.toks, prev.pos, prev.budget
        with span("serve.window.dispatch", rung=rung, window=idx):
            toks, pos, budget, out = self._window_call(rung, toks, pos,
                                                       budget)
        advance_rows(self._pos[:rung], self._budget[:rung], self.window,
                     self.max_seq)
        self._inflight = _Window(idx, rung, toks, pos, budget, out,
                                 self._pos.copy(), self._budget.copy())
        entries = jit_cache_entries(self._window_fn)
        raw = entries if entries is not None else self._window_traces
        self.stats["decode_compiles"] = max(0, raw - self._compile_base)
        ext = self.stats["engine"]
        ext["windows"] += 1
        ext["rungs"].append(rung)
        ext["windows_overlapped"] += prev is not None
        self.stats["decode_steps"] += self.window
        return prev

    def _read_back(self, win: _Window) -> None:
        """Read a window's tokens back and post them: extend each row's
        stream, and finish and release the rows whose budget it spent.
        Windows are read back in dispatch order."""
        with span("serve.window.sync", rung=win.rung, window=win.idx):
            out, toks, pos, budget = jax.device_get(
                (win.out, win.toks, win.pos, win.budget))   # (T, rung), ...
        with span("serve.window.post", rung=win.rung, window=win.idx) as sp:
            if not (np.array_equal(pos, win.pos_after)
                    and np.array_equal(budget, win.budget_after)):
                raise AssertionError(
                    f"window {win.idx} left pos/budget {pos}/{budget}, "
                    f"the host mirrors {win.pos_after}/{win.budget_after}")
            self._tok[:] = toks
            n_done = 0
            for slot in range(win.rung):
                req = self._req[slot]
                if req is None:
                    continue
                col = out[:, slot]
                req.generated.extend(int(t) for t in col[col >= 0])
                if win.budget_after[slot] <= 0:
                    req.done = True
                    req.finished_at = now()
                    self._finished.append(req)
                    self._req[slot] = None
                    self._release_slot(slot)
                    self.stats["engine"]["slot_releases"] += 1
                    n_done += 1
            sp.set_metadata(finished=n_done)

    def drain_pipeline(self, reason: str) -> None:
        """Read back the window in flight, if any, so that the row set
        and storage can change: the next window then starts from the
        host mirrors.  ``reason`` names the cause on the
        ``serve.window.drain`` span (``admit``, ``preempt``, ``cancel``,
        ``remesh``, ``reset``, ``fault``, ``idle`` — nothing left to
        decode — or ``shutdown``)."""
        win, self._inflight = self._inflight, None
        if win is None:
            return
        with span("serve.window.drain", reason=reason, rung=win.rung,
                  window=win.idx):
            self._read_back(win)
        self.stats["engine"]["pipeline_drains"] += 1

    def _plan_step(self) -> int:
        """Multi-tenant co-schedule of this window (stats + backfill
        count) — the same shared accounting the sequential engine uses
        (:func:`repro.serve.engine.record_step_packing`)."""
        if not self.multi_tenant or not self.queue:
            # Nothing waiting -> nothing to co-schedule; skip the packer
            # simulation on the drain tail (it runs once per window
            # here, not once per batch as in the sequential engine).
            return 0
        waiting = [len(r.prompt) for r in self.queue]
        return record_step_packing(self.stats, self._n_active(), waiting,
                                   self.cfg, bool(self.coexec_backend))

    def step(self, finished: List[Request], max_steps: int = 512) -> int:
        """One scheduler iteration: admit up to the ladder target,
        dispatch the next decode window, read back the one before it,
        then execute co-scheduled prefills.  Appends newly finished
        requests to ``finished`` and returns the decode steps dispatched
        (1 for a step that only read back or found nothing to decode; 0
        when idle).  This is the granularity the online frontend drives:
        between two calls at most one window is in flight, every token
        of the windows before it is on the host, and the frontend can
        inject batched prefills and read fresh tokens.
        """
        with span("serve.step") as sp:
            if not (self.queue or self._backfilled or self._n_active()
                    or self._inflight) or max_steps <= 0:
                finished.extend(self._finished)
                self._finished.clear()
                sp.set_metadata(rung=0)
                return 0
            with span("serve.admit") as admit:
                n0 = self.stats["engine"]["slot_admits"]
                self._admit()
                admit.set_metadata(
                    admitted=self.stats["engine"]["slot_admits"] - n0)
            n_pre = self._plan_step()
            to_backfill: List[Request] = []
            if self.coexec_backend and self.multi_tenant:
                nb = min(n_pre, len(self.queue))
                to_backfill = [self.queue.popleft() for _ in range(nb)]
            rung = self._current_rung()
            sp.set_metadata(rung=rung)
            consumed = 1
            if rung:
                prev = self._dispatch(rung)
                if prev is not None:
                    self._read_back(prev)
                consumed = self.window
            else:
                self.drain_pipeline("idle")
            # Co-scheduled prefills run at the window boundary (the
            # fused grid interleaves them with the decode window on the
            # array; at the host level they overlap the window in
            # flight).
            for r in to_backfill:
                self._backfill_one(r)
            finished.extend(self._finished)
            self._finished.clear()
            return consumed

    def run(self, max_steps: int = 512) -> List[Completion]:
        """Serve everything in the queue (greedy decoding); returns one
        :class:`~repro.serve.api.Completion` per finished request.

        ``max_steps`` counts decode iterations like the sequential
        engine; the slot engine consumes them ``window`` at a time.
        """
        finished: List[Request] = []
        while ((self.queue or self._backfilled or self._n_active())
               and max_steps > 0):
            max_steps -= self.step(finished, max_steps)
        self.drain_pipeline("idle")        # the window left in flight
        finished.extend(self._finished)    # and rows cancelled after it
        self._finished.clear()
        return [completion_of(r) for r in finished]

    # ------------------------------------------------------------------
    # Online-frontend hooks: coalesced prefill + AOT warmup
    # ------------------------------------------------------------------
    def prefill_batch(self, reqs: List[Request]) -> None:
        """Coalesced multi-prompt prefill: one batched call for a group
        of same-bucket prompts, each row parked decode-ready in the
        backfill queue (admitted FIFO by the next ``step``, never
        re-prefilled).

        The batch axis pads to the smallest ladder rung covering the
        group (dummy rows replicate row 0 and are discarded), so with
        power-of-two buckets the prefill entry points form the same
        finite ``(rung, bucket)`` grid as the decode windows — the set
        :meth:`warmup` pre-compiles.  Rows are independent in prefill
        exactly as in decode, so each row's logits and cache are
        bitwise those of its single-prompt prefill (pinned in
        ``tests/test_frontend.py``); engines without a vector-index
        prefill (injected ``prefill_fn``, exact-length configs) fall
        back to serial single prefills.
        """
        groups: List[Tuple[Optional[int], List[Request]]] = []
        for req in reqs:
            b = self._bucket_len(len(req.prompt))
            if groups and groups[-1][0] == b and b is not None:
                groups[-1][1].append(req)
            else:
                groups.append((b, [req]))
        for b, group in groups:
            if not self._batch_prefill or b is None or len(group) == 1:
                for req in group:
                    self._backfill_one(req)
                continue
            for i in range(0, len(group), self.rungs[-1]):
                self._prefill_group(group[i:i + self.rungs[-1]], b)

    def _prefill_group(self, group: List[Request], b: int) -> None:
        k = len(group)
        rung = next(r for r in self.rungs if r >= k)
        # Stat values may not hold commas: the ids are space-separated.
        with span("serve.prefill", rids=" ".join(str(r.rid) for r in group),
                  rows=rung, bucket=b):
            t = now()
            for req in group:
                if req.admitted_at is None:
                    req.admitted_at = t
            sig = (rung, b)
            if sig in self._seen_buckets:
                self.stats["engine"]["prefill_bucket_hits"] += 1
            else:
                self._seen_buckets.add(sig)
                self.stats["engine"]["prefill_bucket_misses"] += 1
            toks = np.zeros((rung, b), np.int32)
            last = np.zeros(rung, np.int32)
            for i in range(rung):
                src = group[i] if i < k else group[0]
                toks[i, :len(src.prompt)] = src.prompt
                last[i] = len(src.prompt) - 1
            batch = {"tokens": jnp.asarray(toks),
                     "last_index": jnp.asarray(last)}
            if self.cfg.enc_dec:
                encs = [encoder_inputs(group[i] if i < k else group[0],
                                       self.cfg) for i in range(rung)]
                batch["frontend_embeds"] = jnp.asarray(np.stack(encs))
            logits, cache = self.prefill_fn(self.params, batch)
            for i, req in enumerate(group):
                note_first_token(req, logits[i:i + 1], self.cfg.vocab_size,
                                 self.stats)
                row = jax.tree.map(lambda x, i=i: x[:, i:i + 1], cache)
                self._backfilled.append((req, row, len(req.prompt)))
        self.stats["engine"]["prefill_batches"] += 1
        self.stats["engine"]["prefill_batched_reqs"] += k

    def _warm_storage(self) -> None:
        """Admit (and keep) one dummy request so the decode-window
        warmup below runs against allocated storage — slot buffers for
        the dense engine, pools + a valid table row for the paged one."""
        dummy = Request(rid=-1, prompt=np.zeros(1, np.int32),
                        max_new_tokens=1)
        self.submit(dummy)
        self._admit()

    def warmup(self, max_prompt_len: Optional[int] = None,
               rungs: Optional[Sequence[int]] = None) -> None:
        """AOT-compile every serving entry point so steady state runs
        with zero compiles (``stats["decode_compiles"] == 0`` from the
        first real window onward).

        Traces the single-prompt prefill for every bucket covering
        prompts up to ``max_prompt_len`` (default: the engine's bucket
        capacity), the batched multi-prompt prefill at every
        ``(rung, bucket)`` pair, and the decode window at every rung,
        then resets all serving state.  Compile caches survive the
        reset, and the decode-compile counter is re-based so the stat
        reports compiles *since warmup*.
        """
        max_len = min(max_prompt_len or self._bucket_cap, self._bucket_cap)
        warm_rungs = tuple(r for r in self.rungs
                           if rungs is None or r in set(rungs))
        buckets = sorted({self._bucket_len(s)
                          for s in range(1, max_len + 1)} - {None})
        for b in buckets:
            probe = Request(rid=-1, prompt=np.zeros(b, np.int32),
                            max_new_tokens=1)
            self._backfill_one(probe)          # scalar-index signature
            if self._batch_prefill:
                for rung in warm_rungs:
                    if rung < 2:
                        continue               # k==1 takes the scalar path
                    group = [Request(rid=-i - 1,
                                     prompt=np.zeros(b, np.int32),
                                     max_new_tokens=1)
                             for i in range(rung)]
                    self._prefill_group(group, b)
            self._backfilled.clear()
        self._warm_storage()
        zeros = np.zeros(self.max_batch, np.int32)
        for rung in warm_rungs:
            # Budget-0 rows are frozen: the window computes and discards
            # their logits, and released rows only write the sink/own
            # slot, so warmup mutates no live state besides storage.
            # Fed from the host, then from its own outputs, as the
            # pipeline feeds it.
            rows = [self._rows_to_device(zeros) for _ in range(3)]
            rows = self._window_call(rung, *rows)[:3]
            jax.block_until_ready(self._window_call(rung, *rows))
        self.reset()
        entries = jit_cache_entries(self._window_fn)
        self._compile_base = (entries if entries is not None
                              else self._window_traces)
        self.stats["decode_compiles"] = 0
