"""Serving engine: continuous batching with SISA-aware batch quantization.

The paper's utilization analysis (§4.3) shows distinct efficiency regimes
at effective-M = 16/32/64/128 (slab / fused / monolithic).  The engine's
admission policy therefore *quantizes* the decode batch to the slab
ladder: a batch of 19 live requests runs as 32 (fused pair) only if the
simulator predicts a cycle win over running 16 + 3 deferred, so the
accelerator always executes at a utilization knee.  Prefill requests are
scheduled one-at-a-time (latency-sensitive, skewed-M — the slab case).

Multi-tenant co-execution (``coexec_backend``): every step the packer
(:func:`plan_step_packing`) co-schedules the quantized decode batch's
GEMMs with the *waiting prompts'* prefill GEMMs on the slab groups the
decode work leaves idle.  With ``coexec_backend`` set the engine
executes that placement at the serving level instead of only predicting
it: the co-scheduled prefills run inside the decode window (one per
decode iteration), their caches park in the backfill queue, and the
next step admits them decode-ready.  The flag does **not** re-route the
jitted ``prefill_fn``/``decode_fn`` GEMMs through
``repro.kernels.coexec`` — those are closed jitted functions; the
GEMM-level fused grid is exercised with real operands by
``benchmarks/multi_tenant_bench.py`` and ``tests/test_coexec.py``.  The
engine does lower each step's placement to the fused kernel's grid-task
order (``repro.core.multi.coexec_tile_sequence``) and records its size
and tenant interleaving in ``stats["coexec_tiles"]`` /
``stats["coexec_interleave"]``.  With the flag unset the sequential
path is the fallback, and the two paths are numerics-equivalent:
prefill/decode are deterministic and the step-level batch composition
is identical, so every request generates the same tokens either way
(regression-tested in ``tests/test_coexec.py``).

Deferred-request accounting: a prefill that completed this step via
backfill is *live* next step — it is admitted from the backfill queue
(never re-prefilled) and it no longer appears in the next placement's
waiting-prefill set.  Counting it again — as pre-PR-3 drafts of this
loop did — double-books its GEMMs in the ladder quantization and the
packed-speedup stats.

On CPU this drives the real jitted decode step; on an ASIC deployment the
same policy feeds the slab scheduler.
"""
from __future__ import annotations

from collections import deque
import dataclasses
import functools
from typing import Any, Callable, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import (coexec_tile_sequence, GemmRequest, packed_speedup,
                        requests_from_workload, simulate_workload, SISA_128)
from repro.core.workloads import GemmLayer, LLMWorkload
from repro.serve.api import now
from repro.serve.spans import span

SLAB_LADDER = (1, 2, 4, 8, 16, 32, 64, 128)


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int
    arrived: float = 0.0
    generated: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # Stamps on ``repro.serve.api.now``: taken into its first prefill
    # call, its first token on the host, finished.
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    # SLO scheduling (repro.serve.policy): admission class (None ->
    # batch), absolute deadline stamp enforced by the frontend, times
    # evicted under pool pressure, and an explicit finish reason for
    # lifecycle exits (cancel/deadline) that budget accounting alone
    # cannot express.
    klass: Optional[str] = None
    deadline: Optional[float] = None
    preemptions: int = 0
    finish_reason: Optional[str] = None
    # enc-dec only: fixed-shape (cfg.enc_frames, cfg.frontend_dim)
    # encoder features (whisper mel frames through the stub frontend).
    # None serves against all-zero features (still a valid encoding).
    enc_embeds: Optional[np.ndarray] = None


def encoder_inputs(req: Request, cfg: ModelConfig) -> Optional[np.ndarray]:
    """The fixed-shape encoder feature block a prefill of ``req`` needs.

    Enc-dec serving keeps the encoder at one static source length
    (``cfg.enc_frames``) so the encoder traces exactly once and decoder
    prompt bucketing stays exact — features must arrive pre-padded.
    """
    if not cfg.enc_dec:
        return None
    if req.enc_embeds is None:
        return np.zeros((cfg.enc_frames, cfg.frontend_dim), np.float32)
    e = np.asarray(req.enc_embeds, np.float32)
    if e.shape != (cfg.enc_frames, cfg.frontend_dim):
        raise ValueError(
            f"enc_embeds must be ({cfg.enc_frames}, {cfg.frontend_dim}), "
            f"got {e.shape}")
    return e


def effective_tokens(req: Request) -> np.ndarray:
    """Token sequence a (re-)prefill of ``req`` must run over.

    Fresh requests prefill their prompt.  A preempted request resumes by
    re-prefilling ``prompt + generated[:-1]`` — every token *written* to
    its released cache — and re-entering decode with
    ``tok = generated[-1]`` at ``pos = len(prompt) + len(generated) - 1``,
    which regenerates the identical stream an unpreempted serve produces
    (greedy decode is deterministic and causal attention makes prefill
    and decode KV paths agree position-for-position; pinned in
    ``tests/test_overload.py`` / ``tests/test_serve_differential.py``).
    """
    if not req.generated:
        return np.asarray(req.prompt, np.int32)
    return np.concatenate([np.asarray(req.prompt, np.int32),
                           np.asarray(req.generated[:-1], np.int32)])


def _llm_workload_of(cfg: ModelConfig) -> LLMWorkload:
    """Project a ModelConfig onto Table-2-style GEMM layers."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return LLMWorkload(name=cfg.name, n_layers=cfg.n_layers, layers=(
        GemmLayer(0, cfg.n_heads * hd, d, 2 * cfg.n_layers, "q/o"),
        GemmLayer(1, cfg.n_kv_heads * hd, d, 2 * cfg.n_layers, "k/v"),
        GemmLayer(2, cfg.d_ff, d, 2 * cfg.n_layers, "gate/up"),
        GemmLayer(3, d, cfg.d_ff, cfg.n_layers, "down"),
        GemmLayer(4, cfg.vocab_size, d, 1, "lm_head"),
    ))


@functools.lru_cache(maxsize=4096)
def _rung_cycles(cfg: ModelConfig, rung: int) -> float:
    """Simulated cycles for one full decode pass at batch = ``rung``.

    ``ModelConfig`` is a frozen (hashable) dataclass, so the simulator
    sweep is memoized per ``(cfg, ladder_rung)`` — the engine calls
    :func:`choose_decode_batch` every step, and re-running
    ``simulate_workload`` for the whole ladder each time dominated the
    admission path.
    """
    wl = _llm_workload_of(cfg)
    return simulate_workload(wl.gemms(rung), SISA_128).cycles


def choose_decode_batch(n_live: int, cfg: ModelConfig,
                        max_batch: int = 128, *,
                        admit_cap: Optional[int] = None) -> int:
    """SISA-aware batch quantization: pick the ladder size minimizing
    predicted cycles-per-token (simulator-driven, not a heuristic).
    The per-rung simulation is cached on ``(cfg, rung)``.

    ``admit_cap`` is the page-budget constraint of the paged engine: at
    most this many requests can actually be resident (live rows plus
    whatever the page pool can still reserve worst-case), so rungs
    larger than it only buy masked holes — the sweep counts served
    requests as ``min(n_live, b, admit_cap)`` and admission can never
    over-commit the pool chasing a bigger rung.
    """
    if n_live <= 0:
        return 0
    cap = n_live if admit_cap is None else min(n_live, max(admit_cap, 1))
    best_b, best_cpt = None, float("inf")
    for b in SLAB_LADDER:
        if b > max_batch:
            break
        served = min(cap, b)
        cpt = _rung_cycles(cfg, b) / served
        if cpt < best_cpt - 1e-9:
            best_b, best_cpt = b, cpt
        if b >= cap:
            break
    return best_b


def plan_step_packing(decode_bsz: int, prompt_lens: List[int],
                      cfg: ModelConfig, max_coresident: int = 4):
    """Multi-tenant co-schedule of one engine step on the slab array.

    The decode batch's per-layer GEMMs (skewed, m = decode_bsz) are
    packed together with the *next waiting prompts'* prefill GEMMs: while
    the decode GEMMs leave slab groups idle (narrow k/v projections, few
    N tiles), prefill work from queued requests rides on them instead of
    waiting for the full decode pass — the multi-GEMM scheduling the
    single-tenant §3.2 planner cannot express.

    Returns ``(packed_schedule, serial_result, n_prefills_packed)``.
    """
    wl = _llm_workload_of(cfg)
    reqs: List[GemmRequest] = []
    if decode_bsz > 0:
        reqs = requests_from_workload(wl.gemms(decode_bsz), tag="decode")
    prompts = prompt_lens[:max_coresident]
    for s in prompts:
        reqs += requests_from_workload(wl.gemms(max(1, s)), tag="prefill",
                                       start_rid=len(reqs))
    sp, packed, serial = packed_speedup(reqs, SISA_128)
    return packed, serial, len(prompts)


def note_first_token(req: Request, logits, vocab: int,
                     stats: Dict[str, Any]) -> None:
    """Record a prefill's greedy first token and TTFT on ``req``.

    Shared by the sequential and slot engines so the first-token
    bookkeeping (greedy argmax over the real vocab, TTFT sample) cannot
    drift between them.
    """
    nxt = int(jnp.argmax(logits[0, -1, :vocab]))
    req.generated.append(nxt)
    req.first_token_at = now()
    stats["ttft"].append(req.first_token_at - req.arrived)


def init_serve_stats(coexec_backend: Optional[str],
                     expert_backend: Optional[str]) -> Dict[str, Any]:
    """Validate backends, apply the expert backend, and build the stats
    dict shared by both serving engines.

    With ``expert_backend`` set, MoE expert FFNs lower through the flat
    ragged grouped kernel (``repro.kernels.grouped_gemm``) for both EP
    impls — no capacity buffer on the hot path.  One definition serves
    :class:`ServeEngine` and
    :class:`~repro.serve.slot_engine.SlotServeEngine` so accepted
    backends and stats keys cannot drift between them.
    """
    if coexec_backend not in (None, "pallas", "pallas_interpret", "xla"):
        raise ValueError(f"unknown coexec_backend {coexec_backend!r}")
    from repro.models.moe import EXPERT_BACKEND
    if expert_backend is not None:
        from repro.models.moe import set_expert_backend
        set_expert_backend(expert_backend)
    # Exactly the shared schema of repro.serve.api.STATS_KEYS —
    # engine-specific extras go under the "engine" namespace, never at
    # the top level (validate_stats enforces this).
    return {"batches": [], "ttft": [], "decode_steps": 0,
            "decode_compiles": None,
            "packed_speedup": [], "packed_prefills": 0,
            "backfilled": 0, "coexec_tiles": [], "coexec_interleave": [],
            "coexec_backend": coexec_backend,
            "expert_backend": expert_backend or EXPERT_BACKEND["impl"],
            "engine": {}}


def record_step_packing(stats: Dict[str, Any], decode_bsz: int,
                        waiting: List[int], cfg: ModelConfig,
                        coexec: bool) -> int:
    """Plan one step's multi-tenant placement and record its stats.

    Runs :func:`plan_step_packing` over the live decode batch and the
    waiting prompts, appends the packed-speedup sample and (when
    ``coexec`` is set) the fused grid-task order's size/interleaving,
    and returns the number of co-scheduled prefills.  Shared by both
    engines — the deferred-accounting rules around this block are
    subtle enough that they must exist exactly once.
    """
    packed, serial, n_pre = plan_step_packing(decode_bsz, waiting, cfg)
    if packed.makespan > 0:
        stats["packed_speedup"].append(serial.cycles / packed.makespan)
    stats["packed_prefills"] += n_pre
    if coexec:
        seq = coexec_tile_sequence(packed)
        stats["coexec_tiles"].append(len(seq))
        stats["coexec_interleave"].append(
            sum(a != b for a, b in zip(seq, seq[1:])))
    return n_pre


class ServeEngine:
    """Drives jitted prefill/decode over a request queue."""

    def __init__(self, cfg: ModelConfig, params, *, prefill_fn: Callable,
                 decode_fn: Callable, cache_init_fn: Callable,
                 max_batch: int = 8, max_seq: int = 256,
                 multi_tenant: bool = True,
                 expert_backend: Optional[str] = None,
                 coexec_backend: Optional[str] = None,
                 policy=None, default_klass: str = "batch"):
        from repro.serve.policy import SchedulingPolicy
        self.default_klass = default_klass
        self.cfg = cfg
        self.params = params
        self.prefill_fn = prefill_fn
        self.decode_fn = decode_fn
        self.cache_init_fn = cache_init_fn
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.multi_tenant = multi_tenant
        self.policy = policy or SchedulingPolicy()
        # Co-execution: execute (not just predict) each step's packed
        # placement — deferred prefills ride the decode window and join
        # the next batch decode-ready.  Requires multi_tenant.
        self.stats: Dict[str, Any] = init_serve_stats(coexec_backend,
                                                      expert_backend)
        self.stats["engine"].update({"cancelled": 0})
        self.coexec_backend = coexec_backend
        self._expert_backend = expert_backend
        self.queue: Deque[Request] = deque()
        # (request, prefilled cache, position): prefills completed via
        # backfill, awaiting decode admission.
        self._backfilled: Deque[Tuple[Request, Any, int]] = deque()
        # Cancelled mid-flight, awaiting delivery via ``finished``.
        self._cancelled: List[Request] = []

    def submit(self, req: Request) -> None:
        req.arrived = now()
        if req.klass is None:
            req.klass = self.default_klass
        self.policy.enqueue(self.queue, req)

    def cancel(self, rid: int) -> bool:
        """Release a queued request mid-flight (the sequential engine
        holds nothing resident between ``step()`` calls, so queue and
        backfill are the whole in-flight set).  Marks the request done
        with ``finish_reason="cancelled"``; returns True iff found."""
        from repro.serve.api import FINISH_CANCELLED
        for req in list(self.queue):
            if req.rid == rid:
                self.queue.remove(req)
                break
        else:
            for item in list(self._backfilled):
                if item[0].rid == rid:
                    self._backfilled.remove(item)
                    req = item[0]
                    break
            else:
                return False
        req.done = True
        req.finish_reason = FINISH_CANCELLED
        req.finished_at = now()
        self._cancelled.append(req)
        self.stats["engine"]["cancelled"] += 1
        return True

    def reset(self) -> None:
        """Clear queues and stats for a fresh serve on the same engine.

        The jitted ``prefill_fn``/``decode_fn`` keep their compile
        caches, so a long-lived engine (or a fuzz harness running many
        workloads) pays tracing/compilation once, not per serve.
        """
        self.queue.clear()
        self._backfilled.clear()
        self._cancelled.clear()
        self.stats = init_serve_stats(self.coexec_backend,
                                      self._expert_backend)
        self.stats["engine"].update({"cancelled": 0})

    def _prefill_one(self, req: Request):
        s = len(req.prompt)
        with span("serve.prefill", rids=req.rid, rows=1, bucket=s):
            if req.admitted_at is None:
                req.admitted_at = now()
            tokens = jnp.asarray(req.prompt[None], jnp.int32)
            batch = {"tokens": tokens}
            enc = encoder_inputs(req, self.cfg)
            if enc is not None:
                batch["frontend_embeds"] = jnp.asarray(enc[None])
            logits, cache = self.prefill_fn(self.params, batch)
            note_first_token(req, logits, self.cfg.vocab_size, self.stats)
        return cache, s

    def _backfill_one(self, req: Request) -> None:
        """Execute one deferred prefill inside the current decode window
        and park it decode-ready for the next admission."""
        cache, pos = self._prefill_one(req)
        self._backfilled.append((req, cache, pos))
        self.stats["backfilled"] += 1

    def step(self, finished: List[Request], max_steps: int = 512) -> int:
        """One scheduler iteration: admit a ladder batch and serve it to
        completion.  Returns the number of decode steps consumed (0 when
        there is no work) — the granularity the online frontend drives;
        the slot engines override this with a window-boundary step.
        """
        if self._cancelled:
            finished.extend(self._cancelled)
            self._cancelled.clear()
        if not (self.queue or self._backfilled) or max_steps <= 0:
            return 0
        budget = max_steps
        # Admission: SISA-aware batch size over live requests.  A
        # backfilled request *is* live (its prefill already ran);
        # counting it as a pending prefill again would double-book
        # its GEMMs against this step's ladder quantization.
        n_live = len(self.queue) + len(self._backfilled)
        bsz = choose_decode_batch(n_live, self.cfg, self.max_batch)
        bsz = max(1, min(bsz, n_live, self.max_batch))
        self.stats["batches"].append(bsz)
        # Backfilled requests first (FIFO — they were at the queue
        # front when backfilled, so batch composition matches the
        # sequential path exactly), then fresh queue admits.
        active: List[Request] = []
        caches, positions = [], []
        while self._backfilled and len(active) < bsz:
            r, cache, pos_r = self._backfilled.popleft()
            active.append(r)
            caches.append(cache)
            positions.append(pos_r)
        fresh = [self.queue.popleft()
                 for _ in range(bsz - len(active))]
        active += fresh
        n_pre = 0
        if self.multi_tenant:
            # Co-schedule this step on the slab array: decode GEMMs
            # of the admitted batch packed with the waiting prompts'
            # prefill GEMMs on idle slab groups.  Already-backfilled
            # prefills are excluded — their work is done.
            waiting = [len(r.prompt) for r in self.queue]
            # The placement is lowered to the fused kernel's
            # grid-task order when coexec is set: adjacent-task
            # tenant switches are the interleaving the fused grid
            # would execute for this step.
            n_pre = record_step_packing(
                self.stats, bsz, waiting, self.cfg,
                bool(self.coexec_backend))
        # Prefill each fresh admit (latency-sensitive, slab-mode
        # skewed GEMMs), then batch the decode loop.
        for r in fresh:
            cache, pos_r = self._prefill_one(r)
            caches.append(cache)
            positions.append(pos_r)
        # Co-execution: the prefills the packer placed on this
        # step's idle slabs run inside the decode window below.
        to_backfill: List[Request] = []
        if self.coexec_backend and self.multi_tenant:
            nb = min(n_pre, len(self.queue))
            to_backfill = [self.queue.popleft() for _ in range(nb)]
        batched_cache = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=1), *caches)
        pos = max(positions)
        live = list(active)
        while live and budget > 0:
            toks = jnp.asarray([[r.generated[-1]] for r in live],
                               jnp.int32)
            logits, batched_cache = self.decode_fn(
                self.params, batched_cache, toks, jnp.int32(pos))
            self.stats["decode_steps"] += 1
            pos += 1
            budget -= 1
            if to_backfill:
                # One co-resident prefill per decode iteration — the
                # serving-level interleave of the fused grid axis.
                self._backfill_one(to_backfill.pop(0))
            nxt = np.asarray(
                jnp.argmax(logits[:, -1, :self.cfg.vocab_size], -1))
            still = []
            for i, r in enumerate(live):
                r.generated.append(int(nxt[i]))
                if len(r.generated) >= r.max_new_tokens \
                        or pos >= self.max_seq - 1:
                    r.done = True
                    r.finished_at = now()
                    finished.append(r)
                else:
                    still.append(r)
            if len(still) != len(live):
                # shrink the batch (release finished rows)
                keep = [i for i, r in enumerate(live) if not r.done]
                if keep:
                    idx = jnp.asarray(keep)
                    batched_cache = jax.tree.map(
                        lambda x: x[:, idx], batched_cache)
                live = still
        # Decode drained before every co-scheduled prefill ran:
        # finish them now, still within this step's window.
        for r in to_backfill:
            self._backfill_one(r)
        from repro.serve.slot_engine import jit_cache_entries
        entries = jit_cache_entries(self.decode_fn)
        if entries is not None:
            self.stats["decode_compiles"] = entries
        return max_steps - budget

    def run(self, max_steps: int = 512) -> List["Completion"]:
        """Serve everything in the queue (greedy decoding); returns one
        :class:`~repro.serve.api.Completion` per finished request."""
        from repro.serve.api import completion_of
        finished: List[Request] = []
        while (self.queue or self._backfilled) and max_steps > 0:
            max_steps -= self.step(finished, max_steps)
        finished.extend(self._cancelled)   # cancelled with no step after
        self._cancelled.clear()
        return [completion_of(r) for r in finished]
