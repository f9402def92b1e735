"""Unified serving API: one factory, one options record, one result
contract, one stats schema.

After PRs 4-6 the three serving engines had drifted into three
constructor signatures and three ad-hoc stats dicts; the online front
end (:mod:`repro.serve.frontend`) needs a *stable* contract to build
on, so this module pins it down:

* :func:`make_engine` — the single construction path.  ``kind``
  selects the engine (``"sequential"`` | ``"slot"`` | ``"paged"``),
  :class:`EngineOptions` carries every tuning knob, and the factory
  builds the jitted prefill/decode steps the sequential engine used to
  demand from every caller.  The three constructors keep working (and
  the factory routes through them), but direct constructor calls
  outside ``repro/serve`` fail the API lint (``scripts/check_api.py``).

* :class:`EngineOptions` — a frozen dataclass of engine knobs
  (``max_slots``, ``page_size``, ``kv_quant``, ``coexec_backend``,
  ``ladder``, ``buckets``, ...).  Frozen so an options value can be
  shared across engines and used as a cache key without aliasing
  surprises.

* :class:`Completion` — the result of serving one request.  Engines
  return ``List[Completion]`` from ``run()`` instead of leaking their
  internal mutated :class:`~repro.serve.engine.Request` objects;
  the frontend delivers the same type through streaming handles.

* ``STATS_KEYS`` / :func:`validate_stats` — the one documented stats
  schema every engine emits.  Engine-specific extras are namespaced
  under ``stats["engine"]`` so cross-engine consumers (benches, the
  differential harness, the frontend) can rely on the shared keys
  without per-engine special cases.

Stats schema (all engines)::

    batches           list[int]  ladder-quantized target per admission
    ttft              list[float]  seconds from submit to first token
    decode_steps      int        decode iterations executed
    decode_compiles   int|None   decode-path compiles since warmup
                                 (0 in steady state after ``warmup()``)
    packed_speedup    list[float]  predicted step speedup (multi-tenant)
    packed_prefills   int        prefills co-scheduled by the packer
    backfilled        int        prefills executed inside decode windows
    coexec_tiles      list[int]  fused grid-task counts per step
    coexec_interleave list[int]  tenant switches in each task order
    coexec_backend    str|None   requested co-execution backend
    expert_backend    str        MoE expert GEMM lowering in effect
    engine            dict       engine-specific extras (slot/page/pool
                                 counters — see each engine's docs)
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Tuple

ENGINE_KINDS = ("sequential", "slot", "paged")

#: The shared stats schema — every engine's ``stats`` dict has exactly
#: these keys (engine-specific extras live under ``stats["engine"]``).
STATS_KEYS = frozenset({
    "batches", "ttft", "decode_steps", "decode_compiles",
    "packed_speedup", "packed_prefills", "backfilled",
    "coexec_tiles", "coexec_interleave", "coexec_backend",
    "expert_backend", "engine",
})

FINISH_LENGTH = "length"        # max_new_tokens budget exhausted
FINISH_MAX_SEQ = "max_seq"      # hit the engine's sequence capacity
FINISH_ABORTED = "aborted"      # shutdown(drain=False) tore it down
FINISH_CANCELLED = "cancelled"  # RequestHandle.cancel()/engine.cancel()
FINISH_DEADLINE = "deadline"    # per-request deadline expired


@dataclasses.dataclass(frozen=True)
class Completion:
    """Result of serving one request — the unified return contract.

    ``tokens`` is the full greedy stream (prefill's first token
    included); ``ttft`` is seconds from submission to the first token;
    ``tpot`` is mean seconds per subsequent token (on the slot engines
    the host reads tokens back once per decode window of ``window``
    steps, so a stream arrives in chunks of that many);
    ``finish_reason`` is one of ``"length"`` (budget exhausted),
    ``"max_seq"`` (sequence capacity), ``"aborted"``.
    """
    rid: int
    tokens: Tuple[int, ...]
    ttft: float
    tpot: float
    finish_reason: str

    @property
    def n_tokens(self) -> int:
        return len(self.tokens)


def completion_of(req) -> Completion:
    """Build a :class:`Completion` from a finished engine ``Request``."""
    n = len(req.generated)
    first = req.first_token_at if req.first_token_at is not None else 0.0
    done_at = req.finished_at if req.finished_at is not None else first
    ttft = max(0.0, first - req.arrived) if req.first_token_at else 0.0
    tpot = (done_at - first) / (n - 1) if n > 1 else 0.0
    # Lifecycle exits (cancel, deadline) stamp an explicit reason on the
    # request; budget accounting covers only the natural finishes.
    reason = getattr(req, "finish_reason", None) or (
        FINISH_LENGTH if n >= req.max_new_tokens else FINISH_MAX_SEQ)
    return Completion(rid=req.rid, tokens=tuple(req.generated),
                      ttft=ttft, tpot=max(0.0, tpot), finish_reason=reason)


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Every serving-engine knob, in one frozen record.

    ``max_slots`` is the concurrent decode-row capacity (the dense
    engines' ``max_batch``); ``window`` is the decode steps of one
    slot-engine window program (``None``:
    :func:`~repro.serve.slot_engine.default_window`, one on an
    accelerator — the engines keep the next window queued on the device
    while the host reads the last one, so a short window costs no host
    gap, and a new prompt's prefill waits behind at most one queued
    window); ``ladder`` overrides the ``SLAB_LADDER``
    decode rungs (``None`` keeps the paper's ladder); ``buckets``
    selects prefill padding (``"auto"`` — powers of two on the slot
    engine, page multiples on the paged engine, exact lengths on the
    sequential engine — or ``"off"`` for exact-length prefills
    everywhere).  Paged-only knobs (``page_size``, ``num_pages``,
    ``kv_quant``, ``prefix_sharing``) are ignored by the dense kinds.
    ``policy`` is the admission-class scheduler
    (:class:`repro.serve.policy.SchedulingPolicy`; ``None`` keeps the
    default interactive-over-batch policy with preemption) and
    ``default_klass`` resolves requests submitted without a class.
    """
    max_slots: int = 8
    max_seq: int = 256
    window: Optional[int] = None
    ladder: Optional[Tuple[int, ...]] = None
    buckets: str = "auto"
    page_size: int = 16
    num_pages: Optional[int] = None
    kv_quant: Optional[str] = None
    prefix_sharing: bool = True
    multi_tenant: bool = True
    coexec_backend: Optional[str] = None
    expert_backend: Optional[str] = None
    policy: Optional[Any] = None
    default_klass: str = "batch"

    def __post_init__(self):
        if self.buckets not in ("auto", "off"):
            raise ValueError(f"buckets={self.buckets!r} not in "
                             "('auto', 'off')")
        from repro.serve.policy import KLASSES, SchedulingPolicy
        if self.default_klass not in KLASSES:
            raise ValueError(f"default_klass={self.default_klass!r} "
                             f"not in {KLASSES}")
        if self.policy is not None \
                and not isinstance(self.policy, SchedulingPolicy):
            raise ValueError(f"policy={self.policy!r} is not a "
                             "SchedulingPolicy")
        if self.ladder is not None:
            rungs = tuple(self.ladder)
            if not rungs or list(rungs) != sorted(set(rungs)) \
                    or rungs[0] < 1:
                raise ValueError(f"ladder {rungs} must be a strictly "
                                 "increasing tuple of positive rungs")
            object.__setattr__(self, "ladder", rungs)


def make_engine(cfg, params, kind: str = "slot",
                options: Optional[EngineOptions] = None, *,
                mesh=None, **overrides):
    """Build a serving engine — the single blessed construction path.

    ``kind`` selects the engine class; ``options`` (plus keyword
    ``overrides`` applied on top via :func:`dataclasses.replace`)
    carries the knobs.  Extra engine-specific keyword arguments that
    are not ``EngineOptions`` fields (``prefill_fn``, ``decode_fn``,
    ``prefill_is_bucketed`` — test-injection hooks) pass through to the
    constructor unchanged.

        eng = make_engine(cfg, params, kind="paged",
                          options=EngineOptions(max_slots=16,
                                                kv_quant="int8"))

    For ``kind="sequential"`` the factory also builds the jitted
    prefill/decode steps the legacy constructor requires, so callers
    stop hand-assembling them.

    ``mesh`` (a ``("data", "model")`` :class:`jax.sharding.Mesh`) turns
    the slot/paged fast path tensor-parallel: params and KV storage are
    committed to the sharding rules of ``repro.distributed.sharding``
    (TP over heads, expert-parallel MoE, replicated page table) and the
    decode windows run GSPMD-partitioned with the paged-attention step
    per-shard under ``shard_map`` — token-identical to the single-device
    engines, same zero-steady-state-compile invariants.  The sequential
    engine has no mesh path (its per-shape recompiles are exactly what
    the fast path exists to remove).
    """
    import jax

    from repro.serve.engine import ServeEngine
    from repro.serve.paged_engine import PagedServeEngine
    from repro.serve.serve_step import make_decode_step, make_prefill_step
    from repro.serve.slot_engine import SlotServeEngine

    if kind not in ENGINE_KINDS:
        raise ValueError(f"kind={kind!r} not in {ENGINE_KINDS}")
    opts = options or EngineOptions()
    opt_fields = {f.name for f in dataclasses.fields(EngineOptions)}
    opt_overrides = {k: v for k, v in overrides.items() if k in opt_fields}
    passthrough = {k: v for k, v in overrides.items() if k not in opt_fields}
    if opt_overrides:
        opts = dataclasses.replace(opts, **opt_overrides)

    common = dict(max_batch=opts.max_slots, max_seq=opts.max_seq,
                  multi_tenant=opts.multi_tenant,
                  expert_backend=opts.expert_backend,
                  coexec_backend=opts.coexec_backend,
                  policy=opts.policy, default_klass=opts.default_klass)
    if kind == "sequential":
        if mesh is not None:
            raise ValueError(
                "mesh-aware serving requires kind='slot' or 'paged'")
        if "prefill_fn" not in passthrough:
            passthrough["prefill_fn"] = jax.jit(
                make_prefill_step(cfg, cache_len=opts.max_seq))
        if "decode_fn" not in passthrough:
            passthrough["decode_fn"] = jax.jit(make_decode_step(cfg))
        passthrough.setdefault("cache_init_fn", None)
        return ServeEngine(cfg, params, **common, **passthrough)
    common.update(window=opts.window,
                  prefill_bucketing=opts.buckets != "off")
    if mesh is not None:
        common["mesh"] = mesh
    if opts.ladder is not None:
        common["ladder"] = opts.ladder
    if kind == "slot":
        return SlotServeEngine(cfg, params, **common, **passthrough)
    return PagedServeEngine(cfg, params, page_size=opts.page_size,
                            num_pages=opts.num_pages,
                            kv_quant=opts.kv_quant,
                            prefix_sharing=opts.prefix_sharing,
                            **common, **passthrough)


def validate_stats(stats: Dict[str, Any]) -> None:
    """Assert ``stats`` matches the documented cross-engine schema:
    exactly the shared ``STATS_KEYS`` at the top level, extras (a dict)
    under ``stats["engine"]``.  Raises ``AssertionError`` on drift —
    used by the differential harness to pin schema equality."""
    keys = set(stats)
    missing, extra = STATS_KEYS - keys, keys - STATS_KEYS
    assert not missing, f"stats missing shared keys: {sorted(missing)}"
    assert not extra, (f"stats carries non-schema top-level keys "
                       f"{sorted(extra)} — namespace them under "
                       f"stats['engine']")
    assert isinstance(stats["engine"], dict), "stats['engine'] not a dict"


def now() -> float:
    """The one clock of every serving stamp and deadline: arrival,
    admission, first token, finish (``time.perf_counter``, the clock the
    chip benchmark times requests by).  Stamps are seconds on it; only
    their differences mean anything."""
    return time.perf_counter()
