"""Fused multi-tenant co-execution: one Pallas grid, many GEMMs.

``repro.core.multi`` packs concurrent GEMMs onto disjoint slab groups and
*predicts* the packed speedup; this module executes that placement.  The
tile tasks of all co-resident tenants — heterogeneous ``(Mᵢ, Nᵢ, Kᵢ)``
problems, each with its own weight — are flattened into a **single grid
axis**, so one ``pallas_call`` sweeps the whole co-schedule instead of
launching the tenants back-to-back.  Per-task metadata is
scalar-prefetched (the same ownership machinery as
``repro.kernels.grouped_gemm``): each grid step knows, before its body
runs, which tenant it serves, which A/C row block and which B/C column
block it owns, and how many rows / K columns are real.

Layout (built host-side by :func:`build_coexec_plan`):

* activations share one flat ``(M_flat, Kp)`` buffer — tenant ``t``'s
  rows live at the block-aligned cumulative offset ``row_offset[t]``
  (``flat_group_offsets`` semantics), columns ``[0, kᵗ)`` are real and
  the tail up to the common ``Kp`` is zero;
* weights share one ``(T, Kp, Np)`` buffer, tenant-indexed on the
  leading axis exactly like the grouped kernel's expert axis, zero
  padded past ``(kᵗ, nᵗ)``;
* outputs share a flat ``(M_flat, Np)`` buffer; tenant ``t``'s result is
  the slice ``[row_offset[t] : row_offset[t]+mᵗ, :nᵗ]``.

The tile table (``(5, n_tasks)`` int32, SMEM) carries per task:
``[tenant, row_block, col_block, row_hi, k_hi]``.  ``row_hi`` masks the
ragged M tail (rows ``>= row_hi`` never reach the MXU — the power-gated
slabs above ``ceil(Mᵢ/slab_h)``); ``k_hi`` skips whole K steps past a
tenant's contraction depth (scale-in along K for skewed co-residents).

Task *order* is the co-schedule: :func:`interleave_order` round-robins
tasks across tenants, and ``order=`` accepts the tenant sequence emitted
by ``repro.core.multi.coexec_tile_sequence`` so the grid walks tiles in
the packer's placement order.  On a megacore TPU the task axis is
``parallel``, so consecutive tasks from different tenants genuinely
co-execute; on a single core they interleave in one launch, which is
already the measured win over per-tenant dispatch (see
``benchmarks/multi_tenant_bench.py``).

Numerics contract: the fused kernel accumulates each output tile in f32
over the same ``bk``-sized K blocks as the sequential per-tenant path,
so fused and sequential results agree bit-for-bit when built from the
same :class:`CoexecPlan` block shapes (asserted in
``tests/test_coexec.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp
import numpy as np

from repro.kernels.runtime import resolve_interpret
from repro.kernels.sisa_gemm import choose_block_config


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class CoexecTenant:
    """One co-resident GEMM: ``C[m, n] = A[m, k] @ B[k, n]``."""

    rid: int
    m: int
    n: int
    k: int

    def __post_init__(self):
        if min(self.m, self.n, self.k) <= 0:
            raise ValueError(f"tenant dims must be positive: {self}")


@dataclasses.dataclass(frozen=True)
class CoexecPlan:
    """Host-side placement of a tenant set into the fused buffers.

    ``meta`` is the kernel's scalar-prefetched tile table, one column per
    grid task: ``[tenant, row_block, col_block, row_hi, k_hi]``.
    ``row_offsets[t]`` is tenant ``t``'s first row in the flat A/C
    buffers (a multiple of ``bm``); ``m_flat/kp/np_pad`` are the padded
    fused buffer extents.
    """

    tenants: Tuple[CoexecTenant, ...]
    bm: int
    bn: int
    bk: int
    m_flat: int
    kp: int
    np_pad: int
    row_offsets: Tuple[int, ...]
    meta: np.ndarray                      # (5, n_tasks) int32

    @property
    def n_tasks(self) -> int:
        return int(self.meta.shape[1])

    @property
    def n_k(self) -> int:
        return self.kp // self.bk

    def tenant_tasks(self, idx: int) -> int:
        """Number of grid tasks owned by tenant ``idx``."""
        return int(np.sum(self.meta[0] == idx))


def interleave_order(task_counts: Sequence[int],
                     sequence: Optional[Sequence[int]] = None) -> List[int]:
    """Flatten per-tenant task queues into one interleaved grid order.

    ``task_counts[t]`` is tenant ``t``'s task count.  Without
    ``sequence`` the tenants are drained round-robin (arrival order, one
    task each — the packer's default placement discipline).  With
    ``sequence`` (tenant indices, e.g. from
    ``repro.core.multi.coexec_tile_sequence``) the queues are drained in
    that order, cycling until every queue is empty, so the grid axis
    follows the event-driven schedule's start times.  Sequence entries
    naming no tenant (a schedule covering more requests than the fused
    tenant set) are ignored, mirroring ``coexec_tile_sequence``'s own
    rid filter.
    """
    remaining = [int(c) for c in task_counts]
    order: List[int] = []
    seq = (list(range(len(remaining))) if sequence is None
           else [t for t in sequence if 0 <= t < len(remaining)])
    if not seq:
        seq = list(range(len(remaining)))
    while sum(remaining):
        progressed = False
        for t in seq:
            if remaining[t] > 0:
                order.append(t)
                remaining[t] -= 1
                progressed = True
        if not progressed:          # sequence names no tenant with work left
            for t, left in enumerate(remaining):
                order.extend([t] * left)
                remaining[t] = 0
    return order


def build_coexec_plan(tenants: Sequence[CoexecTenant],
                      dtype=jnp.float32, *,
                      order: Optional[Sequence[int]] = None,
                      block_rows: Optional[int] = None,
                      block_cols: Optional[int] = None,
                      block_k: Optional[int] = None,
                      m_hint: Optional[int] = None) -> CoexecPlan:
    """Place a tenant set into fused flat buffers and emit the tile table.

    ``bm`` defaults to the slab height for the *smallest* co-resident M
    (scale-in: decode tenants take one row block, a co-resident prefill
    takes many), ``bn``/``bk`` to the §3.2 block choice for the widest
    tenant; all three can be pinned explicitly (``block_rows`` /
    ``block_cols`` / ``block_k``) — the sequential baseline pins them so
    fused and serial execution share one accumulation order.  ``order``
    is a tenant-index sequence (see :func:`interleave_order`); the
    default round-robin already interleaves all tenants.
    """
    tens = tuple(tenants)
    if not tens:
        raise ValueError("build_coexec_plan needs at least one tenant")
    ms = [t.m for t in tens]
    ns = [t.n for t in tens]
    ks = [t.k for t in tens]
    mh = m_hint or min(ms)
    cfg = choose_block_config(mh, max(ns), max(ks), dtype)
    bm = block_rows or cfg.bm
    bn, bk = block_cols or cfg.bn, block_k or cfg.bk
    kp = _round_up(max(ks), bk)
    np_pad = _round_up(max(ns), bn)

    row_offsets: List[int] = []
    off = 0
    for t in tens:
        row_offsets.append(off)
        off += _round_up(t.m, bm)
    m_flat = off

    # Per-tenant task queues: row-major over the tenant's C blocks.
    queues: List[List[Tuple[int, int, int, int, int]]] = []
    for idx, t in enumerate(tens):
        rows = _round_up(t.m, bm) // bm
        cols = _round_up(t.n, bn) // bn
        base = row_offsets[idx] // bm
        queues.append([(idx, base + r, c, row_offsets[idx] + t.m, t.k)
                       for r in range(rows) for c in range(cols)])

    cols_meta: List[Tuple[int, int, int, int, int]] = []
    for idx in interleave_order([len(q) for q in queues], order):
        cols_meta.append(queues[idx].pop(0))
    meta = np.asarray(cols_meta, np.int32).T.copy()
    assert meta.shape == (5, sum(_round_up(t.m, bm) // bm
                                 * _round_up(t.n, bn) // bn for t in tens))
    return CoexecPlan(tenants=tens, bm=bm, bn=bn, bk=bk, m_flat=m_flat,
                      kp=kp, np_pad=np_pad, row_offsets=tuple(row_offsets),
                      meta=meta)


def _coexec_kernel(meta_ref, a_ref, b_ref, o_ref, acc_ref, *,
                   n_k: int, bm: int, bk: int):
    """One grid task = one (tenant, C tile) pair, OS-accumulated over K.

    ``meta`` rows: 0 tenant (B block to DMA), 1 row block, 2 col block,
    3 absolute valid-row end, 4 tenant K depth.  Tiles past their
    tenant's row extent and K steps past its contraction depth never
    touch the MXU — the fused analogue of power-gating.
    """
    t = pl.program_id(0)
    k_step = pl.program_id(1)
    hi = meta_ref[3, t]
    k_hi = meta_ref[4, t]
    row0 = meta_ref[1, t] * bm

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(row0 < hi, k_step * bk < k_hi))
    def _mac():
        acc_ref[...] += jnp.dot(a_ref[...], b_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _drain():
        rows = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0) + row0
        o_ref[...] = jnp.where(rows < hi, acc_ref[...],
                               jnp.zeros_like(acc_ref)).astype(o_ref.dtype)


def _coexec_call(plan: CoexecPlan, a_flat: jax.Array, b_stack: jax.Array,
                 interpret: bool) -> jax.Array:
    bm, bn, bk = plan.bm, plan.bn, plan.bk
    n_k = plan.n_k
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(plan.n_tasks, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda t, kk, mt: (mt[1, t], kk)),
            pl.BlockSpec((1, bk, bn), lambda t, kk, mt: (mt[0, t], kk,
                                                         mt[2, t])),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda t, kk, mt: (mt[1, t],
                                                            mt[2, t])),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_coexec_kernel, n_k=n_k, bm=bm, bk=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((plan.m_flat, plan.np_pad),
                                       a_flat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name=f"coexec_t{len(plan.tenants)}_{bm}x{bn}x{bk}",
    )(jnp.asarray(plan.meta), a_flat, b_stack)


def pack_operands(plan: CoexecPlan, xs: Sequence[jax.Array],
                  ws: Sequence[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Assemble the fused ``(M_flat, Kp)`` A and ``(T, Kp, Np)`` B buffers.

    Zero padding past each tenant's real ``(m, k, n)`` extents keeps the
    shared-K contraction exact: padded A columns multiply padded B rows,
    contributing exact zeros to every accumulator.
    """
    dtype = xs[0].dtype
    a_flat = jnp.zeros((plan.m_flat, plan.kp), dtype)
    b_stack = jnp.zeros((len(plan.tenants), plan.kp, plan.np_pad), dtype)
    for i, (t, x, w) in enumerate(zip(plan.tenants, xs, ws)):
        assert x.shape == (t.m, t.k), (x.shape, t)
        assert w.shape == (t.k, t.n), (w.shape, t)
        off = plan.row_offsets[i]
        a_flat = a_flat.at[off:off + t.m, :t.k].set(x.astype(dtype))
        b_stack = b_stack.at[i, :t.k, :t.n].set(w.astype(dtype))
    return a_flat, b_stack


def run_plan(plan: CoexecPlan, a_flat: jax.Array, b_stack: jax.Array, *,
             interpret: bool = False) -> jax.Array:
    """Launch the fused grid on pre-packed operands.

    The launch-only hot path: ``a_flat``/``b_stack`` come from
    :func:`pack_operands`, the result is the flat ``(M_flat, Np)``
    output for :func:`unpack_outputs`.  Benchmarks time this directly so
    fused-vs-serial ratios compare launch structure, not host-side
    operand packing.
    """
    return _coexec_call(plan, a_flat, b_stack, interpret)


def unpack_outputs(plan: CoexecPlan, out_flat: jax.Array) -> List[jax.Array]:
    """Slice the fused ``(M_flat, Np)`` output back into per-tenant results."""
    outs = []
    for i, t in enumerate(plan.tenants):
        off = plan.row_offsets[i]
        outs.append(out_flat[off:off + t.m, :t.n])
    return outs


def coexec_matmul(xs: Sequence[jax.Array], ws: Sequence[jax.Array], *,
                  order: Optional[Sequence[int]] = None,
                  plan: Optional[CoexecPlan] = None,
                  block_rows: Optional[int] = None,
                  m_hint: Optional[int] = None,
                  interpret: bool = False) -> List[jax.Array]:
    """Execute T heterogeneous GEMMs ``xs[i] @ ws[i]`` in one fused grid.

    ``xs[i]: (mᵢ, kᵢ)``, ``ws[i]: (kᵢ, nᵢ)`` → list of ``(mᵢ, nᵢ)``.
    This is the executable form of a ``pack_requests`` placement: pass
    ``order=multi.coexec_tile_sequence(packed)`` to walk tiles in the
    packer's schedule order (the result is order-independent; only the
    co-residency interleaving changes).  An empty tenant set returns an
    empty list — the empty placement is legal and does nothing.

    A pre-built ``plan`` (same shapes) skips the host-side placement;
    use it to pin block shapes when comparing against a sequential
    per-tenant execution of the same plan.
    """
    if len(xs) != len(ws):
        raise ValueError(f"{len(xs)} activations vs {len(ws)} weights")
    if not xs:
        return []
    tenants = [CoexecTenant(rid=i, m=x.shape[0], n=w.shape[1], k=x.shape[1])
               for i, (x, w) in enumerate(zip(xs, ws))]
    if plan is None:
        plan = build_coexec_plan(tenants, xs[0].dtype, order=order,
                                 block_rows=block_rows, m_hint=m_hint)
    else:
        assert tuple(t.m for t in plan.tenants) == tuple(t.m for t in tenants)
    a_flat, b_stack = pack_operands(plan, xs, ws)
    out = run_plan(plan, a_flat, b_stack, interpret=interpret)
    return unpack_outputs(plan, out)


def single_tenant_plans(plan: CoexecPlan, dtype=jnp.float32) -> List[CoexecPlan]:
    """Per-tenant single-GEMM plans pinned to ``plan``'s block shapes.

    These are what :func:`sequential_matmul` launches back-to-back;
    building them once (outside any timed region) keeps host-side plan
    construction out of fused-vs-serial comparisons.
    """
    return [build_coexec_plan([CoexecTenant(rid=0, m=t.m, n=t.n, k=t.k)],
                              dtype, block_rows=plan.bm,
                              block_cols=plan.bn, block_k=plan.bk)
            for t in plan.tenants]


def sequential_matmul(xs: Sequence[jax.Array], ws: Sequence[jax.Array], *,
                      plan: Optional[CoexecPlan] = None,
                      singles: Optional[Sequence[CoexecPlan]] = None,
                      interpret: bool = False) -> List[jax.Array]:
    """The serial baseline: one kernel launch per tenant, back-to-back.

    Each tenant runs through the *same* co-exec kernel as a
    single-tenant grid with the same block shapes (a shared ``plan``
    pins them; pre-built ``singles`` from :func:`single_tenant_plans`
    skip per-call plan construction), so fused-vs-sequential
    comparisons isolate the co-scheduling — identical MACs, identical
    accumulation order, different launch structure.
    """
    if not xs:
        return []
    if singles is None:
        if plan is None:
            tenants = [CoexecTenant(rid=i, m=x.shape[0], n=w.shape[1],
                                    k=x.shape[1])
                       for i, (x, w) in enumerate(zip(xs, ws))]
            plan = build_coexec_plan(tenants, xs[0].dtype)
        singles = single_tenant_plans(plan, xs[0].dtype)
    outs = []
    for x, w, single in zip(xs, ws, singles):
        outs.extend(coexec_matmul([x], [w], plan=single,
                                  interpret=interpret))
    return outs
