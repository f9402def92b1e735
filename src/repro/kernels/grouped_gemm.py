"""Flat ragged grouped GEMM: megablocks-style layout, trainable, one call.

PR 1's ragged kernel proved the scale-in idea but kept the monolithic
``(G, C, d)`` capacity-padded layout the paper argues against (§4.3's
skewed-M regimes).  This module replaces it with a *flat* token layout:

* activations live in one ``(sum(M̃ᵢ), d)`` buffer where group ``g``'s
  rows occupy ``[offsets[g], offsets[g] + sizes[g])`` and ``offsets`` are
  *cumulative* — rounded up to the row-block (slab height), not to a
  per-group capacity ``C``.  Padding waste is bounded by one row block
  per group instead of ``C - Mᵢ`` rows, and no ``(G, C)`` tensor is ever
  materialized;
* tile ownership is resolved on the host into scalar-prefetched per-tile
  metadata (owning group, valid-row extent), so the kernel's weight
  ``BlockSpec`` DMAs exactly one expert block per row tile — the
  megablocks block-diagonal schedule on MXU tiles;
* a ``jax.custom_vjp`` makes the path trainable: dX reuses the *same*
  flat kernel with ``Wᵀ`` (identical skew), dW runs a segment-sum kernel
  that contracts each group's row range into its ``(d, f)`` gradient;
* :func:`segment_grouped_gemm` generalizes from prefix groups to
  arbitrary *segments* ``(start, size, group)`` — the layout produced by
  ``EP_IMPL="all_to_all"``'s post-exchange buffers, where each expert's
  rows are ``ms`` non-prefix slices (one per source rank).

The old ``ragged_grouped_gemm(x: (G, C, d), ...)`` entry point survives
as a thin shim that reshapes through the flat path (and is now
differentiable as a side effect).

Public API
----------
``flat_ragged_gemm(x, w, group_sizes, group_offsets=None, ...)``
    The grouped GEMM: ``x: (M, d)`` flat tokens against ``w: (G, d, f)``
    where group ``g`` owns rows ``[offsets[g], offsets[g] + sizes[g])``.
    ``group_offsets`` defaults to :func:`flat_group_offsets` (cumulative
    block-aligned starts).  Differentiable (see *VJP semantics*).
``segment_grouped_gemm(x, w, seg_starts, seg_sizes, seg_gids, ...)``
    Generalization to arbitrary *segments*: segment ``s`` covers rows
    ``[starts[s], starts[s] + sizes[s])`` and contracts against
    ``w[gids[s]]``.  Starts must be ascending and gids non-decreasing;
    several segments may share one gid (the ``EP_IMPL="all_to_all"``
    post-exchange layout — :func:`a2a_segments` builds the table).
``ragged_grouped_gemm(x, w, group_sizes, ...)``
    Capacity-layout shim: ``x: (G, C, d) -> (G, C, f)``; reshapes
    through the flat path.  New code should lay tokens out flat.
``flat_block_rows`` / ``aligned_block_rows`` / ``flat_group_offsets``
    Layout helpers: the row block the kernels will pick, the largest
    row block dividing a fixed stride, and cumulative aligned offsets.
``packed_decode_matmul(xs, w, ...)``
    Shared-weight co-scheduled decode: concatenates the requests into
    one tall GEMM.  For *per-tenant* weights use
    ``repro.kernels.coexec`` instead.

VJP semantics
-------------
The segment kernels carry a ``jax.custom_vjp``: dX = dY·Wᵀ reuses the
*same* flat kernel (``w.swapaxes(1, 2)`` — identical M-skew, identical
tile ownership), and dW[g] = X[rows g]ᵀ·dY[rows g] runs a dedicated
segment-sum kernel whose accumulator initializes/drains at each group's
first/last row tile.  Integer layout arguments (sizes, offsets,
segments) get no cotangent; gradients match the dense reference to f32
accumulation tolerance (see ``tests/test_grouped_flat.py``).  Groups
with zero rows receive exactly-zero dW blocks.

Alignment invariants
--------------------
* Every segment/group start must be a multiple of the row block
  ``block_rows`` (build layouts with :func:`flat_group_offsets` /
  :func:`aligned_block_rows`), so each MXU row tile is owned by exactly
  one group; weight raggedness is masked at the tile's tail rows, never
  split across owners.
* ``seg_starts`` ascending, ``seg_gids`` non-decreasing — required by
  the dW segment-sum's init/drain flags.
* Rows covered by no segment produce zeros and are never MAC'd — the
  kernel-side power gating of slabs above ``ceil(Mᵢ/slab_h)``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from repro.kernels.runtime import resolve_interpret
from repro.kernels.sisa_gemm import choose_block_config


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def flat_block_rows(m_hint: int, n: int, k: int, dtype=jnp.float32) -> int:
    """Row-block (slab height) the flat kernels will use for this problem;
    segment starts must be aligned to it."""
    return choose_block_config(m_hint, n, k, dtype).bm


def aligned_block_rows(m_hint: int, n: int, k: int, dtype=jnp.float32,
                       align_to: Optional[int] = None) -> int:
    """Row block that additionally divides ``align_to`` (static).

    The segment kernels require every segment start to be a multiple of
    the row block.  When a caller's layout fixes the stride between
    segment starts — e.g. the all_to_all dispatch, whose segments sit at
    multiples of the (8-aligned) expert capacity — the block must divide
    that stride.  Keeping the reduction here, next to the kernels that
    enforce the contract, saves every call site from re-deriving it.
    """
    bm = flat_block_rows(m_hint, n, k, dtype)
    if align_to is not None:
        while align_to % bm:
            bm //= 2
        assert bm >= 1, (align_to, bm)
    return bm


def flat_group_offsets(group_sizes: jax.Array, block_rows: int) -> jax.Array:
    """Cumulative block-aligned offsets for a flat prefix layout.

    ``(G,) -> (G+1,)``: group ``g`` owns rows
    ``[offsets[g], offsets[g] + sizes[g])``; consecutive groups are
    separated by at most ``block_rows - 1`` alignment rows (one slab), in
    contrast to the capacity layout's ``C - Mᵢ``.
    """
    sizes = jnp.asarray(group_sizes, jnp.int32)
    aligned = ((sizes + block_rows - 1) // block_rows) * block_rows
    zero = jnp.zeros((1,), jnp.int32)
    return jnp.concatenate([zero, jnp.cumsum(aligned)])


def _tile_metadata(seg_starts: jax.Array, seg_sizes: jax.Array,
                   seg_gids: jax.Array, n_mt: int, bm: int,
                   visits: bool) -> jax.Array:
    """Per-row-tile ownership table, scalar-prefetched into SMEM.

    Row 0: owning group id (weight block to DMA); row 1: ``hi`` — the
    absolute end of the tile's valid rows (``hi <= i*bm`` marks a fully
    invalid tile: alignment gap or flat-buffer tail).  With ``visits``,
    rows 2/3 flag the first/last tile of each group run — the dW kernel's
    accumulator init/drain points.
    """
    row0 = jnp.arange(n_mt, dtype=jnp.int32) * bm
    s = jnp.searchsorted(seg_starts, row0, side="right").astype(jnp.int32) - 1
    s = jnp.clip(s, 0, seg_starts.shape[0] - 1)
    gid = seg_gids[s]
    hi = seg_starts[s] + seg_sizes[s]
    hi = jnp.where(row0 >= seg_starts[s], hi, 0)   # tiles before segment 0
    if not visits:
        return jnp.stack([gid, hi])
    first = jnp.concatenate([jnp.ones((1,), jnp.int32),
                             (gid[1:] != gid[:-1]).astype(jnp.int32)])
    last = jnp.concatenate([(gid[1:] != gid[:-1]).astype(jnp.int32),
                            jnp.ones((1,), jnp.int32)])
    return jnp.stack([gid, hi, first, last])


def _flat_fwd_kernel(meta_ref, x_ref, w_ref, o_ref, acc_ref, *,
                     n_k: int, bm: int):
    """Output-stationary flat GEMM: tile i contracts against the weight
    block of its owning group; invalid/tail rows are masked at drain."""
    i = pl.program_id(0)
    k_step = pl.program_id(2)
    hi = meta_ref[1, i]
    row0 = i * bm

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Scale-in: tiles past their segment's extent never touch the MXU.
    @pl.when(row0 < hi)
    def _mac():
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _drain():
        rows = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0) + row0
        o_ref[...] = jnp.where(rows < hi, acc_ref[...],
                               jnp.zeros_like(acc_ref)).astype(o_ref.dtype)


def _flat_dw_kernel(meta_ref, x_ref, dy_ref, dw_ref, acc_ref, *, bm: int):
    """Segment-sum dW: accumulate ``Xᵀ @ dY`` over each group's row tiles
    (grid sweeps tiles innermost; gid runs are contiguous by contract)."""
    i = pl.program_id(2)
    hi = meta_ref[1, i]
    row0 = i * bm

    @pl.when(meta_ref[2, i] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(row0 < hi)
    def _mac():
        rows = jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 0) + row0
        xm = jnp.where(rows < hi, x_ref[...], jnp.zeros_like(x_ref))
        acc_ref[...] += jax.lax.dot_general(
            xm, dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(meta_ref[3, i] == 1)
    def _drain():
        dw_ref[0] = acc_ref[...].astype(dw_ref.dtype)


def _flat_forward(x, w, starts, sizes, gids, *, bm, m_hint, interpret):
    m, d = x.shape
    g, d2, f = w.shape
    assert d == d2, (x.shape, w.shape)
    cfg = choose_block_config(min(m_hint, max(m, 1)), f, d, x.dtype)
    bd, bf = cfg.bk, cfg.bn
    mp, dp, fp = _round_up(m, bm), _round_up(d, bd), _round_up(f, bf)
    if (mp, dp) != (m, d):
        x = jnp.pad(x, ((0, mp - m), (0, dp - d)))
    if (dp, fp) != (d, f):
        w = jnp.pad(w, ((0, 0), (0, dp - d), (0, fp - f)))
    n_mt, n_f, n_k = mp // bm, fp // bf, dp // bd
    meta = _tile_metadata(starts, sizes, gids, n_mt, bm, visits=False)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_mt, n_f, n_k),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda i, j, kk, mt: (i, kk)),
            pl.BlockSpec((1, bd, bf), lambda i, j, kk, mt: (mt[0, i], kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j, kk, mt: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bf), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_flat_fwd_kernel, n_k=n_k, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp, fp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name=f"flat_grouped_gemm_g{g}_{bm}x{bf}x{bd}",
    )(meta, x, w)
    return out[:m, :f]


def _flat_dw(x, dy, starts, sizes, gids, n_groups, *, bm, m_hint, interpret):
    m, d = x.shape
    m2, f = dy.shape
    assert m == m2, (x.shape, dy.shape)
    cfg = choose_block_config(min(m_hint, max(m, 1)), f, d, x.dtype)
    bd, bf = min(cfg.bk, 512), min(cfg.bn, 512)
    mp, dp, fp = _round_up(m, bm), _round_up(d, bd), _round_up(f, bf)
    if (mp, dp) != (m, d):
        x = jnp.pad(x, ((0, mp - m), (0, dp - d)))
    if (mp, fp) != (m, f):
        dy = jnp.pad(dy, ((0, mp - m), (0, fp - f)))
    n_mt, n_d, n_f = mp // bm, dp // bd, fp // bf
    meta = _tile_metadata(starts, sizes, gids, n_mt, bm, visits=True)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_d, n_f, n_mt),
        in_specs=[
            pl.BlockSpec((bm, bd), lambda dd, ff, i, mt: (i, dd)),
            pl.BlockSpec((bm, bf), lambda dd, ff, i, mt: (i, ff)),
        ],
        out_specs=pl.BlockSpec((1, bd, bf),
                               lambda dd, ff, i, mt: (mt[0, i], dd, ff)),
        scratch_shapes=[pltpu.VMEM((bd, bf), jnp.float32)],
    )
    dw = pl.pallas_call(
        functools.partial(_flat_dw_kernel, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, dp, fp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name=f"flat_grouped_dw_g{n_groups}_{bm}x{bf}x{bd}",
    )(meta, x, dy)[:, :d, :f]
    # Groups with no rows own no tiles: their blocks are never written.
    rows_per_group = jnp.zeros((n_groups,), jnp.int32).at[gids].add(sizes)
    return jnp.where(rows_per_group[:, None, None] > 0, dw,
                     jnp.zeros_like(dw))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _segment_gemm(bm: int, m_hint: int, interpret: bool,
                  x, w, starts, sizes, gids):
    return _flat_forward(x, w, starts, sizes, gids, bm=bm, m_hint=m_hint,
                         interpret=interpret)


def _segment_gemm_fwd(bm, m_hint, interpret, x, w, starts, sizes, gids):
    out = _flat_forward(x, w, starts, sizes, gids, bm=bm, m_hint=m_hint,
                        interpret=interpret)
    return out, (x, w, starts, sizes, gids)


def _segment_gemm_bwd(bm, m_hint, interpret, res, dy):
    x, w, starts, sizes, gids = res
    dy = dy.astype(x.dtype)
    # dX = dY @ Wᵀ: the same ragged skew, the same flat kernel.
    dx = _flat_forward(dy, w.swapaxes(1, 2), starts, sizes, gids,
                       bm=bm, m_hint=m_hint, interpret=interpret)
    # dW[g] = X[rows g]ᵀ @ dY[rows g]: segment-sum kernel.
    dw = _flat_dw(x, dy, starts, sizes, gids, w.shape[0],
                  bm=bm, m_hint=m_hint, interpret=interpret)
    return dx.astype(x.dtype), dw.astype(w.dtype), None, None, None


_segment_gemm.defvjp(_segment_gemm_fwd, _segment_gemm_bwd)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "m_hint", "interpret"))
def segment_grouped_gemm(x: jax.Array, w: jax.Array, seg_starts: jax.Array,
                         seg_sizes: jax.Array, seg_gids: jax.Array, *,
                         block_rows: Optional[int] = None,
                         m_hint: Optional[int] = None,
                         interpret: bool = False) -> jax.Array:
    """x: (M, d), w: (G, d, f) -> (M, f) over arbitrary row segments.

    Segment ``s`` covers rows ``[seg_starts[s], seg_starts[s] +
    seg_sizes[s])`` and contracts against ``w[seg_gids[s]]``.  Starts
    must be ascending, multiples of ``block_rows``, with ``seg_gids``
    non-decreasing (required by the dW segment-sum); rows outside every
    segment yield zeros and skip the MXU.  This is the
    ``EP_IMPL="all_to_all"`` layout: each expert's post-exchange rows are
    ``ms`` non-prefix slices, one per source rank.
    """
    m, d = x.shape
    g, _, f = w.shape
    mh = m_hint or 128
    bm = block_rows or flat_block_rows(mh, f, d, x.dtype)
    return _segment_gemm(bm, mh, bool(interpret), x, w,
                         jnp.asarray(seg_starts, jnp.int32),
                         jnp.asarray(seg_sizes, jnp.int32),
                         jnp.asarray(seg_gids, jnp.int32))


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "m_hint", "interpret"))
def flat_ragged_gemm(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                     group_offsets: Optional[jax.Array] = None, *,
                     block_rows: Optional[int] = None,
                     m_hint: Optional[int] = None,
                     interpret: bool = False) -> jax.Array:
    """x: (M, d) flat tokens, w: (G, d, f), sizes: (G,) -> (M, f).

    Group ``g``'s rows live at ``[offsets[g], offsets[g] + sizes[g])``;
    ``group_offsets`` (``(G,)`` starts or ``(G+1,)`` cumulative) defaults
    to :func:`flat_group_offsets` — block-aligned cumulative sums, *not*
    a per-group capacity stride.  Differentiable: dX reuses this kernel,
    dW runs the segment-sum kernel.
    """
    m, d = x.shape
    g, _, f = w.shape
    mh = m_hint or 128
    bm = block_rows or flat_block_rows(mh, f, d, x.dtype)
    sizes = jnp.asarray(group_sizes, jnp.int32)
    if group_offsets is None:
        starts = flat_group_offsets(sizes, bm)[:g]
    else:
        starts = jnp.asarray(group_offsets, jnp.int32)[:g]
    return _segment_gemm(bm, mh, bool(interpret), x, w, starts, sizes,
                         jnp.arange(g, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("m_hint", "interpret"))
def ragged_grouped_gemm(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
                        *, m_hint: Optional[int] = None,
                        interpret: bool = False) -> jax.Array:
    """Capacity-layout shim: x: (G, C, d), w: (G, d, f) -> (G, C, f).

    Kept for callers that still hold ``(G, C, d)`` buffers; execution
    reshapes through the flat kernel (group ``g`` at offset ``g * C``),
    so rows ``>= group_sizes[g]`` are zero in the output and skipped by
    the MACs.  New code should lay tokens out flat and call
    :func:`flat_ragged_gemm` directly.
    """
    g, c, d = x.shape
    g2, d2, f = w.shape
    assert g == g2 and d == d2, (x.shape, w.shape)
    assert group_sizes.shape == (g,), (group_sizes.shape, g)
    mh = min(m_hint or c, c)
    cp = _round_up(c, 8)
    bm = aligned_block_rows(mh, f, d, x.dtype, align_to=cp)
    if cp != c:
        x = jnp.pad(x, ((0, 0), (0, cp - c), (0, 0)))
    starts = jnp.arange(g, dtype=jnp.int32) * cp
    out = _segment_gemm(bm, mh, bool(interpret), x.reshape(g * cp, d), w,
                        starts, jnp.asarray(group_sizes, jnp.int32),
                        jnp.arange(g, dtype=jnp.int32))
    return out.reshape(g, cp, f)[:, :c, :]


def a2a_segments(e_local: int, ms: int, cap: int,
                 recv_sizes: jax.Array) -> Tuple[jax.Array, jax.Array,
                                                 jax.Array]:
    """Segment table for a flattened post-all_to_all dispatch buffer.

    The exchanged buffer is ``(e_local, ms * cap, d)``: local expert
    ``j``'s rows from source rank ``r`` form a dense prefix of
    ``recv_sizes[r, j]`` rows inside slice ``[r*cap, (r+1)*cap)`` — a
    non-prefix segment per (expert, rank).  Flattened row-major, segment
    ``(j, r)`` starts at ``(j*ms + r) * cap``; starts are ``cap``-aligned
    and gids expert-major (non-decreasing), as the kernels require.
    """
    starts = jnp.arange(e_local * ms, dtype=jnp.int32) * cap
    sizes = jnp.transpose(jnp.asarray(recv_sizes, jnp.int32)).reshape(-1)
    gids = jnp.repeat(jnp.arange(e_local, dtype=jnp.int32), ms)
    return starts, sizes, gids


def packed_decode_matmul(xs, w, *, interpret: bool = False) -> list:
    """Batched heterogeneous decode: many (mᵢ, K) activations against one
    weight (K, N), e.g. the co-scheduled per-request GEMMs the slab packer
    admits together.  Shared weights make this a concatenation — the
    kernel sees one tall GEMM and the SISA block scheduler tiles it —
    then the outputs are split back per request.
    """
    from repro.kernels.ops import _pallas_matmul
    sizes = [x.shape[0] for x in xs]
    cat = jnp.concatenate(xs, axis=0)
    out = _pallas_matmul(cat, w, interpret=interpret)
    outs = []
    off = 0
    for s in sizes:
        outs.append(out[off:off + s])
        off += s
    return outs
