"""Fused paged-attention decode kernel over the flat page pool.

:func:`repro.models.attention.paged_attn_decode_step` used to *gather*
``pool[table]`` into a dense ``(B, max_pages * page_size, ...)`` view
and hand it to the dense SDPA — materializing, per decode step, exactly
the worst-case rectangle the paged allocator exists to avoid.  This
module keeps the pool stationary instead (the DiP/MatrixFlow argument,
one level above the array): the page table is **scalar-prefetched**, so
each grid step's ``BlockSpec`` index map reads ``table[i, j]`` and
Pallas DMAs physical page ``table[i, j]`` straight from the flat pool
into VMEM — K/V never exists in dense logical order anywhere.

Kernel layout (grid ``(B, max_pages_per_slot)``, pages innermost):

* scalar-prefetch operands: the ``(B, max_pages)`` int32 page table and
  the ``(B,)`` per-row write positions;
* VMEM scratch ``(m, l, acc)`` carries a flash-style online softmax
  across the page axis: initialized at page 0, rescaled by
  ``exp(m_old - m_new)`` per page, drained to the output block on the
  last page;
* the per-row ring mask ``j * page_size + offset <= pos_i`` is applied
  *inside* the kernel, so sink/stale pages are DMA'd but never attended
  (pages entirely beyond ``pos_i`` are skipped under ``pl.when``);
* int8 pools dequantize per page in VMEM (``k * scale``) — the pool
  stays quantized in HBM, halving resident bytes again.

Backends (:func:`set_paged_attn_backend`): ``"pallas"`` (TPU),
``"pallas_interpret"`` (the CI kernel leg — same kernel body under the
interpreter), ``"xla"`` (a page-blocked online-softmax twin built on
``lax.scan`` — identical accumulation order, no dense materialization;
the CPU default so serving benches measure compiled code), and
``"gather"`` (the PR-5 dense-gather reference, kept in
``models/attention.py`` for differential testing).  GQA is handled with
a static loop over KV heads so every contraction is a 2D dot (Mosaic
has no batched ``dot_general``).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from repro.kernels.runtime import resolve_interpret

NEG_INF = jnp.finfo(jnp.float32).min

_BACKENDS = ("gather", "xla", "pallas", "pallas_interpret")
_PAGED_ATTN = {"impl": None}


def set_paged_attn_backend(impl: Optional[str]) -> None:
    """Select the paged-attention decode backend process-wide.

    ``None`` restores auto selection (``"pallas"`` on TPU, ``"xla"``
    elsewhere).  ``"pallas_interpret"`` runs the real kernel body under
    the Pallas interpreter (the CI kernel leg); ``"gather"`` is the
    dense-gather reference path in ``models/attention.py``.  Set before
    engines trace their decode windows — the choice is baked into jit
    traces.
    """
    if impl is not None and impl not in _BACKENDS:
        raise ValueError(f"unknown paged-attn backend {impl!r}; "
                         f"pick from {_BACKENDS} or None")
    _PAGED_ATTN["impl"] = impl


def resolve_paged_attn_backend() -> str:
    """The effective paged-attention backend: the explicit override from
    :func:`set_paged_attn_backend`, else ``"pallas"`` on TPU and the
    compiled ``"xla"`` twin everywhere else."""
    impl = _PAGED_ATTN["impl"]
    if impl is not None:
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _dequant_block(x, scale):
    """Per-page dequant: int8 (or any) K/V block * its scale plane."""
    x = x.astype(jnp.float32)
    return x * scale.astype(jnp.float32) if scale is not None else x


def _paged_attn_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                       psz: int, pmax: int, n_rep: int, quant: bool):
    """Grid ``(B, pmax)``: row i, logical page j at physical
    ``table[i, j]``.  Online-softmax scratch carries across j."""
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
        ks_ref = vs_ref = None
    i = pl.program_id(0)
    j = pl.program_id(1)
    p = pos_ref[i]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Pages entirely beyond the row's position hold sink/stale content —
    # skip the math (the DMA still happens; correctness needs the mask
    # below, the `when` is the fast path).
    @pl.when(j * psz <= p)
    def _page():
        q = q_ref[0].astype(jnp.float32)                     # (H, hd)
        k = _dequant_block(k_ref[0],
                           ks_ref[0] if quant else None)     # (psz,Hkv,hd)
        v = _dequant_block(v_ref[0], vs_ref[0] if quant else None)
        hkv = k.shape[1]
        scale = jnp.sqrt(jnp.float32(q.shape[-1]))
        # GQA: query heads h*n_rep..(h+1)*n_rep share KV head h; a
        # static python loop keeps every contraction a 2D dot.
        parts = []
        for h in range(hkv):
            qh = q[h * n_rep:(h + 1) * n_rep]                # (rep, hd)
            parts.append(jax.lax.dot_general(
                qh, k[:, h, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))         # (rep, psz)
        logits = jnp.concatenate(parts, axis=0) / scale      # (H, psz)
        idx = j * psz + jax.lax.broadcasted_iota(jnp.int32, (1, psz), 1)
        logits = jnp.where(idx <= p, logits, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)                      # (H, psz)
        m_ref[...] = m_new
        l_ref[...] = alpha * l_ref[...] + jnp.sum(probs, axis=-1,
                                                  keepdims=True)
        accs = []
        for h in range(hkv):
            accs.append(jax.lax.dot_general(
                probs[h * n_rep:(h + 1) * n_rep], v[:, h, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))         # (rep, hd)
        acc_ref[...] = alpha * acc_ref[...] + jnp.concatenate(accs, axis=0)

    @pl.when(j == pmax - 1)
    def _drain():
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _paged_attention_pallas(q, pk, pv, table, pos, pk_scale, pv_scale,
                            interpret: bool):
    b, n_heads, hd = q.shape
    _, psz, n_kv, _ = pk.shape
    pmax = table.shape[1]
    n_rep = n_heads // n_kv
    quant = pk_scale is not None
    page_block = pl.BlockSpec(
        (1, psz, n_kv, pk.shape[-1]),
        lambda i, j, tbl, ps: (tbl[i, j], 0, 0, 0))
    scale_block = pl.BlockSpec((1, psz, n_kv, 1),
                               lambda i, j, tbl, ps: (tbl[i, j], 0, 0, 0))
    row_block = pl.BlockSpec((1, n_heads, hd),
                             lambda i, j, tbl, ps: (i, 0, 0))
    in_specs = [row_block, page_block, page_block]
    operands = [q, pk, pv]
    if quant:
        in_specs += [scale_block, scale_block]
        operands += [pk_scale, pv_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, pmax),
        in_specs=in_specs,
        out_specs=row_block,
        scratch_shapes=[pltpu.VMEM((n_heads, 1), jnp.float32),
                        pltpu.VMEM((n_heads, 1), jnp.float32),
                        pltpu.VMEM((n_heads, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, psz=psz, pmax=pmax,
                          n_rep=n_rep, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_heads, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name=f"paged_attn_{n_heads}h_{psz}p",
    )(table.astype(jnp.int32), pos.astype(jnp.int32), *operands)


def _paged_attention_xla(q, pk, pv, table, pos, pk_scale, pv_scale):
    """Page-blocked online-softmax twin of the kernel, in pure XLA.

    Scans logical pages; each step gathers one physical page per row
    (``pool[table[:, j]]`` — a (B, psz, ...) working set, never the
    dense rectangle) and folds it into the same (m, l, acc) recurrence
    the kernel carries in scratch.  Numerics are kept op-for-op
    identical to the kernel so backend choice never changes tokens.
    """
    b, n_heads, hd = q.shape
    _, psz, n_kv, _ = pk.shape
    pmax = table.shape[1]
    n_rep = n_heads // n_kv
    qf = q.astype(jnp.float32)
    scale = jnp.sqrt(jnp.float32(hd))
    offs = jnp.arange(psz, dtype=jnp.int32)

    def page_step(carry, j):
        m, l, acc = carry
        phys = table[:, j]                                   # (B,)
        k = pk[phys].astype(jnp.float32)                     # (B,psz,Hkv,hd)
        v = pv[phys].astype(jnp.float32)
        if pk_scale is not None:
            k = k * pk_scale[phys].astype(jnp.float32)
            v = v * pv_scale[phys].astype(jnp.float32)
        if n_rep > 1:
            k = jnp.repeat(k, n_rep, axis=2)                 # (B,psz,H,hd)
            v = jnp.repeat(v, n_rep, axis=2)
        logits = jnp.einsum("bhd,bkhd->bhk", qf, k,
                            preferred_element_type=jnp.float32) / scale
        idx = j * psz + offs                                 # (psz,)
        logits = jnp.where(idx[None, None, :] <= pos[:, None, None],
                           logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        probs = jnp.exp(logits - m_new)
        l = alpha * l + jnp.sum(probs, axis=-1, keepdims=True)
        acc = alpha * acc + jnp.einsum("bhk,bkhd->bhd", probs, v,
                                       preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, n_heads, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_heads, 1), jnp.float32)
    a0 = jnp.zeros((b, n_heads, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(page_step, (m0, l0, a0),
                                  jnp.arange(pmax, dtype=jnp.int32))
    return (acc / l).astype(q.dtype)


def paged_attention(q, pk, pv, table, pos, *,
                    pk_scale=None, pv_scale=None,
                    impl: Optional[str] = None):
    """Fused paged-attention decode: attend rows to their mapped pages.

    Args:
      q: ``(B, n_heads, head_dim)`` post-RoPE queries, one per row.
      pk, pv: flat page pools ``(num_pages(+sink), page_size, n_kv, hd)``
        — float, or int8 when ``pk_scale``/``pv_scale`` (bf16 planes
        ``(pages, page_size, n_kv, 1)``) are given.
      table: ``(B, max_pages_per_slot)`` int32 logical->physical map;
        unmapped tail entries may point anywhere (typically the sink
        page) — the ring mask keeps them unattended.
      pos: ``(B,)`` int32 per-row write positions; row ``i`` attends
        logical positions ``<= pos[i]`` only.
      impl: backend override for this call (defaults to
        :func:`resolve_paged_attn_backend`); ``"gather"`` is not valid
        here — that reference lives in ``models/attention.py``.

    Returns ``(B, n_heads, head_dim)`` attention outputs in ``q.dtype``.
    """
    impl = impl or resolve_paged_attn_backend()
    if impl == "xla":
        return _paged_attention_xla(q, pk, pv, table, pos,
                                    pk_scale, pv_scale)
    if impl in ("pallas", "pallas_interpret"):
        return _paged_attention_pallas(q, pk, pv, table, pos,
                                       pk_scale, pv_scale,
                                       interpret=impl == "pallas_interpret")
    raise ValueError(f"paged_attention cannot dispatch impl={impl!r}")


def kv_pool_layout(n_heads: int, n_kv_heads: int, ms: int) -> str:
    """How a paged KV pool spreads over a ``model`` axis of size ``ms``.

    * ``"heads"`` — both head counts divide ``ms``: each shard holds its
      query heads and their KV heads, so the GQA group ratio survives.
    * ``"replicated"`` — multi-query attention (one KV head) with the
      query heads dividing ``ms``: query heads split, and every shard
      holds the whole single-head pool (``2 * head_dim`` elements per
      token and layer).
    * ``"seq"`` — anything else (or no model axis): the page interior
      splits and attention runs unsharded under GSPMD.

    The one rule behind :func:`paged_attention_sharded`,
    ``distributed.sharding.cache_specs`` and the ``kv_pool`` activation
    role, so pool shardings never disagree between allocation and the
    decode step.
    """
    if ms > 1 and n_heads % ms == 0:
        if n_kv_heads % ms == 0:
            return "heads"
        if n_kv_heads == 1:
            return "replicated"
    return "seq"


def paged_attention_sharded(q, pk, pv, table, pos, *, mesh,
                            model_axis: str = "model",
                            pk_scale=None, pv_scale=None,
                            impl: Optional[str] = None):
    """Tensor-parallel :func:`paged_attention` under ``shard_map``.

    Heads are embarrassingly parallel in the online-softmax recurrence,
    so each ``model``-axis shard runs the *unmodified* kernel (fused
    Pallas or its XLA twin — whichever ``impl`` resolves to) over its
    own slice of the query heads, against the pool as
    :func:`kv_pool_layout` places it:

    * ``q``: ``P(None, model, None)`` — query heads split;
    * ``pk``/``pv`` (+ scale planes): ``P(None, None, model, None)`` —
      KV heads split — or replicated for multi-query attention, where
      every shard's query heads share the one KV head; the *page* axis
      is never split (the table indexes pages globally);
    * ``table``/``pos``: replicated.

    Without a model axis the plain call runs.  For a ``"seq"`` layout on
    a real model axis the XLA twin runs unsharded under GSPMD, and the
    Pallas kernel — a custom call GSPMD cannot partition — raises
    instead of being fed an all-gathered pool on every step.
    """
    from jax.sharding import PartitionSpec as P

    ms = mesh.shape[model_axis] if model_axis in mesh.axis_names else 1
    # Resolve the backend *outside* shard_map so a context-manager
    # override at trace time is honored inside every shard.
    impl = impl or resolve_paged_attn_backend()
    layout = kv_pool_layout(q.shape[1], pk.shape[2], ms)
    if layout == "seq":
        if ms > 1 and impl == "pallas":
            raise ValueError(
                f"paged_attn: {q.shape[1]} query / {pk.shape[2]} KV heads "
                f"do not shard over a {ms}-way model axis; the Pallas "
                "kernel cannot run on a page-split pool")
        return paged_attention(q, pk, pv, table, pos,
                               pk_scale=pk_scale, pv_scale=pv_scale,
                               impl=impl)
    head = P(None, model_axis, None)
    pool = P(None, None, model_axis, None) if layout == "heads" else P()
    args = [q, pk, pv, table, pos]
    in_specs = [head, pool, pool, P(None, None), P(None)]
    if pk_scale is not None:
        args += [pk_scale, pv_scale]
        in_specs += [pool, pool]

    def shard_fn(q_, pk_, pv_, tbl_, pos_, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention(q_, pk_, pv_, tbl_, pos_,
                               pk_scale=ks, pv_scale=vs, impl=impl)

    return jax.shard_map(shard_fn, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=head, check_vma=False)(*args)


def quantize_page_pool(x) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization over the head dim (the pool layout's
    per-page scale planes): returns ``(int8 values, bf16 scales)`` with
    ``scale = max|x| / 127 + eps`` per (page, offset, kv-head) cell —
    numerics shared with the dense cache's ``_quant_kv``."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)
