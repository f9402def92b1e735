"""Mixture-of-Experts FFN with expert parallelism.

Token-choice top-k routing with per-expert capacity (Switch-style
position-in-expert cumsum).  Expert weights are sharded over the mesh
``model`` axis (EP); tokens stay sharded over the batch axes and
replicated over ``model``, each rank computes *its* experts for all local
tokens and the outputs are ``psum``-combined — collectives are explicit
via ``shard_map``, no GSPMD guessing (DESIGN.md §5).

The per-expert GEMM batch is ``(E_local, capacity, d)`` — exactly the
small-and-variable-M skewed GEMM regime SISA targets (DESIGN.md §4);
on TPU it lowers through ``repro.kernels.moe_gemm`` tiles.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import activation, Array, dense_init


def moe_init(key, cfg, dtype):
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "up": jnp.stack([dense_init(k, d, ff, dtype)
                         for k in jax.random.split(ks[1], e)]),
        "down": jnp.stack([dense_init(k, ff, d, dtype)
                           for k in jax.random.split(ks[2], e)]),
    }
    if cfg.gated_mlp:
        p["gate"] = jnp.stack([dense_init(k, d, ff, dtype)
                               for k in jax.random.split(ks[3], e)])
    return p


def _capacity(n_tokens: int, n_experts: int, top_k: int,
              factor: float) -> int:
    cap = math.ceil(top_k * n_tokens / n_experts * factor)
    return max(8, ((cap + 7) // 8) * 8)          # sublane-aligned


def _dynamic_capacity(n_real, n_static: int, cfg) -> Array:
    """Capacity threshold for a *traced* real-token count.

    Bucketed prefill routes a right-padded (static ``n_static``-token)
    batch but must drop exactly the tokens an exact-length prefill
    would, i.e. apply ``_capacity(n_real)``.  ``math.ceil`` on floats is
    not safely reproducible inside a trace, so precompute the exact
    table over every possible real count and gather.
    """
    moe_cfg = cfg.moe
    table = jnp.asarray(
        [_capacity(i, moe_cfg.n_experts, moe_cfg.top_k,
                   moe_cfg.capacity_factor)
         for i in range(n_static + 1)], jnp.int32)
    return table[jnp.clip(n_real, 0, n_static)]


def _moe_local(x: Array, p, cfg, act: str, e_offset: int, e_local: int,
               model_axis: Optional[str],
               valid: Optional[Array] = None) -> Tuple[Array, Array]:
    """Per-shard MoE. x: (B_loc, S, d) replicated over the model axis.

    ``valid`` (B_loc, S) bool marks real (non-pad) tokens under bucketed
    prefill: pad tokens neither claim capacity slots nor shift real
    tokens' position-in-expert, and the keep threshold is the capacity
    the real token count alone would get — routing is exactly that of an
    exact-length prefill (pads read back zero).
    """
    b, s, d = x.shape
    n = b * s
    moe_cfg = cfg.moe
    e = moe_cfg.n_experts
    cap = _capacity(n, e, moe_cfg.top_k, moe_cfg.capacity_factor)
    xt = x.reshape(n, d)

    gates = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(gates, axis=-1)
    topw, topi = jax.lax.top_k(probs, moe_cfg.top_k)      # (n, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    flat_e = topi.reshape(-1)                             # (n*k,)
    flat_w = topw.reshape(-1)
    tok_of = jnp.arange(n * moe_cfg.top_k) // moe_cfg.top_k
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    if valid is not None:
        pair_valid = valid.reshape(-1)[tok_of]
        onehot = onehot * pair_valid[:, None].astype(jnp.int32)
        dyn_cap = _dynamic_capacity(jnp.sum(valid.astype(jnp.int32)),
                                    n, cfg)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    if valid is not None:
        keep = (pos < dyn_cap) & pair_valid
    else:
        keep = pos < cap
    is_local = (flat_e >= e_offset) & (flat_e < e_offset + e_local) & keep
    le = jnp.clip(flat_e - e_offset, 0, e_local - 1)
    lp = jnp.clip(pos, 0, cap - 1)

    # Ragged per-expert row counts: min(#routed, cap) rows per expert.
    # (dynamic_slice: e_offset is a traced axis_index under shard_map.)
    counts = jax.lax.dynamic_slice_in_dim(jnp.sum(onehot, axis=0),
                                          e_offset, e_local)
    sizes = jnp.minimum(counts, dyn_cap if valid is not None else cap)
    vals = jnp.where(is_local[:, None], xt[tok_of], 0).astype(x.dtype)

    if EXPERT_BACKEND["impl"] != "xla":
        # Flat megablocks-style dispatch: one (sum(M̃ᵢ), d) buffer with
        # block-aligned *cumulative* offsets — no (E_loc, cap) capacity
        # padding is materialized; alignment waste is < one row block per
        # expert and tiles past an expert's extent skip the MXU.
        from repro.kernels.grouped_gemm import (flat_block_rows,
                                                flat_group_offsets)
        ff = p["up"].shape[-1]
        m_hint = min(cap, 64)
        bm = flat_block_rows(m_hint, ff, d, x.dtype)
        offs = flat_group_offsets(sizes, bm)          # (E_loc + 1,)
        m_flat = e_local * (-(-cap // bm)) * bm       # static upper bound
        dst = offs[le] + lp
        flat = jnp.zeros((m_flat, d), x.dtype).at[dst].add(vals)
        segments = (offs[:-1], sizes,
                    jnp.arange(e_local, dtype=jnp.int32), bm, m_hint)
        out_flat = _expert_ffn(flat, p, act, segments=segments)
        pair_out = out_flat[dst] \
            * (is_local * flat_w)[:, None].astype(x.dtype)
    else:
        # Dense path: (E_loc, cap, d) buffer, capacity-padded einsum
        # (composes with GSPMD; masked pairs contribute zeros).
        buf = jnp.zeros((e_local, cap, d), x.dtype).at[le, lp].add(vals)
        out_e = _expert_ffn(buf, p, act, sizes=sizes)
        pair_out = out_e[le, lp] \
            * (is_local * flat_w)[:, None].astype(x.dtype)
    y = jnp.sum(pair_out.reshape(n, moe_cfg.top_k, d), axis=1)
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)
    # Aux: load-balancing loss ingredients (mean prob x mean assignment).
    density = jnp.mean(jax.nn.one_hot(topi, e, dtype=jnp.float32),
                       axis=(0, 1))
    aux = jnp.sum(jnp.mean(probs, axis=0) * density) * e
    return y.reshape(b, s, d), aux


# "psum": tokens replicated over the model axis, each rank computes its
#         experts for all tokens, outputs psum-combined (robust; decode).
# "all_to_all": tokens sequence-sharded over the model axis; dispatch
#         buffers exchanged with two all_to_alls (canonical EP — ~6x less
#         collective traffic and 1/ms the dispatch compute; §Perf #B).
EP_IMPL = {"impl": "psum"}


def set_ep_impl(impl: str) -> None:
    assert impl in ("psum", "all_to_all")
    EP_IMPL["impl"] = impl


# "xla": dense einsum over the capacity-padded buffer (default; composes
#        with GSPMD).  "pallas"/"pallas_interpret": the *flat* grouped
#        kernel (repro.kernels.grouped_gemm) — tokens are dispatched into
#        one (sum(M̃ᵢ), d) buffer at block-aligned cumulative offsets and
#        both EP impls ("psum" prefix groups, "all_to_all" per-rank
#        segments) lower through it; row tiles past an expert's real
#        batch skip the MXU, the kernel-side analogue of giving idle
#        slabs to other tenants.  Differentiable (custom VJP), so the
#        kernel path is trainable end-to-end.
EXPERT_BACKEND = {"impl": "xla"}


def set_expert_backend(impl: str) -> None:
    assert impl in ("xla", "pallas", "pallas_interpret")
    EXPERT_BACKEND["impl"] = impl


def _grouped(x: Array, w_edf: Array, sizes, segments=None) -> Array:
    """Per-expert contraction, ragged-aware when a kernel backend is on.

    ``segments`` = ``(starts, sizes, gids, block_rows, m_hint)`` selects
    the flat layout: ``x`` is ``(M, d)`` and each row segment contracts
    against its expert's weight through the flat SISA kernel.  Otherwise
    ``x`` is the dense ``(E_loc, C, d)`` buffer.
    """
    impl = EXPERT_BACKEND["impl"]
    if segments is not None:
        from repro.kernels.grouped_gemm import segment_grouped_gemm
        starts, seg_sizes, gids, bm, m_hint = segments
        return segment_grouped_gemm(
            x, w_edf.astype(x.dtype), starts, seg_sizes, gids,
            block_rows=bm, m_hint=m_hint,
            interpret=(impl == "pallas_interpret")).astype(jnp.float32)
    if impl != "xla" and sizes is not None:
        from repro.kernels.grouped_gemm import ragged_grouped_gemm
        return ragged_grouped_gemm(
            x, w_edf.astype(x.dtype), sizes,
            interpret=(impl == "pallas_interpret")).astype(jnp.float32)
    return jnp.einsum("ecd,edf->ecf", x, w_edf,
                      preferred_element_type=jnp.float32)


def _expert_ffn(buf: Array, p, act: str, sizes=None, segments=None) -> Array:
    """Local-expert FFN over either layout.

    Dense: ``(E_loc, C, d) -> (E_loc, C, d)`` with optional ``sizes``
    (E_loc,) when rows form a dense prefix.  Flat: ``(M, d) -> (M, d)``
    with ``segments`` metadata (see :func:`_grouped`).
    """
    h = _grouped(buf, p["up"], sizes, segments)
    if "gate" in p:
        g = _grouped(buf, p["gate"], sizes, segments)
        h = activation(act)(g) * h
    else:
        h = activation(act)(h)
    return _grouped(h.astype(buf.dtype), p["down"], sizes,
                    segments).astype(buf.dtype)


def _moe_a2a(x: Array, p, cfg, act: str, model_axis: str, ms: int,
             valid: Optional[Array] = None) -> Tuple[Array, Array]:
    """All-to-all EP over sequence-sharded tokens. x: (B, S_loc, d).

    ``valid`` masks pad tokens per shard exactly as in
    :func:`_moe_local` (capacity is per-shard either way, so the
    dynamic threshold uses the shard's real count).
    """
    b, s, d = x.shape
    n = b * s
    moe_cfg = cfg.moe
    e = moe_cfg.n_experts
    cap = _capacity(n, e, moe_cfg.top_k, moe_cfg.capacity_factor)
    xt = x.reshape(n, d)

    gates = jnp.einsum("nd,de->ne", xt.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(gates, axis=-1)
    topw, topi = jax.lax.top_k(probs, moe_cfg.top_k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

    flat_e = topi.reshape(-1)
    flat_w = topw.reshape(-1)
    tok_of = jnp.arange(n * moe_cfg.top_k) // moe_cfg.top_k
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    if valid is not None:
        pair_valid = valid.reshape(-1)[tok_of]
        onehot = onehot * pair_valid[:, None].astype(jnp.int32)
        dyn_cap = _dynamic_capacity(jnp.sum(valid.astype(jnp.int32)),
                                    n, cfg)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    if valid is not None:
        keep = (pos < dyn_cap) & pair_valid
    else:
        keep = pos < cap
    lp = jnp.clip(pos, 0, cap - 1)
    vals = jnp.where(keep[:, None], xt[tok_of], 0).astype(x.dtype)
    buf = jnp.zeros((e, cap, d), x.dtype).at[flat_e, lp].add(vals)

    # exchange: (E, C, d) -> (E/ms, ms*C, d): every rank keeps its experts
    buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                             tiled=True)
    if EXPERT_BACKEND["impl"] != "xla":
        # Post-exchange rows are *non-prefix* segments: local expert j
        # holds one dense prefix per source rank inside [r*cap, (r+1)*cap).
        # Exchange the per-expert row counts alongside the tokens and
        # lower through the segment-offset flat kernel.
        from repro.kernels.grouped_gemm import a2a_segments, aligned_block_rows
        e_local = e // ms
        sizes = jnp.minimum(jnp.sum(onehot, axis=0),
                            dyn_cap if valid is not None else cap)  # (E,)
        recv = jax.lax.all_to_all(sizes.reshape(ms, e_local), model_axis,
                                  split_axis=0, concat_axis=0, tiled=True)
        m_hint = min(cap, 64)
        # segment starts are cap-strided: bm must divide the capacity
        bm = aligned_block_rows(m_hint, p["up"].shape[-1], d, x.dtype,
                                align_to=cap)
        starts, seg_sizes, gids = a2a_segments(e_local, ms, cap, recv)
        segments = (starts, seg_sizes, gids, bm, m_hint)
        out = _expert_ffn(buf.reshape(e_local * ms * cap, d), p, act,
                          segments=segments).reshape(e_local, ms * cap, d)
    else:
        out = _expert_ffn(buf, p, act)
    out = jax.lax.all_to_all(out, model_axis, split_axis=1, concat_axis=0,
                             tiled=True)                     # back to (E,C,d)

    pair_out = out[flat_e, lp] * (keep * flat_w)[:, None].astype(x.dtype)
    y = jnp.sum(pair_out.reshape(n, moe_cfg.top_k, d), axis=1)
    density = jnp.mean(jax.nn.one_hot(topi, e, dtype=jnp.float32),
                       axis=(0, 1))
    aux = jnp.sum(jnp.mean(probs, axis=0) * density) * e
    return y.reshape(b, s, d), aux


def moe_apply(p, x: Array, cfg, *, mesh=None,
              batch_axes: Sequence[str] = (),
              model_axis: str = "model",
              valid: Optional[Array] = None) -> Tuple[Array, Array]:
    """x: (B, S, d) -> (y, aux_loss).  EP over ``model_axis`` if a mesh
    with that axis (size > 1) is supplied.  ``valid`` (B, S) bool marks
    real tokens under bucketed (right-padded) prefill — see
    :func:`_moe_local`."""
    e = cfg.moe.n_experts
    if mesh is None or model_axis not in mesh.axis_names \
            or mesh.shape[model_axis] == 1:
        y, aux = _moe_local(x, p, cfg, cfg.act, 0, e, None, valid=valid)
        return y, aux

    ms = mesh.shape[model_axis]
    if e % ms:
        # Divisibility-guarded like every sharding rule: a model axis
        # that cannot split the expert count degrades to the replicated
        # local path (param_specs leaves the expert weights unsharded
        # under the same guard, so this is GSPMD-consistent) instead of
        # refusing to serve on an odd mesh shape.
        y, aux = _moe_local(x, p, cfg, cfg.act, 0, e, None, valid=valid)
        return y, aux
    e_local = e // ms
    use_a2a = (EP_IMPL["impl"] == "all_to_all"
               and x.shape[1] % ms == 0 and x.shape[1] >= ms)

    bspec = P(tuple(batch_axes) if batch_axes else None, None, None)
    b_sp = P(tuple(batch_axes) if batch_axes else None, model_axis, None)
    espec = P(model_axis, None, None)
    args = [x, p["router"], p["up"], p["down"]]
    in_specs = [b_sp if use_a2a else bspec, P(None, None), espec, espec]
    if "gate" in p:
        args.append(p["gate"])
        in_specs.append(espec)
    has_gate = "gate" in p
    if valid is not None:
        args.append(valid)
        in_specs.append(P(tuple(batch_axes) if batch_axes else None,
                          model_axis if use_a2a else None))
    has_valid = valid is not None

    def unpack(router, up, down, rest):
        rest = list(rest)
        pp = {"router": router, "up": up, "down": down}
        if has_gate:
            pp["gate"] = rest.pop(0)
        v = rest.pop(0) if has_valid else None
        return pp, v

    all_axes = tuple(batch_axes) + (model_axis,)
    if use_a2a:
        def shard_fn(x_, router, up, down, *rest):
            pp, v = unpack(router, up, down, rest)
            y, aux = _moe_a2a(x_, pp, cfg, cfg.act, model_axis, ms,
                              valid=v)
            return y, jax.lax.pmean(aux, all_axes)
        out_specs = (b_sp, P())
    else:
        def shard_fn(x_, router, up, down, *rest):
            rank = jax.lax.axis_index(model_axis)
            pp, v = unpack(router, up, down, rest)
            y, aux = _moe_local(x_, pp, cfg, cfg.act, rank * e_local,
                                e_local, model_axis, valid=v)
            return y, jax.lax.pmean(aux, all_axes)
        out_specs = (bspec, P())

    y, aux = jax.shard_map(
        shard_fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=out_specs, check_vma=False)(*args)
    return y, aux
