"""GQA attention: full/sliding-window/bidirectional + cross, with KV cache.

Cache layout per attention layer:
  {"k": (B, cap, Hkv, hd), "v": (B, cap, Hkv, hd)}
where ``cap`` is the sequence capacity — full ``seq_len`` for global
attention, ``min(seq_len, window)`` (ring buffer) for sliding-window
layers, so a 500k-token gemma3 decode keeps only its 1-in-6 global layers
at full length (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (apply_rope, Array, IDENTITY_SHARDER,
                                 linear_apply, linear_init, Sharder)

NEG_INF = jnp.finfo(jnp.float32).min


def attn_init(key, cfg, dtype, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "q": linear_init(ks[0], d, cfg.n_heads * hd, dtype, cfg.use_bias),
        "k": linear_init(ks[1], d, cfg.n_kv_heads * hd, dtype, cfg.use_bias),
        "v": linear_init(ks[2], d, cfg.n_kv_heads * hd, dtype, cfg.use_bias),
        "o": linear_init(ks[3], cfg.n_heads * hd, d, dtype, cfg.use_bias),
    }


def _split_heads(x: Array, n_heads: int) -> Array:
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, -1)


def _repeat_kv(kv: Array, n_rep: int) -> Array:
    if n_rep == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.broadcast_to(kv[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def _sdpa(q: Array, k: Array, v: Array, mask: Optional[Array],
          sharder: Sharder) -> Array:
    """q: (B,Sq,H,hd), k/v: (B,Skv,H,hd), mask: (1|B, 1, Sq, Skv) bool."""
    hd = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(hd))
    if mask is not None:
        logits = jnp.where(mask, logits, NEG_INF)
    logits = sharder.constrain(logits, "attn_logits")
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
    return out


def _causal_mask(sq: int, skv: int, window: Optional[int]) -> Array:
    qi = jnp.arange(sq)[:, None]
    kj = jnp.arange(skv)[None, :]
    mask = kj <= qi
    if window is not None:
        mask &= (qi - kj) < window
    return mask[None, None]          # (1, 1, Sq, Skv)


# --------------------------------------------------------------------------
# Optimized attention paths (EXPERIMENTS.md §Perf)
# --------------------------------------------------------------------------
def _banded_local_attn(q: Array, k: Array, v: Array, window: int,
                       sharder: Sharder) -> Array:
    """Exact sliding-window attention in O(S x 2w) memory.

    Blocks the sequence into window-sized chunks; query block n attends to
    key blocks n-1 and n, which exactly covers the causal window
    ``(p - w, p]``.  Replaces the naive O(S^2) masked softmax (the memory
    bottleneck of gemma3/recurrentgemma train+prefill — §Perf #A).
    """
    b, s, h, hd = q.shape
    w = window
    nb = s // w
    qb = q.reshape(b, nb, w, h, hd)
    kb = k.reshape(b, nb, w, h, hd)
    vb = v.reshape(b, nb, w, h, hd)
    kprev = jnp.pad(kb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :nb]
    vprev = jnp.pad(vb, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :nb]
    kcat = jnp.concatenate([kprev, kb], axis=2)        # (B, nb, 2w, H, hd)
    vcat = jnp.concatenate([vprev, vb], axis=2)
    logits = jnp.einsum("bnqhd,bnkhd->bnhqk", qb, kcat,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.float32(hd))
    qi = jnp.arange(w)[:, None]                        # in-block q pos
    kj = jnp.arange(2 * w)[None, :]                    # kcat pos (-w offset)
    rel = qi - (kj - w)                                # q_abs - k_abs
    mask = (rel >= 0) & (rel < w)                      # causal + window
    first = (kj >= w)[None, :]                         # block 0: no prev
    block_mask = jnp.where(jnp.arange(nb)[:, None, None] == 0,
                           mask & first, mask)         # (nb, w, 2w)
    logits = jnp.where(block_mask[None, :, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnhqk,bnkhd->bnqhd", probs, vcat)
    return out.reshape(b, s, h, hd)


def _chunked_causal_attn(q: Array, k: Array, v: Array, *, causal: bool,
                         chunk: int = 1024) -> Array:
    """Flash-style online-softmax attention: outer scan over Q chunks
    (carry-free — outputs are per-chunk ys), inner scan over KV chunks
    with a chunk-sized (m, l, acc) carry.

    O(chunk^2) live logits + O(chunk) carries instead of O(S^2) — a
    first version carried the full (B,S,H,hd) accumulator through the KV
    scan, which *rewrote S-sized state nc times* and regressed the 32k
    prefill memory term ~25 % (§Perf, cross-cutting note); blocking Q
    fixed it.  Inference-only — used by the prefill path for
    S >= _CHUNK_THRESHOLD.
    """
    b, s, h, hd = q.shape
    nc = s // chunk
    qc = jnp.moveaxis(q.reshape(b, nc, chunk, h, hd), 1, 0)
    kc = jnp.moveaxis(k.reshape(b, nc, chunk, h, hd), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, nc, chunk, h, hd), 1, 0)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def q_block(_, qinp):
        qi, qblk = qinp
        qf = qblk.astype(jnp.float32) * scale

        def kv_block(carry, kinp):
            m, den, acc = carry
            kj, kb, vb = kinp
            logits = jnp.einsum("bqhd,bkhd->bhqk", qf,
                                kb.astype(jnp.float32))
            if causal:
                qpos = qi * chunk + jnp.arange(chunk)
                kpos = kj * chunk + jnp.arange(chunk)
                valid = (kpos[None, :] <= qpos[:, None])[None, None]
            else:
                valid = jnp.ones((1, 1, chunk, chunk), bool)
            logits = jnp.where(valid, logits, NEG_INF)
            new_m = jnp.maximum(m, jnp.max(logits, axis=-1, keepdims=True))
            p = jnp.exp(logits - new_m)
            corr = jnp.exp(m - new_m)
            den = den * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * corr[..., 0][..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))
            return (new_m, den, acc), None

        m0 = jnp.full((b, h, chunk, 1), NEG_INF, jnp.float32)
        den0 = jnp.zeros((b, h, chunk, 1), jnp.float32)
        a0 = jnp.zeros((b, h, chunk, hd), jnp.float32)
        (m, den, acc), _ = jax.lax.scan(
            kv_block, (m0, den0, a0), (jnp.arange(nc), kc, vc))
        out = acc / jnp.maximum(den, 1e-30)
        return None, jnp.moveaxis(out, 1, 2)        # (b, chunk, h, hd)

    _, outs = jax.lax.scan(q_block, None, (jnp.arange(nc), qc))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h, hd)
    return out.astype(q.dtype)


# toggled by the perf profile (repro.launch.dryrun --profile optimized)
ATTN_IMPL = {"local": "naive", "global_prefill": "naive"}
_CHUNK_THRESHOLD = 8192


def set_attention_impl(local: str = "naive",
                       global_prefill: str = "naive") -> None:
    assert local in ("naive", "banded")
    assert global_prefill in ("naive", "chunked")
    ATTN_IMPL["local"] = local
    ATTN_IMPL["global_prefill"] = global_prefill


def attn_apply(p, x: Array, cfg, *, kind: str,
               positions: Optional[Array] = None,
               kv_x: Optional[Array] = None,
               sharder: Sharder = IDENTITY_SHARDER,
               inference: bool = False) -> Array:
    """Full-sequence (train/prefill) attention. kind: attn|local|bidir|cross."""
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    if positions is None:
        positions = jnp.arange(s)[None, :]
    src = kv_x if kv_x is not None else x
    q = _split_heads(linear_apply(p["q"], x), cfg.n_heads)
    k = _split_heads(linear_apply(p["k"], src), cfg.n_kv_heads)
    v = _split_heads(linear_apply(p["v"], src), cfg.n_kv_heads)
    if kind != "cross":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = sharder.constrain(q, "attn_q")
    k = sharder.constrain(k, "attn_kv")
    v = sharder.constrain(v, "attn_kv")
    k = _repeat_kv(k, cfg.n_heads // cfg.n_kv_heads)
    v = _repeat_kv(v, cfg.n_heads // cfg.n_kv_heads)

    w = cfg.sliding_window
    if (kind == "local" and ATTN_IMPL["local"] == "banded"
            and s % w == 0 and s >= 2 * w):
        out = _banded_local_attn(q, k, v, w, sharder)
    elif (kind in ("attn", "bidir") and inference
            and ATTN_IMPL["global_prefill"] == "chunked"
            and s >= _CHUNK_THRESHOLD and s % 1024 == 0):
        out = _chunked_causal_attn(q, k, v, causal=(kind == "attn"))
    else:
        if kind == "attn":
            mask = _causal_mask(s, k.shape[1], None)
        elif kind == "local":
            mask = _causal_mask(s, k.shape[1], w)
        else:                        # bidir / cross: no mask
            mask = None
        out = _sdpa(q, k, v, mask, sharder)
    out = out.reshape(b, s, cfg.n_heads * hd)
    return linear_apply(p["o"], out)


# --------------------------------------------------------------------------
# KV-cached decode
# --------------------------------------------------------------------------
# int8 KV-cache quantization (per-position, per-head symmetric scales);
# halves the decode memory term (EXPERIMENTS.md §Perf #C).
CACHE_QUANT = {"enabled": False}


def set_kv_cache_quant(enabled: bool) -> None:
    CACHE_QUANT["enabled"] = enabled


def _quant_kv(x: Array) -> Tuple[Array, Array]:
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0 + 1e-8
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127
                 ).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def _dequant_kv(q: Array, scale: Array, dtype) -> Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def cache_capacity(kind: str, seq_len: int, window: int) -> int:
    return min(seq_len, window) if kind == "local" else seq_len


def init_cache(batch: int, cap: int, n_kv_heads: int, head_dim: int,
               dtype) -> Dict[str, Array]:
    shape = (batch, cap, n_kv_heads, head_dim)
    if CACHE_QUANT["enabled"]:
        sshape = (batch, cap, n_kv_heads, 1)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_s": jnp.zeros(sshape, jnp.bfloat16),
                "v_s": jnp.zeros(sshape, jnp.bfloat16)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill_into_cache(p, x: Array, cfg, *, kind: str, cap: int,
                       last_index: Optional[Array] = None,
                       sharder: Sharder = IDENTITY_SHARDER
                       ) -> Dict[str, Array]:
    """Compute post-RoPE K/V for a full prompt and lay it into a cache.

    ``last_index`` (scalar or (B,), traced) is the index of each row's
    real last token when ``x`` is right-padded to a bucket length.  It
    only matters for the ``s > cap`` ring layout: the static roll places
    the last ``cap`` of the *padded* sequence, which is wrong when pads
    trail the prompt.  With ``last_index`` the ring is laid per row by
    gather — cell ``j`` takes position ``last - ((last - j) mod cap)``,
    the unique position in ``(last - cap, last]`` congruent to ``j`` —
    which reduces to the identity layout for rows shorter than ``cap``
    (cells beyond the row's length are zeroed; the decode-time ring mask
    already invalidates them).
    """
    b, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    k = _split_heads(linear_apply(p["k"], x), cfg.n_kv_heads)
    v = _split_heads(linear_apply(p["v"], x), cfg.n_kv_heads)
    k = apply_rope(k, positions, cfg.rope_theta)
    if s <= cap:
        pad = cap - s
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    elif last_index is not None:     # ring layout at the rows' real lengths
        last = jnp.asarray(last_index)
        last = last[:, None] if last.ndim == 1 else jnp.full((b, 1), last)
        src = last - jnp.mod(last - jnp.arange(cap)[None, :], cap)  # (B,cap)
        valid = (src >= 0)[:, :, None, None]
        idx = jnp.clip(src, 0, s - 1)[:, :, None, None]
        k = jnp.where(valid, jnp.take_along_axis(k, idx, axis=1), 0)
        v = jnp.where(valid, jnp.take_along_axis(v, idx, axis=1), 0)
    else:                            # ring buffer: keep the last cap, rolled
        k, v = k[:, -cap:], v[:, -cap:]
        shift = s % cap
        k = jnp.roll(k, shift, axis=1)
        v = jnp.roll(v, shift, axis=1)
    if CACHE_QUANT["enabled"]:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        return {"k": sharder.constrain(kq, "kv_cache"),
                "v": sharder.constrain(vq, "kv_cache"),
                "k_s": ks, "v_s": vs}
    return {"k": sharder.constrain(k, "kv_cache"),
            "v": sharder.constrain(v, "kv_cache")}


def attn_decode_step(p, x: Array, cache: Dict[str, Array], pos: Array,
                     cfg, *, kind: str,
                     sharder: Sharder = IDENTITY_SHARDER
                     ) -> Tuple[Array, Dict[str, Array]]:
    """One-token step. x: (B, 1, d); pos: current position — a scalar
    (whole batch at one position) or a (B,) vector of per-row positions
    (the slot-engine case: each slot decodes at its own sequence length,
    so short requests never attend past their own prompt)."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    cap = cache["k"].shape[1]
    pos = jnp.asarray(pos)
    per_row = pos.ndim == 1
    positions = pos[:, None] if per_row else jnp.full((1, 1), pos)
    q = _split_heads(linear_apply(p["q"], x), cfg.n_heads)
    k = _split_heads(linear_apply(p["k"], x), cfg.n_kv_heads)
    v = _split_heads(linear_apply(p["v"], x), cfg.n_kv_heads)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    slot = pos % cap

    if per_row:
        # Per-row scatter: row i writes its (Hkv, hd) K/V at its own
        # ring slot — O(B) stores (in-place under buffer donation), not
        # a select over the whole (B, cap, ...) cache.
        rows = jnp.arange(b)

        def upd(buf, new):
            return buf.at[rows, slot].set(new[:, 0])
    else:
        def upd(buf, new):
            return jax.lax.dynamic_update_slice_in_dim(buf, new, slot,
                                                       axis=1)

    if CACHE_QUANT["enabled"]:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        ck = upd(cache["k"], kq)
        cv = upd(cache["v"], vq)
        cks = upd(cache["k_s"], ks)
        cvs = upd(cache["v_s"], vs)
        ck = sharder.constrain(ck, "kv_cache")
        cv = sharder.constrain(cv, "kv_cache")
        new_cache = {"k": ck, "v": cv, "k_s": cks, "v_s": cvs}
        kd = _dequant_kv(ck, cks, x.dtype)
        vd = _dequant_kv(cv, cvs, x.dtype)
    else:
        ck = upd(cache["k"], k)
        cv = upd(cache["v"], v)
        ck = sharder.constrain(ck, "kv_cache")
        cv = sharder.constrain(cv, "kv_cache")
        new_cache = {"k": ck, "v": cv}
        kd, vd = ck, cv
    # Valid slots: ring-buffer logical position of slot j is
    # pos - ((pos - j) mod cap); valid iff >= 0 (and causality is implied).
    j = jnp.arange(cap)
    if per_row:
        logical = pos[:, None] - jnp.mod(pos[:, None] - j[None, :], cap)
        mask = (logical >= 0)[:, None, None, :]     # (B,1,1,cap)
    else:
        logical = pos - jnp.mod(pos - j, cap)
        mask = (logical >= 0)[None, None, None, :]  # (1,1,1,cap)
    kk = _repeat_kv(kd, cfg.n_heads // cfg.n_kv_heads)
    vv = _repeat_kv(vd, cfg.n_heads // cfg.n_kv_heads)
    out = _sdpa(q, kk, vv, mask, sharder)
    out = out.reshape(b, 1, cfg.n_heads * hd)
    return linear_apply(p["o"], out), new_cache


def paged_attn_decode_step(p, x: Array, cache: Dict[str, Array],
                           page_table: Array, pos: Array, cfg, *,
                           sharder: Sharder = IDENTITY_SHARDER
                           ) -> Tuple[Array, Dict[str, Array]]:
    """One-token step against block-granular paged KV storage.

    ``cache`` holds this layer's slice of the shared page pool:
    ``{"pk": (n_pages, page_size, Hkv, hd), "pv": ...}`` — a flat pool of
    fixed-size sequence blocks with no per-request ``max_seq``
    reservation — plus, for int8 pools, per-page scale planes
    ``{"pk_s": (n_pages, page_size, Hkv, 1) bf16, "pv_s": ...}``.
    ``page_table`` is the per-row indirection
    ``(B, max_pages_per_slot) int32``: logical page ``j`` of row ``i``
    lives at physical page ``page_table[i, j]``.  ``pos`` is the per-row
    ``(B,)`` write position (the paged engine always decodes with
    per-slot positions).

    The new token's K/V is scattered through the table (row ``i`` writes
    physical cell ``(table[i, pos_i // P], pos_i % P)`` — one O(B) store,
    page ownership is exclusive so rows never collide; quantized pools
    scatter the int8 values and their scales).  Attention then reads the
    pool through the backend chosen by
    :func:`repro.kernels.paged_attention` — the fused Pallas kernel
    (TPU / interpret CI leg) or its page-blocked XLA twin keep the pool
    *stationary* and apply the per-row ring mask ``j <= pos_i`` inside
    the kernel; the ``"gather"`` reference materializes the PR-5 dense
    ``(B, max_pages * P, ...)`` view and masks in SDPA.  Either way,
    unmapped table entries (released rows point at the pool's sink page,
    live rows' tail entries are beyond their mapped span) are read but
    never attended — the slot engine's stale-K/V invariant,
    page-granular.
    """
    from repro.kernels.paged_attn import (paged_attention,
                                          paged_attention_sharded,
                                          resolve_paged_attn_backend)
    if CACHE_QUANT["enabled"]:
        raise NotImplementedError(
            "paged storage quantizes at the pool boundary (see "
            "PagedServeEngine(kv_quant=...)), not via CACHE_QUANT")
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    psz = cache["pk"].shape[1]
    pos = jnp.asarray(pos)
    assert pos.ndim == 1, "paged decode requires per-row (B,) positions"
    positions = pos[:, None]
    q = _split_heads(linear_apply(p["q"], x), cfg.n_heads)
    k = _split_heads(linear_apply(p["k"], x), cfg.n_kv_heads)
    v = _split_heads(linear_apply(p["v"], x), cfg.n_kv_heads)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    rows = jnp.arange(b)
    phys = page_table[rows, pos // psz]              # (B,) physical pages
    off = pos % psz
    quant = "pk_s" in cache
    if quant:
        kq, ks = _quant_kv(k)
        vq, vs = _quant_kv(v)
        pk = cache["pk"].at[phys, off].set(kq[:, 0])
        pv = cache["pv"].at[phys, off].set(vq[:, 0])
        pk_s = cache["pk_s"].at[phys, off].set(ks[:, 0])
        pv_s = cache["pv_s"].at[phys, off].set(vs[:, 0])
    else:
        pk = cache["pk"].at[phys, off].set(k[:, 0])
        pv = cache["pv"].at[phys, off].set(v[:, 0])
        pk_s = pv_s = None
    pk = sharder.constrain(pk, "kv_pool")
    pv = sharder.constrain(pv, "kv_pool")
    new_cache = {"pk": pk, "pv": pv}
    if quant:
        new_cache.update({"pk_s": pk_s, "pv_s": pv_s})
    impl = resolve_paged_attn_backend()
    if impl == "gather":
        # PR-5 reference: gather each row's pages back into logical
        # sequence order (the transient dense view the fused kernel
        # avoids) and mask in SDPA.
        if quant:
            kd = _dequant_kv(pk[page_table], pk_s[page_table], x.dtype)
            vd = _dequant_kv(pv[page_table], pv_s[page_table], x.dtype)
        else:
            kd, vd = pk[page_table], pv[page_table]
        kd = kd.reshape(b, -1, cfg.n_kv_heads, hd)
        vd = vd.reshape(b, -1, cfg.n_kv_heads, hd)
        j = jnp.arange(kd.shape[1])
        mask = (j[None, :] <= pos[:, None])[:, None, None, :]  # (B,1,1,Skv)
        kk = _repeat_kv(kd, cfg.n_heads // cfg.n_kv_heads)
        vv = _repeat_kv(vd, cfg.n_heads // cfg.n_kv_heads)
        out = _sdpa(q, kk, vv, mask, sharder)
    else:
        mesh = getattr(sharder, "mesh", None)
        if mesh is not None:
            # Mesh-aware engines run the fused kernel (or its XLA twin)
            # per shard: each model rank attends its own head slice
            # against its slice of the page pool, pages replicated.
            out = paged_attention_sharded(
                q[:, 0], pk, pv, page_table, pos, mesh=mesh,
                pk_scale=pk_s, pv_scale=pv_s, impl=impl)[:, None]
        else:
            out = paged_attention(q[:, 0], pk, pv, page_table, pos,
                                  pk_scale=pk_s, pv_scale=pv_s,
                                  impl=impl)[:, None]  # (B, 1, H, hd)
    out = out.reshape(b, 1, cfg.n_heads * hd)
    return linear_apply(p["o"], out), new_cache


def paged_local_attn_decode_step(p, x: Array, cache: Dict[str, Array],
                                 page_table: Array, pos: Array, cfg, *,
                                 window_cap: int,
                                 sharder: Sharder = IDENTITY_SHARDER
                                 ) -> Tuple[Array, Dict[str, Array]]:
    """One-token sliding-window step against a paged ring of blocks.

    ``cache`` holds this layer's slice of the *local* page pool
    ``{"lk": (n_lpages, page_size, Hkv, hd), "lv": ...}`` and
    ``page_table`` is the per-row ring table ``(B, R) int32``: the page
    holding sequence block ``q`` of row ``i`` is
    ``page_table[i, q mod R]``.  ``R`` is sized by the engine so that
    ``R * page_size >= window_cap + decode_window + page_size`` — the
    engine swaps a ring column's physical page (freeing the old one back
    to the pool) only for blocks the upcoming decode window will enter,
    and at that point the overwritten content is at least ``window_cap``
    positions behind every read in the window, i.e. already masked.

    ``window_cap`` is the dense engine's ring capacity
    ``min(sliding_window, max_seq)``: the read path gathers cell ``j``
    of the *logical* ring (position ``pos - ((pos - j) mod window_cap)``,
    masked when negative) through the ring table, reproducing the dense
    :func:`attn_decode_step` gather order and mask bit for bit.
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    psz = cache["lk"].shape[1]
    ring = page_table.shape[1]
    pos = jnp.asarray(pos)
    assert pos.ndim == 1, "paged decode requires per-row (B,) positions"
    positions = pos[:, None]
    q = _split_heads(linear_apply(p["q"], x), cfg.n_heads)
    k = _split_heads(linear_apply(p["k"], x), cfg.n_kv_heads)
    v = _split_heads(linear_apply(p["v"], x), cfg.n_kv_heads)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    rows = jnp.arange(b)
    phys = page_table[rows, (pos // psz) % ring]
    off = pos % psz
    lk = cache["lk"].at[phys, off].set(k[:, 0])
    lv = cache["lv"].at[phys, off].set(v[:, 0])
    lk = sharder.constrain(lk, "kv_pool")
    lv = sharder.constrain(lv, "kv_pool")
    new_cache = {"lk": lk, "lv": lv}
    # Logical ring cell j holds position pos - ((pos - j) mod window_cap);
    # gather it back through the ring table (same cell order and validity
    # mask as the dense ring, so SDPA sees identical operands).
    j = jnp.arange(window_cap)
    logical = pos[:, None] - jnp.mod(pos[:, None] - j[None, :], window_cap)
    valid = logical >= 0
    pc = jnp.maximum(logical, 0)
    pages = page_table[rows[:, None], (pc // psz) % ring]    # (B, w)
    kd = lk[pages, pc % psz]                                 # (B, w, Hkv, hd)
    vd = lv[pages, pc % psz]
    mask = valid[:, None, None, :]
    kk = _repeat_kv(kd, cfg.n_heads // cfg.n_kv_heads)
    vv = _repeat_kv(vd, cfg.n_heads // cfg.n_kv_heads)
    out = _sdpa(q, kk, vv, mask, sharder)
    out = out.reshape(b, 1, cfg.n_heads * hd)
    return linear_apply(p["o"], out), new_cache


def paged_cross_attn_decode(p, x: Array, cache: Dict[str, Array],
                            page_table: Array, cfg, *, enc_len: int,
                            sharder: Sharder = IDENTITY_SHARDER) -> Array:
    """Decoder cross-attention against paged, read-only encoder KV.

    ``cache`` is the cross pool slice ``{"ck": (n_cpages, page_size,
    Hkv, hd), "cv": ...}`` and ``page_table`` the per-row ``(B, C)``
    table written once at admit (refcount-shared between requests with
    identical encoder features).  The gathered view is sliced back to
    the static ``enc_len`` before SDPA — cross attention carries no mask
    (every encoder frame is visible), so page-padding cells must not
    reach the softmax.  Operands match :func:`cross_attn_decode` on the
    dense encoder KV bit for bit.
    """
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    q = _split_heads(linear_apply(p["q"], x), cfg.n_heads)
    kd = cache["ck"][page_table].reshape(b, -1, cfg.n_kv_heads, hd)
    vd = cache["cv"][page_table].reshape(b, -1, cfg.n_kv_heads, hd)
    kd, vd = kd[:, :enc_len], vd[:, :enc_len]
    k = _repeat_kv(kd, cfg.n_heads // cfg.n_kv_heads)
    v = _repeat_kv(vd, cfg.n_heads // cfg.n_kv_heads)
    out = _sdpa(q, k, v, None, sharder)
    return linear_apply(p["o"], out.reshape(b, x.shape[1], cfg.n_heads * hd))


def cross_attn_decode(p, x: Array, cross_kv: Dict[str, Array], cfg,
                      sharder: Sharder = IDENTITY_SHARDER) -> Array:
    """Decoder cross-attention against a static encoder KV."""
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    q = _split_heads(linear_apply(p["q"], x), cfg.n_heads)
    k = _repeat_kv(cross_kv["k"], cfg.n_heads // cfg.n_kv_heads)
    v = _repeat_kv(cross_kv["v"], cfg.n_heads // cfg.n_kv_heads)
    out = _sdpa(q, k, v, None, sharder)
    return linear_apply(p["o"], out.reshape(b, x.shape[1], cfg.n_heads * hd))


def encode_cross_kv(p, enc_out: Array, cfg,
                    sharder: Sharder = IDENTITY_SHARDER) -> Dict[str, Array]:
    """Project encoder output once into the decoder's cross-attn K/V."""
    k = _split_heads(linear_apply(p["k"], enc_out), cfg.n_kv_heads)
    v = _split_heads(linear_apply(p["v"], enc_out), cfg.n_kv_heads)
    return {"k": sharder.constrain(k, "kv_cache"),
            "v": sharder.constrain(v, "kv_cache")}
