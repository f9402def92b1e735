"""Model assembly: blocks, scan-over-layers, train/prefill/decode entry points.

All ten assigned architectures are instances of this module driven by
``ModelConfig`` (repro.configs.base): dense/GQA/local-attention decoders,
MoE decoders (EP via repro.models.moe), the RG-LRU hybrid, RWKV6, and the
whisper-style encoder-decoder with stub modality frontends.

Layers are scanned (``lax.scan`` over stacked per-layer params, grouped by
the config's cyclic layer pattern) so the lowered HLO stays compact for
80-layer models, with optional remat per scan body.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, BIDIR, LOCAL, ModelConfig, RGLRU, WKV
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.common import (Array, embedding_init, embedding_lookup,
                                 IDENTITY_SHARDER, linear_apply, linear_init,
                                 lm_head_logits, mlp_apply, mlp_init,
                                 rmsnorm_apply, rmsnorm_init, Sharder)

PyTree = Any


# ==========================================================================
# Init
# ==========================================================================
def _block_init(key, kind: str, cfg: ModelConfig, dtype, *,
                with_cross: bool = False) -> PyTree:
    ks = jax.random.split(key, 6)
    p: Dict[str, PyTree] = {"norm1": rmsnorm_init(cfg.d_model, dtype),
                            "norm2": rmsnorm_init(cfg.d_model, dtype)}
    if kind in (ATTN, LOCAL, BIDIR):
        p["mixer"] = attn.attn_init(ks[0], cfg, dtype)
    elif kind == RGLRU:
        p["mixer"] = rglru_mod.rglru_init(ks[0], cfg, dtype)
    elif kind == WKV:
        p["mixer"] = rwkv_mod.rwkv_init(ks[0], cfg, dtype)
    else:
        raise ValueError(f"unknown layer kind {kind}")
    if with_cross:
        p["norm_cross"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attn.attn_init(ks[1], cfg, dtype)
    if cfg.moe is not None:
        p["moe"] = moe_mod.moe_init(ks[2], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype,
                            cfg.gated_mlp, cfg.use_bias)
    return p


def _stacked_group_init(key, pattern: Tuple[str, ...], n_repeats: int,
                        cfg: ModelConfig, dtype, with_cross: bool) -> PyTree:
    def one(k):
        kk = jax.random.split(k, len(pattern))
        return {f"b{i}": _block_init(kk[i], kind, cfg, dtype,
                                     with_cross=with_cross)
                for i, kind in enumerate(pattern)}
    # vmap draws every leaf already stacked: the values of stacking the
    # per-layer inits, without a list of layers alive beside the stack.
    return jax.vmap(one)(jax.random.split(key, n_repeats))


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype_override: Optional[str] = None) -> PyTree:
    """Random parameters for ``cfg`` drawn from ``key``.

    At published widths call it as one compiled program,
    ``jax.jit(partial(init_params, cfg), out_shardings=...)(key)``: each
    weight is then drawn, scaled and cast inside XLA, so the device holds
    only the final weights (eagerly, a float32 draw of each stacked leaf
    stays alive beside them), and ``out_shardings`` places every leaf
    straight onto its shards.  The values are those of the eager call.
    """
    dtype = jnp.dtype(dtype_override or cfg.param_dtype)
    ks = jax.random.split(key, 8)
    params: Dict[str, PyTree] = {
        "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    params["groups"] = [
        _stacked_group_init(k, pattern, reps, cfg, dtype,
                            with_cross=cfg.enc_dec)
        for k, (pattern, reps) in zip(jax.random.split(ks[1], 8),
                                      cfg.layer_groups())
    ]
    if not cfg.tie_embeddings:
        params["lm_head"] = embedding_init(ks[2], cfg.vocab_size,
                                           cfg.d_model, dtype)
    if cfg.frontend is not None:
        params["frontend_proj"] = linear_init(
            ks[3], cfg.frontend_dim, cfg.d_model, dtype, use_bias=True)
    if cfg.enc_dec:
        enc_groups = []
        reps, rem = divmod(cfg.n_enc_layers, 1)
        enc_groups.append(_stacked_group_init(
            ks[4], (BIDIR,), cfg.n_enc_layers, cfg, dtype, with_cross=False))
        params["encoder"] = {"groups": enc_groups,
                             "final_norm": rmsnorm_init(cfg.d_model, dtype)}
    return params


# ==========================================================================
# Forward blocks
# ==========================================================================
def _block_apply(p, x: Array, kind: str, cfg: ModelConfig, *,
                 sharder: Sharder, mesh, batch_axes,
                 positions: Optional[Array], enc_out: Optional[Array],
                 inference: bool = False) -> Tuple[Array, Array]:
    """Full-sequence block. Returns (x, moe_aux)."""
    h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if kind in (ATTN, LOCAL, BIDIR):
        mix = attn.attn_apply(p["mixer"], h, cfg, kind=kind,
                              positions=positions, sharder=sharder,
                              inference=inference)
    elif kind == RGLRU:
        mix = rglru_mod.rglru_apply(p["mixer"], h, cfg, sharder=sharder)
    else:
        mix = rwkv_mod.rwkv_apply(p["mixer"], h, cfg, sharder=sharder)
    x = sharder.constrain(x + mix, "hidden")
    if "cross" in p and enc_out is not None:
        h = rmsnorm_apply(p["norm_cross"], x, cfg.norm_eps)
        x = x + attn.attn_apply(p["cross"], h, cfg, kind="cross",
                                kv_x=enc_out, sharder=sharder)
    h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    if cfg.moe is not None:
        ffn, aux = moe_mod.moe_apply(p["moe"], h, cfg, mesh=mesh,
                                     batch_axes=batch_axes)
    else:
        ffn = mlp_apply(p["mlp"], h, cfg.act, sharder)
        aux = jnp.zeros((), jnp.float32)
    x = sharder.constrain(x + ffn, "hidden")
    return x, aux


def _run_groups(params_groups, x: Array, patterns, cfg: ModelConfig, *,
                sharder: Sharder, mesh, batch_axes, positions, enc_out,
                remat: str, inference: bool = False) -> Tuple[Array, Array]:
    aux = jnp.zeros((), jnp.float32)

    for gp, (pattern, n_reps) in zip(params_groups, patterns):
        def body(carry, layer_p, pattern=pattern):
            x, aux = carry
            for i, kind in enumerate(pattern):
                x, a = _block_apply(layer_p[f"b{i}"], x, kind, cfg,
                                    sharder=sharder, mesh=mesh,
                                    batch_axes=batch_axes,
                                    positions=positions, enc_out=enc_out,
                                    inference=inference)
                aux = aux + a
            return (x, aux), None

        if remat == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        elif remat == "dots":
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        (x, aux), _ = jax.lax.scan(body, (x, aux), gp)
    return x, aux


def _embed_inputs(params, cfg: ModelConfig, batch: Dict[str, Array]) -> Array:
    if cfg.frontend is not None and "frontend_embeds" in batch:
        return linear_apply(params["frontend_proj"],
                            batch["frontend_embeds"])
    x = embedding_lookup(params["embed"], batch["tokens"])
    return x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)


def _encode(params, cfg: ModelConfig, batch, *, sharder, remat,
            inference: bool = False) -> Array:
    enc_in = linear_apply(params["frontend_proj"], batch["frontend_embeds"])
    x, _ = _run_groups(params["encoder"]["groups"], enc_in, [((BIDIR,),
                       cfg.n_enc_layers)], cfg, sharder=sharder, mesh=None,
                       batch_axes=(), positions=None, enc_out=None,
                       remat=remat, inference=inference)
    return rmsnorm_apply(params["encoder"]["final_norm"], x, cfg.norm_eps)


def _logits(params, cfg: ModelConfig, x: Array) -> Array:
    table = (params["embed"]["table"] if cfg.tie_embeddings
             else params["lm_head"]["table"])
    return lm_head_logits(table, x, cfg.vocab_size)


# ==========================================================================
# Train forward
# ==========================================================================
def forward_train(params, cfg: ModelConfig, batch: Dict[str, Array], *,
                  sharder: Sharder = IDENTITY_SHARDER, mesh=None,
                  batch_axes=(), remat: str = "full"
                  ) -> Tuple[Array, Dict[str, Array]]:
    """Returns (loss, metrics).  batch: tokens (B,S) [+ frontend_embeds]."""
    enc_out = None
    if cfg.enc_dec:
        enc_out = _encode(params, cfg, batch, sharder=sharder, remat=remat)
        x = embedding_lookup(params["embed"], batch["tokens"])
    else:
        x = _embed_inputs(params, cfg, batch)
    x = sharder.constrain(x, "hidden")
    x, aux = _run_groups(params["groups"], x, cfg.layer_groups(), cfg,
                         sharder=sharder, mesh=mesh, batch_axes=batch_axes,
                         positions=None, enc_out=enc_out, remat=remat)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    logits = _logits(params, cfg, x)
    labels = batch["labels"] if "labels" in batch else batch["tokens"]
    loss, acc = _next_token_loss(logits, labels, sharder)
    if cfg.moe is not None:
        loss = loss + 0.01 * aux / cfg.n_layers
    return loss, {"loss": loss, "accuracy": acc, "moe_aux": aux}


# "f32": upcast the full (B, S, vocab) logits before the loss (baseline);
# "bf16": keep logits bf16, upcast only inside the fused max/exp-sum
# reductions — avoids materializing a 4-byte logits copy (for gemma3's
# 262k vocab that copy is 4.3 GB/device/step; §Perf #A iteration 3).
LOSS_DTYPE = {"mode": "f32"}


def set_loss_dtype(mode: str) -> None:
    assert mode in ("f32", "bf16")
    LOSS_DTYPE["mode"] = mode


def _next_token_loss(logits: Array, labels: Array, sharder: Sharder
                     ) -> Tuple[Array, Array]:
    logits = sharder.constrain(logits, "logits")
    tg = labels[:, 1:]
    if LOSS_DTYPE["mode"] == "f32":
        lg = logits[:, :-1].astype(jnp.float32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
    else:
        lg = logits[:, :-1]
        m = jnp.max(lg, axis=-1, keepdims=True)           # bf16 reduce
        # exp/sum in f32 but fused into the reduction (no f32 copy of
        # the logits lives in HBM)
        s = jnp.sum(jnp.exp((lg - m).astype(jnp.float32)), axis=-1)
        lse = m[..., 0].astype(jnp.float32) + jnp.log(s)
        picked = jnp.take_along_axis(lg, tg[..., None], axis=-1
                                     )[..., 0].astype(jnp.float32)
    loss = jnp.mean(lse - picked)
    acc = jnp.mean((jnp.argmax(lg, -1) == tg).astype(jnp.float32))
    return loss, acc


# ==========================================================================
# Serving: cache init / prefill / decode
# ==========================================================================
def _layer_cache_init(kind: str, cfg: ModelConfig, batch: int, seq_len: int,
                      dtype) -> PyTree:
    hd = cfg.resolved_head_dim
    if kind in (ATTN, BIDIR):
        cap = attn.cache_capacity("attn", seq_len, cfg.sliding_window)
        return attn.init_cache(batch, cap, cfg.n_kv_heads, hd, dtype)
    if kind == LOCAL:
        cap = attn.cache_capacity("local", seq_len, cfg.sliding_window)
        return attn.init_cache(batch, cap, cfg.n_kv_heads, hd, dtype)
    if kind == RGLRU:
        return rglru_mod.rglru_init_cache(batch, cfg.d_model, dtype)
    if kind == WKV:
        return rwkv_mod.rwkv_init_cache(batch, cfg, dtype)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               dtype_override: Optional[str] = None,
               enc_len: Optional[int] = None) -> List[PyTree]:
    """Stacked per-group cache pytrees mirroring params['groups'].

    For enc-dec models each layer cache is {"self": ..., "cross": static
    encoder KV of length ``enc_len``}.
    """
    dtype = jnp.dtype(dtype_override or cfg.param_dtype)
    caches = []
    for pattern, n_reps in cfg.layer_groups():
        def one_layer(kind):
            base = _layer_cache_init(kind, cfg, batch, seq_len, dtype)
            if cfg.enc_dec:
                cross = attn.init_cache(batch, enc_len or seq_len,
                                        cfg.n_kv_heads,
                                        cfg.resolved_head_dim, dtype)
                return {"self": base, "cross": cross}
            return base
        one = {f"b{i}": one_layer(kind) for i, kind in enumerate(pattern)}
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_reps,) + x.shape), one))
    return caches


def _block_prefill(p, x, kind, cfg, cap_seq, *, sharder, enc_out,
                   mesh=None, batch_axes=(), last_index=None):
    """Block forward that also emits its filled cache.

    ``last_index`` marks each row's real last token under bucketed
    (right-padded) prefill: ring-capacity attention layers lay their
    cache at the real length, and recurrent layers freeze their carried
    state there — so padded prefill fills caches identically to an
    exact-length prefill."""
    h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    if kind in (ATTN, LOCAL, BIDIR):
        mix = attn.attn_apply(p["mixer"], h, cfg,
                              kind=kind, sharder=sharder, inference=True)
        cap = attn.cache_capacity("local" if kind == LOCAL else "attn",
                                  cap_seq, cfg.sliding_window)
        cache = attn.prefill_into_cache(p["mixer"], h, cfg,
                                        kind=kind, cap=cap,
                                        last_index=last_index,
                                        sharder=sharder)
    elif kind == RGLRU:
        mix = rglru_mod.rglru_apply(p["mixer"], h, cfg, sharder=sharder)
        cache = rglru_mod.rglru_prefill_cache(p["mixer"], h, cfg,
                                              last_index=last_index)
    else:
        mix, cache = rwkv_mod.rwkv_apply(p["mixer"], h, cfg, sharder=sharder,
                                         return_state=True,
                                         last_index=last_index)
    x = sharder.constrain(x + mix, "hidden")
    if "cross" in p and enc_out is not None:
        h = rmsnorm_apply(p["norm_cross"], x, cfg.norm_eps)
        x = x + attn.attn_apply(p["cross"], h, cfg, kind="cross",
                                kv_x=enc_out, sharder=sharder)
        cache = {"self": cache,
                 "cross": attn.encode_cross_kv(p["cross"], enc_out, cfg,
                                               sharder)}
    h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    if cfg.moe is not None:
        valid = None
        if last_index is not None:
            last = jnp.asarray(last_index)
            last = (last if last.ndim == 1
                    else jnp.full((x.shape[0],), last))
            valid = jnp.arange(x.shape[1])[None, :] <= last[:, None]
        ffn, _ = moe_mod.moe_apply(p["moe"], h, cfg, mesh=mesh,
                                   batch_axes=batch_axes, valid=valid)
    else:
        ffn = mlp_apply(p["mlp"], h, cfg.act, sharder)
    return sharder.constrain(x + ffn, "hidden"), cache


def forward_prefill(params, cfg: ModelConfig, batch: Dict[str, Array], *,
                    cache_len: Optional[int] = None,
                    sharder: Sharder = IDENTITY_SHARDER, mesh=None,
                    batch_axes=(),
                    logits_index: Optional[Array] = None
                    ) -> Tuple[Array, List[PyTree]]:
    """Process a prompt; return (last-position logits, filled cache).

    ``logits_index`` (traced scalar or per-row ``(B,)`` vector) selects
    which position's logits to return instead of the static last
    position — the bucketed-prefill path pads prompts to a shape bucket
    and reads the logits of the last *real* token, so one compilation
    serves every prompt length in the bucket (causal masking makes
    trailing pad tokens invisible to it).  The vector form is the
    coalesced multi-prompt prefill: each batch row carries its own
    last-token position, one gather instead of a shared slice.  A
    ``(B, K)`` matrix returns ``(B, K, vocab)`` logits at K positions
    per row — a teacher-forced read of one causal forward — with each
    row's cache laid at its last column.
    """
    last_index = logits_index
    if logits_index is not None and jnp.ndim(logits_index) == 2:
        last_index = logits_index[:, -1]
    enc_out = None
    if cfg.enc_dec:
        enc_out = _encode(params, cfg, batch, sharder=sharder, remat="none",
                          inference=True)
        x = embedding_lookup(params["embed"], batch["tokens"])
    else:
        x = _embed_inputs(params, cfg, batch)
    x = sharder.constrain(x, "hidden")
    seq = x.shape[1]
    cap_seq = cache_len or seq
    caches = []
    for gp, (pattern, n_reps) in zip(params["groups"], cfg.layer_groups()):
        def body(carry, layer_p, pattern=pattern):
            x = carry
            cache = {}
            for i, kind in enumerate(pattern):
                x, c = _block_prefill(layer_p[f"b{i}"], x, kind, cfg,
                                      cap_seq, sharder=sharder,
                                      enc_out=enc_out, mesh=mesh,
                                      batch_axes=batch_axes,
                                      last_index=last_index)
                cache[f"b{i}"] = c
            return x, cache
        x, cache = jax.lax.scan(body, x, gp)
        caches.append(cache)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if logits_index is not None and jnp.ndim(logits_index) >= 1:
        idx = logits_index.astype(jnp.int32)
        idx = idx[:, :, None] if idx.ndim == 2 else idx[:, None, None]
        x_last = jnp.take_along_axis(x, idx, axis=1)
    elif logits_index is not None:
        x_last = jax.lax.dynamic_slice_in_dim(x, logits_index, 1, axis=1)
    else:
        x_last = x[:, -1:]
    return _logits(params, cfg, x_last), caches


def _block_decode(p, x, cache, pos, kind, cfg, *, sharder,
                  mesh=None, batch_axes=(), page_table=None,
                  window_cap=None):
    h = rmsnorm_apply(p["norm1"], x, cfg.norm_eps)
    self_cache = cache["self"] if "cross" in p else cache
    if kind in (ATTN, LOCAL, BIDIR) and "pk" in self_cache:
        # Paged global layer: the cache leaf is this layer's slice of
        # the shared page pool; indirection goes through ``page_table``.
        mix, new_cache = attn.paged_attn_decode_step(
            p["mixer"], h, self_cache, page_table["global"], pos, cfg,
            sharder=sharder)
    elif kind in (ATTN, LOCAL, BIDIR) and "lk" in self_cache:
        # Paged sliding-window layer: ring of R pages per row.
        mix, new_cache = attn.paged_local_attn_decode_step(
            p["mixer"], h, self_cache, page_table["local"], pos, cfg,
            window_cap=window_cap or cfg.sliding_window, sharder=sharder)
    elif kind in (ATTN, LOCAL, BIDIR):
        mix, new_cache = attn.attn_decode_step(
            p["mixer"], h, self_cache, pos, cfg, kind=kind, sharder=sharder)
    elif kind == RGLRU:
        mix, new_cache = rglru_mod.rglru_decode_step(p["mixer"], h,
                                                     self_cache, cfg)
    else:
        mix, new_cache = rwkv_mod.rwkv_decode_step(p["mixer"], h,
                                                   self_cache, cfg)
    x = x + mix
    if "cross" in p:
        h = rmsnorm_apply(p["norm_cross"], x, cfg.norm_eps)
        if "ck" in cache["cross"]:
            x = x + attn.paged_cross_attn_decode(
                p["cross"], h, cache["cross"], page_table["cross"], cfg,
                enc_len=cfg.enc_frames, sharder=sharder)
        else:
            x = x + attn.cross_attn_decode(p["cross"], h, cache["cross"],
                                           cfg, sharder)
        new_cache = {"self": new_cache, "cross": cache["cross"]}
    h = rmsnorm_apply(p["norm2"], x, cfg.norm_eps)
    if cfg.moe is not None:
        ffn, _ = moe_mod.moe_apply(p["moe"], h, cfg, mesh=mesh,
                                   batch_axes=batch_axes)
    else:
        ffn = mlp_apply(p["mlp"], h, cfg.act, sharder)
    return x + ffn, new_cache


def forward_decode(params, cfg: ModelConfig, tokens: Array,
                   caches: List[PyTree], pos: Array, *,
                   sharder: Sharder = IDENTITY_SHARDER, mesh=None,
                   batch_axes=(), page_table=None,
                   window_cap: Optional[int] = None
                   ) -> Tuple[Array, List[PyTree]]:
    """One decode step. tokens: (B, 1); pos: scalar position index, or a
    (B,) vector of per-row positions (slot-engine decode — see
    :func:`repro.models.attention.attn_decode_step`).

    With ``page_table`` set, attention cache leaves are expected to be
    page pools with leading layer axis, scanned like dense caches.  It
    may be a bare ``(B, max_pages)`` array (pure global paging, the
    PR-5 calling convention) or a dict of per-class tables —
    ``{"global": ..., "local": (B, R) ring table, "cross": (B, C)}`` —
    each layer resolving K/V through the table matching its cache leaf
    names (``pk``/``lk``/``ck``).  ``window_cap`` is the dense-ring
    capacity ``min(sliding_window, max_seq)`` for paged local layers
    (defaults to ``cfg.sliding_window``)."""
    if page_table is not None and not isinstance(page_table, dict):
        page_table = {"global": page_table}
    x = embedding_lookup(params["embed"], tokens)
    x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    x = sharder.constrain(x, "hidden_decode")
    new_caches = []
    for gp, cache, (pattern, n_reps) in zip(params["groups"], caches,
                                            cfg.layer_groups()):
        def body(carry, xs, pattern=pattern):
            x = carry
            layer_p, layer_c = xs
            new_c = {}
            for i, kind in enumerate(pattern):
                x, c = _block_decode(layer_p[f"b{i}"], x, layer_c[f"b{i}"],
                                     pos, kind, cfg, sharder=sharder,
                                     mesh=mesh, batch_axes=batch_axes,
                                     page_table=page_table,
                                     window_cap=window_cap)
                new_c[f"b{i}"] = c
            return x, new_c
        x, new_cache = jax.lax.scan(body, x, (gp, cache))
        new_caches.append(new_cache)
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), new_caches
