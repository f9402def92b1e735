"""Per-architecture smoke tests (reduced configs, CPU).

For each assigned arch: one forward/train step asserting output shapes and
no NaNs, plus a prefill->decode consistency check (the decode step at
position S must reproduce the full-sequence forward's next-token logits).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config, smoke_config
from repro.configs.base import cell_applicable, SHAPE_CELLS
from repro.models import (forward_decode, forward_prefill, forward_train,
                          init_cache, init_params)
from repro.models.common import padded_vocab

B, S = 2, 32


def _batch(cfg, key, seq=S):
    ks = jax.random.split(key, 3)
    batch = {}
    if cfg.enc_dec:
        batch["frontend_embeds"] = jax.random.normal(
            ks[0], (B, seq, cfg.frontend_dim), jnp.float32)
        batch["tokens"] = jax.random.randint(
            ks[1], (B, cfg.dec_max_len), 0, cfg.vocab_size)
    elif cfg.frontend is not None:
        batch["frontend_embeds"] = jax.random.normal(
            ks[0], (B, seq, cfg.frontend_dim), jnp.float32)
        batch["tokens"] = jax.random.randint(ks[1], (B, seq), 0,
                                             cfg.vocab_size)
        batch["labels"] = batch["tokens"]
    else:
        batch["tokens"] = jax.random.randint(ks[1], (B, seq), 0,
                                             cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_forward_and_train_step(arch):
    cfg = smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = _batch(cfg, key)

    def loss_fn(p):
        loss, metrics = forward_train(p, cfg, batch, remat="none")
        return loss, metrics

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    assert jnp.isfinite(loss), (arch, loss)
    assert loss.shape == ()
    # gradient sanity: finite, nonzero somewhere
    leaves = jax.tree.leaves(grads)
    assert all(jnp.all(jnp.isfinite(leaf)) for leaf in leaves), arch
    total = sum(float(jnp.sum(jnp.abs(leaf))) for leaf in leaves)
    assert total > 0, arch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_train_step_with_remat(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(1))
    batch = _batch(cfg, jax.random.PRNGKey(2))
    loss, _ = forward_train(params, cfg, batch, remat="full")
    assert jnp.isfinite(loss)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_prefill_decode_consistency(arch):
    """Decode at position S must match the (S+1)-length forward pass."""
    cfg = smoke_config(arch)
    if cfg.enc_dec:
        pytest.skip("enc-dec covered by test_whisper_encdec_decode")
    key = jax.random.PRNGKey(3)
    params = init_params(cfg, key)
    seq = 16
    tokens = jax.random.randint(key, (B, seq + 1), 0, cfg.vocab_size)
    if cfg.frontend is not None:
        # prefill from embeds; decode continues with tokens
        embeds = jax.random.normal(key, (B, seq, cfg.frontend_dim),
                                   jnp.float32)
        batch_pre = {"frontend_embeds": embeds}
        batch_full = {"frontend_embeds": jnp.pad(
            embeds, ((0, 0), (0, 1), (0, 0)))}
    else:
        batch_pre = {"tokens": tokens[:, :seq]}
        batch_full = {"tokens": tokens}

    logits_pre, cache = forward_prefill(params, cfg, batch_pre,
                                        cache_len=seq + 1)
    logits_step, _ = forward_decode(params, cfg, tokens[:, seq:seq + 1],
                                    cache, jnp.int32(seq))
    if cfg.frontend is not None:
        return  # mixed-modality continuation has no full-seq reference
    # full forward reference over S+1 tokens, compare logits at position S
    from repro.models.transformer import _embed_inputs, _logits, _run_groups
    from repro.models.common import rmsnorm_apply
    x = _embed_inputs(params, cfg, batch_full)
    x, _ = _run_groups(params["groups"], x, cfg.layer_groups(), cfg,
                       sharder=__import__("repro.models.common",
                                          fromlist=["IDENTITY_SHARDER"]
                                          ).IDENTITY_SHARDER,
                       mesh=None, batch_axes=(), positions=None,
                       enc_out=None, remat="none")
    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    ref = _logits(params, cfg, x[:, -1:])
    np.testing.assert_allclose(np.asarray(logits_step, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_whisper_encdec_decode():
    cfg = smoke_config("whisper-base")
    key = jax.random.PRNGKey(4)
    params = init_params(cfg, key)
    seq_enc, seq_dec = 24, 8
    batch = {"frontend_embeds": jax.random.normal(
                 key, (B, seq_enc, cfg.frontend_dim), jnp.float32),
             "tokens": jax.random.randint(key, (B, seq_dec), 0,
                                          cfg.vocab_size)}
    logits, cache = forward_prefill(params, cfg, batch,
                                    cache_len=cfg.dec_max_len)
    assert logits.shape == (B, 1, padded_vocab(cfg.vocab_size))
    tok = jnp.argmax(logits[:, :, :cfg.vocab_size], -1)
    logits2, cache2 = forward_decode(params, cfg, tok, cache,
                                     jnp.int32(seq_dec))
    assert jnp.all(jnp.isfinite(logits2))
    # cross-attention cache must be static across decode steps
    c0 = jax.tree.leaves(cache[0]["b0"]["cross"])
    c1 = jax.tree.leaves(cache2[0]["b0"]["cross"])
    for a, b in zip(c0, c1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sliding_window_cache_is_bounded():
    cfg = smoke_config("gemma3-1b")
    cache = init_cache(cfg, batch=2, seq_len=1024)
    # local layers: capacity == window; global layers: full seq
    local = cache[0]["b0"]["k"]     # first pattern slot is LOCAL
    glob = cache[0]["b5"]["k"]      # sixth slot is global ATTN
    assert local.shape[2] == cfg.sliding_window
    assert glob.shape[2] == 1024


def test_rwkv_chunked_matches_stepwise():
    """The chunkwise-parallel WKV must equal sequential decode steps."""
    from repro.models import rwkv6
    cfg = smoke_config("rwkv6-3b")
    key = jax.random.PRNGKey(5)
    p = rwkv6.rwkv_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (B, 40, cfg.d_model), jnp.float32) * 0.3
    y_par = rwkv6.rwkv_apply(p, x, cfg)
    cache = rwkv6.rwkv_init_cache(B, cfg, jnp.float32)
    ys = []
    for t in range(40):
        y_t, cache = rwkv6.rwkv_decode_step(p, x[:, t:t + 1], cache, cfg)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               atol=1e-3, rtol=1e-3)


def test_rglru_assoc_scan_matches_stepwise():
    from repro.models import rglru
    cfg = smoke_config("recurrentgemma-2b")
    key = jax.random.PRNGKey(6)
    p = rglru.rglru_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (B, 24, cfg.d_model), jnp.float32) * 0.3
    y_par = rglru.rglru_apply(p, x, cfg)
    cache = rglru.rglru_init_cache(B, cfg.d_model, jnp.float32)
    ys = []
    for t in range(24):
        y_t, cache = rglru.rglru_decode_step(p, x[:, t:t + 1], cache, cfg)
        ys.append(y_t)
    y_seq = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_seq),
                               atol=1e-4, rtol=1e-3)


def test_moe_routes_all_tokens():
    """With generous capacity, combine weights must sum to ~1 per token."""
    from repro.models import moe as moe_mod
    cfg = smoke_config("dbrx-132b")
    key = jax.random.PRNGKey(7)
    p = moe_mod.moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (B, 16, cfg.d_model), jnp.float32)
    y, aux = moe_mod.moe_apply(p, x, cfg, mesh=None)
    assert y.shape == x.shape
    assert jnp.all(jnp.isfinite(y)) and jnp.isfinite(aux)
    # zero-input tokens produce zero output (no bias paths)
    y0, _ = moe_mod.moe_apply(p, jnp.zeros_like(x), cfg, mesh=None)
    np.testing.assert_allclose(np.asarray(y0), 0.0, atol=1e-6)


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_full_config_structure(arch):
    """Full (published) configs are structurally valid without allocation."""
    cfg = get_config(arch)
    assert cfg.params_count() > 0
    assert len(cfg.layer_kinds()) == cfg.n_layers
    groups = cfg.layer_groups()
    assert sum(len(p) * r for p, r in groups) == cfg.n_layers
    for cell in SHAPE_CELLS.values():
        ok, why = cell_applicable(cfg, cell)
        assert ok or why


@pytest.mark.parametrize("arch", ["yi-6b", "whisper-base"])
def test_jitted_init_params_matches_eager(arch):
    """The compiled init draws the same weights from the same key as the
    eager one, and the stacked layer axis holds each layer's own init."""
    from repro.models.transformer import _block_init
    cfg = smoke_config(arch)
    key = jax.random.PRNGKey(4)
    eager = init_params(cfg, key)
    jitted = jax.jit(functools.partial(init_params, cfg))(key)
    for a, b in zip(jax.tree.leaves(eager), jax.tree.leaves(jitted)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # Layer j of group 0 is _block_init under the j-th split key — the
    # values a per-layer loop followed by a stack produces.
    (pattern, reps), = cfg.layer_groups()[:1]
    gkey = jax.random.split(jax.random.split(key, 8)[1], 8)[0]
    for j, lk in enumerate(jax.random.split(gkey, reps)):
        kk = jax.random.split(lk, len(pattern))
        one = _block_init(kk[0], pattern[0], cfg, jnp.dtype(cfg.param_dtype),
                          with_cross=cfg.enc_dec)
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(eager["groups"][0]["b0"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b[j]))


def test_prefill_logits_at_many_positions():
    """A (B, K) logits_index returns the logits of K positions per row —
    each equal to the scalar-index prefill at that position."""
    cfg = smoke_config("yi-6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0,
                              cfg.vocab_size)
    idx = jnp.asarray([[3, 10, 17, 23]], jnp.int32)
    many, _ = forward_prefill(params, cfg, {"tokens": toks},
                              logits_index=idx)
    assert many.shape[:2] == (1, 4)
    for k, i in enumerate(np.asarray(idx[0])):
        one, _ = forward_prefill(params, cfg, {"tokens": toks},
                                 logits_index=jnp.int32(i))
        np.testing.assert_allclose(np.asarray(many[0, k]),
                                   np.asarray(one[0, 0]), rtol=1e-5,
                                   atol=1e-5)
