"""The dense block: pre-norm attention with RoPE and grouped KV heads,
then a plain or gated MLP, as the repository's ``transformer`` implements
it for every ``family="dense"`` configuration.

    per layer:  h = rmsnorm(x);  x += o(attention(rope(q(h)), rope(k(h)), v(h)))
                h = rmsnorm(x);  x += down(act(gate(h)) * up(h))   (gated)
                                 x += down(act(up(h)))              (plain)

Key/value head ``j`` serves query heads ``j * n_rep ... (j + 1) * n_rep
- 1``; RoPE rotates the two halves of each head; ``gelu`` is the tanh
form.  What lies outside the layers is :mod:`reference`'s.

A configuration file names this module with ``"block": "dense"``; the
harness loads it by path and reads what is below: the registry keys the
file must match, each leaf's role and shape, the reference, and the
operations and bytes the metric readers count.
"""
from __future__ import annotations

import math
from typing import Iterable, Optional

import jax
import jax.numpy as jnp

from benchmarks.chip import reference
from benchmarks.chip.reference import act, linear, rmsnorm, rope

# Config keys the registry's ModelConfig must match, so the program runs
# the configuration the file states (file key -> ModelConfig attribute).
REGISTRY_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "resolved_head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "norm_eps": "norm_eps", "gated_mlp": "gated_mlp", "hidden_act": "act",
    "use_bias": "use_bias", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "param_dtype",
}

# The role of each leaf of a layer, by its path under the layer in the
# program's tree.
ROLES = {
    "norm1/scale": "norm1", "norm2/scale": "norm2",
    "mixer/q/w": "q.w", "mixer/k/w": "k.w", "mixer/v/w": "v.w",
    "mixer/o/w": "o.w", "mixer/q/b": "q.b", "mixer/k/b": "k.b",
    "mixer/v/b": "v.b", "mixer/o/b": "o.b",
    "mlp/up/w": "up.w", "mlp/gate/w": "gate.w", "mlp/down/w": "down.w",
    "mlp/up/b": "up.b", "mlp/gate/b": "gate.b", "mlp/down/b": "down.b",
}

# No weight of this block has an expert axis.
EXPERT_ROLES = frozenset()

KV_DTYPE_BYTES = 2  # the configurations serve a bfloat16 KV pool


def role(keys) -> Optional[str]:
    """The role of the leaf at ``keys`` under a layer, or ``None``."""
    return ROLES.get("/".join(keys))


def layer_shapes(spec: dict, layer: int) -> dict:
    """Every layer's weights by role (all layers alike)."""
    d, hd, ff = spec["hidden_size"], spec["head_dim"], spec["intermediate_size"]
    h, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    shapes = {"norm1": (d,), "norm2": (d,), "q.w": (d, h * hd),
              "k.w": (d, hkv * hd), "v.w": (d, hkv * hd), "o.w": (h * hd, d),
              "up.w": (d, ff), "down.w": (ff, d)}
    if spec["gated_mlp"]:
        shapes["gate.w"] = (d, ff)
    if spec["use_bias"]:
        for name, shp in list(shapes.items()):
            if name.endswith(".w"):
                shapes[name[:-2] + ".b"] = (shp[1],)
    return shapes


# --------------------------------------------------------------------------
# The reference
# --------------------------------------------------------------------------
def _layer(spec, w, x, quant=None):
    """One block over x: (B, S, d) float32; ``quant`` rounds the inputs
    of the projections (the control)."""
    b, s, _ = x.shape
    h_n, kv_n = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, eps = spec["head_dim"], spec["norm_eps"]
    h = rmsnorm(x, w["norm1"], eps)
    q = linear(w, "q", h, quant).reshape(b, s, h_n, hd)
    k = linear(w, "k", h, quant).reshape(b, s, kv_n, hd)
    v = linear(w, "v", h, quant).reshape(b, s, kv_n, hd)
    q, k = rope(q, spec["rope_theta"]), rope(k, spec["rope_theta"])
    q = q.reshape(b, s, kv_n, h_n // kv_n, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, reference.NEG)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(b, s, h_n * hd)
    x = x + linear(w, "o", att, quant)
    h = rmsnorm(x, w["norm2"], eps)
    if spec["gated_mlp"]:
        mid = act(spec["hidden_act"], linear(w, "gate", h, quant)) \
            * linear(w, "up", h, quant)
    else:
        mid = act(spec["hidden_act"], linear(w, "up", h, quant))
    return x + linear(w, "down", mid, quant)


class Reference(reference.Reference):
    """The float32 reference of a dense configuration."""

    layer = staticmethod(_layer)
    layer_shapes = staticmethod(layer_shapes)
    expert_roles = EXPERT_ROLES


# --------------------------------------------------------------------------
# Operations and bytes that the algorithm needs, from the configuration's
# shapes, never from a compile.  Counts are multiply-adds times two.
# Padding, recomputation and rows a batch carries without a request are
# not work and are not counted.
# --------------------------------------------------------------------------
def layer_matmul_params(spec: dict) -> int:
    d, hd = spec["hidden_size"], spec["head_dim"]
    h, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    ff = spec["intermediate_size"]
    attn = d * h * hd * 2 + d * hkv * hd * 2      # q, o; k, v
    mlp = (3 if spec["gated_mlp"] else 2) * d * ff
    return attn + mlp


def layer_flops_per_token(spec: dict) -> float:
    """Every layer's projections for one token (attention scores apart)."""
    return 2.0 * spec["num_hidden_layers"] * layer_matmul_params(spec)


def head_flops_per_token(spec: dict) -> float:
    return 2.0 * spec["hidden_size"] * spec["vocab_size"]


def attn_flops(spec: dict, context: int) -> float:
    """Scores and weighted values of one token over ``context`` keys, in
    every layer."""
    return (4.0 * spec["num_hidden_layers"] * spec["num_attention_heads"]
            * spec["head_dim"] * context)


def decode_token_flops(spec: dict, context: int) -> float:
    return (layer_flops_per_token(spec) + head_flops_per_token(spec)
            + attn_flops(spec, context))


def prompt_flops(spec: dict, prompt_len: int) -> float:
    """A causal prefill of ``prompt_len`` tokens: every layer for every
    token, token i attending to i + 1 keys, and the head once (only the
    last position's logits are needed)."""
    p = prompt_len
    per_ctx = attn_flops(spec, 1)
    return (p * layer_flops_per_token(spec) + head_flops_per_token(spec)
            + per_ctx * p * (p + 1) / 2)


def kv_bytes_per_token_layer(spec: dict, tp: int = 1) -> float:
    """K and V of one token in one layer, as one of ``tp`` chips that
    split the heads holds them (a KV head is held whole by every chip
    whose query heads read it)."""
    kv_heads = math.ceil(spec["num_key_value_heads"] / tp)
    return 2 * kv_heads * spec["head_dim"] * KV_DTYPE_BYTES


def paged_attn_need(spec: dict, contexts: Iterable[int], tp: int = 1):
    """(bytes, flops) that the decode-attention kernel needs on one of
    ``tp`` chips, over all layers (every layer attends through it), for
    decode tokens at ``contexts``."""
    total_ctx = float(sum(contexts))
    layers = spec["num_hidden_layers"]
    nbytes = total_ctx * kv_bytes_per_token_layer(spec, tp) * layers
    flops = attn_flops(spec, 1) * total_ctx / tp
    return nbytes, flops
