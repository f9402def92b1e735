"""Operations and bytes that the algorithm needs, from a configuration's
shapes (the ``shapes`` of ``configs/<name>.json``), never from a compile.

Counts are multiply-adds times two.  Padding, recomputation and rows a
batch carries without a request are not work and are not counted.
"""
from __future__ import annotations

import math
from typing import Iterable

KV_DTYPE_BYTES = 2  # the configurations serve a bfloat16 KV pool


def layer_matmul_params(shapes: dict) -> int:
    d, hd = shapes["hidden_size"], shapes["head_dim"]
    h, hkv = shapes["num_attention_heads"], shapes["num_key_value_heads"]
    ff = shapes["intermediate_size"]
    attn = d * h * hd * 2 + d * hkv * hd * 2      # q, o; k, v
    mlp = (3 if shapes["gated_mlp"] else 2) * d * ff
    return attn + mlp


def layer_flops_per_token(shapes: dict) -> float:
    """Every layer's projections for one token (attention scores apart)."""
    return 2.0 * shapes["num_hidden_layers"] * layer_matmul_params(shapes)


def head_flops_per_token(shapes: dict) -> float:
    return 2.0 * shapes["hidden_size"] * shapes["vocab_size"]


def attn_flops(shapes: dict, context: int) -> float:
    """Scores and weighted values of one token over ``context`` keys, in
    every layer."""
    return (4.0 * shapes["num_hidden_layers"] * shapes["num_attention_heads"]
            * shapes["head_dim"] * context)


def decode_token_flops(shapes: dict, context: int) -> float:
    return (layer_flops_per_token(shapes) + head_flops_per_token(shapes)
            + attn_flops(shapes, context))


def prompt_flops(shapes: dict, prompt_len: int) -> float:
    """A causal prefill of ``prompt_len`` tokens: every layer for every
    token, token i attending to i + 1 keys, and the head once (only the
    last position's logits are needed)."""
    p = prompt_len
    per_ctx = attn_flops(shapes, 1)
    return (p * layer_flops_per_token(shapes) + head_flops_per_token(shapes)
            + per_ctx * p * (p + 1) / 2)


def kv_bytes_per_token_layer(shapes: dict, tp: int = 1) -> float:
    """K and V of one token in one layer, as one of ``tp`` chips that
    split the heads holds them (a KV head is held whole by every chip
    whose query heads read it)."""
    kv_heads = math.ceil(shapes["num_key_value_heads"] / tp)
    return 2 * kv_heads * shapes["head_dim"] * KV_DTYPE_BYTES


def paged_attn_need(shapes: dict, contexts: Iterable[int], tp: int = 1):
    """(bytes, flops) that the decode-attention kernel needs on one of
    ``tp`` chips, over all layers, for decode tokens at ``contexts``."""
    total_ctx = float(sum(contexts))
    layers = shapes["num_hidden_layers"]
    nbytes = total_ctx * kv_bytes_per_token_layer(shapes, tp) * layers
    flops = attn_flops(shapes, 1) * total_ctx / tp
    return nbytes, flops
