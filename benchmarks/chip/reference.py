"""What every block's plain float32 reference shares.

Straightforward ``jax.numpy`` under ``jax.default_matmul_precision
("highest")``: no kernel, no cache, no batching of requests beyond
padding them to one length.  A reference reads only the configuration's
file and the seed; its weights come from :mod:`weights` one layer at a
time, so it fits next to nothing else on one chip.

A configuration's block (``blocks/<name>.py``) subclasses
:class:`Reference` with its layer's equations and weight layout.  What
lies outside the layers is here, as the repository implements it (each
departure from a published model is listed in the configuration's
file):

    x = embed[token] * sqrt(hidden)
    per layer:  the block's layer
    logits = rmsnorm(x) @ head^T

The helpers below (RMSNorm, the activations, RoPE, the projections, the
control's rounding) are for any block to use.

``quant="int8"`` or ``"fp8"`` is the control, the model computed as an
int8 or float8 (e4m3) server would: every projection (each ``*.w``
role, contracted over its next-to-last axis) and the head take weights
rounded with one scale per output channel and inputs rounded with one
scale per token, the rest as in float32.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights

NEG = -1e30


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def act(name, x):
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    if name == "gelu":
        c = math.sqrt(2.0 / math.pi)
        return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown activation {name!r}")


def rope(x, theta):
    """x: (B, S, H, hd), positions 0..S-1."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def quantize(w: jax.Array, axis: int, kind: str) -> jax.Array:
    """Round ``w`` to ``kind`` (``"int8"``, ``"fp8"``: float8 e4m3) with
    one symmetric scale per slice along ``axis`` (the contracted
    dimension), and back."""
    top = {"int8": 127.0, "fp8": 448.0}[kind]
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / top
    scale = jnp.where(scale == 0, 1.0, scale)
    if kind == "int8":
        return jnp.round(w / scale) * scale
    return (w / scale).astype(jnp.float8_e4m3fn).astype(w.dtype) * scale


def linear(w, name, x, quant=None):
    """``x @ w[name.w]``, plus ``w[name.b]`` where the block has it;
    ``quant`` rounds ``x`` per token first (the control)."""
    if quant is not None:
        x = quantize(x, -1, quant)
    y = x @ w[name + ".w"]
    if name + ".b" in w:
        y = y + w[name + ".b"]
    return y


class Reference:
    """Logits of the reference at chosen positions of padded sequences.

    ``rows`` sequences of ``seq_len`` tokens run ``block`` at a time
    through each layer; the shapes are fixed per cell so the programs
    compile once (once per kind of layer) and come from the persistent
    cache afterwards.

    A block's subclass sets ``layer(spec, w, x, quant)``, one layer over
    ``x: (B, S, d)`` float32 with its weights ``w`` by role, and
    ``layer_shapes(spec, layer)`` and ``expert_roles`` as its module
    states them.
    """

    expert_roles: frozenset = frozenset()

    @staticmethod
    def layer(spec: dict, w: dict, x: jax.Array, quant=None) -> jax.Array:
        raise NotImplementedError

    @staticmethod
    def layer_shapes(spec: dict, layer: int) -> dict:
        raise NotImplementedError

    def __init__(self, spec: dict, seed: int, rows: int, seq_len: int,
                 block: int, quant: Optional[str] = None, device=None):
        if rows % block:
            raise ValueError(f"rows {rows} not a multiple of block {block}")
        self.spec, self.rows, self.seq_len, self.block = (
            spec, rows, seq_len, block)
        self.quant = quant
        self.device = device or jax.devices()[0]
        self.key = jax.device_put(weights.base_key(seed), self.device)
        self._layer_w = jax.jit(self._weights_of_layer, static_argnums=2)
        if quant not in (None, "int8", "fp8"):
            raise ValueError(f"unknown control precision {quant!r}")
        self._layer = jax.jit(functools.partial(self.layer, spec,
                                                quant=quant))
        self._embed = jax.jit(self._embed_tokens)
        self._logits = jax.jit(self._head_logits)

    def _weights_of_layer(self, key, layer, shapes):
        w = weights.layer_weights(dict(shapes), key, layer,
                                  self.expert_roles)
        w = {r: a.astype(jnp.float32) for r, a in w.items()}
        if self.quant is not None:
            w = {r: quantize(a, -2, self.quant) if r.endswith(".w") else a
                 for r, a in w.items()}
        return w

    def _table(self, key, role):
        shape = (self.spec["vocab_size"], self.spec["hidden_size"])
        return weights.draw(key, role, weights.NO_LAYER, shape,
                            jnp.bfloat16).astype(jnp.float32)

    def _embed_tokens(self, key, toks):
        scale = math.sqrt(self.spec["hidden_size"])
        return self._table(key, "embed")[toks] * scale

    def _head_logits(self, key, x, flat_idx):
        spec = self.spec
        d = spec["hidden_size"]
        fn = weights.draw(key, "final_norm", weights.NO_LAYER, (d,),
                          jnp.bfloat16)
        h = x.reshape(-1, d)[flat_idx]
        h = rmsnorm(h, fn.astype(jnp.float32), spec["norm_eps"])
        head = self._table(key, "embed" if spec["tie_word_embeddings"]
                           else "lm_head")
        if self.quant is not None:
            h = quantize(h, 1, self.quant)
            head = quantize(head, 1, self.quant)
        return h @ head.T

    def logits(self, seqs: Sequence[np.ndarray],
               positions: Sequence[np.ndarray], n_out: int) -> jax.Array:
        """Logits ``(n_out, vocab)`` at ``positions[i]`` of ``seqs[i]``,
        concatenated in order (rows past the positions given are
        padding)."""
        spec = self.spec
        toks = np.zeros((self.rows, self.seq_len), np.int32)
        flat = np.zeros(n_out, np.int32)
        at = 0
        for i, (seq, pos) in enumerate(zip(seqs, positions)):
            toks[i, :len(seq)] = seq
            flat[at:at + len(pos)] = i * self.seq_len + np.asarray(pos)
            at += len(pos)
        with jax.default_matmul_precision("highest"), \
                jax.default_device(self.device):
            x = self._embed(self.key, jnp.asarray(toks))
            blocks = [x[i:i + self.block]
                      for i in range(0, self.rows, self.block)]
            for layer in range(spec["num_hidden_layers"]):
                shapes = tuple(self.layer_shapes(spec, layer).items())
                w = self._layer_w(self.key, jnp.int32(layer), shapes)
                blocks = [self._layer(w, xb) for xb in blocks]
                del w
            x = jnp.concatenate(blocks)
            return self._logits(self.key, x, jnp.asarray(flat))


def gaps_of(ref_logits: jax.Array, tokens: np.ndarray) -> np.ndarray:
    """Per position: how far the reference logit of the token chosen
    there lies below the reference's best."""
    lg = ref_logits[:len(tokens)]
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, jnp.asarray(tokens)[:, None], -1)[:, 0]
    return np.asarray(best - got)


def errs_of(ref_logits: jax.Array, tokens: np.ndarray,
            served: np.ndarray) -> np.ndarray:
    """Per position: how far the logit served with the token chosen there
    lies from the reference's logit of that token, either way."""
    lg = ref_logits[:len(tokens)]
    got = jnp.take_along_axis(lg, jnp.asarray(tokens)[:, None], -1)[:, 0]
    return np.abs(np.asarray(got) - np.asarray(served, np.float32))


def top_picks(logits: jax.Array, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """The token ranked first at each of the first ``n`` positions, and
    its logit there."""
    lg = logits[:n]
    picks = jnp.argmax(lg, axis=-1)
    vals = jnp.take_along_axis(lg, picks[:, None], -1)[:, 0]
    return np.asarray(picks, np.int32), np.asarray(vals, np.float32)


def teacher_forced(sample: Sequence[tuple]):
    """(sequence fed, positions read, tokens served) of each
    ``(prompt, served, ...)`` entry: the reference reads position
    ``len(prompt) - 1 + j`` for served token ``j``."""
    seqs, pos, toks = [], [], []
    for prompt, served, *_ in sample:
        seqs.append(np.concatenate([prompt, served[:-1]]).astype(np.int32))
        pos.append(len(prompt) - 1 + np.arange(len(served)))
        toks.append(np.asarray(served, np.int32))
    return seqs, pos, np.concatenate(toks)
