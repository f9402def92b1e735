"""FSDP x TP x EP x SP sharding rules (DESIGN.md §5).

Parameters: Megatron-style tensor parallelism over the ``model`` axis
(column-split up-projections / heads, row-split down-projections), ZeRO-3
style fully-sharded storage over the ``data`` (+``pod``) axes on the
complementary dimension.  Every rule is divisibility-guarded: if a dim
does not divide over the proposed axes the spec degrades gracefully
(fewer axes -> replication) instead of failing — this is what lets one
rule set cover d_model from 512 (whisper) to 12288 (command-r+) and head
counts from 4 to 96.

Activations: sequence parallelism over ``model`` between blocks, head
parallelism inside attention, vocab parallelism on logits; KV caches
shard heads when divisible, else sequence (the long-context decode path).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.paged_attn import kv_pool_layout
from repro.models.common import Sharder

PyTree = Any


# --------------------------------------------------------------------------
# Helpers
# --------------------------------------------------------------------------
def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        return mesh.shape[axes]
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out


def _fit(mesh: Mesh, dim: int, axes) -> Optional[Any]:
    """Return ``axes`` (possibly reduced) such that dim divides the axis
    product, or None for replication."""
    if axes is None:
        return None
    cand = axes if isinstance(axes, tuple) else (axes,)
    # try full tuple, then drop leading axes
    for start in range(len(cand)):
        sub = cand[start:]
        size = _axes_size(mesh, sub)
        if size > 1 and dim % size == 0:
            return sub if len(sub) > 1 else sub[0]
    return None


def _canon(entries) -> P:
    """Build a PartitionSpec with trailing ``None``s stripped.

    ``with_sharding_constraint`` canonicalizes its output sharding to
    the short form (``P(None, 'model')`` not ``P(None, 'model', None)``),
    and jit compile caches key on the exact sharding object — so every
    spec we hand to ``device_put`` must use the same spelling or a
    freshly-allocated buffer triggers a spurious recompile against the
    constrained form.
    """
    entries = list(entries)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def _spec(mesh: Mesh, shape: Sequence[int], *axes) -> P:
    """Divisibility-guarded PartitionSpec builder."""
    return _canon(_fit(mesh, d, a) for d, a in zip(shape, axes))


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    pod: Optional[str] = "pod"       # None when single-pod
    data: str = "data"
    model: str = "model"

    @property
    def batch(self) -> Tuple[str, ...]:
        return (self.pod, self.data) if self.pod else (self.data,)

    @property
    def fsdp(self) -> Tuple[str, ...]:
        return (self.pod, self.data) if self.pod else (self.data,)


def mesh_axes_for(mesh: Mesh) -> MeshAxes:
    return MeshAxes(pod="pod" if "pod" in mesh.axis_names else None)


# --------------------------------------------------------------------------
# Parameter specs (path-pattern rules)
# --------------------------------------------------------------------------
def _param_rule(path: str, shape: Tuple[int, ...], mesh: Mesh, ax: MeshAxes,
                cfg, fsdp: bool) -> P:
    """Sharding rule for one parameter leaf; `path` like
    'groups/0/b1/mixer/q/w' (leading stack dim already stripped)."""
    F = ax.fsdp if fsdp else None
    M = ax.model
    ndim = len(shape)

    if ndim <= 1:
        return P()                                   # norms, biases, gates

    # --- embeddings / lm head: (vocab_padded, d) ---
    if re.search(r"(embed|lm_head)/table$", path):
        return _spec(mesh, shape, M, F)

    # --- MoE expert weights: (E, d, ff) / (E, ff, d): EP over model ---
    if "/moe/" in path:
        if path.endswith("router"):
            return P()
        return _spec(mesh, shape, M, F, None)

    # --- attention projections ---
    m = re.search(r"/(mixer|cross)/([qkvo])/w$", path)
    if m:
        which = m.group(2)
        heads = cfg.n_heads if which in ("q", "o") else cfg.n_kv_heads
        head_ok = heads % mesh.shape[M] == 0
        if which == "o":      # (H*hd, d): row-parallel over heads
            return _spec(mesh, shape, M if head_ok else None, F)
        # q/k/v: (d, H*hd): column-parallel over heads
        return _spec(mesh, shape, F, M if head_ok else None)

    # --- dense MLP ---
    if re.search(r"/mlp/(up|gate)/w$", path):
        return _spec(mesh, shape, F, M)              # (d, ff): col-parallel
    if re.search(r"/mlp/down/w$", path):
        return _spec(mesh, shape, M, F)              # (ff, d): row-parallel

    # --- recurrent blocks: square projections — col/row parallel ---
    if re.search(r"/mixer/(in_gate|in_rec|r|k|v|w)/w$", path):
        return _spec(mesh, shape, F, M)
    if re.search(r"/mixer/(out|o)/w$", path):
        return _spec(mesh, shape, M, F)

    if "frontend_proj" in path:
        return _spec(mesh, shape, None, M)

    # fallback: FSDP on dim0
    return _spec(mesh, shape, F, *([None] * (ndim - 1)))


def _tree_paths(tree: PyTree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        segs = []
        for p in path:
            if hasattr(p, "key"):
                segs.append(str(p.key))
            elif hasattr(p, "idx"):
                segs.append(str(p.idx))
            else:
                segs.append(str(p))
        yield "/".join(segs), leaf
    return


def param_specs(params_shapes: PyTree, cfg, mesh: Mesh,
                fsdp: bool = True) -> PyTree:
    """PartitionSpec pytree matching ``params_shapes`` (ShapeDtypeStructs
    or arrays)."""
    ax = mesh_axes_for(mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    specs = []
    for path, leaf in flat:
        segs = []
        for p in path:
            if hasattr(p, "key"):
                segs.append(str(p.key))
            elif hasattr(p, "idx"):
                segs.append(str(p.idx))
        spath = "/".join(segs)
        shape = tuple(leaf.shape)
        stacked = spath.startswith("groups/") or "/groups/" in spath
        if stacked and len(shape) >= 1:
            inner = _param_rule(spath, shape[1:], mesh, ax, cfg, fsdp)
            spec = P(None, *inner)
        else:
            spec = _param_rule(spath, shape, mesh, ax, cfg, fsdp)
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def opt_state_specs(param_spec_tree: PyTree, opt_state) -> Any:
    """AdamW moments shard exactly like their params; step is replicated."""
    from repro.optim.adamw import AdamWState
    return AdamWState(step=P(), mu=param_spec_tree, nu=param_spec_tree)


def to_named(tree_specs: PyTree, mesh: Mesh) -> PyTree:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# Serving-cache specs (slot buffers, paged page pools, recurrent states)
# --------------------------------------------------------------------------
_POOL_LEAVES = ("pk", "pv", "pk_s", "pv_s",   # global page pool
                "lk", "lv",                    # sliding-window ring pool
                "ck", "cv")                    # enc-dec cross pool


def _pool_axes(layout: str, model) -> Tuple:
    """Mesh axes of one layer's paged pool ``(pages, psz, Hkv, hd)`` for
    a :func:`~repro.kernels.paged_attn.kv_pool_layout`; the page axis is
    never split."""
    return {"heads": (None, None, model, None),
            "replicated": (None, None, None, None)}.get(
                layout, (None, model, None, None))


def cache_specs(cache_shapes: PyTree, cfg, mesh: Mesh, *,
                batch_axes=None) -> PyTree:
    """PartitionSpec pytree for serving KV storage on a TP/DP mesh.

    Covers every cache layout the engines allocate, dispatching on the
    leaf *name* (the paged pool and the dense slot cache are both 5-dim,
    so shape alone cannot distinguish them):

    * ``pk``/``pv`` (+ ``pk_s``/``pv_s`` int8 scale planes), ``lk``/``lv``
      (sliding-window ring pool) and ``ck``/``cv`` (enc-dec cross pool) —
      paged pools ``(L, pages+1, psz, Hkv, hd|1)``: the page axis is
      **never** sharded (the page tables index physical pages globally,
      so every shard must see every page row); K/V heads go
      tensor-parallel over ``model`` when divisible, a single KV head
      (multi-query attention) is replicated, else the page interior
      seq-shards (:func:`repro.kernels.paged_attn.kv_pool_layout`).
    * ``k``/``v`` (+ scales) — dense slot cache ``(L, B, cap, Hkv,
      hd|1)``: batch over ``batch_axes`` and heads over ``model`` when
      divisible, else the sequence axis shards (long-context fallback).
    * 3-dim ``(L, B, d)`` recurrent states: feature dim over ``model``.
    * anything else: replicated.

    Every rule is divisibility-guarded through :func:`_fit` — an odd
    mesh degrades to replication, it never raises.  The page table and
    position vectors are deliberately *not* covered here: they are
    replicated (``P()``) by construction.

    ``batch_axes=None`` means the mesh's data axes; serving engines pass
    ``()`` because their leading cache dim is the logical slot index
    (fixed ``max_slots``), not a data-parallel batch.
    """
    ax = mesh_axes_for(mesh)
    M = ax.model if ax.model in mesh.axis_names else None
    if batch_axes is None:
        B = tuple(a for a in ax.batch if a in mesh.axis_names) or None
    else:
        B = tuple(batch_axes) or None
    ms = _axes_size(mesh, M)
    head_ok = (ms > 1 and cfg.n_heads % ms == 0
               and cfg.n_kv_heads % ms == 0)
    pool_layout = kv_pool_layout(cfg.n_heads, cfg.n_kv_heads, ms)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shapes)
    specs = []
    for path, leaf in flat:
        name = ""
        for p in reversed(path):
            if hasattr(p, "key"):
                name = str(p.key)
                break
        shape = tuple(leaf.shape)
        nd = len(shape)
        if name in _POOL_LEAVES and nd == 5:
            spec = _spec(mesh, shape, None, *_pool_axes(pool_layout, M))
        elif name == "state" and nd == 5:
            # WKV state — dense (L, B, H, hd, hd) or paged slab
            # (L, slots, H, hd, hd): heads live on axis 2 (not axis 3
            # like attention caches), so the generic 5-dim rule would
            # split the hd x hd outer product instead of the heads.
            spec = _canon((None, _fit(mesh, shape[1], B),
                           _fit(mesh, shape[2], M), None, None))
        elif nd == 5:
            b = _fit(mesh, shape[1], B)
            if head_ok:
                spec = _canon((None, b, None, _fit(mesh, shape[3], M),
                               None))
            else:
                spec = _canon((None, b, _fit(mesh, shape[2], M), None,
                               None))
        elif nd == 4:
            spec = _canon((None, _fit(mesh, shape[1], B), None, None))
        elif nd == 3:
            spec = _canon((None, _fit(mesh, shape[1], B),
                           _fit(mesh, shape[2], M)))
        else:
            spec = P()
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


# --------------------------------------------------------------------------
# Activation sharding
# --------------------------------------------------------------------------
class MeshSharder(Sharder):
    """Activation-constraint injector used by the model zoo."""

    def __init__(self, mesh: Mesh, cfg, batch_axes=None):
        self.mesh = mesh
        self.cfg = cfg
        self.ax = mesh_axes_for(mesh)
        # Serving constrains per-slot activations whose leading dim is
        # the logical slot index, not a data-parallel batch: engines
        # pass batch_axes=() so slot counts never alias the data axis.
        self._batch = (self.ax.batch if batch_axes is None
                       else tuple(batch_axes))
        # Sequence parallelism conflicts with *sequentially*-scanned
        # recurrences: WKV's chunk loop is a sequential lax.scan whose
        # leading axis must be unsharded, so XLA all-gathers the full
        # sequence per model rank (measured 6.5x memory blowup on rwkv
        # train with dim-preserved linears — §Perf X3).  RG-LRU uses an
        # associative_scan (log-depth, parallel) and keeps SP: forcing
        # it batch-only measured 2.8x WORSE (recurrentgemma train).
        # For WKV the trade is mesh-dependent (batch-only wins 1.45x on
        # the 512-chip mesh, loses 1.4x single-pod), so SP is dropped
        # only when a pod axis exists.
        from repro.configs.base import WKV
        self.seq_shard = (WKV not in cfg.layer_pattern
                          or "pod" not in mesh.axis_names)

    def _c(self, x, *axes):
        spec = _spec(self.mesh, x.shape, *axes)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def constrain(self, x, role: str):
        ax = self.ax
        B, M = self._batch, ax.model
        head_ok = (self.cfg.n_heads % self.mesh.shape[M] == 0
                   and self.cfg.n_kv_heads % self.mesh.shape[M] == 0)
        if role == "hidden":            # (B, S, d): SP over seq
            return self._c(x, B, M if self.seq_shard else None, None)
        if role == "hidden_decode":     # (B, 1, d)
            return self._c(x, B, None, None)
        if role == "mlp_hidden":        # (B, S, ff)
            return self._c(x, B, None, M)
        if role in ("attn_q",):         # (B, S, H, hd)
            return self._c(x, B, None, M if head_ok else None, None)
        if role == "attn_kv":
            return self._c(x, B, None, M if head_ok else None, None)
        if role == "attn_logits":       # (B, H, Sq, Skv)
            if head_ok:
                return self._c(x, B, M, None, None)
            return self._c(x, B, None, None, M)   # seq-sharded softmax
        if role == "kv_pool":           # (pages, psz, Hkv, hd)
            return self._c(x, *_pool_axes(kv_pool_layout(
                self.cfg.n_heads, self.cfg.n_kv_heads, self.mesh.shape[M]),
                M))
        if role == "kv_cache":          # (B, cap, Hkv, hd)
            if head_ok:
                return self._c(x, B, None, M, None)
            return self._c(x, B, M, None, None)   # sequence-sharded cache
        if role == "logits":            # (B, S, vocab_p)
            return self._c(x, B, None, M)
        if role == "rnn_state_seq":     # (B, S, d)
            return self._c(x, B, M if self.seq_shard else None, None)
        return x


def batch_specs(cell_step: str, mesh: Mesh, cfg) -> PyTree:
    """Input-batch PartitionSpecs for a shape cell."""
    ax = mesh_axes_for(mesh)
    return {
        "tokens": P(ax.batch, None),
        "labels": P(ax.batch, None),
        "frontend_embeds": P(ax.batch, None, None),
    }
