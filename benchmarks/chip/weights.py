"""Random weights drawn from ``--seed``, shared by the served program and
the reference without either taking them from the other.

Each weight is named by a role (``q.w``, ``up.b``, ``norm1``, ``embed``,
...) and a layer.  Row ``r`` of a matrix is drawn from a key folded from
the seed, the role, the layer and ``r``, so its values do not depend on
how many rows the holder keeps (a padded vocabulary), how the layers are
stacked or how the matrix is sharded.  The program's parameter tree is
filled by role through :func:`program_params`; the reference draws one
layer at a time through :func:`layer_weights`.
"""
from __future__ import annotations

import math
import zlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Scale of each role's draw: projections at 1/sqrt(fan_in) keep unit
# activations; norms and biases are near 1 and 0 but not equal to them,
# so a path that drops one shows.
NORM_STD = 0.1
BIAS_STD = 0.1

# The "layer" of the weights outside the layers (embeddings, final norm).
NO_LAYER = 0xFFFF

LAYER_ROLES = ("norm1", "norm2", "q.w", "k.w", "v.w", "o.w", "q.b", "k.b",
               "v.b", "o.b", "up.w", "gate.w", "down.w", "up.b", "gate.b",
               "down.b")


def base_key(seed: int) -> jax.Array:
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _role_key(key: jax.Array, role: str, layer) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32(role.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(key, layer)


def draw(key: jax.Array, role: str, layer, shape: Tuple[int, ...],
         dtype) -> jax.Array:
    """The weight ``role`` of ``layer`` (``NO_LAYER`` outside them), shaped
    ``shape``: row ``r`` of a matrix from ``fold_in(key, r)``."""
    k = _role_key(key, role, layer)
    if len(shape) == 1:
        z = jax.random.normal(k, shape, jnp.float32)
        if role.startswith("norm") or role == "final_norm":
            return (np.float32(1.0) + np.float32(NORM_STD) * z).astype(dtype)
        return (np.float32(BIAS_STD) * z).astype(dtype)
    rows = jnp.arange(shape[0])
    z = jax.vmap(lambda r: jax.random.normal(jax.random.fold_in(k, r),
                                             shape[1:], jnp.float32))(rows)
    fan_in = shape[1] if role in ("embed", "lm_head") else shape[0]
    return (z * np.float32(1.0 / math.sqrt(fan_in))).astype(dtype)


# --------------------------------------------------------------------------
# The program's tree, filled by role
# --------------------------------------------------------------------------
def role_of(path) -> str:
    """Role of a leaf of the program's parameter tree, from its path
    (``groups/0/b0/mixer/q/w`` -> ``q.w``); an unknown leaf is an error,
    so a change of the program's tree cannot pass unseen."""
    keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    last = keys[-2:]
    if keys[0] in ("embed", "lm_head") and keys[-1] == "table":
        return keys[0]
    if keys == ["final_norm", "scale"]:
        return "final_norm"
    if keys[0] == "groups" and last[-1] == "scale" and last[0] in (
            "norm1", "norm2"):
        return last[0]
    if keys[0] == "groups" and last[-1] in ("w", "b"):
        role = f"{last[0]}.{last[1]}"
        if role in LAYER_ROLES:
            return role
    raise KeyError(f"no role for parameter {'/'.join(keys)}")


def _layer_index(path, group_offsets, period) -> Callable:
    """Layers of the stacked leaf at ``path``: group ``g``'s repeat ``r``
    of pattern block ``b<i>`` is layer ``offset_g + r * period + i``."""
    keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    g = int(keys[1])
    i = int(keys[2][1:])
    return lambda r: group_offsets[g] + r * period[g] + i


def program_params(shapes_tree, seed: int, out_shardings=None):
    """Fill the program's parameter tree (``jax.eval_shape`` of its
    ``init_params``) from ``seed``, as one jitted call on the device,
    each leaf in its own dtype and, with ``out_shardings``, straight onto
    its shards."""
    groups = shapes_tree.get("groups", [])
    period = [len(g) for g in groups]
    reps = [jax.tree.leaves(g)[0].shape[0] for g in groups]
    offsets = [sum(reps[j] * period[j] for j in range(g))
               for g in range(len(groups))]

    def fill(key):
        def leaf(path, sd):
            role = role_of(path)
            if role in ("embed", "lm_head", "final_norm"):
                return draw(key, role, NO_LAYER, sd.shape, sd.dtype)
            layer = _layer_index(path, offsets, period)
            rs = jnp.arange(sd.shape[0])
            return jax.vmap(lambda r: draw(key, role, layer(r),
                                           sd.shape[1:], sd.dtype))(rs)
        return jax.tree_util.tree_map_with_path(leaf, shapes_tree)

    return jax.jit(fill, out_shardings=out_shardings)(base_key(seed))


# --------------------------------------------------------------------------
# One layer at a time, for the reference
# --------------------------------------------------------------------------
def layer_shapes(spec: dict) -> Dict[str, Tuple[int, ...]]:
    d, hd, ff = spec["hidden_size"], spec["head_dim"], spec["intermediate_size"]
    h, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    shapes = {"norm1": (d,), "norm2": (d,), "q.w": (d, h * hd),
              "k.w": (d, hkv * hd), "v.w": (d, hkv * hd), "o.w": (h * hd, d),
              "up.w": (d, ff), "down.w": (ff, d)}
    if spec["gated_mlp"]:
        shapes["gate.w"] = (d, ff)
    if spec["use_bias"]:
        for role, shp in list(shapes.items()):
            if role.endswith(".w"):
                shapes[role[:-2] + ".b"] = (shp[1],)
    return shapes


def layer_weights(spec: dict, key: jax.Array, layer,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Layer ``layer``'s weights as the program holds them (``dtype``),
    for the reference to upcast."""
    return {role: draw(key, role, layer, shp, dtype)
            for role, shp in layer_shapes(spec).items()}
