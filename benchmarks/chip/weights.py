"""Random weights drawn from ``--seed``, shared by the served program and
the reference without either taking them from the other.

Each weight is named by a role (``q.w``, ``up.b``, ``norm1``, ``embed``,
...) and a layer.  Row ``r`` of a matrix is drawn from a key folded from
the seed, the role, the layer and ``r``, so its values do not depend on
how many rows the holder keeps (a padded vocabulary), how the layers are
stacked or how the matrix is sharded.  A role that a block marks as
expert-stacked leads with an expert axis: expert ``e`` is a matrix of its
own, drawn from the role's key folded with ``e``'s global index, so a
holder of experts ``[e0, e0 + n)`` draws what the whole layer holds
there.  Which leaf of the program's tree has which role, and which shape
a layer's weights have, is the block's (``blocks/<name>.py``); the
program's tree is filled through :func:`program_params`, and the
reference draws one layer at a time through :func:`layer_weights`.
"""
from __future__ import annotations

import math
import zlib
from typing import Callable, Collection, Dict, Tuple

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
OUTER_ROLES = ("embed", "lm_head", "final_norm")


def base_key(seed: int) -> jax.Array:
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def _role_key(key: jax.Array, role: str, layer) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32(role.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(key, layer)


def _sample(k: jax.Array, role: str, shape: Tuple[int, ...],
            dtype) -> jax.Array:
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


def draw(key: jax.Array, role: str, layer, shape: Tuple[int, ...],
         dtype) -> jax.Array:
    """The weight ``role`` of ``layer`` (``NO_LAYER`` outside them), shaped
    ``shape``: row ``r`` of a matrix from ``fold_in(key, r)``."""
    return _sample(_role_key(key, role, layer), role, shape, dtype)


def draw_experts(key: jax.Array, role: str, layer, shape: Tuple[int, ...],
                 dtype, first_expert=0) -> jax.Array:
    """The expert-stacked weight ``role`` of ``layer``, shaped
    ``(n, ...)``: experts ``first_expert ... first_expert + n - 1`` of the
    layer, each drawn as :func:`draw` draws one weight of ``shape[1:]``
    (a matrix's fan-in is its own input width), from the role's key
    folded with the expert's global index."""
    k = _role_key(key, role, layer)
    experts = first_expert + jnp.arange(shape[0])
    return jax.vmap(lambda e: _sample(jax.random.fold_in(k, e), role,
                                      shape[1:], dtype))(experts)


def draw_role(key: jax.Array, role: str, layer, shape: Tuple[int, ...],
              dtype, expert_roles: Collection[str] = (),
              first_expert=0) -> jax.Array:
    """:func:`draw_experts` for a role in ``expert_roles``, else
    :func:`draw`."""
    if role in expert_roles:
        return draw_experts(key, role, layer, shape, dtype, first_expert)
    return draw(key, role, layer, shape, dtype)


# --------------------------------------------------------------------------
# The program's tree, filled by role
# --------------------------------------------------------------------------
def role_of(path, block) -> str:
    """Role of a leaf of the program's parameter tree, from its path
    (``groups/0/b0/mixer/q/w`` -> ``q.w``): the weights outside the
    layers here, a layer's leaves by ``block.role`` of the path under the
    layer.  An unknown leaf is an error, so a change of the program's tree
    cannot pass unseen."""
    keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    if keys[0] in ("embed", "lm_head") and keys[-1] == "table":
        return keys[0]
    if keys == ["final_norm", "scale"]:
        return "final_norm"
    if keys[0] == "groups":
        role = block.role(keys[3:])
        if role is not None:
            return role
    raise KeyError(f"no role for parameter {'/'.join(keys)} in block "
                   f"{block.__name__}")


def _layer_index(path, group_offsets, period) -> Callable:
    """Layers of the stacked leaf at ``path``: group ``g``'s repeat ``r``
    of pattern block ``b<i>`` is layer ``offset_g + r * period + i``."""
    keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
    g = int(keys[1])
    i = int(keys[2][1:])
    return lambda r: group_offsets[g] + r * period[g] + i


def program_params(shapes_tree, seed: int, block, out_shardings=None,
                   first_expert: int = 0):
    """Fill the program's parameter tree (``jax.eval_shape`` of its
    ``init_params``) from ``seed``, as one jitted call on the device,
    each leaf in its own dtype and, with ``out_shardings``, straight onto
    its shards.  ``block`` names each leaf's role; an expert-stacked
    leaf holds the layer's experts from ``first_expert`` on."""
    groups = shapes_tree.get("groups", [])
    period = [len(g) for g in groups]
    reps = [jax.tree.leaves(g)[0].shape[0] for g in groups]
    offsets = [sum(reps[j] * period[j] for j in range(g))
               for g in range(len(groups))]

    def fill(key):
        def leaf(path, sd):
            role = role_of(path, block)
            if role in OUTER_ROLES:
                return draw(key, role, NO_LAYER, sd.shape, sd.dtype)
            layer = _layer_index(path, offsets, period)
            rs = jnp.arange(sd.shape[0])
            return jax.vmap(lambda r: draw_role(
                key, role, layer(r), sd.shape[1:], sd.dtype,
                block.EXPERT_ROLES, first_expert))(rs)
        return jax.tree_util.tree_map_with_path(leaf, shapes_tree)

    return jax.jit(fill, out_shardings=out_shardings)(base_key(seed))


# --------------------------------------------------------------------------
# One layer at a time, for the reference
# --------------------------------------------------------------------------
def layer_weights(shapes: Dict[str, Tuple[int, ...]], key: jax.Array, layer,
                  expert_roles: Collection[str] = (), first_expert: int = 0,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Layer ``layer``'s weights of ``shapes`` (a block's
    ``layer_shapes``), as the program holds them (``dtype``), for the
    reference to upcast."""
    return {role: draw_role(key, role, layer, shp, dtype, expert_roles,
                            first_expert)
            for role, shp in shapes.items()}
