"""GPipe-style pipeline parallelism over a mesh axis (optional path).

The default distribution treats the ``pod`` axis as outer data parallelism
(DESIGN.md §5); this module provides the alternative: stages laid out
along an axis, microbatches streamed with ``lax.ppermute``, 1F1B-less
(plain GPipe) schedule.  Bubble fraction = (S-1)/(M+S-1).

Usage (inside jit, mesh in scope):

    y = pipeline_apply(stage_fn, stage_params, x_micro, mesh, axis="pod")

where ``stage_params`` is stacked on a leading stage axis (sharded over
``axis``) and ``x_micro`` is (n_micro, mb, ...) with outputs gathered from
the last stage.  ``schedule_bubble_fraction`` exposes the analytical
schedule model used by tests and the §Perf napkin math.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any


def schedule_bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble: idle slots / total slots."""
    total = n_micro + n_stages - 1
    return (n_stages - 1) / total


def pipeline_apply(stage_fn: Callable, stage_params: PyTree,
                   x_micro: jax.Array, mesh, axis: str = "pod"):
    """Run ``stage_fn(params_s, x)`` as a pipeline over ``axis``.

    stage_params leaves: (n_stages, ...) sharded over ``axis``;
    x_micro: (n_micro, mb, d) replicated over ``axis``.
    Returns (n_micro, mb, d) outputs of the final stage (replicated).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    steps = n_micro + n_stages - 1
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def shard_fn(params_local, xs):
        # params_local: (1, ...) this stage's slice; xs: all microbatches
        stage = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_local)
        mb_shape = xs.shape[1:]

        def body(carry, t):
            recv, outs = carry
            # stage 0 injects microbatch t (if still in range)
            inject = xs[jnp.clip(t, 0, n_micro - 1)]
            inp = jnp.where(stage == 0, inject, recv)
            out = stage_fn(p, inp)
            # pass activations down the pipe
            nxt = jax.lax.ppermute(out, axis, perm)
            # last stage records its output at slot t-(S-1)
            slot = t - (n_stages - 1)
            valid = (stage == n_stages - 1) & (slot >= 0)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, out[None], jnp.maximum(slot, 0), axis=0),
                lambda o: o, outs)
            return (nxt, outs), None

        outs0 = jnp.zeros((n_micro,) + mb_shape, xs.dtype)
        (_, outs), _ = jax.lax.scan(body, (jnp.zeros(mb_shape, xs.dtype),
                                           outs0), jnp.arange(steps))
        # broadcast final-stage outputs to every stage
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, 0), axis)
        return outs

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis), P()), out_specs=P(),
        check_vma=False)(stage_params, x_micro)
