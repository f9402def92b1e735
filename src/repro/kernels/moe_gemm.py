"""Grouped per-expert GEMM Pallas kernel.

``(E, C, d) @ (E, d, f) -> (E, C, f)`` — the expert-parallel MoE hot spot.
Expert token batches are exactly the skewed-GEMM case SISA targets: ``C``
(capacity) is small relative to the weight dims, so the scheduler picks
slab-shaped ``bc`` tiles the same way ``sisa_gemm`` picks ``bm``.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from repro.kernels.runtime import resolve_interpret
from repro.kernels.sisa_gemm import choose_block_config


def _moe_kernel(x_ref, w_ref, o_ref, acc_ref, *, n_k: int):
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                            preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _drain():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_grouped_gemm(x: jax.Array, w: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """x: (E, C, d), w: (E, d, f) -> (E, C, f).  C, d, f must be tileable."""
    e, c, d = x.shape
    e2, d2, f = w.shape
    assert e == e2 and d == d2, (x.shape, w.shape)
    cfg = choose_block_config(c, f, d, x.dtype)
    bc, bf, bd = cfg.bm, cfg.bn, cfg.bk
    # Pad C/d/f up to the block grid.
    cp = ((c + bc - 1) // bc) * bc
    dp = ((d + bd - 1) // bd) * bd
    fp = ((f + bf - 1) // bf) * bf
    if (cp, dp) != (c, d):
        x = jnp.pad(x, ((0, 0), (0, cp - c), (0, dp - d)))
    if (dp, fp) != (d, f):
        w = jnp.pad(w, ((0, 0), (0, dp - d), (0, fp - f)))
    n_c, n_f, n_k = cp // bc, fp // bf, dp // bd

    out = pl.pallas_call(
        functools.partial(_moe_kernel, n_k=n_k),
        grid=(e, n_c, n_f, n_k),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda ee, i, j, kk: (ee, i, kk)),
            pl.BlockSpec((1, bd, bf), lambda ee, i, j, kk: (ee, kk, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda ee, i, j, kk: (ee, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, cp, fp), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name=f"moe_gemm_e{e}_{bc}x{bf}x{bd}",
    )(x, w)
    return out[:, :c, :f]
