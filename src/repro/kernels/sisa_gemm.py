"""SISA-scheduled GEMM as a Pallas TPU kernel.

TPU adaptation of the paper's scale-in execution (DESIGN.md §2b): the MXU
cannot be physically partitioned, so the slab mechanism becomes *tile-shape
scheduling*.  ``choose_block_config`` plays the role of §3.2's scheduler:

* ``M <= 16``           -> slab tiles: ``bm`` = one sublane group
  (8 f32 / 16 bf16 rows — the "slab height"), and the freed resources are
  re-invested along N (``bn`` up to 512) so the grid exposes the same
  parallelism the 8 independent slabs provide.
* ``16 < M <= 64``      -> fused tiles: ``bm`` = 32/64 (slab fusion).
* ``M > 64``            -> monolithic 128-row MXU tiles.
* ragged M              -> instead of padding the residual up to 128 (the
  monolithic baseline's behaviour), ``bm`` is scaled in so padding waste
  stays < ~1 sublane group — the paper's residual-tile handling.

The kernel itself is output-stationary: a f32 accumulator tile lives in
VMEM scratch for the whole K sweep (the analogue of SISA's per-PE
accumulators), A and B stream block-by-block, and the C block is written
once on the last K step — no partial sums ever leave the "array".
"""
from __future__ import annotations

import dataclasses
import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import jax.numpy as jnp

from repro.kernels.runtime import resolve_interpret

LANE = 128


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """MXU tile shape ``(bm, bn, bk)`` chosen by the §3.2 scheduler —
    ``bm`` is the slab height, ``bk`` the resident K depth."""

    bm: int
    bn: int
    bk: int

    @property
    def vmem_bytes(self) -> int:
        # double-buffered bf16 A/B streams + resident f32 accumulator + C out
        return 2 * 2 * (self.bm * self.bk + self.bk * self.bn) \
            + 4 * self.bm * self.bn + 2 * self.bm * self.bn


def _sublane(dtype) -> int:
    return {jnp.dtype(jnp.float32): 8, jnp.dtype(jnp.bfloat16): 16,
            jnp.dtype(jnp.float16): 16}.get(jnp.dtype(dtype), 8)


def _round_up(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


def choose_block_config(m: int, n: int, k: int, dtype=jnp.bfloat16,
                        vmem_budget: int = 8 * 1024 * 1024) -> BlockConfig:
    """§3.2 mode selection mapped to MXU tile shapes."""
    sub = _sublane(dtype)
    # --- bm: the slab height ---
    if m <= sub:
        bm = sub                                   # independent slab mode
    elif m <= 64:
        bm = _round_up(m, sub)                     # fused slabs
        bm = 1 << (bm - 1).bit_length() if bm not in (8, 16, 32, 64) else bm
        bm = min(bm, 64)
    else:
        # Monolithic 128-row tiles.  Ragged M > 128 is handled one level
        # up (ops._pallas_matmul) as a main pass + scale-in residual pass,
        # mirroring §3.2's "M > array height" strategy.
        bm = 128
    # --- bn: slab width (re-invest small-M savings along N) ---
    if m <= 64 and n >= 512:
        bn = 512
    elif n >= 256:
        bn = 256
    else:
        bn = _round_up(min(n, 256), LANE)
    # --- bk: as deep as VMEM allows (fewer accumulator round-trips) ---
    bk = _round_up(min(k, 2048), LANE)
    while BlockConfig(bm, bn, bk).vmem_bytes > vmem_budget and bk > LANE:
        bk //= 2
    while BlockConfig(bm, bn, bk).vmem_bytes > vmem_budget and bn > LANE:
        bn //= 2
    return BlockConfig(bm=bm, bn=bn, bk=bk)


def _gemm_kernel(a_ref, b_ref, c_ref, acc_ref, *, n_k: int):
    """Output-stationary inner kernel: acc += A_blk @ B_blk over the K grid."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _drain():
        c_ref[...] = acc_ref[...].astype(c_ref.dtype)


def _splitk_kernel(a_ref, b_ref, o_ref):
    """Split-K partial-product kernel: each K-slab writes its own
    partial C tile; the wrapper reduces over the K grid axis."""
    o_ref[0] = jnp.dot(a_ref[...], b_ref[...],
                       preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def sisa_gemm_splitk(a: jax.Array, b: jax.Array, cfg: BlockConfig,
                     interpret: bool = False) -> jax.Array:
    """Beyond-paper scale-in along K (DESIGN.md §2b): when M *and* N are
    both small (decode GEMV), N-tiling exposes too little parallelism to
    fill the chip; this kernel re-invests the idle "slabs" as independent
    K-range reducers, each producing a partial C in f32, summed outside.
    The TPU analogue of giving idle slabs reduction work.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and m % cfg.bm == 0 and n % cfg.bn == 0 \
        and k % cfg.bk == 0, ((m, n, k), cfg)
    n_m, n_n, n_k = m // cfg.bm, n // cfg.bn, k // cfg.bk
    partial = pl.pallas_call(
        _splitk_kernel,
        grid=(n_k, n_m, n_n),
        in_specs=[
            pl.BlockSpec((cfg.bm, cfg.bk), lambda kk, i, j: (i, kk)),
            pl.BlockSpec((cfg.bk, cfg.bn), lambda kk, i, j: (kk, j)),
        ],
        out_specs=pl.BlockSpec((1, cfg.bm, cfg.bn),
                               lambda kk, i, j: (kk, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_k, m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ),
        interpret=resolve_interpret(interpret),
        name=f"sisa_gemm_splitk_{cfg.bm}x{cfg.bn}x{cfg.bk}",
    )(a, b)
    return jnp.sum(partial, axis=0).astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "interpret"))
def sisa_gemm(a: jax.Array, b: jax.Array, cfg: BlockConfig,
              interpret: bool = False) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N]; dims must be multiples of the block cfg.

    Use :func:`repro.kernels.ops.sisa_matmul` for the padded, scheduled,
    differentiable public entry point.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % cfg.bm == 0 and n % cfg.bn == 0 and k % cfg.bk == 0, (
        (m, n, k), cfg)
    n_m, n_n, n_k = m // cfg.bm, n // cfg.bn, k // cfg.bk

    return pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=n_k),
        grid=(n_m, n_n, n_k),
        in_specs=[
            pl.BlockSpec((cfg.bm, cfg.bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((cfg.bk, cfg.bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((cfg.bm, cfg.bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((cfg.bm, cfg.bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
        name=f"sisa_gemm_{cfg.bm}x{cfg.bn}x{cfg.bk}",
    )(a, b)
