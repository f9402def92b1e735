"""Compile rehearsals at published widths for a described TPU v5e.

Nothing here runs on a chip: each test compiles a main-path kernel or
step for a v5e that is described, not attached, so what interpret mode
passes but the chip's compiler refuses (unaligned tiles, VMEM over
budget, a program larger than HBM, a kernel that cannot be partitioned)
fails here at no chip time.  The topology is described inside a
module-scoped fixture — never at import — and the tests skip when this
installation cannot describe one.  The persistent compilation cache is
off around the compiles: an entry written for a chip cannot be read back
without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import (flat_ragged_gemm, paged_attention,
                           paged_attention_sharded, sisa_matmul)
from repro.kernels.runtime import force_interpret_enabled, set_force_interpret
from repro.models import init_params
from repro.serve import make_bucketed_prefill_step

V5E_HBM = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    interpret_was = force_interpret_enabled()
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    set_force_interpret(False)
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        desc = None
        reason = f"no v5e:2x2 topology can be described here: {e}"
    try:
        if desc is None:
            pytest.skip(reason)
        yield desc
    finally:
        set_force_interpret(interpret_was)
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _pool_case(n_heads, n_kv, hd, quant, sharding, *, rows=8, pages=73,
               psz=128, pmax=9):
    q = _sds((rows, n_heads, hd), jnp.bfloat16, sharding)
    table = _sds((rows, pmax), jnp.int32, sharding)
    pos = _sds((rows,), jnp.int32, sharding)
    pool_shape = (pages, psz, n_kv, hd)
    if quant:
        pool = _sds(pool_shape, jnp.int8, sharding)
        scale = _sds(pool_shape[:-1] + (1,), jnp.bfloat16, sharding)
        return q, pool, table, pos, scale
    return q, _sds(pool_shape, jnp.bfloat16, sharding), table, pos, None


@pytest.mark.parametrize("n_heads,n_kv,hd,quant", [
    (32, 4, 128, False),       # yi-6b, bf16 pool
    (32, 4, 128, True),        # yi-6b, int8 pool + bf16 scale planes
    (4, 1, 256, False),        # head_dim 256 (gemma3-1b widths)
])
def test_paged_attention_compiles(one_chip, n_heads, n_kv, hd, quant):
    q, pool, table, pos, scale = _pool_case(n_heads, n_kv, hd, quant,
                                            one_chip)

    def step(q, pk, pv, table, pos, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_attention(q, pk, pv, table, pos, pk_scale=ks,
                               pv_scale=vs, impl="pallas")

    args = (q, pool, pool, table, pos) + ((scale, scale) if quant else ())
    text = _compile(step, *args).as_text()
    assert "tpu_custom_call" in text
    assert f"paged_attn_{n_heads}h_128p" in text


def test_mqa_paged_attention_shards_without_pool_gather(topo):
    """granite-20b decode attention on a (1, 4) mesh: query heads split,
    the single-KV-head pool replicated — the kernel runs per shard and no
    all-gather touches the pool."""
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    rep = NamedSharding(mesh, P())
    q, pool, table, pos, _ = _pool_case(48, 1, 128, False, rep, pages=41,
                                        pmax=5)
    q = _sds(q.shape, q.dtype, NamedSharding(mesh, P(None, "model")))
    text = _compile(functools.partial(paged_attention_sharded, mesh=mesh,
                                      impl="pallas"),
                    q, pool, pool, table, pos).as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text


def test_sisa_gemm_compiles_at_decode_m(one_chip):
    a = _sds((8, 4096), jnp.bfloat16, one_chip)
    b = _sds((4096, 11008), jnp.bfloat16, one_chip)
    text = _compile(lambda a, b: sisa_matmul(a, b, "pallas"), a, b).as_text()
    assert "tpu_custom_call" in text


def test_flat_ragged_gemm_compiles_at_phi35_moe_widths(one_chip):
    g, d, f, bm = 16, 4096, 6400, 128
    x = _sds((2176, d), jnp.bfloat16, one_chip)
    w = _sds((g, d, f), jnp.bfloat16, one_chip)
    sizes = _sds((g,), jnp.int32, one_chip)
    offs = _sds((g + 1,), jnp.int32, one_chip)
    text = _compile(functools.partial(flat_ragged_gemm, block_rows=bm,
                                      m_hint=bm), x, w, sizes, offs).as_text()
    assert "tpu_custom_call" in text


def test_yi6b_bucketed_prefill_fits_one_chip(one_chip):
    """The full-width yi-6b bucketed prefill, from abstract params: it
    compiles for one v5e, and weights plus temporaries fit its HBM."""
    cfg = get_config("yi-6b")
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), shapes)
    batch = {"tokens": _sds((1, 512), jnp.int32, one_chip),
             "last_index": _sds((), jnp.int32, one_chip)}
    step = make_bucketed_prefill_step(cfg, None, batch_axes=())
    mem = _compile(step, params, batch).memory_analysis()
    weights = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in jax.tree.leaves(shapes))
    assert mem.argument_size_in_bytes >= weights
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes) < V5E_HBM

