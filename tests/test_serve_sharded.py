"""Mesh-sharded serving: spec rules, token identity, fault recovery.

The slot/paged fast path runs tensor-parallel (and expert-parallel for
MoE stacks) when ``make_engine`` is given a ``("data", "model")`` mesh:
params and KV storage are committed to the rules in
``repro.distributed.sharding`` and the decode windows run
GSPMD-partitioned with the paged-attention step per-shard.  This suite
pins the three contracts that make that admissible:

* **Spec rules are total and canonical** (tier-1, no devices needed —
  the rules are pure functions of shapes + mesh axis sizes, exercised
  over every registry config x mesh shape with a duck-typed mesh):
  ``cache_specs`` never raises, every sharded dim divides, the head
  axis shards exactly when divisible, the paged pool's page axis is
  never sharded, and no spec carries trailing ``None``s (jit compile
  caches key on the exact sharding spelling, so allocation-time specs
  must match ``with_sharding_constraint``'s canonical short form — a
  long-form spec costs one spurious decode recompile).

* **Token identity + compile stability on the mesh** (gated on the
  8-device CPU mesh CI brings up with
  ``--xla_force_host_platform_device_count=8``): sharded engines emit
  exactly the single-device engines' streams on mixed and
  pool-pressure workloads across mesh shapes (1x8, 2x4, 4x2), with
  ``stats["decode_compiles"] == 0`` after ``warmup()`` — including
  ``phi3.5-moe-42b`` serving tensor+expert-parallel through the EP
  grouped kernel.

* **Fault recovery instead of a crashed serve**: the frontend's
  watchdog + device probe turn a simulated lost shard into victim
  release + re-prefill on the rebuilt (elastic-planned) mesh; greedy
  determinism makes the resumed streams identical to an uninterrupted
  serve.

The ``ci`` hypothesis profile (see ``conftest.py``) backs the fuzz
classes in the ``serve-sharded`` CI job; the ``slow``-marked sweep
reads ``REPRO_MESH_SHAPE`` from the nightly matrix.
"""
import dataclasses
import os

from hypothesis import given, settings, strategies as st
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
import pytest

from repro.configs import all_configs, smoke_config
from repro.distributed.fault import StragglerWatchdog, simulate_failure
from repro.distributed.sharding import cache_specs, to_named
from repro.models import init_params
from repro.serve import make_engine, Request
from repro.serve.frontend import ServeFrontend

MAX_BATCH = 4
MAX_SEQ = 64
WINDOW = 4
PSZ = 8
SMALL_POOL = 12
MESH_SHAPES = ((1, 8), (2, 4), (4, 2))

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

LENS = st.sampled_from([1, 2, 3, 5, 7, 8, 9, 12, 15, 16, 17, 20, 23])
WORKLOADS = st.lists(st.tuples(LENS, st.integers(1, 7)),
                     min_size=1, max_size=6)
SEEDS = st.integers(0, 2 ** 16)


def _mesh(shape):
    d, m = shape
    return Mesh(np.asarray(jax.devices()[:d * m]).reshape(d, m),
                ("data", "model"))


# --------------------------------------------------------------------------
# Spec rules: pure functions of shapes + axis sizes (tier-1, no devices)
# --------------------------------------------------------------------------
@dataclasses.dataclass
class FakeMesh:
    """Duck-typed mesh: the rules only read .shape and .axis_names."""
    shape: dict
    axis_names: tuple


FAKE_SHAPES = ((1, 1), (1, 8), (2, 4), (4, 2), (8, 1), (2, 3), (3, 2))


def _cache_trees(cfg):
    """Representative serving storage, mirroring the engines' layouts:
    dense slot buffers, int8 pool + scale planes, a recurrent state."""
    sds = jax.ShapeDtypeStruct
    L, B, cap, npages = 2, MAX_BATCH, 32, 13
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    dense = {"k": sds((L, B, cap, hkv, hd), jnp.float32),
             "v": sds((L, B, cap, hkv, hd), jnp.float32),
             "pos": sds((B,), jnp.int32)}
    pool = {"pk": sds((L, npages + 1, PSZ, hkv, hd), jnp.int8),
            "pv": sds((L, npages + 1, PSZ, hkv, hd), jnp.int8),
            "pk_s": sds((L, npages + 1, PSZ, hkv, 1), jnp.float32),
            "pv_s": sds((L, npages + 1, PSZ, hkv, 1), jnp.float32)}
    state = {"h": sds((L, B, cfg.d_model), jnp.float32)}
    return {"dense": dense, "pool": pool, "state": state}


class TestCacheSpecs:
    def test_every_config_every_mesh(self):
        """Never raises; sharded dims divide; head axis shards exactly
        when both head counts divide; the pool page axis is never
        sharded; no trailing-None (non-canonical) specs escape."""
        for name, cfg in all_configs().items():
            trees = _cache_trees(cfg)
            for shape in FAKE_SHAPES:
                mesh = FakeMesh({"data": shape[0], "model": shape[1]},
                                ("data", "model"))
                ms = shape[1]
                head_ok = (ms > 1 and cfg.n_heads % ms == 0
                           and cfg.n_kv_heads % ms == 0)
                specs = cache_specs(trees, cfg, mesh, batch_axes=())
                flat_s = jax.tree.leaves(
                    specs, is_leaf=lambda x: isinstance(x, P))
                flat_l = jax.tree.leaves(trees)
                assert len(flat_s) == len(flat_l)
                for struct, spec in zip(flat_l, flat_s):
                    assert len(spec) <= len(struct.shape), (name, shape)
                    if len(spec):
                        assert spec[-1] is not None, (name, shape, spec)
                    for dim, axes in zip(struct.shape, spec):
                        if axes is None:
                            continue
                        size = 1
                        for a in (axes if isinstance(axes, tuple)
                                  else (axes,)):
                            size *= mesh.shape[a]
                        assert dim % size == 0, (name, shape, spec)
                for leaf in ("pk", "pv", "pk_s", "pv_s"):
                    sp = specs["pool"][leaf]
                    assert all(sp[i] is None
                               for i in range(min(2, len(sp)))), \
                        (name, shape, sp)      # page axis stays global
                if head_ok:
                    assert specs["dense"]["k"][3] == "model", (name, shape)
                    assert specs["pool"]["pk"][3] == "model", (name, shape)

    def test_pool_layout_rule(self):
        """One rule places every paged pool: KV heads split when both
        head counts divide the model axis, a single KV head (MQA) is
        replicated while query heads split, else the page interior
        splits — and cache_specs follows it."""
        from repro.kernels.paged_attn import kv_pool_layout
        assert kv_pool_layout(32, 4, 4) == "heads"
        assert kv_pool_layout(48, 1, 4) == "replicated"
        assert kv_pool_layout(14, 2, 4) == "seq"
        assert kv_pool_layout(48, 1, 1) == "seq"
        assert kv_pool_layout(4, 1, 8) == "seq"
        for name in ("granite-20b", "gemma3-1b"):
            cfg = all_configs()[name]
            mesh = FakeMesh({"data": 1, "model": 4}, ("data", "model"))
            specs = cache_specs(_cache_trees(cfg), cfg, mesh, batch_axes=())
            for leaf in ("pk", "pv", "pk_s", "pv_s"):
                assert specs["pool"][leaf] == P(), (name, leaf)
            assert specs["dense"]["k"][2] == "model"   # dense: seq-split

    def test_slot_dim_never_data_sharded_for_serving(self):
        """batch_axes=() (what the engines pass — the leading cache dim
        is a logical slot index) must keep 'data' out of every spec."""
        cfg = all_configs()["yi-6b"]
        mesh = FakeMesh({"data": 4, "model": 2}, ("data", "model"))
        specs = cache_specs(_cache_trees(cfg), cfg, mesh, batch_axes=())
        for spec in jax.tree.leaves(specs,
                                    is_leaf=lambda x: isinstance(x, P)):
            flatax = [a for entry in spec if entry is not None
                      for a in (entry if isinstance(entry, tuple)
                                else (entry,))]
            assert "data" not in flatax, spec

    @needs_mesh
    def test_device_put_roundtrip(self):
        """Specs are realizable: device_put onto the real mesh keeps the
        spec and the bytes, for a head-divisible and a fallback shape."""
        cfg = smoke_config("yi-6b")
        rng = np.random.default_rng(0)
        trees = jax.tree.map(
            lambda s: jnp.asarray(rng.normal(size=s.shape)
                                  .astype(np.float32)
                                  if s.dtype != jnp.int8 else
                                  rng.integers(-8, 8, size=s.shape)
                                  .astype(np.int8)),
            _cache_trees(cfg))
        for shape in ((4, 2), (2, 4)):
            mesh = _mesh(shape)
            specs = cache_specs(trees, cfg, mesh, batch_axes=())
            placed = jax.device_put(trees, to_named(specs, mesh))
            for x, y, sp in zip(jax.tree.leaves(trees),
                                jax.tree.leaves(placed),
                                jax.tree.leaves(
                                    specs,
                                    is_leaf=lambda x: isinstance(x, P))):
                assert y.sharding.spec == sp
                np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# --------------------------------------------------------------------------
# Differential: sharded vs single-device token identity (8-device mesh)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config("yi-6b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def moe_setup():
    cfg = smoke_config("phi3.5-moe-42b")
    params = init_params(cfg, jax.random.PRNGKey(1))
    return cfg, params


def _make(cfg, params, kind, mesh=None, **extra):
    kw = dict(max_slots=MAX_BATCH, max_seq=MAX_SEQ, window=WINDOW)
    if kind == "paged":
        kw.update(page_size=PSZ)
    kw.update(extra)
    eng = make_engine(cfg, params, kind=kind, mesh=mesh, **kw)
    eng.warmup(max_prompt_len=MAX_SEQ)
    return eng


@pytest.fixture(scope="module")
def engines(setup):
    """Long-lived engines (reset per example so jit caches amortize):
    single-device references + sharded twins on the (2, 4) mesh."""
    cfg, params = setup
    mesh = _mesh((2, 4))
    return {
        "slot": _make(cfg, params, "slot"),
        "paged": _make(cfg, params, "paged"),
        "slot_sh": _make(cfg, params, "slot", mesh=mesh),
        "paged_sh": _make(cfg, params, "paged", mesh=mesh),
        "paged_sh_small": _make(cfg, params, "paged", mesh=mesh,
                                num_pages=SMALL_POOL),
    }


def _prompts(workload, seed, vocab):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=s).astype(np.int32)
            for s, _ in workload]


def _serve(eng, workload, prompts):
    eng.reset()
    for rid, ((_, budget), prompt) in enumerate(zip(workload, prompts)):
        eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=budget))
    done = eng.run(max_steps=4096)
    return {c.rid: c.tokens for c in done}


FIXED = [(5, 6), (17, 8), (9, 5), (33, 7), (12, 9), (7, 6)]


@needs_mesh
class TestShardedIdentity:
    @pytest.mark.parametrize("shape", MESH_SHAPES,
                             ids=["%dx%d" % s for s in MESH_SHAPES])
    @pytest.mark.parametrize("kind", ["slot", "paged"])
    def test_mesh_shapes_token_identical_zero_compiles(self, setup, engines,
                                                       kind, shape):
        """Every mesh shape CI cares about serves the single-device
        streams exactly, with zero decode compiles after warmup — the
        fast-path invariant the tentpole must preserve on the mesh."""
        cfg, params = setup
        prompts = _prompts(FIXED, 3, cfg.vocab_size)
        want = _serve(engines[kind], FIXED, prompts)
        eng = (engines[kind + "_sh"] if shape == (2, 4)
               else _make(cfg, params, kind, mesh=_mesh(shape)))
        got = _serve(eng, FIXED, prompts)
        assert got == want
        assert eng.stats["decode_compiles"] == 0

    def test_moe_serves_tp_ep(self, moe_setup):
        """phi3.5-moe on the mesh: expert FFNs route through the EP
        grouped kernel (4 smoke experts / model axis), attention heads
        tensor-parallel — tokens identical, steady state compile-free."""
        cfg, params = moe_setup
        prompts = _prompts(FIXED[:4], 7, cfg.vocab_size)
        want = _serve(_make(cfg, params, "slot"), FIXED[:4], prompts)
        for shape in ((2, 4), (4, 2)):
            eng = _make(cfg, params, "slot", mesh=_mesh(shape))
            got = _serve(eng, FIXED[:4], prompts)
            assert got == want, shape
            assert eng.stats["decode_compiles"] == 0, shape


@needs_mesh
class TestShardedDifferential:
    @given(workload=WORKLOADS, seed=SEEDS)
    def test_fuzz_mixed_workloads(self, engines, setup, workload, seed):
        """Sharded slot/paged/pool-pressure engines vs the single-device
        slot reference on randomized mixed workloads — identity plus the
        zero-steady-state-compile invariant on every example."""
        cfg, _ = setup
        prompts = _prompts(workload, seed, cfg.vocab_size)
        want = _serve(engines["slot"], workload, prompts)
        for name in ("paged_sh", "slot_sh", "paged_sh_small"):
            got = _serve(engines[name], workload, prompts)
            assert got == want, name
            assert engines[name].stats["decode_compiles"] == 0, name

    @given(pre_pages=st.integers(1, 2),
           exts=st.lists(st.sampled_from([0, 1, 7, 8, 9, 15, 17]),
                         min_size=2, max_size=5),
           budgets=st.lists(st.integers(1, 7), min_size=5, max_size=5),
           seed=SEEDS)
    def test_fuzz_shared_prefix_on_mesh(self, engines, setup, pre_pages,
                                        exts, budgets, seed):
        """Prefix sharing dedups replicated table entries against a
        head-sharded pool without touching tokens."""
        cfg, _ = setup
        rng = np.random.default_rng(seed)
        pre = rng.integers(0, cfg.vocab_size,
                           size=pre_pages * PSZ).astype(np.int32)
        prompts = [np.concatenate(
            [pre, rng.integers(0, cfg.vocab_size,
                               size=e).astype(np.int32)]) for e in exts]
        workload = [(len(p), b) for p, b in zip(prompts, budgets)]
        want = _serve(engines["paged"], workload, prompts)
        got = _serve(engines["paged_sh"], workload, prompts)
        assert got == want
        sh = engines["paged_sh"].stats["engine"]
        ref = engines["paged"].stats["engine"]
        assert sh["pages_shared"] == ref["pages_shared"]


@needs_mesh
def test_paged_attention_sharded_matches_plain(setup):
    """Kernel-level: the shard_map wrapper computes the plain call (heads
    are embarrassingly parallel in the online softmax; per-shard
    reduction order may differ, hence allclose not equality)."""
    from repro.kernels import paged_attention, paged_attention_sharded
    rng = np.random.default_rng(5)
    B, H, Hkv, hd, npages, maxp = 4, 4, 2, 8, 13, 4
    q = jnp.asarray(rng.normal(size=(B, H, hd)).astype(np.float32))
    pk = jnp.asarray(rng.normal(
        size=(npages + 1, PSZ, Hkv, hd)).astype(np.float32))
    pv = jnp.asarray(rng.normal(
        size=(npages + 1, PSZ, Hkv, hd)).astype(np.float32))
    table = jnp.asarray(np.stack([
        rng.permutation(npages)[:maxp] + 1 for _ in range(B)]), jnp.int32)
    pos = jnp.asarray(rng.integers(1, maxp * PSZ, size=(B,)), jnp.int32)
    want = paged_attention(q, pk, pv, table, pos)
    got = paged_attention_sharded(q, pk, pv, table, pos, mesh=_mesh((4, 2)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # head-indivisible mesh: transparent fallback to the plain call
    got8 = paged_attention_sharded(q, pk, pv, table, pos, mesh=_mesh((1, 8)))
    np.testing.assert_allclose(np.asarray(got8), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@needs_mesh
def test_mqa_paged_attention_sharded_matches_plain():
    """Multi-query attention on a 4-way model axis: query heads split,
    the one-KV-head pool replicated, same result as the plain call."""
    from repro.kernels import paged_attention, paged_attention_sharded
    rng = np.random.default_rng(6)
    B, H, hd, npages, maxp = 4, 8, 8, 13, 4
    q = jnp.asarray(rng.normal(size=(B, H, hd)).astype(np.float32))
    pk, pv = (jnp.asarray(rng.normal(size=(npages + 1, PSZ, 1, hd))
                          .astype(np.float32)) for _ in range(2))
    table = jnp.asarray(np.stack([
        rng.permutation(npages)[:maxp] + 1 for _ in range(B)]), jnp.int32)
    pos = jnp.asarray(rng.integers(1, maxp * PSZ, size=(B,)), jnp.int32)
    want = paged_attention(q, pk, pv, table, pos)
    got = paged_attention_sharded(q, pk, pv, table, pos, mesh=_mesh((2, 4)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------------
# Fault injection: lost shard -> elastic remesh, not a crashed serve
# --------------------------------------------------------------------------
@needs_mesh
class TestFaultRecovery:
    PROMPTS = [(5, 10), (13, 8), (9, 12), (21, 6), (7, 9)]

    def _frontend_serve(self, engine, fault):
        devs = jax.devices()[:4]
        calls = {"n": 0}

        def probe():
            calls["n"] += 1
            return (simulate_failure(devs, 2) if fault and calls["n"] > 2
                    else devs)

        fe = ServeFrontend(engine, watchdog=StragglerWatchdog(),
                           device_probe=probe if fault else None)
        fe.warmup(max_prompt_len=MAX_SEQ)
        rng = np.random.default_rng(11)
        handles = [fe.submit(rng.integers(0, 500, size=s).astype(np.int32),
                             b) for s, b in self.PROMPTS]
        comps = {h.rid: tuple(h.result(300).tokens) for h in handles}
        metrics = fe.metrics()
        fe.shutdown()
        return comps, metrics

    @pytest.mark.parametrize("kind", ["slot", "paged"])
    def test_lost_shard_releases_victims_and_resumes(self, setup, kind):
        """Mid-serve the probe shrinks 4 devices to 2: the frontend
        plans a (1, 2) mesh, the engine releases the in-flight victims
        back to its queue and re-prefills them on the rebuilt mesh, and
        greedy determinism resumes every stream where it stopped —
        completions identical to an uninterrupted single-device serve."""
        cfg, params = setup
        want, _ = self._frontend_serve(_make(cfg, params, kind), False)
        eng = _make(cfg, params, kind, mesh=_mesh((2, 2)))
        got, metrics = self._frontend_serve(eng, True)
        assert got == want
        assert metrics["remeshes"] >= 1
        assert eng.stats["engine"]["remeshes"] >= 1
        assert eng.mesh.shape["model"] == 2      # TP survived the shrink
        assert eng.mesh.shape["data"] == 1

    def test_unserveable_shrink_keeps_limping(self, setup):
        """A probe that drops below any plannable mesh must not crash
        the scheduler: the serve finishes on the old mesh."""
        cfg, params = setup
        want, _ = self._frontend_serve(_make(cfg, params, "slot"), False)
        eng = _make(cfg, params, "slot", mesh=_mesh((1, 2)))
        devs = jax.devices()[:2]
        calls = {"n": 0}

        def probe():
            calls["n"] += 1
            return simulate_failure(devs, 2) if calls["n"] > 2 else devs

        fe = ServeFrontend(eng, device_probe=probe, min_data=1)
        fe.warmup(max_prompt_len=MAX_SEQ)
        rng = np.random.default_rng(11)
        handles = [fe.submit(rng.integers(0, 500, size=s).astype(np.int32),
                             b) for s, b in self.PROMPTS]
        got = {h.rid: tuple(h.result(300).tokens) for h in handles}
        assert fe.metrics()["remeshes"] == 0
        fe.shutdown()
        assert got == want


# --------------------------------------------------------------------------
# Nightly wide sweep (mesh shape from the matrix)
# --------------------------------------------------------------------------
@needs_mesh
@pytest.mark.slow
class TestWideSweep:
    @settings(max_examples=25, deadline=None)
    @given(workload=st.lists(st.tuples(st.integers(1, 40),
                                       st.integers(1, 10)),
                             min_size=1, max_size=8), seed=SEEDS)
    def test_wide_mixed_on_matrix_mesh(self, setup, workload, seed):
        shape = tuple(int(x) for x in os.environ.get(
            "REPRO_MESH_SHAPE", "2x4").split("x"))
        cfg, params = setup
        key = "_wide_%dx%d" % shape
        cache = TestWideSweep.__dict__.get("_engines") or {}
        if key not in cache:
            cache[key] = (_make(cfg, params, "paged"),
                          _make(cfg, params, "paged", mesh=_mesh(shape)))
            TestWideSweep._engines = cache
        ref, sh = cache[key]
        prompts = _prompts(workload, seed, cfg.vocab_size)
        want = _serve(ref, workload, prompts)
        got = _serve(sh, workload, prompts)
        assert got == want
        assert sh.stats["decode_compiles"] == 0
