"""HLO cost-walker + roofline tests."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo_cost import analyze, split_computations
from repro.analysis.hlo_utils import collective_bytes
from repro.analysis.roofline import build_report
from repro.hw.specs import chip_spec, TPU_V5E

X = jax.ShapeDtypeStruct((256, 256), jnp.float32)


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_xla_costs_count_loop_bodies_once():
    """Documents WHY the walker exists: XLA cost_analysis reports the
    same flops for 1 matmul and a 10-iteration scan of matmuls."""
    def one(y):
        return y @ y

    def ten(y):
        out, _ = jax.lax.scan(lambda c, _: (c @ c, None), y, None, length=10)
        return out

    f1 = jax.jit(one).lower(X).compile().cost_analysis()["flops"]
    f10 = jax.jit(ten).lower(X).compile().cost_analysis()["flops"]
    assert f1 == f10        # the XLA behavior our walker corrects


def test_walker_single_matmul_exact():
    c = analyze(_hlo(lambda y: y @ y, X))
    assert c.flops == 2 * 256**3


def test_walker_scan_multiplies_by_trip_count():
    def ten(y):
        out, _ = jax.lax.scan(lambda c, _: (c @ c, None), y, None, length=10)
        return out
    c = analyze(_hlo(ten, X))
    assert c.flops == 10 * 2 * 256**3
    assert c.n_while_loops == 1


def test_walker_nested_scans():
    def nested(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ c2, None
            c2, _ = jax.lax.scan(inner, c, None, length=5)
            return c2, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y
    c = analyze(_hlo(nested, X))
    assert c.flops == 20 * 2 * 256**3
    assert c.max_multiplier == 20.0


def test_walker_rectangular_dot():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 512), jnp.float32)
    c = analyze(_hlo(lambda a, b: a @ b, a, b))
    assert c.flops == 2 * 64 * 512 * 128


def test_walker_bytes_positive_and_sane():
    c = analyze(_hlo(lambda y: y @ y + 1.0, X))
    # at least result+operands of the dot, at most a few x total tensors
    assert 3 * 256 * 256 * 4 <= c.bytes_accessed < 100 * 256 * 256 * 4


def test_collective_parse_iota_groups():
    hlo = ("%ag = bf16[32,1024]{1,0} all-gather(%x), replica_groups="
           "[32,16]<=[512], dimensions={0}")
    st = collective_bytes(hlo, default_group=4)
    moved = st.per_op["all-gather"]
    assert moved == pytest.approx(32 * 1024 * 2 * 15 / 16)


def test_collective_parse_explicit_groups():
    hlo = ("%ar = f32[128]{0} all-reduce(%x), replica_groups="
           "{{0,1,2,3},{4,5,6,7}}, to_apply=%add")
    st = collective_bytes(hlo, default_group=16)
    assert st.per_op["all-reduce"] == pytest.approx(2 * 128 * 4 * 3 / 4)


def test_split_computations_finds_entry():
    comps = split_computations(_hlo(lambda y: y @ y, X))
    assert any(c.is_entry for c in comps.values())


def test_chip_peaks_by_device_kind():
    assert chip_spec("TPU v5 lite") is TPU_V5E
    with pytest.raises(KeyError, match="no peaks recorded"):
        chip_spec("cpu")


def test_roofline_refuses_unknown_device():
    """The roofline takes its peaks from the device kind; a device with
    no entry is an error, never a silent v5e default."""
    hlo = _hlo(lambda y: y @ y, X)
    kw = dict(arch="a", cell="c", mesh_name="m", chips=1,
              cost={"flops": 1.0}, hlo_text=hlo, model_flops=1.0,
              tokens_per_step=1.0)
    rep = build_report(device_kind="TPU v5 lite", **kw)
    assert rep.compute_s == pytest.approx(rep.flops_per_chip / 197e12)
    with pytest.raises(KeyError):
        build_report(device_kind="cpu", **kw)
