"""The reference against the served program at smoke size on the CPU,
and the check failing when the timed path is broken underneath.

The run below is the benchmark's own (set-up, window, sample, check)
with the look for a chip skipped and a smoke-width bfloat16 model in
place of the published one; the limit is the cell's.
"""
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_smoke import deep_cell, smoke_cell, spec_of

from benchmarks.chip import harness, weights


@pytest.mark.parametrize("arch", ["yi-6b", "granite-20b"])
def test_program_tree_and_reference_layers_draw_the_same_weights(arch):
    from repro.configs import smoke_config
    from repro.models import init_params

    cfg = dataclasses.replace(smoke_config(arch), param_dtype="bfloat16")
    spec = spec_of(cfg)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    block = harness.block_of(spec)
    params = weights.program_params(shapes, 2**33 + 5, block)
    key = weights.base_key(2**33 + 5)
    seen = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        role = weights.role_of(path, block)
        seen.add(role)
        if role in ("embed", "lm_head", "final_norm"):
            want = weights.draw(key, role, weights.NO_LAYER,
                                leaf.shape, leaf.dtype)
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want))
            continue
        for layer in range(cfg.n_layers):
            want = weights.layer_weights(block.layer_shapes(spec, layer),
                                         key, layer)[role]
            np.testing.assert_array_equal(np.asarray(leaf[layer]),
                                          np.asarray(want))
    assert seen >= set(block.layer_shapes(spec, 0)) | {"embed", "final_norm"}
    # A padded vocabulary holds the reference's rows unchanged.
    table = params["embed"]["table"]
    short = weights.draw(key, "embed", weights.NO_LAYER,
                         (cfg.vocab_size, cfg.d_model), jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(table[:cfg.vocab_size]),
                                  np.asarray(short))


def test_reference_matches_the_program_forward_in_float32():
    """The reference's block is the program's: same logits within float32
    rounding from the same weights."""
    from repro.configs import smoke_config
    from repro.models import init_params
    from repro.models.transformer import forward_prefill

    cfg = dataclasses.replace(smoke_config("granite-20b"),
                              param_dtype="float32")
    spec = spec_of(cfg)
    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = weights.program_params(shapes, 11, harness.block_of(spec))
    # The reference rounds its weights to bfloat16 as the cells serve
    # them; give the program the same values.
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    rng = np.random.default_rng(0)
    toks = rng.integers(2, cfg.vocab_size, size=(2, 24)).astype(np.int32)
    idx = np.tile(np.arange(24, dtype=np.int32), (2, 1))
    with jax.default_matmul_precision("highest"):
        got, _ = forward_prefill(params, cfg, {"tokens": jnp.asarray(toks)},
                                 logits_index=jnp.asarray(idx))
    ref = harness.block_of(spec).Reference(spec, 11, rows=2, seq_len=24,
                                           block=1)
    want = ref.logits(list(toks), [np.arange(24)] * 2, 48)
    got = np.asarray(got)[:, :, :cfg.vocab_size].reshape(48, -1)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4, rtol=2e-4)


@pytest.fixture(scope="module")
def served():
    cell, cfg = smoke_cell()
    s = harness.setup(cell, 2**31 + 77, cfg, jax.devices())
    yield cell, cfg, s
    s.frontend.shutdown(drain=False)


def _check(served, seed, control=None, rate=None, requests=None):
    cell, cfg, s = served
    if rate is not None:
        cell = dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_per_s=rate))
    if requests is not None:
        chk = dict(cell.traffic["check"], requests=requests)
        cell = dataclasses.replace(cell, traffic=dict(cell.traffic, check=chk))
    win = harness.run_window(s, cell, seed, 2.0, cfg.vocab_size, drain_s=30)
    e2e = harness.end_to_end(win)
    line = {"attempted": e2e["attempted"], "failed": e2e["failed"]}
    sample = harness.check_sample(win, cell, seed)
    out = harness.check(cell, 2**31 + 77, line, sample, jax.devices()[0],
                        control)
    return out, e2e


def test_sound_run_is_correct(served):
    out, e2e = _check(served, 1)
    assert e2e["attempted"] > 4 and e2e["failed"] == 0
    assert out["correct"], out["checks"]
    assert 0 <= out["checks"]["logit_gap"]["value"] \
        <= out["checks"]["logit_gap"]["limit"] / 4
    assert list(out)[-1] == "checks"


# A limit for the mechanism below, not one set from readings: the logits
# handed over are the reference's own, or off by twice this.
LOGIT_LIMIT = 0.25


def _with_logits(cell, seed, sample, how):
    """``sample`` as a program that delivers logits would hand it over:
    the float32 reference's logit of each served token, then ``how``
    breaks it (``none_delivered``: no logits at all, as from a program
    that delivers none)."""
    ref, toks = harness.reference_logits(cell, seed, sample,
                                         jax.devices()[0])
    vals = np.asarray(jnp.take_along_axis(
        ref[:len(toks)], jnp.asarray(toks)[:, None], -1)[:, 0])
    at = np.cumsum([0] + [len(t) for _, t, _ in sample])
    out = [(p, t, vals[at[i]:at[i + 1]].copy())
           for i, (p, t, _) in enumerate(sample)]
    if how == "one_off":
        out[0][2][1] += 2 * LOGIT_LIMIT
    elif how == "one_missing":
        out[-1] = (out[-1][0], out[-1][1], out[-1][2][:-1])
    elif how == "none_delivered":
        out = [(p, t, None) for p, t, _ in out]
    return out


@pytest.mark.parametrize("how", ["exact", "one_off", "one_missing",
                                 "none_delivered", "no_limit"])
def test_served_logits_are_judged_by_logit_err(served, how, capsys):
    """A served logit off by more than the limit, or a token served
    without its logit, fails ``logit_err``; a program that delivers no
    logits, or a mix that states no ``logit_limit``, is judged by
    ``logit_gap`` alone, and the run says so."""
    cell, cfg, s = served
    if how != "no_limit":
        chk = dict(cell.traffic["check"], logit_limit=LOGIT_LIMIT)
        cell = dataclasses.replace(cell, traffic=dict(cell.traffic, check=chk))
    win = harness.run_window(s, cell, 5, 2.0, cfg.vocab_size, drain_s=30)
    e2e = harness.end_to_end(win)
    line = {"attempted": e2e["attempted"], "failed": e2e["failed"]}
    sample = _with_logits(cell, 2**31 + 77, harness.check_sample(win, cell, 5),
                          how)
    out = harness.check(cell, 2**31 + 77, line, sample, jax.devices()[0])
    unjudged = how in ("none_delivered", "no_limit")
    assert list(out["checks"]) == (
        ["logit_gap", "failed_requests"] if unjudged
        else ["logit_gap", "logit_err", "failed_requests"])
    assert out["correct"] == (how in ("exact", "none_delivered", "no_limit"))
    err = capsys.readouterr().err
    if how == "none_delivered":
        assert "delivers no logits" in err
    elif how == "no_limit":
        assert "states no check.logit_limit" in err
    else:
        err = out["checks"]["logit_err"]
        assert (err["value"] < 1e-4) if how == "exact" \
            else (err["value"] > err["limit"])


def _wrap_window(monkeypatch, eng, fault):
    orig = eng._window_fn

    def broken(params, pools, tables, toks, pos, budget, *, rung):
        res = orig(params, pools, tables, toks, pos, budget, rung=rung)
        return fault(pools, *res)

    monkeypatch.setattr(eng, "_window_fn", broken)


def test_a_step_that_returns_its_state_unchanged_is_caught(served,
                                                          monkeypatch):
    _wrap_window(monkeypatch, served[2].engine,
                 lambda old, new, *rest: (old, *rest))
    out, _ = _check(served, 2)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] \
        > out["checks"]["logit_gap"]["limit"]


def test_a_token_altered_where_produced_is_caught(served, monkeypatch):
    vocab = served[1].vocab_size

    def alter(_old, pools, toks, pos, budget, out):
        return pools, toks, pos, budget, out.at[3, 0].set(
            jnp.where(out[3, 0] >= 0, (out[3, 0] + 1) % vocab, out[3, 0]))

    _wrap_window(monkeypatch, served[2].engine, alter)
    out, _ = _check(served, 3)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] \
        > out["checks"]["logit_gap"]["limit"]


def test_half_of_the_batch_left_out_is_caught(served, monkeypatch):
    """The window's upper half of rows gets the lower half's tokens, as
    if only half of the batch were computed (at a load that keeps
    several rows live), read over enough requests that some sat in the
    upper half."""

    def halve(_old, pools, toks, pos, budget, out):
        h = out.shape[1] // 2
        if h == 0:
            return pools, toks, pos, budget, out
        lo, hi = out[:, :h], out[:, h:2 * h]
        out = out.at[:, h:2 * h].set(jnp.where((hi >= 0) & (lo >= 0), lo, hi))
        return pools, toks, pos, budget, out

    _wrap_window(monkeypatch, served[2].engine, halve)
    out, _ = _check(served, 4, rate=80.0, requests=16)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] \
        > out["checks"]["logit_gap"]["limit"]


def test_the_fp8_control_is_not_correct():
    """At the published depth and vocabulary: the program's own run is
    correct, and the reference computed in float8, put in the program's
    place on the same requests, is not."""
    cell, cfg = deep_cell()
    s = harness.setup(cell, 5, cfg, jax.devices())
    try:
        win = harness.run_window(s, cell, 46, 3.0, cfg.vocab_size,
                                 drain_s=60)
        e2e = harness.end_to_end(win)
    finally:
        s.frontend.shutdown(drain=False)
    line = {"attempted": e2e["attempted"], "failed": e2e["failed"]}
    sample = harness.check_sample(win, cell, 46)
    assert e2e["failed"] == 0 and sum(len(t) for _, t, _ in sample) > 80
    sound = harness.check(cell, 5, line, sample, jax.devices()[0])
    ctrl = harness.check(cell, 5, line, sample, jax.devices()[0], "fp8")
    assert sound["correct"], sound["checks"]
    assert not ctrl["correct"]
    assert ctrl["checks"]["logit_gap"]["value"] \
        > ctrl["checks"]["logit_gap"]["limit"]


def test_a_request_that_never_finishes_fails_the_check(served):
    cell, cfg, s = served
    t = time.perf_counter()
    rec = harness.Rec(harness.traffic_mod.Req(0, 0.0, np.arange(
        2, 10, dtype=np.int32), 4), t)
    win = harness.Window(t, t + 1.0, [rec], [], t + 1.0)
    e2e = harness.end_to_end(win)
    assert e2e["failed"] == 1
    out = harness.check(cell, 1, {"attempted": 1, "failed": 1}, [],
                        jax.devices()[0])
    assert not out["correct"]


MESH_RUN = """
import dataclasses, json, sys, time
t0 = time.perf_counter()
import jax
from benchmarks.chip import harness
from chipbench_smoke import deep_cell, smoke_cell, spec_of
cell, cfg = smoke_cell("granite-20b")
cfg = dataclasses.replace(cfg, n_heads=8, n_kv_heads=1)
cell.spec = dict(spec_of(cfg), mesh={"data": 1, "model": 4})
cell.traffic = dict(cell.traffic, loop="closed", clients=6)
cell.chips = 4
line = harness.run(cell, 99, 2.0, False, cfg=cfg, devices=jax.devices(),
                   process_t0=t0, compiles=harness.CompileCounter())
print(json.dumps(line))
"""


def test_a_tensor_parallel_cell_runs_on_four_devices():
    """A cell whose configuration names a (1, 4) mesh serves through the
    program's tensor-parallel path and passes the same check (on four
    CPU devices, in a process of its own)."""
    import json
    import os
    import subprocess
    import sys

    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(here.parents[1]), str(here.parents[1] / "src"),
                    str(here)]))
    out = subprocess.run([sys.executable, "-c", MESH_RUN], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"], line["checks"]
    assert line["device"]["count"] == 4 and line["failed"] == 0
