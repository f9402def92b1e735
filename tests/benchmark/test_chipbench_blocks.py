"""What a configuration's block gives the benchmark: weights with an
expert axis drawn per global expert, and the dense block's operation and
byte counts as the metric readers had them before blocks existed."""
import dataclasses
import json
import math
import types
from pathlib import Path

import jax
import numpy as np
import pytest
from chipbench_smoke import spec_of

from benchmarks.chip import harness, weights

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 21


def _moe_block():
    """The dense block's roles, plus a router and three expert-stacked
    matrices under ``moe/`` (the program's tree for ``family="moe"``)."""
    dense = harness.block_of({"block": "dense"})
    moe = {"moe/router": "router.w", "moe/up": "experts.up",
           "moe/gate": "experts.gate", "moe/down": "experts.down"}
    return types.SimpleNamespace(
        __name__="moe_test",
        role=lambda keys: dense.role(keys) or moe.get("/".join(keys)),
        EXPERT_ROLES=frozenset({"experts.up", "experts.gate",
                                "experts.down"}))


@pytest.mark.parametrize("held,first", [(4, 0), (2, 2)])
def test_expert_leaves_draw_their_global_experts(held, first):
    """A program holding experts ``[first, first + held)`` of each layer
    (``phi3.5-moe-42b``'s smoke shapes, 4 experts in all) draws, for each
    of them, what the reference draws for that global expert, whatever
    the leaf holds; each expert's matrix is scaled by its own input
    width (``d_model`` for ``up`` and ``gate``, ``d_ff`` for ``down``),
    not by the expert count."""
    from repro.configs import smoke_config
    from repro.models import init_params

    cfg = dataclasses.replace(smoke_config("phi3.5-moe-42b"),
                              param_dtype="bfloat16")
    total = cfg.moe.n_experts
    assert total == 4
    part = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_experts=held))
    shapes = jax.eval_shape(lambda k: init_params(part, k),
                            jax.random.PRNGKey(0))
    block = _moe_block()
    params = weights.program_params(shapes, SEED, block, first_expert=first)
    key = weights.base_key(SEED)
    seen = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        role = weights.role_of(path, block)
        if role not in block.EXPERT_ROLES:
            continue
        seen.add(role)
        assert leaf.shape[1] == held
        d_in = leaf.shape[2]
        assert d_in == (cfg.d_ff if role == "experts.down" else cfg.d_model)
        for layer in range(cfg.n_layers):
            # Jitted, as the reference draws a layer (an unjitted draw
            # may round an element differently from the fused one).
            whole = jax.jit(lambda k, n: weights.layer_weights(
                {role: (total,) + leaf.shape[2:]}, k, n,
                block.EXPERT_ROLES)[role])(key, layer)
            np.testing.assert_array_equal(
                np.asarray(leaf[layer]), np.asarray(whole[first:first + held]))
            for i in range(held):
                one = jax.jit(lambda k, n: weights.draw_experts(
                    k, role, n, (1,) + leaf.shape[2:], leaf.dtype,
                    first + i))(key, layer)
                np.testing.assert_array_equal(np.asarray(leaf[layer, i]),
                                              np.asarray(one[0]))
            std = float(np.std(np.asarray(leaf[layer], np.float32)))
            assert std * math.sqrt(d_in) == pytest.approx(1.0, abs=0.05)
        # Another layer, or another role, draws other values.
        assert not np.array_equal(np.asarray(leaf[0]), np.asarray(leaf[1]))
    assert seen == block.EXPERT_ROLES


# The dense counts as ``model_math.py`` gave them before they moved into
# ``blocks/dense.py``: for yi-6b (its configuration file) and for
# granite-20b's registry shapes (multi-query attention, a plain MLP with
# biases), at these contexts.
CONTEXTS = [1, 100, 200, 767, 1151]
PINNED = {
    "yi-6b": {
        "prompt_flops": [11597774848.0, 1110468198400.0, 2225654988800.0,
                         8647904067584.0, 13093094555648.0],
        "decode_token_flops": [11597774848.0, 11649679360.0, 11702108160.0,
                               11999379456.0, 12200706048.0],
        "paged_attn_need": {1: (145424384.0, 1163395072.0),
                            4: (36356096.0, 290848768.0)},
    },
    "granite-20b": {
        "prompt_flops": [40027521024.0, 3949283966976.0, 7910743474176.0,
                         30613872574464.0, 46222880538624.0],
        "decode_token_flops": [40027521024.0, 40154038272.0, 40281833472.0,
                               41006432256.0, 41497165824.0],
        "paged_attn_need": {4: (59078656.0, 708943872.0)},
    },
}


def _spec(name):
    if name == "yi-6b":
        return json.loads(
            (ROOT / "benchmarks/chip/configs/yi-6b.json").read_text())
    from repro.configs import get_config

    return spec_of(get_config(name))


@pytest.mark.parametrize("name", sorted(PINNED))
@pytest.mark.parametrize("count", ["prompt_flops", "decode_token_flops",
                                   "paged_attn_need"])
def test_dense_counts_are_the_parents(name, count):
    spec = _spec(name)
    block = harness.block_of(spec)
    want = PINNED[name][count]
    if count == "paged_attn_need":
        for tp, pair in want.items():
            assert block.paged_attn_need(spec, CONTEXTS, tp) == pair
    else:
        assert [getattr(block, count)(spec, c) for c in CONTEXTS] == want
