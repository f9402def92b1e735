"""The check on a model with tied embeddings, where the served tokens
alone cannot tell sound arithmetic from wrong.

With seeded weights and the embedding table as the head, the residual
stream keeps the scaled embedding of the input token and the head scores
that same row, so the input token tops the logits: the program, its
float8 twin and a model whose state is never written all serve about
the same tokens, and ``logit_gap`` reads near 0 for each.  ``logit_err``
compares the logit each token was served by with the reference's, and
that differs.  The cell is ``granite-20b``'s family at ``yi-6b``'s depth
and vocabulary and a narrow width (``chipbench_smoke.tied_deep_cell``).

The program's handles deliver no logits yet, so ``harness.check`` judges
its run, and the control's, by ``logit_gap`` alone.  The tests hand the
check samples that carry logits: the program's own, from its forward
teacher-forced over each request as served, and those of a stand-in (the
float8 reference, or a reference with a fault planted), which serves at
each position the token it ranks first there with its logit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from chipbench_smoke import tied_deep_cell

from benchmarks.chip import harness, weights
from benchmarks.chip.reference import teacher_forced, top_picks

# Readings of ``logit_err`` on this cell (CPU, window seed 46, weight seed
# 5, 113 served tokens): the program's own teacher-forced logits 0.0349;
# the float8 stand-in 0.2150 (int8: 0.0603); the stand-ins with a fault
# planted 3.51 (state never written), 5.49 (token altered), 4.05 (half
# the batch).  ``logit_gap`` reads 0 for the program and both controls.
LOGIT_LIMIT = 0.1
SEED = 5


@pytest.fixture(scope="module")
def tied():
    cell, cfg = tied_deep_cell()
    cell.traffic["check"] = dict(cell.traffic["check"],
                                 logit_limit=LOGIT_LIMIT)
    s = harness.setup(cell, SEED, cfg, jax.devices())
    try:
        win = harness.run_window(s, cell, 46, 3.0, cfg.vocab_size,
                                 drain_s=60)
        e2e = harness.end_to_end(win)
    finally:
        s.frontend.shutdown(drain=False)
    line = {"attempted": e2e["attempted"], "failed": e2e["failed"]}
    sample = harness.check_sample(win, cell, 46)
    assert e2e["failed"] == 0 and sum(len(t) for _, t, _ in sample) > 80
    return cell, cfg, line, sample


def _echo_share(sample) -> float:
    """The share of served tokens equal to the token fed at their
    position (the prompt's last, then the previous served one)."""
    fed = [np.concatenate([p[-1:], t[:-1]]) for p, t, _ in sample]
    return float(np.mean(np.concatenate(
        [t == f for (_, t, _), f in zip(sample, fed)])))


def _programs_own(cell, cfg, sample):
    """``sample`` with the logit the program's forward (bfloat16, as
    served) gives each served token, teacher-forced over the request."""
    from repro.models import init_params
    from repro.models.transformer import forward_prefill

    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    params = weights.program_params(shapes, SEED, cell.block)
    out = []
    for prompt, toks, _ in sample:
        seq = np.concatenate([prompt, toks[:-1]])[None]
        idx = np.arange(seq.shape[1], dtype=np.int32)[None]
        lg, _ = forward_prefill(params, cfg, {"tokens": jnp.asarray(seq)},
                                logits_index=jnp.asarray(idx))
        at = len(prompt) - 1 + np.arange(len(toks))
        out.append((prompt, toks, np.asarray(
            lg[0, at, toks].astype(jnp.float32))))
    return out


def _stand_in(sample, logits):
    """``sample`` as served by a stand-in whose ``logits`` were read at
    the positions of the program's requests: its first-ranked token at
    each, with its logit there."""
    picks, picked = top_picks(logits, sum(len(t) for _, t, _ in sample))
    at = np.cumsum([0] + [len(t) for _, t, _ in sample])
    return [(p, picks[at[i]:at[i + 1]], picked[at[i]:at[i + 1]])
            for i, (p, _, _) in enumerate(sample)]


def test_tied_cell_echoes_and_only_logit_err_fails_the_fp8_control(
        tied, record_property, capsys):
    cell, cfg, line, sample = tied
    dev = jax.devices()[0]
    echo = _echo_share(sample)
    record_property("echo_share", echo)
    assert echo > 0.5
    # The program, with the logits its own forward gives: correct, and
    # judged by logit_err as well.
    sound = harness.check(cell, SEED, line,
                          _programs_own(cell, cfg, sample), dev)
    assert sound["correct"], sound["checks"]
    assert "logit_err" in sound["checks"]
    # The defect: a program that delivers no logits leaves the control
    # judged by its tokens alone, and there it passes.
    blind = harness.check(cell, SEED, line, sample, dev, "fp8")
    assert list(blind["checks"]) == ["logit_gap", "failed_requests"]
    assert blind["correct"], blind["checks"]
    assert "delivers no logits" in capsys.readouterr().err
    # With its logits the same control fails logit_err alone.
    ctrl, _ = harness.reference_logits(cell, SEED, sample, dev, "fp8")
    out = harness.check(cell, SEED, line, _stand_in(sample, ctrl), dev)
    checks = out["checks"]
    assert checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
    assert checks["logit_err"]["value"] > checks["logit_err"]["limit"]
    assert not out["correct"]


def _state_never_written(block):
    class StateNeverWritten(block.Reference):
        """Every position sees only itself, as if no earlier key, value
        or state had been written."""

        @staticmethod
        def layer(spec, w, x, quant=None):
            b, s, d = x.shape
            one = block.Reference.layer(spec, w, x.reshape(b * s, 1, d),
                                        quant)
            return one.reshape(b, s, d)

    return StateNeverWritten


def _token_altered(block):
    class TokenAltered(block.Reference):
        """The fourth served token is the next id after the one picked,
        served with the logit it was picked by."""

        def logits(self, seqs, positions, n_out):
            lg = super().logits(seqs, positions, n_out)
            return lg.at[3].set(jnp.roll(lg[3], 1))

    return TokenAltered


def _half_the_batch(block):
    class HalfTheBatch(block.Reference):
        """The upper half of the requests get the lower half's tokens and
        logits, as if only half of the batch were computed."""

        def logits(self, seqs, positions, n_out):
            lg = super().logits(seqs, positions, n_out)
            at = np.cumsum([0] + [len(p) for p in positions])
            h = len(positions) // 2
            for i in range(h, 2 * h):
                n = min(len(positions[i]), len(positions[i - h]))
                lg = lg.at[at[i]:at[i] + n].set(lg[at[i - h]:at[i - h] + n])
            return lg

    return HalfTheBatch


@pytest.mark.parametrize("fault", [_state_never_written, _token_altered,
                                   _half_the_batch])
def test_faults_on_the_tied_cell_are_caught_by_logit_err(tied, fault):
    """Each fault, planted in a stand-in for the program that delivers
    its logits, fails ``logit_err`` on the tied cell."""
    cell, _, line, sample = tied
    chk, eng = cell.traffic["check"], cell.traffic["engine"]
    ref = fault(cell.block)(cell.spec, SEED, chk["requests"],
                            eng["max_seq"], chk["block"],
                            device=jax.devices()[0])
    seqs, pos, _ = teacher_forced(sample)
    lg = ref.logits(seqs, pos,
                    chk["requests"] * cell.traffic["output_len"]["max"])
    out = harness.check(cell, SEED, line, _stand_in(sample, lg),
                        jax.devices()[0])
    err = out["checks"]["logit_err"]
    assert err["value"] > err["limit"] and not out["correct"]
