"""The traffic generator: the same seed gives the same requests, and
every seed gets the same work in another order."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import traffic as tm

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
TRAFFIC = {w["traffic"]: json.loads(
    (ROOT / "benchmarks/chip/traffic" / f"{w['traffic']}.json").read_text())
    for w in MANIFEST["workloads"]}
OPEN = {n: t for n, t in TRAFFIC.items() if t["loop"] == "open"}
BIG_SEED = 2**31 + 12345


def _sig(reqs):
    return [(r.due_s, r.prompt.tobytes(), r.max_new_tokens) for r in reqs]


@pytest.mark.parametrize("name", sorted(OPEN))
def test_same_seed_same_schedule(name):
    a = tm.Traffic(OPEN[name], BIG_SEED, 64000).schedule(30.0)
    b = tm.Traffic(OPEN[name], BIG_SEED, 64000).schedule(30.0)
    assert a and _sig(a) == _sig(b)
    c = tm.Traffic(OPEN[name], BIG_SEED + 1, 64000).schedule(30.0)
    assert _sig(a) != _sig(c)


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_seeds_share_the_work(name):
    """One block of requests: the same lengths for every seed, permuted."""
    spec = TRAFFIC[name]
    n = 256
    lens = []
    for seed in (1, 2, BIG_SEED):
        gen = tm.Traffic(spec, seed, 64000, block=n)
        reqs = [gen.request(i) for i in range(n)]
        lens.append((Counter(len(r.prompt) for r in reqs),
                     Counter(r.max_new_tokens for r in reqs),
                     [len(r.prompt) for r in reqs]))
    assert lens[0][0] == lens[1][0] == lens[2][0]
    assert lens[0][1] == lens[1][1] == lens[2][1]
    assert lens[0][2] != lens[1][2]


@pytest.mark.parametrize("name", sorted(OPEN))
def test_gaps_keep_the_rate(name):
    gen = tm.Traffic(OPEN[name], 3, 64000)
    reqs = gen.schedule(40.0)
    expect = OPEN[name]["rate_per_s"] * 40.0
    assert abs(len(reqs) - expect) <= 0.05 * expect + 1
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 40.0


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_lengths_in_bounds_and_no_request_overflows(name):
    spec = TRAFFIC[name]
    gen = tm.Traffic(spec, 9, 64000)
    for i in range(512):
        r = gen.request(i)
        assert spec["prompt_len"]["min"] <= len(r.prompt) \
            <= spec["prompt_len"]["max"]
        assert r.max_new_tokens >= 1
        assert r.prompt.min() >= tm.FIRST_TOKEN_ID and r.prompt.max() < 64000
    longest = tm.max_prompt_len(spec) + spec["output_len"]["max"]
    assert longest <= spec["engine"]["max_seq"]


def test_lognormal_quantiles_hold_the_median():
    spec = {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32,
            "max": 512}
    q = tm._quantiles(spec, 1001)
    assert np.median(q) == 192 and q.min() >= 32 and q.max() == 512


@pytest.mark.parametrize("name", sorted(OPEN))
def test_seeds_share_the_arrival_gaps(name):
    """Every seed waits the same gaps between arrivals, in another order."""
    gaps = [np.sort(tm.Traffic(OPEN[name], seed, 64000).gaps(0))
            for seed in (1, BIG_SEED)]
    np.testing.assert_array_equal(gaps[0], gaps[1])
    assert not np.array_equal(tm.Traffic(OPEN[name], 1, 64000).gaps(0),
                              tm.Traffic(OPEN[name], 2, 64000).gaps(0))


def test_uniform_quantiles_cover_the_range_evenly():
    q = tm._quantiles({"dist": "uniform", "min": 8, "max": 32}, 250)
    counts = Counter(q.tolist())
    assert sorted(counts) == list(range(8, 33))
    assert max(counts.values()) - min(counts.values()) <= 1


@pytest.mark.parametrize("name", sorted(OPEN))
def test_every_seed_has_the_same_requests_due_in_the_window(name):
    """The window holds one whole block: the same number of requests,
    with the same lengths and gaps, for every seed."""
    seen = []
    for seed in (1, 2, BIG_SEED):
        gen = tm.Traffic.for_window(OPEN[name], seed, 64000, 50.0)
        due = [r for r in gen.schedule(110.0) if r.due_s < 50.0]
        seen.append((len(due), sorted(len(r.prompt) for r in due),
                     sorted(r.max_new_tokens for r in due)))
    assert seen[0][0] == round(OPEN[name]["rate_per_s"] * 50.0)
    assert seen[0] == seen[1] == seen[2]


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_every_mix_states_its_check(name):
    """The requests the reference reads, the gap limit, and the logit
    limit where the mix states one (``harness.check``)."""
    chk = TRAFFIC[name]["check"]
    assert chk["requests"] % chk["block"] == 0
    assert chk["gap_limit"] > 0 and chk.get("logit_limit", 1) > 0
