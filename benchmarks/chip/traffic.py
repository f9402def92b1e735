"""The one traffic generator: every mix is a data file it reads.

``traffic/<cell>.json`` gives the loop and its rate or client count, the
length distributions, and the engine sizing the operator would choose
for that traffic.  The work does not depend on the seed: every seed gets
the same multiset of prompt lengths, output lengths and arrival gaps
(quantiles of the stated distributions), in an order and with token ids
drawn from the seed.  So two seeds differ in which request meets which,
not in how much there is to do.  An open loop's block is the window
(:meth:`Traffic.for_window`): the same number of requests is due in it
for every seed, with the same lengths.

Keys (all lengths in tokens, times in seconds):

- ``loop``: ``"open"`` (arrivals on a schedule) or ``"closed"``
  (``clients`` callers, each sending its next request when the last one
  completes).
- ``rate_per_s`` (open): mean arrival rate, Poisson arrivals; ``knee``:
  the highest rate the system sustained in the sweep (``sweep.py``).
- ``prompt_len``, ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.
- ``engine``: ``max_slots``, ``max_seq``, ``page_size``.
- ``check``: the requests the reference reads, in blocks of ``block``,
  and the limit of the widest logit gap (``harness.check``).
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List

import numpy as np

FIRST_TOKEN_ID = 2   # ids 0 and 1 are left to padding and specials


@dataclasses.dataclass(frozen=True)
class Req:
    index: int
    due_s: float             # offset from the window start (open loop)
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of ``spec``."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "uniform":
        vals = spec["min"] + u * (spec["max"] + 1 - spec["min"])
        vals = np.floor(vals)
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        vals = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(vals, spec["min"], spec["max"]).astype(np.int64)


def max_prompt_len(traffic: dict) -> int:
    return int(traffic["prompt_len"]["max"])


def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, *stream])


class Traffic:
    """Requests of one mix for one seed, in the order they are sent."""

    def __init__(self, traffic: dict, seed: int, vocab: int,
                 block: int = 256, period_s: float = None):
        self.spec = traffic
        self.seed = seed
        self.vocab = vocab
        self.block = block
        # Open loop: the arrivals of one block span this long.
        self.period_s = (block / traffic["rate_per_s"] if period_s is None
                         else period_s)
        self._prompts = _quantiles(traffic["prompt_len"], block)
        self._outputs = _quantiles(traffic["output_len"], block)
        self._blocks: List[tuple] = []

    @classmethod
    def for_window(cls, traffic: dict, seed: int, vocab: int,
                   seconds: float) -> "Traffic":
        """An open loop whose first block is exactly the requests due in
        a window of ``seconds`` at the mix's rate."""
        n = max(1, round(traffic["rate_per_s"] * seconds))
        return cls(traffic, seed, vocab, block=n, period_s=seconds)

    def _ids(self, rng: np.random.Generator, n: int) -> np.ndarray:
        return rng.integers(FIRST_TOKEN_ID, self.vocab, size=int(n),
                            dtype=np.int32)

    def _block(self, b: int) -> tuple:
        """Block ``b``: a permutation of the prompt and output lengths
        and of the arrival gaps."""
        while len(self._blocks) <= b:
            rng = _rng(self.seed, 2, len(self._blocks))
            self._blocks.append((rng.permutation(self.block),
                                 rng.permutation(self.block),
                                 rng.permutation(self.block)))
        return self._blocks[b]

    def gaps(self, b: int) -> np.ndarray:
        """Gaps after each arrival of block ``b`` (open loop): the
        mid-quantiles of an exponential distribution, scaled to span the
        block's period, permuted."""
        u = (np.arange(self.block) + 0.5) / self.block
        gaps = -np.log1p(-u)
        gaps *= self.period_s / gaps.sum()
        return gaps[self._block(b)[2]]

    def request(self, i: int, due_s: float = 0.0) -> Req:
        b, k = divmod(i, self.block)
        p_perm, o_perm, _ = self._block(b)
        rng = _rng(self.seed, 3, i)
        prompt = self._ids(rng, self._prompts[p_perm[k]])
        return Req(i, due_s, prompt, int(self._outputs[o_perm[k]]))

    def schedule(self, horizon_s: float) -> List[Req]:
        """Open loop: every request due before ``horizon_s``."""
        if self.spec["loop"] != "open":
            raise ValueError("a closed loop has no schedule")
        out: List[Req] = []
        b = 0
        while True:
            gaps = self.gaps(b)
            starts = b * self.period_s + np.concatenate(
                [[0.0], np.cumsum(gaps)[:-1]])
            for t in starts:
                if t >= horizon_s:
                    return out
                out.append(self.request(len(out), float(t)))
            b += 1

