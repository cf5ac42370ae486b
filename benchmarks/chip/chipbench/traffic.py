"""One general generator for every traffic file under ``traffic/``.

A traffic file states the arrivals (``backlog`` or ``poisson``), the
length distributions of prompts and answers, and the engine's slots and
``max_len``.  Lengths and gaps are drawn by strata: every block of
``strata`` consecutive requests holds the same multiset of sizes (the
distribution's quantiles) and the seed only orders them.  So every seed
asks for the same work, in another order, and runs of different seeds
differ by little more than runs of one seed.

The ``poisson`` arrivals are therefore not a Poisson process.  Their
gaps are the exponential distribution's quantiles, permuted within each
block, so every block of ``strata`` arrivals lasts exactly
``strata / rate_rps`` seconds.  Within a block the gaps are bursty;
across blocks the count of arrivals does not vary, as it would under iid
gaps (``runtime/loadgen.py``'s ``poisson_trace``).  Tails read lower
than under iid arrivals.

A backlog with ``staggered`` set starts as a deployment in its steady
state would be: each of the first ``slots`` requests carries a share of
its answer in its prompt (tokens drawn like the prompt's), and asks only
for the rest.  The shares are fixed by the rank of the answer's length,
so every seed holds the same pairs of lengths and shares.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Req:
    req_id: int
    due: float              # seconds from the window's start (< 0: warm-in)
    prompt: np.ndarray      # int32 token ids
    max_new: int


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of one seed; any whole number,
    negative or past 64 bits, is a valid seed."""
    words = [int(seed) % (1 << 64)] + [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words))


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """The distribution's ``n`` mid-quantiles, ``(i + 0.5) / n``, as
    whole token counts inside ``[lo, hi]``."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if dist["dist"] == "uniform":
        v = lo + np.floor(u * (hi - lo + 1))
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = np.round(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def stratified(dist: Dict, n: int, strata: int,
               rng: np.random.Generator) -> np.ndarray:
    """``n`` sizes: each block of ``strata`` is the distribution's
    ``strata`` quantiles in an order drawn from ``rng``."""
    block = quantiles(dist, strata)
    out = [rng.permutation(block) for _ in range(-(-n // strata))]
    return np.concatenate(out)[:n]


def exp_gaps(rate_rps: float, n: int, strata: int,
             rng: np.random.Generator) -> np.ndarray:
    """Exponential inter-arrival gaps by strata: each block is the
    distribution's ``strata`` quantiles, scaled so that its mean is
    exactly ``1 / rate_rps``, in an order drawn from ``rng``."""
    u = (np.arange(strata) + 0.5) / strata
    block = -np.log1p(-u)
    block = block / block.mean() / rate_rps
    out = [rng.permutation(block) for _ in range(-(-n // strata))]
    return np.concatenate(out)[:n]


GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


def answer_shares(outs: np.ndarray) -> np.ndarray:
    """The share of each answer already served at the start: the
    golden-ratio sequence in the order of the answers' lengths, so the
    shares spread over ``(0, 1)`` and do not follow the length."""
    rank = np.empty(len(outs), np.int64)
    rank[np.argsort(outs, kind="stable")] = np.arange(len(outs))
    return np.mod((rank + 1) * GOLDEN, 1.0)


def n_requests(traffic: Dict, seconds: float, drain_s: float) -> int:
    """How many requests a run of ``seconds`` can reach."""
    if traffic["arrivals"] == "backlog":
        return int(traffic["requests"])
    span = float(traffic["warm_s"]) + seconds + drain_s
    return int(math.ceil(float(traffic["rate_rps"]) * span)) + \
        int(traffic["strata"])


def generate(traffic: Dict, vocab: int, seed: int, seconds: float,
             drain_s: float = 60.0) -> List[Req]:
    """The requests of one run, in arrival order."""
    n = n_requests(traffic, seconds, drain_s)
    strata = int(traffic["strata"])
    prompts = stratified(traffic["prompt_tokens"], n, strata,
                         rng_for(seed, "prompt_len"))
    outs = stratified(traffic["output_tokens"], n, strata,
                      rng_for(seed, "output_len"))
    if max(prompts + outs) > int(traffic["max_len"]):
        raise ValueError("a prompt and its answer exceed the cell's max_len")
    if traffic["arrivals"] == "backlog":
        due = np.zeros(n)
    elif traffic["arrivals"] == "poisson":
        gaps = exp_gaps(float(traffic["rate_rps"]), n, strata,
                        rng_for(seed, "gaps"))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
            - float(traffic["warm_s"])
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    served = np.zeros(n, np.int64)
    if traffic.get("staggered"):
        k = int(traffic["slots"])
        served[:k] = np.minimum(
            np.floor(answer_shares(outs[:k]) * outs[:k]), outs[:k] - 1)
    tok = rng_for(seed, "tokens")
    return [Req(i, float(due[i]),
                tok.integers(1, vocab, size=int(prompts[i] + served[i]),
                             dtype=np.int32),
                int(outs[i] - served[i]))
            for i in range(n)]
