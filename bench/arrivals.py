"""The one traffic generator: a traffic mix file plus a cell's rate and a
seed give the list of requests a run offers.

Every seed gets the same trace: the same prompt lengths, output lengths
and due times, in the same order. Each of the three is the set of ``n``
evenly spaced quantiles of its distribution, put in an order drawn once
from ``ORDER_SEED``; the run's seed draws only the prompt token ids (and,
elsewhere, the weights). So two seeds offer the same work at the same
times, and the spread between runs is the system's, not the sampler's.

The arrival process is ``poisson``: gaps are quantiles of an exponential
at the cell's ``rate_per_s``; ``n = floor(rate * seconds)``, scaled so
that the last request is due no later than the window's end.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

# Seed of the one order in which every run offers the trace's lengths
# and gaps.
ORDER_SEED = 0


@dataclasses.dataclass
class Offered:
    """One request as the generator offers it: due (seconds after the
    window opens), prompt token ids and the number of tokens to generate."""
    index: int
    due: float
    prompt: np.ndarray
    max_new: int


def _ppf(dist: dict, u: np.ndarray) -> np.ndarray:
    """Quantile function of a length distribution at probabilities ``u``,
    rounded and clipped to the mix's range."""
    kind = dist["dist"]
    if kind == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def n_requests(mix: dict, rate_per_s: float, seconds: float) -> int:
    proc = mix["arrival"]["process"]
    if proc != "poisson":
        raise ValueError(f"unknown arrival process {proc!r}")
    return max(1, int(math.floor(rate_per_s * seconds)))


def generate(mix: dict, rate_per_s: float, seconds: float, seed: int,
             vocab: int) -> List[Offered]:
    """The requests of one run, in due order."""
    n = n_requests(mix, rate_per_s, seconds)
    order = np.random.default_rng(ORDER_SEED)
    u = _quantiles(n)
    prompt_lens = order.permutation(_ppf(mix["prompt_len"], u))
    out_lens = order.permutation(_ppf(mix["output_len"], u))
    gaps = order.permutation(-np.log1p(-u) / rate_per_s)
    due = np.cumsum(gaps)
    due *= min(1.0, (n / rate_per_s) / due[-1])
    rng = np.random.default_rng(seed)
    return [Offered(i, float(due[i]),
                    rng.integers(0, vocab, size=int(prompt_lens[i]),
                                 dtype=np.int32),
                    int(out_lens[i]))
            for i in range(n)]
