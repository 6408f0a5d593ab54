"""The one traffic generator: reads a mix from `chipbench/traffic/` and
makes its requests or batches from the run's seed.

Every seed gets the same multiset of sizes and inter-arrival gaps, drawn
at evenly spaced quantiles of the mix's distributions, in an order of its
own; the seed also draws the token ids.  So runs with different seeds do
the same amount of work, and only its order and content change.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(spec: dict, n: int) -> np.ndarray:
    """n sizes at evenly spaced quantiles of a lognormal with the spec's
    median and sigma, clipped to [min, max]."""
    z = np.array([_NORMAL.inv_cdf(u) for u in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """n inter-arrival gaps at evenly spaced quantiles of Exp(rate)."""
    return -np.log1p(-_quantiles(n)) / rate


@dataclasses.dataclass
class Request:
    arrival_s: float             # scheduled, from the start of the traffic
    prompt: np.ndarray
    max_new: int
    in_window: bool


def chat_requests(mix: dict, seed: int, vocab: int, window_s: float
                  ) -> list[Request]:
    """Open-loop requests for the warm-up and the measured window, in
    arrival order.  The window begins at mix["warmup_s"]."""
    rng = np.random.default_rng(seed)
    rate = float(mix["rate_per_s"])
    out, t0 = [], 0.0
    for phase_s, in_window in ((float(mix["warmup_s"]), False),
                               (float(window_s), True)):
        n = max(1, round(rate * phase_s))
        order = rng.permutation(n)
        gaps = poisson_gaps(rate, n)[rng.permutation(n)]
        # scale so the phase's arrivals span exactly phase_s
        arrivals = t0 + np.cumsum(gaps) * (phase_s / gaps.sum())
        plen = lognormal_sizes(mix["prompt_len"], n)[order]
        olen = lognormal_sizes(mix["output_len"], n)[rng.permutation(n)]
        for a, p, o in zip(arrivals, plen, olen):
            out.append(Request(float(a), rng.integers(
                1, vocab, size=int(p), dtype=np.int32), int(o), in_window))
        t0 += phase_s
    return out

