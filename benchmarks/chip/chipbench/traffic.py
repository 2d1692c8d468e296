"""The one generator of request schedules: it reads a traffic file's
parameters and gives the arrivals and sizes of an open loop.

The schedule is the same for every seed: the gaps are the quantiles of
the arrival distribution in one fixed order, and the sizes cycle evenly
through their range in one fixed order.  The seed draws the images and
the weights, not the work.  (With the order drawn from the seed, the
95th percentile of latency at 0.8 x the knee moved by 19-23% from seed to
seed on the v5e with the gaps' order drawn, and by 24-64% with the sizes'
order drawn, against 0-3% for two runs of one seed: the order decides
which bursts meet which large requests.)

Parameters (``traffic/<name>.json``):

  rate_per_s        mean arrivals per second over the window
  images_min/max    request sizes, evenly spread over [min, max]
"""
from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def request_count(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["rate_per_s"] * seconds)))


def schedule(traffic: dict, seconds: float):
    """(due_s, images) of every request due in a window of ``seconds``:
    ``due_s`` ascending, all below ``seconds``."""
    n = request_count(traffic, seconds)
    lo, hi = int(traffic["images_min"]), int(traffic["images_max"])
    sizes = _rng(0, 1).permutation(np.arange(n) % (hi - lo + 1) + lo)
    # exponential quantiles at (i + 1/2) / n: Poisson gaps in one fixed
    # order, scaled so that the n arrivals fill the window at the mean
    # rate, the last half a gap before its end
    q = (np.arange(n) + 0.5) / n
    gaps = _rng(0, 2).permutation(-np.log1p(-q))
    due = np.cumsum(gaps)
    due = due / due[-1] * seconds * (n - 0.5) / n
    return due.astype(np.float64), sizes.astype(np.int64)


def sample(n: int, k: int, seed: int, must: list[int]) -> list[int]:
    """``k`` of ``range(n)`` drawn from the seed, plus every index in
    ``must``."""
    pick = set(_rng(seed, 3).permutation(n)[:k].tolist())
    return sorted(pick | set(must))


def offered_images_per_s(traffic: dict) -> float:
    lo, hi = traffic["images_min"], traffic["images_max"]
    return traffic["rate_per_s"] * (lo + hi) / 2
