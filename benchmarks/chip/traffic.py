"""Utterance lengths from a mix's data file and the seed (numpy only).

Every seed gets the same multiset of lengths: the mix's lognormal
quantiles at (i + 0.5) / n, clipped to its range.  The seed orders them,
and makes every utterance's audio and the model's weights."""
from __future__ import annotations

import numpy as np
from statistics import NormalDist


def lengths(mix: dict, n: int) -> np.ndarray:
    """`n` utterance lengths (s): lognormal quantiles of the mix's
    median and sigma, clipped to [min_s, max_s]."""
    d = mix["duration"]
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = d["median_s"] * np.exp(d["sigma"] * z)
    return np.clip(x, d["min_s"], d["max_s"])


def bulk_lengths(mix: dict, seed: int) -> list:
    """The mix's fixed set of `files` utterance lengths, in the seed's
    order; clients take them in turn and start over at the end."""
    n = mix["files"]
    rng = np.random.default_rng([int(seed) % 2 ** 63, 2])
    return [float(x) for x in lengths(mix, n)[rng.permutation(n)]]


def describe(lens) -> dict:
    """Quartiles, 95th percentile and extremes of drawn lengths."""
    a = np.sort(np.asarray(lens, float))
    return {"n": int(a.size), "min": float(a[0]),
            "p25": float(np.percentile(a, 25)),
            "median": float(np.percentile(a, 50)),
            "p75": float(np.percentile(a, 75)),
            "p95": float(np.percentile(a, 95)), "max": float(a[-1]),
            "mean": float(a.mean())}
