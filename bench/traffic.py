"""The one traffic generator: a traffic mix's parameters and a seed give a
cell's open-loop schedule of queries.

Every seed gets the same amount of work: ``round(rate * seconds)`` queries,
the in-range fractions in equal shares.  The seed draws only their order,
their arrival times (a Poisson process conditioned on that count: sorted
uniform times), their base rows, noise and range starts.  The corpus itself comes from the configuration's ``data_seed``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# independent generator streams drawn from one seed
ARRIVALS, QUERIES = range(2)


def stream(seed: int, which: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return np.random.default_rng([seed, which])


@dataclass
class Schedule:
    due: np.ndarray  # f64[N] seconds after the window opens, ascending
    queries: np.ndarray  # f32[N, d]
    ranges: np.ndarray  # f64[N, 2] inclusive attribute bounds
    fractions: np.ndarray  # f64[N] in-range fraction of each query


def in_range_fractions(mix: dict) -> np.ndarray:
    lo, hi = mix["fractions_log2"]
    return 2.0 ** np.arange(lo, hi + 1, dtype=np.float64)


def query_schedule(mix: dict, vectors: np.ndarray, attrs: np.ndarray,
                   rate: float, seconds: float, seed: int) -> Schedule:
    """Open-loop queries over the corpus ``vectors``/``attrs``: each a
    corpus row plus Gaussian noise, with a range holding exactly
    ``floor(n * f)`` rows whose start is uniform over the attribute order."""
    if mix["arrivals"] != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    count = int(round(rate * seconds))
    due = np.sort(stream(seed, ARRIVALS).uniform(0.0, seconds, count))
    rng = stream(seed, QUERIES)
    fr = in_range_fractions(mix)
    fractions = rng.permutation(np.resize(fr, count))
    n = len(vectors)
    base = vectors[rng.integers(0, n, size=count)]
    noise = rng.normal(scale=mix["noise"], size=base.shape)
    queries = (base + noise).astype(np.float32)
    order = np.sort(attrs)
    n_in = np.maximum(1, np.floor(n * fractions).astype(np.int64))
    start = (rng.uniform(size=count) * (n - n_in + 1)).astype(np.int64)
    ranges = np.stack([order[start], order[start + n_in - 1]], axis=1)
    return Schedule(due=due, queries=queries, ranges=ranges,
                    fractions=fractions)

