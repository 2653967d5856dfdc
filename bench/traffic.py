"""The one traffic generator: a traffic mix's parameters and a seed give a
cell's open-loop schedule of queries and, where the mix has an ``ingest``
block, of ingest batches.

Every seed gets the same amount of work: ``round(rate * seconds)`` queries,
the in-range fractions in equal shares, and ``round(batch rate * seconds)``
ingest batches of ``batch_rows`` rows.  The seed draws only their order,
their arrival times (a Poisson process conditioned on that count: sorted
uniform times), their base rows, noise and range starts, and the ingested
rows.  Periodic ingest (a client that flushes on a timer) arrives at the
same times for every seed.  The corpus itself comes from the
configuration's ``data_seed``.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .corpus import cluster_centers, draw_rows

# independent generator streams drawn from one seed
ARRIVALS, QUERIES, INGEST, PROBES = range(4)


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


@dataclass
class IngestSchedule:
    due: np.ndarray  # f64[B] seconds after the window opens, ascending
    vectors: np.ndarray  # f32[B, rows, d]
    attrs: np.ndarray  # f64[B, rows], ascending in arrival order


def ingest_schedule(mix: dict, corpus: dict, rate: float, seconds: float,
                    seed: int, first_attr: float) -> IngestSchedule:
    """Open-loop appends: ``round(rate * seconds)`` batches of
    ``mix["batch_rows"]`` rows drawn from the corpus's own clusters, with
    attributes ``first_attr, first_attr + 1, ...`` in arrival order, as
    timestamps are.  Arrivals are ``"periodic"`` (one batch every
    ``seconds / count``, the first at the open) or ``"poisson"``.  What
    the seed draws comes from its ``INGEST`` stream alone, so the query
    streams stay as they were."""
    if mix["attrs"] != "ascending":
        raise ValueError(f"unknown ingest attribute order {mix['attrs']!r}")
    count = int(round(rate * seconds))
    rows = int(mix["batch_rows"])
    rng = stream(seed, INGEST)
    if mix["arrivals"] == "periodic":
        due = np.arange(count) * (seconds / max(count, 1))
    elif mix["arrivals"] == "poisson":
        due = np.sort(rng.uniform(0.0, seconds, count))
    else:
        raise ValueError(f"unknown arrival process {mix['arrivals']!r}")
    _, centers = cluster_centers(corpus)
    vectors = draw_rows(rng, centers, count * rows)
    attrs = first_attr + np.arange(count * rows, dtype=np.float64)
    return IngestSchedule(due=due,
                          vectors=vectors.reshape(count, rows, -1),
                          attrs=attrs.reshape(count, rows))


def append_probes(mix: dict, vectors: np.ndarray, attrs: np.ndarray,
                  appended: int, count: int, seed: int) -> Schedule:
    """Queries of ``mix`` over an index whose last ``appended`` rows of
    ``vectors``/``attrs`` were appended with attributes above all others:
    each a row of the index plus Gaussian noise, with a range holding
    exactly ``floor(N * f)`` of its ``N`` rows, whose start is uniform over
    the starts from which the range reaches into the appended rows.  Most
    narrow ranges lie among the appended rows alone; the others, and every
    range wider than the appended rows, cross from the older rows into
    them.  Drawn from the seed's ``PROBES`` stream; no arrival
    times."""
    rng = stream(seed, PROBES)
    fractions = rng.permutation(np.resize(in_range_fractions(mix), count))
    N = len(vectors)
    base = vectors[rng.integers(0, N, size=count)]
    noise = rng.normal(scale=mix["noise"], size=base.shape)
    queries = (base + noise).astype(np.float32)
    order = np.sort(attrs)
    n_in = np.maximum(1, np.floor(N * fractions).astype(np.int64))
    lo = np.maximum(0, N - appended - n_in + 1)
    start = lo + (rng.uniform(size=count) * (N - n_in - lo + 1)).astype(
        np.int64)
    ranges = np.stack([order[start], order[start + n_in - 1]], axis=1)
    return Schedule(due=np.zeros(count), queries=queries, ranges=ranges,
                    fractions=fractions)
