"""What decides ``correct``: numbers compared with the plain reference,
each beside its limit.

A reply is judged by what it says:

* ``missing`` - admitted queries with no reply by the end of the drain;
* ``out_of_range`` - returned ids that are not corpus rows inside the
  query's range (the configuration guarantees exact range filtering);
* ``unsorted`` / ``duplicate_ids`` - replies whose distances descend
  somewhere, or that repeat an id;
* ``dist_gap`` - the widest relative gap between a returned distance and
  the exact float64 distance of the same id to the row the slab stores.
  A kernel computing in a lower precision, or an id altered after its
  distance was taken, shows here;
* ``recall_loss`` - one less the mean recall@k of the replies against
  the reference's k nearest rows in range.  An approximate index loses a
  few neighbours by design; a hop loop that stops early, drops candidates
  or skips nodes loses many, while its answers stay sorted, unique, in
  range and exactly measured.

The limits live in the configuration file, under ``reference.limits``.
"""
from __future__ import annotations

import sys

import numpy as np

from . import reference


def reply_arrays(window, k: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """-> (replied bool[N], ids i64[N, k], dists f32[N, k]) in schedule
    order; ids are -1 and dists +inf where there was no reply."""
    N = len(window.rid)
    replied = np.zeros(N, bool)
    ids = np.full((N, k), -1, np.int64)
    dists = np.full((N, k), np.inf, np.float32)
    for i, rid in enumerate(window.rid):
        rep = window.replies.get(int(rid)) if rid >= 0 else None
        if rep is None:
            continue
        replied[i] = True
        m = min(k, len(rep.ids))
        ids[i, :m] = rep.ids[:m]
        dists[i, :m] = rep.dists[:m]
    return replied, ids, dists


def answer_numbers(ids: np.ndarray, dists: np.ndarray, ranges: np.ndarray,
                   attrs: np.ndarray, rows: np.ndarray,
                   queries: np.ndarray) -> dict:
    """The numbers that judge a set of answers against the corpus
    (``attrs``, and ``rows`` as the slab stores them)."""
    n = len(attrs)
    valid = ids >= 0
    known = valid & (ids < n)
    a = attrs[np.where(known, ids, 0)]
    inside = known & (a >= ranges[:, :1]) & (a <= ranges[:, 1:])
    exact = reference.exact_dists(rows, queries, ids)
    gap = np.abs(dists.astype(np.float64) - exact) / np.maximum(exact, 1e-30)
    gap = np.where(inside, gap, 0.0)
    fin = np.where(valid, dists, np.inf).astype(np.float64)
    with np.errstate(invalid="ignore"):  # inf - inf past the last id
        step = np.diff(fin, axis=1)
    unsorted = np.any(np.nan_to_num(step, nan=0.0, posinf=0.0) < 0, axis=1)
    srt = np.sort(np.where(valid, ids, -1 - np.arange(ids.shape[1])), axis=1)
    dup = np.any(srt[:, 1:] == srt[:, :-1], axis=1)
    return {
        "out_of_range": int(np.sum(valid & ~inside)),
        "unsorted": int(np.sum(unsorted)),
        "duplicate_ids": int(np.sum(dup)),
        "dist_gap": float(np.max(gap)) if gap.size else 0.0,
    }


def recall(ids: np.ndarray, gold: np.ndarray) -> np.ndarray:
    """Recall@k of each row of ``ids`` against ``gold`` (-1 padded)."""
    out = np.empty(len(ids))
    for i, (f, g) in enumerate(zip(ids, gold)):
        g = g[g >= 0]
        out[i] = (len(np.intersect1d(f[f >= 0], g)) / len(g)) if len(g) else 1.0
    return out


def recall_numbers(rec: np.ndarray) -> dict:
    """``recall_loss`` from the recall@k of each replied query."""
    return {"recall_loss": float(1.0 - rec.mean()) if rec.size else 1.0}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """-> (correct, {name: {"value", "limit"}}).  A number passes when it
    is at most its limit; one that has no limit in the file fails."""
    table = {name: {"value": value, "limit": limits.get(name)}
             for name, value in numbers.items()}
    ok = all(row["limit"] is not None and row["value"] <= row["limit"]
             for row in table.values())
    return ok, table


def print_table(table: dict) -> None:
    """Each compared number beside its limit, as the last lines on
    standard error."""
    for name, row in table.items():
        print(f"check {name}: {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr, flush=True)
