"""The plain reference against the program's oracle, and the controls
against the compared numbers."""
import numpy as np
import pytest

from bench import check, reference, traffic

MIX = {"arrivals": "poisson", "fractions_log2": [-6, 0], "noise": 0.25}


def _setup(n=1500, d=16, nq=60):
    from bench import corpus

    c = {"n": n, "d": d, "data_seed": 1, "clusters": 32, "attrs": "random"}
    v, a = corpus.corpus_vectors(c), corpus.corpus_attrs(c)
    s = traffic.query_schedule(MIX, v, a, rate=nq, seconds=1, seed=9)
    return v, a, s


def test_topk_equals_the_oracle_brute_force():
    from repro.core.oracle import brute_force

    v, a, s = _setup()
    ids, dists = reference.topk(v, a, s.queries, s.ranges, k=10)
    for i, (q, r) in enumerate(zip(s.queries, s.ranges)):
        gold = brute_force(v, a, q, tuple(r), 10)
        np.testing.assert_array_equal(ids[i][: len(gold)], gold)
    exact = reference.exact_dists(v, s.queries, ids)
    np.testing.assert_allclose(dists, exact, rtol=1e-4)


def test_int8_rows_equal_the_program_slab():
    from repro.core.store import quantize_rows

    v, _, _ = _setup()
    slab, scales = quantize_rows(v, "int8")
    np.testing.assert_array_equal(reference.stored_rows(v, "int8"),
                                  slab.astype(np.float32) * scales[:, None])


def test_reference_passes_its_own_numbers():
    v, a, s = _setup()
    ids, dists = reference.topk(v, a, s.queries, s.ranges, k=10)
    nums = check.answer_numbers(ids, dists, s.ranges, a, v, s.queries)
    assert nums["out_of_range"] == nums["unsorted"] == nums["duplicate_ids"] == 0
    assert nums["dist_gap"] < 1e-4


def _int8_reference():
    import json

    from bench.spec import BENCH

    return json.loads((BENCH / "configs" / "sift128-int8.json").read_text())[
        "reference"]


def test_int4_control_fails_the_int8_dist_gap():
    """The int8 configuration's storage control: the reference over int4
    rows in the program's place reads a distance gap far above its
    limit."""
    ref = _int8_reference()
    assert {"vec_dtype": "int4", "precision": "highest"} in ref["controls"]
    v, a, s = _setup()
    rows8 = reference.stored_rows(v, "int8")
    ids, dists = reference.topk(reference.stored_rows(v, "int4"), a,
                                s.queries, s.ranges, k=10)
    nums = check.answer_numbers(ids, dists, s.ranges, a, rows8, s.queries)
    assert nums["dist_gap"] > 3 * ref["limits"]["dist_gap"]


def test_int8_controls_cover_storage_and_compute_precision():
    """int8 at HIGHEST has two steps below it: int4 rows, and the int8
    rows at ``Precision.HIGH`` (whose gap only the chip shows)."""
    ctrls = _int8_reference()["controls"]
    assert {(c["vec_dtype"], c["precision"]) for c in ctrls} == {
        ("int4", "highest"), ("int8", "high")}


def test_recall_loss_is_one_less_the_mean_recall():
    rec = np.array([1.0, 0.9, 0.5, 0.7])
    assert check.recall_numbers(rec)["recall_loss"] == pytest.approx(0.225)
    assert check.recall_numbers(rec[:0]) == {"recall_loss": 1.0}


def test_a_hop_loop_that_stops_early_fails_recall():
    """Answers that are exact but not the nearest: every number but the
    recall ones passes."""
    v, a, s = _setup()
    gold, _ = reference.topk(v, a, s.queries, s.ranges, k=10)
    far, far_d = reference.topk(v, a, -s.queries, s.ranges, k=10)
    exact = reference.exact_dists(v, s.queries, far)
    order = np.argsort(np.where(far >= 0, exact, np.inf), axis=1)
    ids = np.take_along_axis(far, order, axis=1)
    dists = np.take_along_axis(exact, order, axis=1).astype(np.float32)
    dists[ids < 0] = np.inf
    nums = check.answer_numbers(ids, dists, s.ranges, a, v, s.queries)
    nums.update(check.recall_numbers(check.recall(ids, gold)))
    assert nums["out_of_range"] == nums["unsorted"] == nums["duplicate_ids"] == 0
    assert nums["dist_gap"] < 1e-4
    ok, table = check.judge(nums, {"out_of_range": 0, "unsorted": 0,
                                   "duplicate_ids": 0, "dist_gap": 1e-4,
                                   "recall_loss": 0.05})
    assert not ok and table["recall_loss"]["value"] > 0.05


@pytest.mark.parametrize("fault", ["id", "range", "order", "duplicate"])
def test_altered_answers_fail(fault):
    v, a, s = _setup()
    ids, dists = reference.topk(v, a, s.queries, s.ranges, k=10)
    ids, dists = ids.copy(), dists.copy()
    if fault == "id":  # an id changed after its distance was taken
        ids[5, 0], ids[5, 1] = ids[5, 1], ids[5, 0]
    elif fault == "range":  # a row outside the range
        ids[5, 9] = int(np.argmax(a))
    elif fault == "order":
        dists[5, [0, 1]] = dists[5, [1, 0]]
        ids[5, [0, 1]] = ids[5, [1, 0]]
    else:
        ids[5, 3] = ids[5, 2]
        dists[5, 3] = dists[5, 2]
    nums = check.answer_numbers(ids, dists, s.ranges, a, v, s.queries)
    limits = {"out_of_range": 0, "unsorted": 0, "duplicate_ids": 0,
              "dist_gap": 1e-4}
    ok, _ = check.judge(nums, limits)
    assert not ok


@pytest.mark.parametrize("precision", ["high", "highest"])
def test_control_block_runs_at_its_stated_precision(precision):
    """The float32 configuration's control is the reference at
    ``Precision.HIGH`` (three bf16 passes); on the CPU both precisions
    compute alike, so this checks what the chip is asked to do."""
    import jax.numpy as jnp

    fn = reference._block_fn(10, precision)
    x = jnp.zeros((64, 16), jnp.float32)
    text = fn.lower(x, jnp.zeros(64), jnp.zeros(64), jnp.zeros((8, 16)),
                    jnp.zeros(8), jnp.zeros(8)).as_text()
    p = precision.upper()
    assert f"precision = [{p}, {p}]" in text
