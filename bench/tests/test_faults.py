"""The run, with the timed path broken underneath it, comes out not
correct: once for each fault a cell on one chip can have."""
import time


def _run(layout, cell, seconds=2.0):
    from bench.harness import run_cell

    dev = {"platform": "cpu", "kind": "cpu", "count": 1}
    return run_cell(layout, cell, 5, seconds, False, time.monotonic(), dev)


def test_an_altered_answer_is_caught(layout, monkeypatch):
    from repro.serve.lifecycle import ServeEngine

    reply = ServeEngine._reply

    def altered(self, req, ids, dists, **kw):
        ids = ids.copy()
        ids[[0, 1]] = ids[[1, 0]]  # an answer altered where it is produced
        return reply(self, req, ids, dists, **kw)

    monkeypatch.setattr(ServeEngine, "_reply", altered)
    res = _run(layout, "tiny.query-narrow")
    assert not res["correct"]
    assert res["checks"]["dist_gap"]["value"] > res["checks"]["dist_gap"]["limit"]


def test_half_of_a_wave_left_out_is_caught(layout, monkeypatch):
    from repro.serve.lifecycle import ServeEngine

    run_chunk = ServeEngine._run_chunk

    def halved(self):
        return run_chunk(self)[::2]

    monkeypatch.setattr(ServeEngine, "_run_chunk", halved)
    res = _run(layout, "tiny.query-narrow")
    assert not res["correct"]
    assert res["checks"]["missing"]["value"] > 0


def test_a_hop_loop_that_stops_early_is_caught(layout, monkeypatch):
    """Answers stay sorted, unique, in range and exactly measured; only
    the comparison with the reference's neighbours sees the loss."""
    import dataclasses

    from bench import harness

    sound = harness.engine_config
    monkeypatch.setattr(harness, "engine_config", lambda cfg: dataclasses.replace(
        sound(cfg), max_hops=2))
    res = _run(layout, "tiny.query-narrow")
    assert not res["correct"]
    checks = res["checks"]
    for name in ("out_of_range", "unsorted", "duplicate_ids", "missing"):
        assert checks[name]["value"] == 0
    assert checks["dist_gap"]["value"] <= checks["dist_gap"]["limit"]
    assert checks["recall_loss"]["value"] > checks["recall_loss"]["limit"]
