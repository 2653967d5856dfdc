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


def test_an_ack_before_the_batch_is_logged_is_caught(layout, monkeypatch):
    """The engine acks with the WAL detached: each batch is logged only
    when it is applied, after its ack, in a later record."""
    from repro.serve.lifecycle import ServeEngine

    submit = ServeEngine.submit_ingest

    def unlogged(self, vectors, attrs):
        wal, self.index._wal = self.index._wal, None
        try:
            return submit(self, vectors, attrs)
        finally:
            self.index._wal = wal

    monkeypatch.setattr(ServeEngine, "submit_ingest", unlogged)
    res = _run(layout, "tiny.ingest-append")
    assert not res["correct"]
    assert res["checks"]["lost_acked"]["value"] == 8 * 8
    assert res["checks"]["unread_acked"]["value"] == 0


def test_an_acked_batch_never_applied_is_caught(layout, monkeypatch):
    """The engine acks each batch of the window, durable in the WAL, and
    drops it from its apply queue."""
    from repro.serve.lifecycle import ServeEngine

    submit = ServeEngine.submit_ingest

    def dropped(self, vectors, attrs):
        res = submit(self, vectors, attrs)
        if self.stats.ingest_batches > 2:  # past the set-up batches
            self._ingest_q.pop()
        return res

    monkeypatch.setattr(ServeEngine, "submit_ingest", dropped)
    res = _run(layout, "tiny.ingest-append")
    assert not res["correct"]
    assert res["checks"]["unread_acked"]["value"] == 8
    assert res["checks"]["lost_acked"]["value"] == 0
    assert res["failed"] == 8


def test_an_ack_before_the_fsync_is_caught(layout, monkeypatch):
    """The engine's group-commit barrier does nothing: every batch is in
    the log by the end, but none was durable when it was acked."""
    from bench import faults

    faults.plant("no-sync", monkeypatch.setattr)
    res = _run(layout, "tiny.ingest-append")
    assert not res["correct"]
    checks = res["checks"]
    assert checks["unsynced_acked"]["value"] == 8
    for name in ("lost_acked", "unread_acked"):
        assert checks[name]["value"] == 0


def test_rows_inserted_without_edges_are_caught(layout, monkeypatch):
    """Each apply stores its rows and attributes and leaves them without
    edges: the one-value probes find every row by its attribute, and only
    the searches through the new rows lose their neighbours."""
    from bench import corpus, faults
    from bench.spec import Cell

    cell = Cell.load(layout, "tiny.ingest-append")
    corpus.cached_build(cell.config, layout.cache)  # the corpus stays sound
    faults.plant("no-edges", monkeypatch.setattr)
    res = _run(layout, "tiny.ingest-append")
    assert not res["correct"]
    checks = res["checks"]
    limit = checks["append_recall_loss"]["limit"]
    assert checks["append_recall_loss"]["value"] > 3 * limit
    for name in ("unread_acked", "lost_acked", "unsynced_acked",
                 "out_of_range", "missing"):
        assert checks[name]["value"] == 0
    assert checks["recall_loss"]["value"] <= checks["recall_loss"]["limit"]
