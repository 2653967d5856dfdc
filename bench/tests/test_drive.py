"""The open loop times each query from its due time, not its submission."""
import types

import numpy as np

from bench import drive, traffic


class _Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class _Ticket:
    def __init__(self, rid):
        self.rid = rid


class _Reply:
    def __init__(self, rid, t):
        self.rid, self.finish_t = rid, t
        self.ids, self.dists, self.dc = np.arange(10), np.zeros(10), 1


class _SlowEngine:
    """Each step takes 0.2 s of the clock and answers what was queued."""

    def __init__(self, clock):
        self.clock, self.q, self.rid = clock, [], 0

    @property
    def idle(self):
        return not self.q

    def submit(self, query, rng):
        self.rid += 1
        self.q.append(self.rid)
        return _Ticket(self.rid)

    def step(self):
        self.clock.t += 0.2
        out = [_Reply(r, self.clock.t) for r in self.q]
        self.q = []
        return out


def test_latency_runs_from_the_due_time(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(drive.time, "sleep", lambda s: setattr(
        clock, "t", clock.t + max(s, 1e-3)))
    sched = traffic.Schedule(due=np.array([0.0, 0.05, 0.1, 0.5]),
                             queries=np.zeros((4, 2), np.float32),
                             ranges=np.zeros((4, 2)), fractions=np.ones(4))
    eng = _SlowEngine(clock)
    w = drive.run_window(eng, sched, seconds=1.0, clock=clock)
    replied, lat = w.latency_ms(sched.due)
    assert replied.all()
    # queries 1 and 2 fall due while the first step runs: submitted late,
    # and the lateness counts in their latency
    assert w.submit_t[1] - (w.t_open + 0.05) > 0.1
    finish = np.array([w.replies[r].finish_t for r in w.rid])
    np.testing.assert_allclose(lat, (finish - w.t_open - sched.due) * 1e3)
    assert lat[1] > (finish[1] - w.submit_t[1]) * 1e3


def test_a_lost_reply_counts_beyond_every_reply():
    w = drive.Window(t_open=0.0, t_close=1.0, t_drained=1.5,
                     rid=np.array([1, -1, 2]), submit_t=np.zeros(3))
    w.replies = {1: _Reply(1, 0.4)}
    replied, lat = w.latency_ms(np.array([0.1, 0.2, 0.3]))
    np.testing.assert_array_equal(replied, [True, False, False])
    assert abs(lat[0] - 300.0) < 1e-9 and min(lat[1:]) > 1.5e3


class _Ack:
    def __init__(self, rows, lsn):
        self.accepted, self.rejected, self.lsn = rows, [], lsn


class _IngestEngine(_SlowEngine):
    """Acks each batch at once with the next LSN; each step applies one."""

    def __init__(self, clock):
        super().__init__(clock)
        self.logged = 0
        self.index = types.SimpleNamespace(_applied_lsn=0)

    @property
    def idle(self):
        return not self.q and self.index._applied_lsn == self.logged

    def submit_ingest(self, vectors, attrs):
        self.logged += 1
        return _Ack(len(attrs), self.logged)

    def step(self):
        self.index._applied_lsn = min(self.index._applied_lsn + 1,
                                      self.logged)
        return super().step()


def test_an_ingest_batch_is_visible_once_its_lsn_is_applied(monkeypatch):
    clock = _Clock()
    monkeypatch.setattr(drive.time, "sleep", lambda s: setattr(
        clock, "t", clock.t + max(s, 1e-3)))
    sched = traffic.Schedule(due=np.array([0.9]),
                             queries=np.zeros((1, 2), np.float32),
                             ranges=np.zeros((1, 2)), fractions=np.ones(1))
    feed = traffic.IngestSchedule(due=np.array([0.0, 0.0, 0.5]),
                                  vectors=np.zeros((3, 2, 2), np.float32),
                                  attrs=np.zeros((3, 2)))
    w = drive.run_window(_IngestEngine(clock), sched, seconds=1.0,
                         clock=clock, ingest=feed)
    log = w.ingest
    assert log.acked.all() and list(log.lsn) == [1, 2, 3]
    # two batches due at once: the second is applied a step after the first
    t = log.visible_t - w.t_open
    np.testing.assert_allclose(t[:2], [0.2, 0.4])
    assert t[2] > 0.5 and w.t_drained >= log.visible_t.max()
    np.testing.assert_allclose(w.visible_ms(feed.due), (t - feed.due) * 1e3)
