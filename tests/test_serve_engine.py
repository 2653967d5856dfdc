"""Request-lifecycle invariants of the serve engine
(``repro.serve.lifecycle``): admission stays bounded and rejects with
retry-after, wave scheduling is bitwise a one-shot ``search_batch``,
deadlines degrade (never time out), overload sheds without congestion
collapse, and WAL-backed ingest loses zero acked micro-batches across
in-process crashes, dropped fsyncs and a real SIGKILL with the whole
ingest queue pending.  Engine-level faults are injected with
``EngineFaultPlan`` against a virtual clock, byte-level faults with
``FaultIO``.
"""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core import WoWIndex, make_workload
from repro.core.device_search import (
    chunk_schedule_from_hist,
    hist_percentile,
    search_batch,
)
from repro.core.snapshot import take_snapshot
from repro.persist import (
    CrashError,
    EngineFaultPlan,
    FaultIO,
    open_durable,
    recover,
    state_digest,
)
from repro.serve.lifecycle import (
    EngineConfig,
    Rejected,
    ServeEngine,
    Ticket,
    validate_rows,
)

KW = dict(m=8, ef_construction=32, o=4, seed=0)
# uniform search knobs across the module so every test shares the jit cache
SEARCH = dict(k=5, width=32, visited="bitmap", adaptive=False, chunk=(4, 8))


class VClock:
    """Deterministic virtual clock; ``advance`` doubles as the fault
    plan's ``sleep`` so injected slow waves become pure clock jumps."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t

    def advance(self, s: float) -> None:
        self.t += s


@pytest.fixture(scope="module")
def wl():
    return make_workload(n=500, d=12, nq=40, seed=0, k=5)


@pytest.fixture(scope="module")
def idx(wl):
    ix = WoWIndex(dim=12, **KW)
    ix.insert_batch(wl.vectors, wl.attrs, batch_size=128, backend="numpy")
    return ix


def _engine(idx, **over):
    kw = dict(SEARCH)
    kw.update(over)
    return ServeEngine(index=idx, config=EngineConfig(**kw))


# ------------------------------------------------------------ parity & waves
def test_engine_bitwise_matches_search_batch(wl, idx):
    """Interleaved multi-wave scheduling returns bitwise the ids AND
    distances of a one-shot ``search_batch`` over the same snapshot —
    wave grouping, cross-request compaction and round-robin chunking
    cannot change any answer (per-query trajectories are row-independent
    and iteration-indexed)."""
    snap = take_snapshot(idx)
    ref = search_batch(snap, wl.queries, wl.ranges, k=5, width=32,
                       visited="bitmap")
    eng = ServeEngine(index=idx, config=EngineConfig(**SEARCH, max_wave=16))
    # drip the submissions so several waves are in flight at once: 16 in,
    # then one new request per scheduler step while old waves still run
    tickets, got = [], []
    for i in range(16):
        tickets.append(eng.submit(wl.queries[i], wl.ranges[i]))
    for i in range(16, len(wl.queries)):
        got.extend(eng.step())
        tickets.append(eng.submit(wl.queries[i], wl.ranges[i]))
    got.extend(eng.drain())
    replies = {r.rid: r for r in got}
    assert len(replies) == len(wl.queries)
    for i, t in enumerate(tickets):
        r = replies[t.rid]
        assert not r.degraded and r.reason is None
        assert np.array_equal(r.ids, ref.ids[i])
        assert np.array_equal(r.dists, ref.dists[i])
    s = eng.stats
    assert s.waves >= 3  # the drip actually produced interleaved waves


def test_warmup_precompiles_without_touching_state(wl, idx):
    """``warmup()`` drives every wave/compaction bucket shape through the
    jit caches (so production traffic never blocks on a lazy mid-run XLA
    compile) while leaving the scheduler bitwise untouched: no stats, no
    histogram, no queued or in-flight work — and serving afterwards still
    matches the one-shot ``search_batch`` exactly."""
    eng = _engine(idx, max_wave=16)
    dt = eng.warmup()
    assert dt >= 0.0
    assert eng.idle and eng.in_flight == 0 and eng.queue_len == 0
    s = eng.stats
    assert (s.submitted, s.waves, s.chunks, s.served) == (0, 0, 0, 0)
    assert eng.hop_histogram() is None
    snap = take_snapshot(idx)
    ref = search_batch(snap, wl.queries[:12], wl.ranges[:12], k=5,
                       width=32, visited="bitmap")
    for i in range(12):
        eng.submit(wl.queries[i], wl.ranges[i])
    got = sorted(eng.drain(), key=lambda r: r.rid)
    assert len(got) == 12
    for i, r in enumerate(got):
        assert not r.degraded
        assert np.array_equal(r.ids, ref.ids[i])
        assert np.array_equal(r.dists, ref.dists[i])


def test_engine_serves_from_bare_snapshot(wl, idx):
    """A snapshot-only engine (serve-from-checkpoint cold start) answers
    queries; ingest cleanly refuses instead of crashing."""
    eng = ServeEngine(snapshot=take_snapshot(idx),
                      config=EngineConfig(**SEARCH))
    t = eng.submit(wl.queries[0], wl.ranges[0])
    (r,) = eng.drain()
    assert r.rid == t.rid and not r.degraded
    with pytest.raises(RuntimeError, match="ingest needs a live index"):
        eng.submit_ingest(wl.vectors[:2], wl.attrs[:2])


# -------------------------------------------------- admission & backpressure
def test_queue_bound_and_retry_after(wl, idx):
    """The admission queue NEVER exceeds its configured bound: submits
    past ``queue_cap`` are rejected with a positive retry-after hint, and
    the admitted requests are all eventually served."""
    eng = _engine(idx, max_wave=8, queue_cap=8)
    out = [eng.submit(wl.queries[i % len(wl.queries)], (0.0, 1.0))
           for i in range(20)]
    admitted = [o for o in out if isinstance(o, Ticket)]
    rejected = [o for o in out if isinstance(o, Rejected)]
    assert len(admitted) == 8 and len(rejected) == 12
    assert eng.queue_len == 8 and eng.stats.queue_peak == 8
    assert all(r.retry_after > 0 for r in rejected)
    assert all(r.queue_len == 8 for r in rejected)
    replies = eng.drain()
    assert len(replies) == 8
    assert {r.rid for r in replies} == {t.rid for t in admitted}
    s = eng.stats
    assert s.submitted == 20 and s.admitted == 8 and s.rejected == 12
    assert s.served == 8


def _stalled_open_loop(wl, idx, n=200, rate=400.0, step_s=1e-3,
                       stall_at=0.1, stall_s=0.1):
    """Open-loop arrivals on a virtual clock, driven as one thread drives
    the engine: before each step every query due by now is submitted,
    and each step costs ``step_s``.  One chunk, the first after
    ``stall_at``, stalls for ``stall_s`` (an ``EngineFaultPlan`` slow
    chunk), so the arrivals of the stall land at once.  Returns the
    engine, its replies by rid, and one ``(queue_len, waves in flight,
    wave size)`` per assembled wave."""
    clk = VClock()
    eng = ServeEngine(index=idx, now=clk, config=EngineConfig(
        **SEARCH, max_wave=8, max_slots=32, queue_cap=64))
    plan = EngineFaultPlan(slow_chunk_every=1, slow_chunk_s=stall_s,
                           sleep=clk.advance)
    waves = []
    assemble = eng._assemble_wave

    def spy(free):
        q0, w0 = eng.queue_len, len(eng._waves)
        assemble(free)
        waves.append((q0, w0, q0 - eng.queue_len))

    eng._assemble_wave = spy
    due = np.arange(n) / rate
    i, replies, stalled = 0, {}, False
    while i < n or not eng.idle:
        while i < n and due[i] <= clk():
            eng.submit(wl.queries[i % len(wl.queries)],
                       wl.ranges[i % len(wl.ranges)])
            i += 1
        if eng.idle:
            clk.t = due[i]
            continue
        eng.fault_plan = plan if not stalled and clk() >= stall_at else None
        stalled = stalled or eng.fault_plan is not None
        replies.update((r.rid, r) for r in eng.step())
        clk.advance(step_s)
    assert stalled
    return eng, replies, waves


def test_backlog_after_stall_served_at_full_width(wl, idx):
    """A stall that pushes the queue past ``queue_cap/2`` is served by
    waves of exactly ``max_wave`` while the backlog holds (the engine
    never narrows a wave to shed load), and ``backlog_waves`` counts the
    waves assembled while others were in flight."""
    eng, _, waves = _stalled_open_loop(wl, idx)
    s = eng.stats
    assert s.queue_peak > eng.config.queue_cap // 2
    backlog = [size for q0, _, size in waves if q0 >= eng.config.max_wave]
    assert len(backlog) >= 4
    assert all(size == eng.config.max_wave for size in backlog)
    assert s.backlog_waves == sum(1 for _, w0, _ in waves if w0 > 0)
    assert s.backlog_waves >= len(backlog) - 1
    assert s.summary()["backlog_waves"] == s.backlog_waves


def test_backlog_clears_then_waves_return_to_normal_policy(wl, idx):
    """Once the stall's backlog has cleared, waves go back to the normal
    policy (an idle engine takes whatever is queued, so waves are
    partial again); nothing is rejected below ``queue_cap``, every
    admitted request is served, and the answers are those of a one-shot
    ``search_batch``."""
    eng, replies, waves = _stalled_open_loop(wl, idx)
    s = eng.stats
    assert s.queue_peak < eng.config.queue_cap
    assert s.rejected == 0 and s.served == s.admitted == 200
    last_full = max(j for j, (_, _, size) in enumerate(waves)
                    if size == eng.config.max_wave)
    after = waves[last_full + 1:]
    assert after and all(q0 < eng.config.max_wave for q0, _, _ in after)
    assert any(w0 == 0 and size < eng.config.max_wave
               for _, w0, size in after)
    ref = search_batch(take_snapshot(idx), wl.queries, wl.ranges, k=5,
                       width=32, visited="bitmap")
    for rid, r in replies.items():
        q = rid % len(wl.queries)
        assert not r.degraded
        assert np.array_equal(r.ids, ref.ids[q])
        assert np.array_equal(r.dists, ref.dists[q])


def test_overload_no_congestion_collapse(wl, idx):
    """Closed-loop flood at ~4x the admissible load: steady-state
    throughput of the served requests stays within 10% of the
    non-overloaded rate — rejection is cheap and the scheduler keeps
    doing the same per-wave work, so QPS must not collapse."""
    eng = _engine(idx, max_wave=16, queue_cap=32)
    q, r = wl.queries, wl.ranges

    def flood(n_submit):
        for i in range(n_submit):
            eng.submit(q[i % len(q)], r[i % len(r)])
        t0 = time.perf_counter()
        served = len(eng.drain())
        return served / (time.perf_counter() - t0)

    flood(32)  # warm the jit cache for every wave/compaction shape
    base = max(flood(32) for _ in range(3))  # fills the queue exactly
    over = max(flood(128) for _ in range(3))  # 4x offered, 96 rejected
    assert over >= 0.9 * base, f"congestion collapse: {over:.1f} vs {base:.1f} QPS"
    assert eng.stats.queue_peak <= 32


def test_retry_after_cold_start_bounded_positive(wl, idx):
    """Regression: the very first rejections — before any chunk has run,
    so the service-rate EWMA is still 0 — must carry a bounded positive
    retry-after hint, never 0/inf/NaN (a 0 hint is an immediate-retry
    stampede; inf/NaN parks clients forever)."""
    eng = _engine(idx, max_wave=4, queue_cap=2)
    out = [eng.submit(wl.queries[i], wl.ranges[i]) for i in range(6)]
    rejected = [o for o in out if isinstance(o, Rejected)]
    assert len(rejected) == 4  # cold-start rejections, zero waves executed
    assert eng.stats.waves == 0
    for r in rejected:
        assert np.isfinite(r.retry_after)
        assert 0.0 < r.retry_after <= ServeEngine.RETRY_AFTER_MAX_S
    eng.drain()


def test_retry_after_survives_poisoned_ewma(wl, idx):
    """The hint stays bounded positive for every degenerate EWMA value a
    virtual-clock jump (or a pre-warmup reject) can produce, and an
    injected non-finite wall-clock delta is skipped by the EWMA update
    instead of poisoning every later hint."""
    eng = _engine(idx, max_wave=4, queue_cap=1)
    for bad in (float("nan"), float("inf"), -1.0, 0.0):
        eng._wave_s = bad
        hint = eng._retry_after()
        assert np.isfinite(hint), f"_wave_s={bad}: hint {hint}"
        assert 0.0 < hint <= eng.RETRY_AFTER_MAX_S

    # an inf-jump clock mid-chunk produces dt=inf (then nan): the EWMA
    # update must skip it, so the next hint still comes off the floor
    clk = VClock()
    plan = EngineFaultPlan(slow_chunk_every=1, slow_chunk_s=float("inf"),
                           sleep=clk.advance)
    eng2 = ServeEngine(index=idx, now=clk, fault_plan=plan,
                       config=EngineConfig(**SEARCH, max_wave=4))
    for i in range(4):
        eng2.submit(wl.queries[i], wl.ranges[i])
    replies = eng2.drain()
    assert len(replies) == 4  # the jump never deadlocks the scheduler
    assert np.isfinite(eng2._wave_s) and np.isfinite(eng2._hop_s)
    hint = eng2._retry_after()
    assert np.isfinite(hint) and 0.0 < hint <= eng2.RETRY_AFTER_MAX_S


# ------------------------------------- cold start over read-only mmap slabs
def test_cold_start_then_ingest_over_mmap_snapshot(tmp_path, wl):
    """Serve-from-checkpoint hands the engine *read-only* mmap'd slabs;
    the first post-cold-start ingest refreshes the snapshot incrementally
    with ``prev=<that mmap snapshot>``.  Every consumer on that path must
    copy out of the read-only mapping, never write into it — this is the
    flow that crashes if any of them mutates in place."""
    from repro.persist import load_serving_snapshot

    root = str(tmp_path)
    ix = open_durable(root, create=dict(dim=12, **KW))
    ix.insert_batch(wl.vectors[:300], wl.attrs[:300], batch_size=128,
                    backend="numpy")
    # full checkpoint: delta chains compose in memory, only a full one is
    # served straight off the read-only mapping
    ix.checkpoint(root, incremental=False)
    ix._wal.close()
    del ix

    snap, _ = load_serving_snapshot(root)
    assert not snap.vectors.flags.writeable  # really is a read-only mapping
    eng = ServeEngine(snapshot=snap, config=EngineConfig(**SEARCH))
    eng.submit(wl.queries[0], wl.ranges[0])
    (r0,) = eng.drain()
    assert not r0.degraded

    # first mutation: recover the live twin and ride the mmap snapshot
    # through take_snapshot(prev=...) inside the engine's refresh
    ix2 = open_durable(root)
    eng2 = ServeEngine(index=ix2, snapshot=snap, config=EngineConfig(
        **SEARCH, ingest_batch=50, build_backend="numpy"))
    hi = float(wl.attrs.max()) + 1.0
    nv = wl.vectors[300:350]
    na = np.linspace(hi, hi + 1.0, 50)
    res = eng2.submit_ingest(nv, na)
    assert res.accepted == 50
    eng2.drain()
    t = eng2.submit(nv[0], (hi, hi + 1.0))
    (r,) = eng2.drain()
    assert r.rid == t.rid and (r.ids >= 300).all()
    assert r.dists[0] <= 1e-3  # the ingested rows are really being served
    ix2._wal.close()


# ------------------------------------------------------ deadlines & shedding
def test_deadline_storm_degrades_never_times_out(wl, idx):
    """Deadline storm under injected slow chunks (virtual clock): every
    reply that lands past its deadline is marked degraded — truncated
    requests carry their best-so-far beam, queue-expired requests get an
    empty degraded reply — and the engine drains without deadlock."""
    clk = VClock()
    plan = EngineFaultPlan(slow_chunk_every=1, slow_chunk_s=0.1,
                           sleep=clk.advance)
    eng = ServeEngine(
        index=idx, now=clk, fault_plan=plan,
        config=EngineConfig(**SEARCH, max_wave=8, max_slots=16,
                            default_timeout_s=0.05),
    )
    for i in range(32):
        eng.submit(wl.queries[i % len(wl.queries)], (0.0, 1.0))
    replies = eng.drain()
    assert len(replies) == 32
    assert all(r.degraded for r in replies)  # 0.1s/chunk vs 0.05s deadline
    truncated = [r for r in replies if r.reason == "deadline"]
    expired = [r for r in replies if r.reason == "queue_deadline"]
    assert len(truncated) + len(expired) == 32
    assert truncated and expired  # the storm hit both lifecycle stages
    for r in replies:
        assert r.finish_t > (r.finish_t - r.latency_s) + 0.05 - 1e-9
        assert len(r.ids) == 5 and len(r.dists) == 5
    for r in expired:
        assert (r.ids == -1).all() and r.hops == 0
    s = eng.stats
    assert s.degraded == 32 and s.expired == len(expired)


def test_degraded_reply_is_valid_prefix(wl, idx):
    """A mid-flight truncation returns the beam's best-so-far: a sorted,
    structurally valid result prefix with fewer hops than the full run —
    reduced budget, not garbage."""
    snap = take_snapshot(idx)
    full = search_batch(snap, wl.queries, wl.ranges, k=5, width=32,
                        visited="bitmap")
    clk = VClock()
    plan = EngineFaultPlan(slow_chunk_every=1, slow_chunk_s=0.1,
                           sleep=clk.advance)
    eng = ServeEngine(
        index=idx, now=clk, fault_plan=plan,
        config=EngineConfig(**SEARCH, max_wave=64, default_timeout_s=0.25),
    )
    tickets = [eng.submit(wl.queries[i], wl.ranges[i])
               for i in range(len(wl.queries))]
    replies = {r.rid: r for r in eng.drain()}
    hops_full = np.asarray(full.hops)
    saw_truncated = False
    for i, t in enumerate(tickets):
        r = replies[t.rid]
        got = r.dists[r.ids >= 0]
        assert np.all(np.diff(got) >= 0)  # sorted valid prefix
        if r.reason == "deadline" and r.hops < hops_full[i]:
            saw_truncated = True
            assert (r.ids >= 0).any()  # best-so-far beam, not empty
    assert saw_truncated


def test_queued_expiry_without_execution(wl, idx):
    """Requests whose deadline passes while still queued are answered
    empty-and-degraded without ever reaching the hop loop."""
    clk = VClock()
    eng = ServeEngine(index=idx, now=clk,
                      config=EngineConfig(**SEARCH, default_timeout_s=0.01))
    for i in range(4):
        eng.submit(wl.queries[i], wl.ranges[i])
    clk.advance(1.0)
    replies = eng.drain()
    assert len(replies) == 4
    assert all(r.degraded and r.reason == "queue_deadline" for r in replies)
    assert eng.stats.expired == 4 and eng.stats.chunks == 0


def test_crash_after_chunks_fault(wl, idx):
    """``EngineFaultPlan(crash_after_chunks=...)`` kills the scheduler at
    an exact chunk boundary (deterministic crash-point placement)."""
    plan = EngineFaultPlan(crash_after_chunks=1)
    eng = ServeEngine(index=idx, fault_plan=plan,
                      config=EngineConfig(**SEARCH, max_wave=8))
    for i in range(8):
        eng.submit(wl.queries[i], wl.ranges[i])
    with pytest.raises(CrashError):
        eng.drain()
    assert plan.chunks == 2


# ----------------------------------------------------------- adaptive knobs
def test_chunk_schedule_from_hist():
    """The hist-driven chunk schedule is pow2, bounded, and tracks the
    distribution: a tight histogram yields a short first chunk, a heavy
    tail a longer one."""
    tight = np.zeros(65, np.int64)
    tight[6] = 100
    h0, h1 = chunk_schedule_from_hist(tight)
    assert h0 == 8 and h1 == 4  # p50=6 -> pow2ceil(7)=8; no tail
    heavy = np.zeros(129, np.int64)
    heavy[20] = 90
    heavy[120] = 10
    g0, g1 = chunk_schedule_from_hist(heavy)
    assert g0 >= 16 and g1 >= 16  # tail (p99-p50)/4 = 25 -> 32
    for v in (h0, h1, g0, g1):
        assert v & (v - 1) == 0 and 4 <= v <= 64
    assert hist_percentile(tight, 50.0) == 6.0


def test_engine_adaptive_filter_and_chunks(wl, idx):
    """With ``visited='hash'`` + adaptive, the engine re-sizes the
    visited filter and chunk schedule from its own live hop histogram
    after the first waves."""
    eng = ServeEngine(index=idx, config=EngineConfig(
        k=5, width=32, visited="hash", adaptive=True, max_wave=16))
    assert eng.hop_histogram() is None
    for i in range(16):
        eng.submit(wl.queries[i], wl.ranges[i])
    eng.drain()
    hist = eng.hop_histogram()
    assert hist is not None and hist.sum() == 16
    bits = eng.engine_stats()["visited_bits"]
    assert isinstance(bits, int) and bits & (bits - 1) == 0
    h0, h1 = eng.engine_stats()["chunk_schedule"]
    assert h0 & (h0 - 1) == 0 and h1 & (h1 - 1) == 0
    for i in range(16):
        eng.submit(wl.queries[i], wl.ranges[i])
    replies = eng.drain()
    assert sum(not r.degraded for r in replies) == 16


def test_search_batch_max_hops_budget(wl, idx):
    """``search_batch(max_hops=...)`` (the degraded-budget plumbing) caps
    the hop count; queries that finished under the cap are bitwise the
    full run."""
    snap = take_snapshot(idx)
    full = search_batch(snap, wl.queries, wl.ranges, k=5, width=32)
    capped = search_batch(snap, wl.queries, wl.ranges, k=5, width=32,
                          max_hops=8)
    hf, hc = np.asarray(full.hops), np.asarray(capped.hops)
    assert hc.max() <= 8 and hf.max() > 8  # the cap actually binds
    done = hf <= 8
    assert done.any()
    assert np.array_equal(np.asarray(capped.ids)[done],
                          np.asarray(full.ids)[done])


# ----------------------------------------------------- ingest: WAL lifecycle
def test_ingest_per_row_validation(wl, idx):
    """Half-bad ingest batches commit the good rows and report the bad
    ones explicitly — admission-time validation, before any WAL byte."""
    eng = _engine(idx)
    v = wl.vectors[:10].copy()
    a = wl.attrs[:10].copy()
    v[2, 0] = np.nan
    a[5] = np.inf
    n0 = len(idx)
    res = eng.submit_ingest(v, a)
    assert res.accepted == 8 and res.pending
    assert dict(res.rejected) == {2: "non-finite vector component",
                                  5: "non-finite attribute"}
    eng.drain()
    assert len(idx) == n0 + 8
    with pytest.raises(ValueError, match="dimension"):
        eng.submit_ingest(np.zeros((2, 5), np.float32), [0.1, 0.2])
    keep, rej = validate_rows(np.zeros((3, 12), np.float32),
                              np.asarray([0.1, np.nan, 0.3]), 12)
    assert keep.tolist() == [True, False, True] and len(rej) == 1


def test_ingest_query_interleave_and_visibility(wl):
    """Queries and ingest share the scheduler fairly: both make progress
    under one drive loop, and a query admitted after the ingest applies
    sees the new rows."""
    ix = WoWIndex(dim=12, **KW)
    ix.insert_batch(wl.vectors[:300], wl.attrs[:300], batch_size=128,
                    backend="numpy")
    eng = ServeEngine(index=ix, config=EngineConfig(
        **SEARCH, max_wave=8, ingest_share=0.5, ingest_batch=32))
    hi = float(wl.attrs.max()) + 1.0
    nv = np.random.default_rng(3).standard_normal((64, 12)).astype(np.float32)
    na = np.linspace(hi, hi + 1.0, 64)
    eng.submit_ingest(nv, na)
    for i in range(16):
        eng.submit(wl.queries[i], wl.ranges[i])
    # ingest (2 micro-batches) must complete within a bounded number of
    # steps even though queries keep the scheduler busy
    for _ in range(8):
        eng.step()
    assert eng.pending_ingest == 0
    eng.drain()
    assert len(ix) == 364
    # a post-ingest query restricted to the new attr range finds new rows
    t = eng.submit(nv[0], (hi, hi + 1.0))
    (r,) = eng.drain()
    assert r.rid == t.rid and (r.ids >= 300).all()
    assert r.dists[0] <= 1e-3  # exact vector match (f32 roundoff)


def test_ingest_ack_survives_crash_before_apply(tmp_path, wl):
    """No lost acked ingest: batches acked by ``submit_ingest`` but never
    applied (in-process crash mid-queue) are fully recovered from the
    WAL — the ack is the durability barrier, not the apply."""
    root = str(tmp_path)
    ix = open_durable(root, create=dict(dim=12, **KW))
    ix.insert_batch(wl.vectors[:100], wl.attrs[:100], batch_size=50,
                    backend="numpy")
    plan = EngineFaultPlan(crash_after_ingest_applies=1)
    eng = ServeEngine(index=ix, fault_plan=plan, config=EngineConfig(
        **SEARCH, ingest_batch=50, build_backend="numpy"))
    res = eng.submit_ingest(wl.vectors[100:250], wl.attrs[100:250])
    assert res.accepted == 150 and eng.pending_ingest == 3
    with pytest.raises(CrashError):
        eng.drain()  # applies batch 1, dies entering batch 2
    assert eng.pending_ingest == 2

    rec = recover(root)
    want = WoWIndex(dim=12, **KW)
    want.insert_batch(wl.vectors[:100], wl.attrs[:100], batch_size=50,
                      backend="numpy")
    for s in range(100, 250, 50):
        want.insert_batch(wl.vectors[s:s + 50], wl.attrs[s:s + 50],
                          batch_size=50, backend="numpy")
    assert state_digest(rec) == state_digest(want)


def test_restart_replays_pending_ingest(tmp_path, wl):
    """A restarted server sees every acked-but-unapplied micro-batch:
    recovery replays the WAL suffix, so the new engine's index already
    contains the pending queue."""
    root = str(tmp_path)
    ix = open_durable(root, create=dict(dim=12, **KW))
    eng = ServeEngine(index=ix, config=EngineConfig(
        **SEARCH, ingest_batch=40, build_backend="numpy"))
    eng.submit_ingest(wl.vectors[:120], wl.attrs[:120])
    assert eng.pending_ingest == 3 and len(ix) == 0  # acked, nothing applied
    del eng, ix  # "restart" without ever driving the scheduler

    ix2 = open_durable(root)
    assert len(ix2) == 120
    eng2 = ServeEngine(index=ix2, config=EngineConfig(**SEARCH))
    t = eng2.submit(wl.vectors[0], (float(wl.attrs.min()),
                                    float(wl.attrs.max())))
    (r,) = eng2.drain()
    assert r.rid == t.rid and r.dists[0] <= 1e-3


def test_dropped_fsync_breaks_the_ack(tmp_path, wl):
    """The group-commit ``sync()`` is load-bearing: with fsyncs dropped
    (``FaultIO(drop_fsync=True, model='lost')``) a post-ack crash loses
    the 'acked' batches — proving the ack's durability comes from the
    fsync barrier, not the appends."""
    root = str(tmp_path)
    ix = open_durable(root, create=dict(dim=12, **KW))
    ix.insert_batch(wl.vectors[:60], wl.attrs[:60], batch_size=30,
                    backend="numpy")
    ix.checkpoint(root)
    del ix
    io = FaultIO(drop_fsync=True, model="lost")
    ix = open_durable(root, io=io)
    eng = ServeEngine(index=ix, config=EngineConfig(
        **SEARCH, ingest_batch=30, build_backend="numpy"))
    res = eng.submit_ingest(wl.vectors[60:120], wl.attrs[60:120])
    assert res.accepted == 60  # "acked" — but the fsync was a no-op
    with pytest.raises(CrashError):
        io._crash()
    rec = recover(root)
    assert len(rec) == 60  # the acked-without-fsync rows are gone


def test_sigkill_with_pending_ingest_queue(tmp_path):
    """Real SIGKILL with acked micro-batches sitting in the ingest queue
    (some applied, some only logged): recovery reproduces the exact index
    a clean application of EVERY acked batch builds — zero acked loss,
    the PR's headline gate."""
    root = str(tmp_path)
    child = f"""
import os, signal
from repro.core import make_workload
from repro.persist import open_durable
from repro.serve.lifecycle import ServeEngine, EngineConfig
wl = make_workload(n=300, d=12, nq=1, seed=7, with_gt=False)
idx = open_durable({root!r}, create=dict(dim=12, m=8, ef_construction=32,
                                         o=4, seed=0))
eng = ServeEngine(index=idx, config=EngineConfig(
    k=5, width=32, ingest_batch=50, build_backend="numpy"))
for i in range(6):
    r = eng.submit_ingest(wl.vectors[50*i:50*(i+1)], wl.attrs[50*i:50*(i+1)])
    assert r.accepted == 50 and r.pending
    print("ACK", i, flush=True)
eng.step(); eng.step()  # apply a prefix of the queue, leave the rest pending
print("PENDING", eng.pending_ingest, flush=True)
os.kill(os.getpid(), signal.SIGKILL)
"""
    here = os.path.dirname(__file__)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(here, "..", "src"), here]
    )
    res = subprocess.run([sys.executable, "-c", child], capture_output=True,
                         text=True, timeout=300, env=env)
    assert res.returncode == -signal.SIGKILL, res.stderr
    assert res.stdout.count("ACK") == 6
    assert "PENDING 4" in res.stdout  # 2 applied, 4 still queued at the kill

    rec = recover(root)
    wl = make_workload(n=300, d=12, nq=1, seed=7, with_gt=False)
    want = WoWIndex(dim=12, **KW)
    for i in range(6):
        want.insert_batch(wl.vectors[50 * i:50 * (i + 1)],
                          wl.attrs[50 * i:50 * (i + 1)],
                          batch_size=50, backend="numpy")
    assert state_digest(rec) == state_digest(want)


# ------------------------------------------------------------------ stats
def test_stats_accounting_consistency(wl, idx):
    """The lifecycle counters tie out: submitted = admitted + rejected,
    served = admitted after drain, latency percentiles are monotone."""
    eng = _engine(idx, max_wave=8, queue_cap=16)
    for i in range(24):
        eng.submit(wl.queries[i % len(wl.queries)], (0.0, 1.0))
    eng.drain()
    s = eng.stats.summary()
    assert s["submitted"] == 24
    assert s["submitted"] == s["admitted"] + s["rejected"]
    assert s["served"] == s["admitted"] == 16
    assert 0 < s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"]
    # each request's wait is part of its latency, so the percentiles are too
    assert 0 <= s["queue_wait_p50_ms"] <= s["queue_wait_p95_ms"] \
        <= s["p95_ms"]
    assert s["qps"] > 0
    assert s["shed_fraction"] == pytest.approx(8 / 24)
    es = eng.engine_stats()
    assert es["queue_len"] == 0 and es["in_flight"] == 0
    assert es["pending_ingest"] == 0


# ------------------------------------------------------------------ tracing
def _drip(eng, wl):
    """Submit 16, then one request per step, then drain: several waves in
    flight at once, with compactions (as the bitwise-parity test)."""
    got = []
    for i in range(16):
        eng.submit(wl.queries[i], wl.ranges[i])
    for i in range(16, len(wl.queries)):
        got.extend(eng.step())
        eng.submit(wl.queries[i], wl.ranges[i])
    got.extend(eng.drain())
    return {r.rid: r for r in got}


def _serve_spans(trace_dir):
    """-> [(name, start, end, args)] of the ``serve.*`` host spans."""
    from pathlib import Path

    from jax.profiler import ProfileData

    (f,) = Path(trace_dir).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(f)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda x: (x[1], -x[2]))


def _traced(fn, trace_dir):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def test_serve_spans_nest_and_share_wave_ids(tmp_path, wl, idx):
    """Under ``jax.profiler`` the engine's spans nest as the scheduler
    does, one ``serve.chunk`` per chunk, every launch under the work that
    issued it, and a wave's spans and replies share its id."""
    eng = _engine(idx, max_wave=16)
    eng.warmup()
    chunks0 = eng.stats.chunks
    replies = _traced(lambda: _drip(eng, wl), tmp_path)
    spans = _serve_spans(tmp_path)
    parent = {}
    for i, (n, s, e, _) in enumerate(spans):
        # sorted by start (outer first on ties): the innermost cover is the
        # last earlier span that still covers this one
        up = [j for j in range(i) if spans[j][1] <= s and e <= spans[j][2]]
        parent[i] = spans[up[-1]][0] if up else None
    allowed = {
        "serve.step": {None},
        "serve.assemble": {"serve.step"},
        "serve.chunk": {"serve.step"},
        "serve.dispatch": {"serve.assemble", "serve.chunk", "serve.compact"},
        "serve.sync": {"serve.chunk"},
        "serve.harvest": {"serve.chunk"},
        "serve.compact": {"serve.chunk"},
    }
    for i, (n, *_) in enumerate(spans):
        assert parent[i] in allowed[n], (n, parent[i])
    names = [n for n, *_ in spans]
    assert names.count("serve.chunk") == eng.stats.chunks - chunks0
    assert "serve.compact" in names  # the drip shrinks waves
    programs = {a["program"] for n, _, _, a in spans if n == "serve.dispatch"}
    assert programs == {"_init_jit", "_run_jit_inplace", "_compact_rows"}
    # wave ids: assembled once, chunked after, and carried by the replies
    assembled = {a["wave"]: (s, a["n"]) for n, s, _, a in spans
                 if n == "serve.assemble"}
    assert sorted(assembled) == list(range(eng.stats.waves))
    for n, s, _, a in spans:
        if n == "serve.chunk":
            assert assembled[a["wave"]][0] < s
    per_wave = {}
    for r in replies.values():
        per_wave[r.wave] = per_wave.get(r.wave, 0) + 1
    assert per_wave == {w: n for w, (_, n) in assembled.items()}
    assert sum(a["n"] for n, _, _, a in spans if n == "serve.harvest") \
        == len(replies)


def test_inactive_spans_format_no_arguments():
    """With no trace being captured a span's arguments are never
    formatted, so the engine's spans need no guard on the hot path."""
    from jax.profiler import TraceAnnotation

    class Loud:
        formatted = 0

        def __str__(self):
            Loud.formatted += 1
            return "x"

        __repr__ = __format__ = lambda self, *a: str(self)

    for _ in range(3):
        with TraceAnnotation("serve.chunk", wave=Loud(), h=Loud()):
            pass
    assert Loud.formatted == 0


def test_replies_bitwise_equal_with_profiler_capturing(tmp_path, wl, idx):
    """Capturing a trace changes no answer: ids, distances, hops and
    distance counts are bitwise those of an untraced run."""
    plain = _drip(_engine(idx, max_wave=16), wl)
    traced = _traced(lambda: _drip(_engine(idx, max_wave=16), wl), tmp_path)
    assert sorted(plain) == sorted(traced)
    for rid, a in plain.items():
        b = traced[rid]
        assert np.array_equal(a.ids, b.ids)
        assert np.array_equal(a.dists, b.dists)
        assert (a.hops, a.dc, a.wave) == (b.hops, b.dc, b.wave)


def test_queue_wait_is_exact_under_a_virtual_clock(wl, idx):
    """``Reply.wait_s`` runs from admission to the dispatch of the
    request's wave, read from the engine's clock: exact under a virtual
    one, never negative and never past the latency; a request that
    expires in the queue waited its whole latency."""
    clk = VClock()
    eng = ServeEngine(index=idx, now=clk, config=EngineConfig(**SEARCH))
    full = (wl.attrs.min(), wl.attrs.max())  # all in range: many hops
    a = eng.submit(wl.queries[0], full)
    clk.advance(0.25)
    b = eng.submit(wl.queries[1], full)
    clk.advance(0.75)  # t = 1: the first step assembles both
    got = eng.step()
    # a wave is in flight and the queue is short of a full wave: c waits
    c = eng.submit(wl.queries[2], full, timeout_s=0.5)
    while not eng.idle:
        clk.advance(0.125)
        got.extend(eng.step())
    replies = {r.rid: r for r in got}
    assert replies[a.rid].wait_s == 1.0
    assert replies[b.rid].wait_s == 0.75
    assert replies[a.rid].wave == replies[b.rid].wave == 0
    exp = replies[c.rid]
    assert exp.reason == "queue_deadline" and exp.wave == -1
    assert exp.wait_s == exp.latency_s > 0.5
    for r in replies.values():
        assert 0.0 <= r.wait_s <= r.latency_s
    waits = np.asarray([r.wait_s for r in replies.values()])
    s = eng.stats.summary()
    assert s["queue_wait_p50_ms"] == pytest.approx(
        np.percentile(waits, 50) * 1e3)
    assert s["queue_wait_p95_ms"] == pytest.approx(
        np.percentile(waits, 95) * 1e3)
