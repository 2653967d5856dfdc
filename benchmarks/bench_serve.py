"""Serve-engine bench: request-lifecycle latency under closed-loop and
open-loop (fixed offered load) arrivals, vs the raw one-shot wave.

Modes
-----
* closed burst — admit the whole workload at once and drain: measures
  engine capacity (QPS) and per-request admission->reply p50/p99.
* open loop (``--rate``, default 0.7x the measured closed capacity) —
  arrivals at a fixed offered rate independent of completions, the shape
  real traffic has; latency percentiles now include queue wait.
* overload — offered load ~4x capacity against a bounded queue with a
  deadline: reports degraded fraction (deadline-truncated replies) and
  shed fraction (admission rejects) alongside latency, the graceful-
  degradation columns.

Results merge into ``BENCH_device.json`` under an ``"engine"`` key (the
serving-path perf trajectory file), plus the usual CSV rows.

``--smoke`` runs a short fixed workload and *gates*: the engine's
closed-burst p99 latency is normalized by the raw ``search_batch`` wave
time on the same machine in the same process (a machine-relative ratio,
so a slow CI box does not trip it), and the job fails if that ratio
regresses more than 10% over the recorded baseline
(``benchmarks/baselines/serve_smoke.json``; refresh deliberately with
``--update-baseline``).

``--failover`` benches the replicated cluster instead: a 3-member
cluster under live read traffic has its primary killed mid-run and the
bench measures (a) time-to-first-successful-query after the kill —
reads re-route to the admitted replicas, so this should be ~one step —
and (b) time until the write path is restored (the first quorum-durable
ingest ack under the new epoch), which is bounded below by the
heartbeat timeout.  Results land under a ``"failover"`` key in
``BENCH_device.json``; with ``--smoke`` the write-restore time is
normalized by the configured heartbeat timeout (machine-relative) and
gated against ``benchmarks/baselines/failover_smoke.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from .common import BENCH_D, BENCH_N, BENCH_Q, emit, write_csv

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "baselines", "serve_smoke.json")
_GATE_SLACK = 1.10  # fail --smoke beyond +10% p99 ratio regression
_FAILOVER_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "baselines", "failover_smoke.json")
# failover time = heartbeat timeout + detection/promotion overhead; the
# timeout part is fixed, so the ratio is stable — but the overhead part
# rides on scheduler noise, so the gate is looser than the latency one
_FAILOVER_SLACK = 1.50


def _build(n, d, nq, m, ef):
    from repro.core import WoWIndex, make_workload

    wl = make_workload(n=n, d=d, nq=nq, seed=0, k=10)
    idx = WoWIndex(dim=d, m=m, ef_construction=ef, o=4, seed=0)
    idx.insert_batch(wl.vectors, wl.attrs, batch_size=128, backend="numpy")
    return wl, idx


def _engine(idx, **over):
    from repro.serve.lifecycle import EngineConfig, ServeEngine

    kw = dict(k=10, width=48, visited="bitmap", adaptive=False,
              chunk=(16, 8), max_wave=32, queue_cap=4096)
    kw.update(over)
    eng = ServeEngine(index=idx, config=EngineConfig(**kw))
    # precompile every wave/compaction bucket shape: a mid-run lazy XLA
    # compile (~1s) would otherwise land in the latency percentiles the
    # first time the slot pool forces a mid-bucket wave
    eng.warmup()
    return eng


def _closed_burst(idx, wl, reps=3):
    """Admit everything, drain, repeat; keep the best rep (box noise
    hits the slowest window, not the engine)."""
    best = None
    for _ in range(reps + 1):  # +1 warmup rep compiles every wave shape
        eng = _engine(idx)
        for i in range(len(wl.queries)):
            eng.submit(wl.queries[i], wl.ranges[i])
        t0 = time.perf_counter()
        replies = eng.drain()
        dt = time.perf_counter() - t0
        lat = np.asarray([r.latency_s for r in replies])
        rec = {
            "qps": len(replies) / dt,
            "p50_ms": float(np.percentile(lat, 50) * 1e3),
            "p99_ms": float(np.percentile(lat, 99) * 1e3),
        }
        if best is None or rec["qps"] > best["qps"]:
            best = rec
    return best


def _open_loop(idx, wl, rate, duration_q, deadline_ms=0.0, queue_cap=4096,
               max_slots=256):
    """Fixed offered load: submit at ``rate`` QPS for ``duration_q``
    arrivals while driving the scheduler between arrivals."""
    eng = _engine(
        idx, queue_cap=queue_cap, max_slots=max_slots,
        default_timeout_s=(deadline_ms / 1e3 if deadline_ms > 0 else None),
    )
    period = 1.0 / rate
    replies = []
    next_t = time.perf_counter()
    t_start = next_t
    for i in range(duration_q):
        while True:
            now = time.perf_counter()
            if now >= next_t:
                break
            if not eng.idle:
                replies.extend(eng.step())
            else:
                time.sleep(min(1e-4, next_t - now))
        next_t += period
        eng.submit(wl.queries[i % len(wl.queries)],
                   wl.ranges[i % len(wl.ranges)])
    replies.extend(eng.drain())
    dt = time.perf_counter() - t_start
    s = eng.stats.summary()
    lat = np.asarray([r.latency_s for r in replies]) if replies else np.zeros(1)
    return {
        "offered_qps": round(rate, 1),
        "qps": round(len(replies) / dt, 1),
        "p50_ms": round(float(np.percentile(lat, 50) * 1e3), 3),
        "p99_ms": round(float(np.percentile(lat, 99) * 1e3), 3),
        "degraded_fraction": round(s["degraded_fraction"], 4),
        "shed_fraction": round(s["shed_fraction"], 4),
    }


def _raw_wave_ms(idx, wl, reps=3):
    """One-shot jitted wave over the whole workload (the no-lifecycle
    floor the smoke gate normalizes against)."""
    from repro.core.device_search import search_batch
    from repro.core.snapshot import take_snapshot

    snap = take_snapshot(idx)
    search_batch(snap, wl.queries, wl.ranges, k=10, width=48)  # warm
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        search_batch(snap, wl.queries, wl.ranges, k=10, width=48)
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def run_failover(smoke: bool = False, update_baseline: bool = False) -> int:
    """Kill the primary of a live 3-member cluster and measure recovery:
    read gap (first successful query after the kill) and write restore
    (first quorum-durable ingest ack under the new epoch)."""
    import shutil
    import tempfile

    from repro.core import make_workload
    from repro.serve.cluster import Cluster
    from repro.serve.lifecycle import EngineConfig

    if smoke:
        n, d, nq = 600, 12, 48
    else:
        n, d, nq = min(BENCH_N, 4000), BENCH_D, max(BENCH_Q, 48)
    hb_timeout = 0.2
    wl = make_workload(n=n, d=d, nq=nq, seed=0, k=10)
    tmp = tempfile.mkdtemp(prefix="bench-failover-")
    try:
        cfg = EngineConfig(k=10, width=48, visited="bitmap", adaptive=False,
                           chunk=(16, 8), max_wave=32, queue_cap=512)
        c = Cluster([os.path.join(tmp, f"m{i}") for i in range(3)],
                    create=dict(dim=d, m=8, ef_construction=32, o=4, seed=0),
                    config=cfg, heartbeat_s=0.02,
                    heartbeat_timeout_s=hb_timeout)
        for lo in range(0, n, 256):
            c.submit_ingest(wl.vectors[lo:lo + 256], wl.attrs[lo:lo + 256])
            c.drain()
        c.warmup()
        for i in range(8):  # steady state: reads flowing on every member
            c.submit(wl.queries[i % nq], wl.ranges[i % nq])
        c.drain()

        victim = c.primary_id
        t_kill = time.perf_counter()
        c.kill(victim)
        first_read = None
        write_restore = None
        qi = 0
        while (time.perf_counter() - t_kill) < 60.0:
            if len(c._outstanding) < 8:
                c.submit(wl.queries[qi % nq], wl.ranges[qi % nq])
                qi += 1
            got = c.step()
            now = time.perf_counter()
            if got and first_read is None:
                first_read = now - t_kill
            if write_restore is None:
                try:
                    c.submit_ingest(wl.vectors[:1], wl.attrs[:1])
                    write_restore = now - t_kill
                except RuntimeError:
                    pass  # no live primary yet: the failover window
            if first_read is not None and write_restore is not None:
                break
        c.drain()
        if first_read is None or write_restore is None:
            print("FAIL: cluster did not recover within 60s after the "
                  "primary kill", flush=True)
            return 1
        assert c.failovers and not c.failovers[0]["planned"]
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    ratio = write_restore / hb_timeout
    emit("failover_first_read", first_read * 1e6,
         f"read gap after primary kill; n={n};members=3")
    emit("failover_write_restore", write_restore * 1e6,
         f"heartbeat_timeout={hb_timeout};ratio={ratio:.2f}")
    record = {
        "workload": {"n": n, "d": d, "nq": nq, "members": 3,
                     "heartbeat_timeout_s": hb_timeout},
        "first_read_ms": round(first_read * 1e3, 3),
        "write_restore_ms": round(write_restore * 1e3, 3),
        "restore_over_timeout": round(ratio, 3),
    }
    write_csv("bench_failover.csv",
              ["members", "first_read_ms", "write_restore_ms",
               "restore_over_timeout"],
              [[3, record["first_read_ms"], record["write_restore_ms"],
                record["restore_over_timeout"]]])

    if not smoke:
        path = os.path.join(_REPO_ROOT, "BENCH_device.json")
        blob = {}
        if os.path.exists(path):
            with open(path) as f:
                blob = json.load(f)
        blob["failover"] = record
        with open(path, "w") as f:
            json.dump(blob, f, indent=1)
        return 0

    # --smoke: gate restore/timeout ratio against the recorded baseline
    if update_baseline or not os.path.exists(_FAILOVER_BASELINE):
        os.makedirs(os.path.dirname(_FAILOVER_BASELINE), exist_ok=True)
        with open(_FAILOVER_BASELINE, "w") as f:
            json.dump({"restore_over_timeout": round(ratio, 3),
                       "workload": record["workload"]}, f, indent=1)
        emit("failover_smoke_baseline_recorded", 0.0, f"ratio={ratio:.3f}")
        return 0
    with open(_FAILOVER_BASELINE) as f:
        base = json.load(f)["restore_over_timeout"]
    limit = base * _FAILOVER_SLACK
    status = "ok" if ratio <= limit else "REGRESSION"
    emit("failover_smoke_gate", 0.0,
         f"ratio={ratio:.3f};baseline={base:.3f};limit={limit:.3f};{status}")
    if ratio > limit:
        print(f"FAIL: write-restore/heartbeat-timeout ratio {ratio:.3f} "
              f"exceeds baseline {base:.3f} by more than "
              f"{_FAILOVER_SLACK - 1:.0%} (limit {limit:.3f}) — failover "
              f"regression", flush=True)
        return 1
    return 0


def run(smoke: bool = False, rate: float = 0.0, deadline_ms: float = 0.0,
        update_baseline: bool = False) -> int:
    if smoke:
        n, d, nq, m, ef = 600, 12, 48, 8, 32
    else:
        n, d, nq, m, ef = BENCH_N, BENCH_D, max(BENCH_Q, 48), 16, 64
    wl, idx = _build(n, d, nq, m, ef)

    closed = _closed_burst(idx, wl)
    raw_ms = _raw_wave_ms(idx, wl)
    p99_ratio = closed["p99_ms"] / raw_ms
    emit("serve_closed_burst", 1e6 / closed["qps"],
         f"p50={closed['p50_ms']:.1f}ms;p99={closed['p99_ms']:.1f}ms;"
         f"raw_wave={raw_ms:.1f}ms;p99_ratio={p99_ratio:.2f}")

    offered = rate if rate > 0 else 0.7 * closed["qps"]
    open_rec = _open_loop(idx, wl, offered, duration_q=2 * nq,
                          deadline_ms=deadline_ms)
    emit("serve_open_loop", 1e6 / max(open_rec["qps"], 1e-9),
         f"offered={open_rec['offered_qps']};p50={open_rec['p50_ms']}ms;"
         f"p99={open_rec['p99_ms']}ms")

    over_rec = _open_loop(idx, wl, 4.0 * closed["qps"], duration_q=6 * nq,
                          deadline_ms=deadline_ms or 50.0, queue_cap=64,
                          max_slots=64)
    emit("serve_overload_4x", 1e6 / max(over_rec["qps"], 1e-9),
         f"degraded={over_rec['degraded_fraction']};"
         f"shed={over_rec['shed_fraction']};p99={over_rec['p99_ms']}ms")

    record = {
        "workload": {"n": n, "d": d, "nq": nq, "m": m, "ef": ef,
                     "k": 10, "width": 48},
        "closed": {k: round(v, 3) for k, v in closed.items()},
        "raw_wave_ms": round(raw_ms, 3),
        "p99_ratio": round(p99_ratio, 3),
        "open": open_rec,
        "overload_4x": over_rec,
    }
    write_csv("bench_serve.csv",
              ["mode", "offered_qps", "qps", "p50_ms", "p99_ms",
               "degraded_fraction", "shed_fraction"],
              [["closed", "", round(closed["qps"], 1),
                round(closed["p50_ms"], 3), round(closed["p99_ms"], 3),
                0.0, 0.0],
               ["open", open_rec["offered_qps"], open_rec["qps"],
                open_rec["p50_ms"], open_rec["p99_ms"],
                open_rec["degraded_fraction"], open_rec["shed_fraction"]],
               ["overload_4x", over_rec["offered_qps"], over_rec["qps"],
                over_rec["p50_ms"], over_rec["p99_ms"],
                over_rec["degraded_fraction"], over_rec["shed_fraction"]]])

    if not smoke:  # merge the engine columns into the tracked perf file
        path = os.path.join(_REPO_ROOT, "BENCH_device.json")
        blob = {}
        if os.path.exists(path):
            with open(path) as f:
                blob = json.load(f)
        blob["engine"] = record
        with open(path, "w") as f:
            json.dump(blob, f, indent=1)
        return 0

    # --smoke: gate the p99 ratio against the recorded baseline
    if update_baseline or not os.path.exists(_BASELINE):
        os.makedirs(os.path.dirname(_BASELINE), exist_ok=True)
        with open(_BASELINE, "w") as f:
            json.dump({"p99_ratio": round(p99_ratio, 3),
                       "workload": record["workload"]}, f, indent=1)
        emit("serve_smoke_baseline_recorded", 0.0,
             f"p99_ratio={p99_ratio:.3f}")
        return 0
    with open(_BASELINE) as f:
        base = json.load(f)["p99_ratio"]
    limit = base * _GATE_SLACK
    status = "ok" if p99_ratio <= limit else "REGRESSION"
    emit("serve_smoke_gate", 0.0,
         f"p99_ratio={p99_ratio:.3f};baseline={base:.3f};"
         f"limit={limit:.3f};{status}")
    if p99_ratio > limit:
        print(f"FAIL: engine p99/raw-wave ratio {p99_ratio:.3f} exceeds "
              f"baseline {base:.3f} by more than {_GATE_SLACK - 1:.0%} "
              f"(limit {limit:.3f}) — serve-path latency regression",
              flush=True)
        return 1
    return 0


def main() -> None:
    from repro.launch.device import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description="serve-engine lifecycle bench")
    ap.add_argument("--smoke", action="store_true",
                    help="short fixed workload + p99-regression gate (CI)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop offered load in QPS "
                         "(0 = 0.7x measured closed capacity)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline for the open-loop runs")
    ap.add_argument("--update-baseline", action="store_true",
                    help="re-record the smoke gate baseline")
    ap.add_argument("--failover", action="store_true",
                    help="bench primary-kill recovery of a 3-member "
                         "replicated cluster instead of the single engine")
    args = ap.parse_args()
    if args.failover:
        raise SystemExit(run_failover(
            smoke=args.smoke, update_baseline=args.update_baseline))
    raise SystemExit(run(smoke=args.smoke, rate=args.rate,
                         deadline_ms=args.deadline_ms,
                         update_baseline=args.update_baseline))


if __name__ == "__main__":
    main()
