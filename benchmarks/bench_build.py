"""Table 4 + Table 6: indexing time and index size vs baselines, the §3.6
complexity claims, and — beyond paper — sequential-vs-batched-vs-device
construction throughput (``WoWIndex.insert`` vs ``insert_batch`` on the
``numpy`` and ``device`` backends).

Emits the usual CSV rows plus a machine-readable ``BENCH_build.json`` at the
repo root so the construction-path perf trajectory is tracked across PRs:

  builds.<n>.sequential_ips        Alg. 1 inserts/sec, one-at-a-time
  builds.<n>.batched_ips           vectorized Alg. 1 (insert_batch, numpy)
  builds.<n>.device_ips            accelerator-resident build (insert_batch
                                   backend="device": jitted hop pipeline over
                                   the frozen snapshot + delta arena)
  builds.<n>.sharded_ips           device build sharded over every visible
                                   device (insert_batch backend="sharded":
                                   shard_map'd phase-1 searches against the
                                   replicated arena, deterministic commit)
  builds.<n>.device_int8_ips       device build over the int8 quantized
                                   arena (per-row f32 scales, dequant fused
                                   in the gather kernel); _bf16_ips likewise
  builds.<n>.device_int8_vs_f32    quantized vs f32 device build (median of
                                   paired-window ratios); _bf16_ likewise
  builds.<n>.speedup               batched vs sequential (median of ratios)
  builds.<n>.device_speedup        device vs sequential (median of ratios)
  builds.<n>.device_vs_host        device vs batched-numpy (median of ratios)
  builds.<n>.sharded_vs_device     sharded vs device (median of ratios)
  builds.<n>.shards                build-mesh size the sharded column used
  parity.{sequential,batched,device,sharded}_recall10  recall@10 vs brute
  parity.bands                     per-selectivity-band recall@10 for all
                                   four paths (gate: batched/device/sharded
                                   within 0.01 of sequential in EVERY band)

Datasets come from the shared regime generators (``tests/_workloads.py`` —
the same Fig. 8 regimes the conformance harness gates); ``--regime`` picks
one (default ``random``, the tracked configuration).

The device backend's beam width is swept over {ef/4, ef/2, ef} and the
fastest setting that passes the per-band parity gate is the one timed and
recorded (``device_width`` in the json) — recall-matched throughput, the
standard accelerator-ANN comparison.  The Thm-3.1 carry keeps quality: the
carry accumulates up to 2*ef+2 already-evaluated candidates per member
regardless of the device search's own beam width.

Sequential and batched builds are timed as back-to-back PAIRS and the
speedup is the median of the per-pair ratios: a shared-core box drifts
between fast and slow epochs, and pairing cancels the epoch out of the
ratio (a ratio-of-minima statistic instead rewards whichever path got the
single luckiest window).  The ips fields report each path's best window.

CLI: ``python -m benchmarks.bench_build [--smoke] [--backend device]``.
``--smoke`` runs a tiny workload end to end (CI) without clobbering the
tracked numbers; with ``--backend device`` the smoke additionally builds on
the device backend and FAILS (non-zero exit) if its recall falls more than
0.01 below the sequential oracle in any selectivity band.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from .common import BENCH_D, BENCH_N, emit, write_csv

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BATCH = 128  # insert_batch micro-batch size under test (host backends)
_DEVICE_BATCH = 512  # device-backend micro-batch (lock-step amortisation)


def _regime_workload(regime: str, n: int, nq: int, with_gt: bool = False,
                     k: int = 10, seed: int = 0):
    """Datasets from the shared regime generators
    (``repro.core.datasets.make_regime_workload``, re-exported to tests as
    ``tests/_workloads.py``) so the bench stresses exactly the
    distributions the build-equivalence harness gates."""
    from repro.core.datasets import make_regime_workload

    return make_regime_workload(regime, n=n, d=BENCH_D, nq=nq, seed=seed,
                                k=k, with_gt=with_gt)


def _recall10(idx, wl, ef=64) -> float:
    from repro.core import brute_force, recall

    recs = []
    for i in range(len(wl.queries)):
        ids, _, _ = idx.search(wl.queries[i], tuple(wl.ranges[i]), k=10, ef=ef)
        gold = brute_force(
            idx.store.vectors[: idx.store.n],
            idx.store.attrs[: idx.store.n],
            wl.queries[i], tuple(wl.ranges[i]), 10,
        )
        recs.append(recall(ids, gold))
    return float(np.mean(recs))


def _band_recalls(idx, wl, fractions=(1.0, 0.25, 0.05), per_band=12, seed=3):
    """Mean recall@10 per selectivity band (the parity-gate statistic)."""
    from repro.core import brute_force, recall

    n = len(wl.attrs)
    sorted_a = np.sort(wl.attrs)
    rng = np.random.default_rng(seed)
    out = {}
    for frac in fractions:
        recs = []
        for i in range(per_band):
            n_in = max(5, int(n * frac))
            s = int(rng.integers(0, n - n_in + 1))
            r = (sorted_a[s], sorted_a[s + n_in - 1])
            q = wl.queries[i % len(wl.queries)]
            ids, _, _ = idx.search(q, r, k=10, ef=80)
            gold = brute_force(
                idx.store.vectors[: idx.store.n],
                idx.store.attrs[: idx.store.n], q, r, 10,
            )
            recs.append(recall(ids, gold))
        out[frac] = float(np.mean(recs))
    return out


def _pick_device_width(wl, kw, seq_bands, dim) -> tuple[int, dict]:
    """Sweep the device beam width small-to-large; keep the fastest setting
    whose per-band recall stays within 0.01 of the sequential oracle."""
    from repro.core import WoWIndex

    ef = kw["ef_construction"]
    for width in (max(kw["m"], ef // 4), ef // 2, ef):
        idx = WoWIndex(dim=dim, **kw)
        idx.insert_batch(wl.vectors, wl.attrs, batch_size=_DEVICE_BATCH,
                         backend="device", device_width=width)
        bands = _band_recalls(idx, wl)
        if all(bands[f] >= seq_bands[f] - 0.01 for f in bands):
            return width, bands
    return ef, bands  # full width is the always-correct fallback


def _bench_persistence(regime: str = "random") -> dict:
    """Durable-lifecycle timings (the ``persistence`` key of
    ``BENCH_build.json``): full vs incremental checkpoint save, checkpoint
    load, crash recovery (checkpoint + WAL-suffix replay), and the
    serve-from-checkpoint cold-start-to-first-query latency."""
    import shutil
    import tempfile

    from repro.core import WoWIndex
    from repro.core.device_search import search_batch
    from repro.persist import load, load_serving_snapshot, open_durable, recover, save

    n = BENCH_N // 4
    wl = _regime_workload(regime, n=n, nq=8)
    kw = dict(m=16, ef_construction=64, o=4, seed=0)
    tail = max(n // 16, 1)  # steady-state mutation interval between ckpts
    out = {"n": n, "delta_rows": tail}
    root = tempfile.mkdtemp(prefix="wow-persist-")
    root2 = tempfile.mkdtemp(prefix="wow-recover-")
    try:
        idx = WoWIndex(dim=BENCH_D, **kw)
        idx.insert_batch(wl.vectors, wl.attrs, batch_size=_BATCH)
        t0 = time.perf_counter()
        path = save(idx, root, incremental=False)
        out["full_save_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        out["checkpoint_bytes"] = sum(
            os.path.getsize(os.path.join(path, f)) for f in os.listdir(path)
        )
        idx.insert_batch(wl.vectors[:tail] + 0.5, wl.attrs[:tail] + 1.0,
                         batch_size=_BATCH)
        t0 = time.perf_counter()
        save(idx, root, incremental=True)
        out["delta_save_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        t0 = time.perf_counter()
        load(root)
        out["load_ms"] = round((time.perf_counter() - t0) * 1e3, 1)

        # recovery: checkpoint + a WAL suffix of one mutation interval
        idx2 = open_durable(root2, create=dict(dim=BENCH_D, **kw))
        idx2.insert_batch(wl.vectors, wl.attrs, batch_size=_BATCH)
        idx2.checkpoint(root2)
        idx2.insert_batch(wl.vectors[:tail] + 0.5, wl.attrs[:tail] + 1.0,
                          batch_size=_BATCH)
        idx2._wal.close()
        t0 = time.perf_counter()
        recover(root2)
        out["recover_ms"] = round((time.perf_counter() - t0) * 1e3, 1)

        # cold start: mmap the newest full checkpoint + first serve wave
        t0 = time.perf_counter()
        snap, _ = load_serving_snapshot(root2)
        out["cold_load_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        search_batch(snap, wl.queries, wl.ranges, k=10, width=64,
                     backend="auto")
        out["cold_first_query_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
    finally:
        shutil.rmtree(root, ignore_errors=True)
        shutil.rmtree(root2, ignore_errors=True)
    emit("persist_full_save", out["full_save_ms"],
         f"bytes={out['checkpoint_bytes']}")
    emit("persist_delta_save", out["delta_save_ms"], f"rows={tail}")
    emit("persist_recover", out["recover_ms"], f"n={n}")
    emit("persist_cold_first_query", out["cold_first_query_ms"],
         f"load={out['cold_load_ms']}")
    return out


def run(regime: str = "random") -> list[list]:
    """Full tracked run: always measures sequential + batched + device +
    sharded (the ``--backend`` flag only selects which SMOKE gate runs)."""
    import jax

    from repro.core import FlatNSW, WoWIndex

    rows = []
    sizes, reps, nq = [BENCH_N // 4, BENCH_N // 2, BENCH_N], 5, 40
    builds = {}
    parity = None
    device_width = None
    shards = len(jax.devices())
    for n in sizes:
        wl = _regime_workload(regime, n=n, nq=nq)
        kw = dict(m=16, ef_construction=64, o=4, seed=0)
        if device_width is None:  # sweep once, on the first (smallest) size
            seq0 = WoWIndex(dim=BENCH_D, **kw)
            for v, a in zip(wl.vectors, wl.attrs):
                seq0.insert(v, a)
            device_width, _ = _pick_device_width(
                wl, kw, _band_recalls(seq0, wl), BENCH_D
            )
        t_seq = t_bat = t_dev = t_shd = np.inf
        idx = idx_b = idx_d = idx_s = None
        ratios, dev_ratios, dev_host, shd_dev = [], [], [], []
        for _ in range(reps):  # paired windows -> per-pair ratios
            idx = WoWIndex(dim=BENCH_D, **kw)
            t0 = time.perf_counter()
            for v, a in zip(wl.vectors, wl.attrs):
                idx.insert(v, a)
            dt_s = time.perf_counter() - t0
            t_seq = min(t_seq, dt_s)
            idx_b = WoWIndex(dim=BENCH_D, **kw)
            t0 = time.perf_counter()
            idx_b.insert_batch(wl.vectors, wl.attrs, batch_size=_BATCH)
            dt_b = time.perf_counter() - t0
            t_bat = min(t_bat, dt_b)
            ratios.append(dt_s / dt_b)
            idx_d = WoWIndex(dim=BENCH_D, **kw)
            t0 = time.perf_counter()
            idx_d.insert_batch(wl.vectors, wl.attrs,
                               batch_size=_DEVICE_BATCH, backend="device",
                               device_width=device_width)
            dt_d = time.perf_counter() - t0
            t_dev = min(t_dev, dt_d)
            dev_ratios.append(dt_s / dt_d)
            dev_host.append(dt_b / dt_d)
            idx_s = WoWIndex(dim=BENCH_D, **kw)
            t0 = time.perf_counter()
            idx_s.insert_batch(wl.vectors, wl.attrs,
                               batch_size=_DEVICE_BATCH, backend="sharded",
                               device_width=device_width, shards=shards)
            dt_sh = time.perf_counter() - t0
            t_shd = min(t_shd, dt_sh)
            shd_dev.append(dt_d / dt_sh)
        speedup = float(np.median(ratios))
        builds[str(n)] = {
            "sequential_ips": round(n / t_seq, 1),
            "batched_ips": round(n / t_bat, 1),
            "device_ips": round(n / t_dev, 1),
            "sharded_ips": round(n / t_shd, 1),
            "speedup": round(speedup, 2),
            "device_speedup": round(float(np.median(dev_ratios)), 2),
            "device_vs_host": round(float(np.median(dev_host)), 2),
            "sharded_vs_device": round(float(np.median(shd_dev)), 2),
            "shards": shards,
            "batch_size": _BATCH,
            "device_batch": _DEVICE_BATCH,
            "device_width": device_width,
        }
        # quantized arena columns: paired windows against a fresh f32
        # device build.  Pair 0 is excluded from the ratio: the f32
        # pipelines are warm from the main reps loop above, but the first
        # quantized build pays jit compilation of the quantized gather /
        # scatter shapes, which would contaminate the paired statistic.
        # (The ips columns report each mode's best window regardless.)
        t_q = {"int8": np.inf, "bf16": np.inf}
        q_ratio = {"int8": [], "bf16": []}
        idx_q = {}
        for pair in range(3):
            idx_f = WoWIndex(dim=BENCH_D, **kw)
            t0 = time.perf_counter()
            idx_f.insert_batch(wl.vectors, wl.attrs,
                               batch_size=_DEVICE_BATCH, backend="device",
                               device_width=device_width)
            dt_f = time.perf_counter() - t0
            for mode in ("int8", "bf16"):
                iq = WoWIndex(dim=BENCH_D, vec_dtype=mode, **kw)
                t0 = time.perf_counter()
                iq.insert_batch(wl.vectors, wl.attrs,
                                batch_size=_DEVICE_BATCH, backend="device",
                                device_width=device_width)
                dt_q = time.perf_counter() - t0
                idx_q[mode] = iq
                if pair == 0:
                    continue  # quantized compile warmup
                t_q[mode] = min(t_q[mode], dt_q)
                q_ratio[mode].append(dt_f / dt_q)
        builds[str(n)].update({
            f"device_{mode}_ips": round(n / t_q[mode], 1)
            for mode in t_q
        })
        builds[str(n)].update({
            f"device_{mode}_vs_f32": round(float(np.median(q_ratio[mode])), 2)
            for mode in q_ratio
        })
        for mode in ("int8", "bf16"):
            rows.append([f"wow_device_{mode}", n, round(t_q[mode], 3),
                         idx_q[mode].memory_bytes(),
                         idx_q[mode].graph.num_layers])
            emit(f"build_wow_device_{mode}_n{n}", t_q[mode] / n * 1e6,
                 f"vs_f32={np.median(q_ratio[mode]):.2f}x")
        rows.append(["wow", n, round(t_seq, 3), idx.memory_bytes(),
                     idx.graph.num_layers])
        rows.append(["wow_batched", n, round(t_bat, 3), idx_b.memory_bytes(),
                     idx_b.graph.num_layers])
        rows.append(["wow_device", n, round(t_dev, 3), idx_d.memory_bytes(),
                     idx_d.graph.num_layers])
        rows.append(["wow_sharded", n, round(t_shd, 3), idx_s.memory_bytes(),
                     idx_s.graph.num_layers])
        emit(f"build_wow_n{n}", t_seq / n * 1e6, f"bytes={idx.memory_bytes()}")
        emit(f"build_wow_batched_n{n}", t_bat / n * 1e6,
             f"speedup={speedup:.2f}x;batch={_BATCH}")
        emit(f"build_wow_device_n{n}", t_dev / n * 1e6,
             f"vs_host={np.median(dev_host):.2f}x;width={device_width}")
        emit(f"build_wow_sharded_n{n}", t_shd / n * 1e6,
             f"vs_device={np.median(shd_dev):.2f}x;shards={shards}")
        if n == sizes[-1]:
            r_seq = _recall10(idx, wl)
            r_bat = _recall10(idx_b, wl)
            r_dev = _recall10(idx_d, wl)
            r_shd = _recall10(idx_s, wl)
            b_seq = _band_recalls(idx, wl)
            b_bat = _band_recalls(idx_b, wl)
            b_dev = _band_recalls(idx_d, wl)
            b_shd = _band_recalls(idx_s, wl)
            parity = {
                "sequential_recall10": round(r_seq, 4),
                "batched_recall10": round(r_bat, 4),
                "device_recall10": round(r_dev, 4),
                "sharded_recall10": round(r_shd, 4),
                "delta": round(r_bat - r_seq, 4),
                "device_delta": round(r_dev - r_seq, 4),
                "sharded_delta": round(r_shd - r_seq, 4),
                "bands": {
                    str(f): {
                        "sequential": round(b_seq[f], 4),
                        "batched": round(b_bat[f], 4),
                        "device": round(b_dev[f], 4),
                        "sharded": round(b_shd[f], 4),
                    }
                    for f in b_seq
                },
            }
            emit(f"build_parity_n{n}", 0.0,
                 f"seq={r_seq:.4f};batched={r_bat:.4f};device={r_dev:.4f};"
                 f"sharded={r_shd:.4f}")
            bad = [
                (path, f)
                for f in b_seq
                for path, bands in (("batched", b_bat), ("device", b_dev),
                                    ("sharded", b_shd))
                if bands[f] < b_seq[f] - 0.01
            ]
            if bad:
                print(f"WARNING: recall-parity regression: {bad}")

        # WoW o=2 (more layers) + HNSW-L0, sequential baselines as before
        idx2 = WoWIndex(dim=BENCH_D, m=16, ef_construction=64, o=2, seed=0)
        t0 = time.perf_counter()
        for v, a in zip(wl.vectors, wl.attrs):
            idx2.insert(v, a)
        dt2 = time.perf_counter() - t0
        rows.append(["wow_o2", n, round(dt2, 3), idx2.memory_bytes(),
                     idx2.graph.num_layers])
        emit(f"build_wow_o2_n{n}", dt2 / n * 1e6, f"bytes={idx2.memory_bytes()}")
        flat = FlatNSW(BENCH_D, m=16, ef_construction=64, seed=0)
        t0 = time.perf_counter()
        for v, a in zip(wl.vectors, wl.attrs):
            flat.insert(v, a)
        dt3 = time.perf_counter() - t0
        fbytes = sum(l.nbytes for l in flat.graph.layers)
        rows.append(["hnsw_l0", n, round(dt3, 3), fbytes, 1])
        emit(f"build_hnswl0_n{n}", dt3 / n * 1e6, f"bytes={fbytes}")

    # per-insert scaling: O(log^2 n) claim — fit us/insert against log2(n)^2
    per_insert = [r[2] / r[1] * 1e6 for r in rows if r[0] == "wow"]
    l2 = [np.log2(n) ** 2 for n in sizes]
    if len(sizes) > 1:
        slope = np.polyfit(l2, per_insert, 1)[0]
        emit("build_scaling_slope", per_insert[-1], f"us_per_log2sq={slope:.3f}")
        rows.append(["wow_scaling_slope", sizes[-1], slope, 0, 0])

    record = {
        "platform": jax.devices()[0].platform,
        "devices": shards,
        "workload": {"d": BENCH_D, "m": 16, "ef_construction": 64,
                     "o": 4, "regime": regime},
        "builds": builds,
        "parity": parity,
        "persistence": _bench_persistence(regime),
    }
    with open(os.path.join(_REPO_ROOT, "BENCH_build.json"), "w") as f:
        json.dump(record, f, indent=1)

    write_csv("bench_build.csv", ["index", "n", "seconds", "bytes", "layers"], rows)
    return rows


def _run_smoke_host_only(regime: str = "random") -> list[list]:
    """The pre-device smoke: sequential + batched numpy only (fast path for
    ``--smoke`` without ``--backend device``)."""
    from repro.core import WoWIndex

    wl = _regime_workload(regime, n=400, nq=10)
    kw = dict(m=16, ef_construction=64, o=4, seed=0)
    rows = []
    idx = WoWIndex(dim=BENCH_D, **kw)
    t0 = time.perf_counter()
    for v, a in zip(wl.vectors, wl.attrs):
        idx.insert(v, a)
    rows.append(["wow", 400, round(time.perf_counter() - t0, 3),
                 idx.memory_bytes(), idx.graph.num_layers])
    idx_b = WoWIndex(dim=BENCH_D, **kw)
    t0 = time.perf_counter()
    idx_b.insert_batch(wl.vectors, wl.attrs, batch_size=_BATCH)
    rows.append(["wow_batched", 400, round(time.perf_counter() - t0, 3),
                 idx_b.memory_bytes(), idx_b.graph.num_layers])
    r_seq, r_bat = _recall10(idx, wl), _recall10(idx_b, wl)
    emit("build_parity_smoke", 0.0, f"seq={r_seq:.4f};batched={r_bat:.4f}")
    if r_bat < r_seq - 0.01:
        raise SystemExit(
            f"batched recall regression: {r_bat:.4f} vs {r_seq:.4f}"
        )
    write_csv("bench_build.csv", ["index", "n", "seconds", "bytes", "layers"],
              rows)
    return rows


def _smoke_oracle(regime: str):
    """Shared smoke scaffold: tiny regime workload + the sequential-oracle
    index and its per-band recalls (the reference side of every gate)."""
    from repro.core import WoWIndex

    wl = _regime_workload(regime, n=400, nq=10)
    kw = dict(m=16, ef_construction=64, o=4, seed=0)
    seq = WoWIndex(dim=BENCH_D, **kw)
    for v, a in zip(wl.vectors, wl.attrs):
        seq.insert(v, a)
    return wl, kw, _band_recalls(seq, wl)


def _gate_bands(label: str, seq_bands: dict, got_bands: dict) -> None:
    """Per-band recall-parity gate shared by every smoke (non-zero exit)."""
    bad = [f for f in seq_bands if got_bands[f] < seq_bands[f] - 0.01]
    if bad:
        raise SystemExit(
            f"{label} recall-parity regression in bands {bad}: "
            f"{label}={got_bands} vs sequential={seq_bands}"
        )


def _gate_graphs_bitwise(label: str, a, b) -> None:
    """Bitwise adjacency/degree equality gate (non-zero exit) — the bench
    twin of ``tests/_invariants.assert_graph_equal``."""
    if a.graph.num_layers != b.graph.num_layers:
        raise SystemExit(f"{label}: layer counts diverge")
    for l in range(a.graph.num_layers):
        if not (np.array_equal(a.graph.layers[l], b.graph.layers[l])
                and np.array_equal(a.graph.counts[l], b.graph.counts[l])):
            raise SystemExit(f"{label}: graphs diverge at layer {l}")


def _run_smoke_device(regime: str = "random") -> None:
    """CI gate for the accelerator-resident build: sequential oracle vs
    device-backend build on a tiny workload, per-band recall parity
    enforced (non-zero exit on regression)."""
    from repro.core import WoWIndex

    wl, kw, seq_bands = _smoke_oracle(regime)
    t0 = time.perf_counter()
    dev = WoWIndex(dim=BENCH_D, **kw)
    dev.insert_batch(wl.vectors, wl.attrs, batch_size=_BATCH,
                     backend="device", device_width=16)
    dt = time.perf_counter() - t0
    dev_bands = _band_recalls(dev, wl)
    # the arenas must have stayed delta-maintained (no per-batch re-stack)
    assert dev._arena is not None and dev._arena.stats["full_uploads"] <= 2, (
        dev._arena.stats
    )
    emit("build_device_smoke", dt * 1e3,
         ";".join(f"{f}={dev_bands[f]:.4f}" for f in dev_bands))
    _gate_bands("device-build", seq_bands, dev_bands)
    print(f"device smoke OK: {len(wl.attrs)} inserts in {dt:.1f}s, "
          f"bands {dev_bands}")


def _run_smoke_sharded(regime: str = "random") -> None:
    """CI gate for the sharded build (multi-device job): the sharded
    backend over every visible device must produce a graph bitwise
    identical to ``backend="device"`` AND stay within the per-band recall
    parity gate vs the sequential oracle (non-zero exit on either)."""
    import jax

    from repro.core import WoWIndex

    wl, kw, seq_bands = _smoke_oracle(regime)
    dev = WoWIndex(dim=BENCH_D, **kw)
    dev.insert_batch(wl.vectors, wl.attrs, batch_size=_BATCH,
                     backend="device", device_width=16)
    shards = len(jax.devices())
    t0 = time.perf_counter()
    shd = WoWIndex(dim=BENCH_D, **kw)
    shd.insert_batch(wl.vectors, wl.attrs, batch_size=_BATCH,
                     backend="sharded", device_width=16, shards=shards)
    dt = time.perf_counter() - t0
    _gate_graphs_bitwise(
        f"sharded build (shards={shards}) vs device — the "
        "shard-count-invariance gate", dev, shd,
    )
    shd_bands = _band_recalls(shd, wl)
    emit("build_sharded_smoke", dt * 1e3,
         f"shards={shards};" + ";".join(
             f"{f}={shd_bands[f]:.4f}" for f in shd_bands))
    _gate_bands("sharded-build", seq_bands, shd_bands)
    print(f"sharded smoke OK: {len(wl.attrs)} inserts over {shards} "
          f"shard(s) in {dt:.1f}s, bitwise == device, bands {shd_bands}")


def main() -> None:
    import argparse

    from repro.launch.device import use_compile_cache

    use_compile_cache()
    ap = argparse.ArgumentParser(description="construction-path bench")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny workload end to end (CI); with --backend "
                         "device/sharded, gates build recall parity (and "
                         "sharded-vs-device bitwise equality)")
    ap.add_argument("--backend", default="numpy",
                    choices=("numpy", "device", "sharded"),
                    help="batched-construction engine the smoke exercises: "
                         "'numpy' = host BLAS lock-step search; 'device' = "
                         "the accelerator-resident build (jitted hop "
                         "pipeline over the frozen snapshot + delta arena; "
                         "insert_batch(backend='device')); 'sharded' = the "
                         "device build shard_map'd over every visible "
                         "device.  Full (non-smoke) runs always measure all "
                         "of them and record every column in "
                         "BENCH_build.json")
    ap.add_argument("--regime", default="random",
                    help="workload regime from tests/_workloads.py "
                         "(random, correlated, anticorrelated, clustered, "
                         "duplicate_heavy, adversarial_sorted)")
    ap.add_argument("--persist-only", action="store_true",
                    help="re-measure only the durable-lifecycle timings "
                         "(checkpoint save/load, recovery, cold start) and "
                         "update the 'persistence' key of BENCH_build.json "
                         "in place, leaving the build columns untouched")
    args = ap.parse_args()
    if args.persist_only:
        path = os.path.join(_REPO_ROOT, "BENCH_build.json")
        record = {}
        if os.path.exists(path):
            with open(path) as f:
                record = json.load(f)
        record["persistence"] = _bench_persistence(args.regime)
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        print(f"persistence: {record['persistence']}")
    elif args.smoke and args.backend == "sharded":
        _run_smoke_sharded(args.regime)
    elif args.smoke and args.backend == "device":
        _run_smoke_device(args.regime)
    elif args.smoke:
        _run_smoke_host_only(args.regime)
    else:
        if args.backend != "numpy":
            print(f"note: full runs measure every backend; --backend "
                  f"{args.backend} only selects a smoke gate")
        run(regime=args.regime)


if __name__ == "__main__":
    main()
