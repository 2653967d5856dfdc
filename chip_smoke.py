#!/usr/bin/env python3
"""Smoke run of the WoW build-and-serve path on a TPU.

    python chip_smoke.py              # one chip, n = 2^16
    python chip_smoke.py --n 262144   # a larger corpus
    python chip_smoke.py --chips 4    # query-sharded serving over 4 chips

One chip: builds a d = 128 index (SIFT1M's width) with the device build,
serves the paper's mixed-selectivity workload (2^-10 .. 2^0) through
``ServeEngine`` with f32 and int8 vector slabs, ingests 4,096 rows under
serving through the write-ahead log, and checks: f32 recall@10 per
selectivity band against the host search on the same graph (before and
after the ingest), the int8 kernel per band against the XLA gather on the
same int8 slab, int8 per band against f32, that the one-hot and
sort merges return identical ids, and that the compiled Pallas gather
kernel is in the serve chunk program.

``--chips 4``: builds n = 2^14 on the host as set-up, serves the same
queries through ``make_serving_fn`` over a (4, 1) data x model mesh (index
replicated, queries sharded) and checks the ids equal one-chip
``search_batch`` on the same snapshot.  It runs no other phase.

Exits non-zero, printing no result, when JAX finds no TPU or any check
fails.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

import numpy as np  # noqa: E402

from repro.analysis.compile_guard import CompileCounter  # noqa: E402
from repro.launch.device import describe_devices, use_compile_cache  # noqa: E402

D = 128  # SIFT1M's width
NQ = 256
K = 10
WIDTH = 64  # serving beam (EngineConfig default)
SEED = 0
N_INGEST = 4096
N_SHARDED = 1 << 14  # corpus of the four-chip phase
BUILD_BATCH = 512
F32_GATE = 0.01  # host vs device f32, per band (CPU equivalence harness)
INT8_GATE = 0.03  # int8 vs f32, per band (quantization loss)
WORK = os.path.join(HERE, ".chip_smoke")  # WAL + index root (git-ignored)


def check(ok: bool, what: str) -> None:
    if not ok:
        print(f"FAILED: {what}", file=sys.stderr, flush=True)
        raise SystemExit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


def band_recall(found: list, gold: list, fractions: np.ndarray) -> dict:
    """Mean recall@K per selectivity band (in-range fraction)."""
    from repro.core import recall

    out = {}
    for f in sorted(set(fractions.tolist())):
        rows = np.flatnonzero(fractions == f)
        out[f] = float(np.mean([recall(found[i], gold[i]) for i in rows]))
    return out


def fmt_bands(bands: dict) -> str:
    return " ".join(f"2^{int(np.log2(f))}={r:.4f}" for f, r in bands.items())


def host_search(idx, queries, ranges) -> list:
    return [idx.search(q, tuple(r), k=K, ef=WIDTH)[0]
            for q, r in zip(queries, ranges)]


def serve_burst(eng, queries, ranges) -> tuple[list, float]:
    """A closed burst through the engine: submit all, drain, ids by rid."""
    from repro.serve.lifecycle import Ticket

    t0 = time.perf_counter()
    tickets = [eng.submit(q, r) for q, r in zip(queries, ranges)]
    check(all(isinstance(t, Ticket) for t in tickets), "burst admitted")
    replies = {r.rid: r for r in eng.drain()}
    wall = time.perf_counter() - t0
    check(all(t.rid in replies for t in tickets), "every request replied")
    check(not any(replies[t.rid].degraded for t in tickets),
          "no degraded replies")
    return [replies[t.rid].ids[replies[t.rid].ids >= 0] for t in tickets], wall


def gate_bands(name: str, got: dict, ref: dict, gate: float) -> None:
    worst = min(got[f] - ref[f] for f in got)
    say(f"{name}: worst band delta {worst:+.4f} (gate -{gate})")
    check(worst >= -gate, f"{name} recall within {gate} of its reference")


def kernel_in_serve_chunk(eng) -> None:
    """The serve chunk jit the engine ran, lowered again: its compiled text
    must hold the Pallas kernel (interpret mode or the jnp reference would
    leave no ``tpu_custom_call``)."""
    import jax.numpy as jnp

    from repro.core.device_search import _init_jit, _run_jit

    cfg = eng._wave_cfg(eng._snap)
    B = eng.config.max_wave
    st = _init_jit(eng._di, jnp.zeros((B, D), jnp.float32),
                   jnp.tile(jnp.asarray([[1.0, 0.0]], jnp.float32), (B, 1)),
                   cfg)
    text = _run_jit.lower(eng._di, st, cfg=cfg,
                          h=eng._chunk_schedule()[1]).compile().as_text()
    check("tpu_custom_call" in text, "tpu_custom_call in the serve chunk")
    say("kernel: tpu_custom_call present in the compiled serve chunk")


def merges_agree(di, snap, queries, ranges) -> None:
    """One-hot and sort writebacks at W + K > 256, where a one-hot
    contraction rounded to bf16 would write back wrong slots."""
    import jax.numpy as jnp

    from repro.core.device_search import device_search

    q = jnp.asarray(queries, jnp.float32)
    r = jnp.asarray(ranges, jnp.float32)
    kw = dict(k=K, width=256, m=snap.m, o=snap.o)
    a = device_search(di, q, r, merge="onehot", **kw)
    b = device_search(di, q, r, merge="sort", **kw)
    same = np.array_equal(np.asarray(a.ids), np.asarray(b.ids))
    check(same, "merge='onehot' ids == merge='sort' ids (width 256)")
    say("merge: onehot == sort ids at width 256 (W + K = 273)")


def one_chip(n: int) -> None:
    from repro.core import make_workload
    from repro.core.datasets import make_vectors
    from repro.core.oracle import brute_force
    from repro.persist import open_durable
    from repro.serve.lifecycle import EngineConfig, ServeEngine

    say(f"scale: n = {n} rows, cut {1_000_000 / n:.1f}x from SIFT1M's 10^6: "
        f"the host edge commit bounds the build")
    t0 = time.perf_counter()
    wl = make_workload(n=n, d=D, nq=NQ, seed=SEED, k=K)
    say(f"workload: {n} x {D}, {NQ} queries, ground truth on the host in "
        f"{time.perf_counter() - t0:.3f} s")

    shutil.rmtree(WORK, ignore_errors=True)
    idx = open_durable(os.path.join(WORK, "index"),
                       create=dict(dim=D, m=16, o=4, seed=SEED))
    t0 = time.perf_counter()
    with CompileCounter() as cc:
        idx.insert_batch(wl.vectors, wl.attrs, batch_size=BUILD_BATCH,
                         backend="device")
    build_s = time.perf_counter() - t0
    say(f"build: {n} rows in {build_s:.3f} s = {n / build_s:.1f} rows/s "
        f"(insert_batch backend=device, batch {BUILD_BATCH}, "
        f"{idx.graph.num_layers} layers; {cc.count} compiles, "
        f"{cc.total_secs:.3f} s in the backend compiler)")

    fr = wl.fractions
    t0 = time.perf_counter()
    host = band_recall(host_search(idx, wl.queries, wl.ranges), wl.gt, fr)
    say(f"host search ({time.perf_counter() - t0:.3f} s): {fmt_bands(host)}")

    bands = {}
    for name, vec_dtype, backend in (("f32", "f32", "auto"),
                                     ("int8", "int8", "auto"),
                                     ("int8 jnp reference", "int8", "ref")):
        eng = ServeEngine(index=idx, config=EngineConfig(
            k=K, width=WIDTH, vec_dtype=vec_dtype, backend=backend))
        with CompileCounter() as cc:
            warm = eng.warmup()
        found, wall = serve_burst(eng, wl.queries, wl.ranges)
        bands[name] = band_recall(found, wl.gt, fr)
        say(f"serve {name}: warmup {warm:.3f} s ({cc.count} compiles), "
            f"burst of {NQ} in {wall:.3f} s ({NQ / wall:.1f} q/s): "
            f"{fmt_bands(bands[name])}")
        if name == "f32":
            f32_eng = eng
    gate_bands("f32 vs host", bands["f32"], host, F32_GATE)
    # the kernel's int8 path against the XLA gather on the same int8 slab
    # first: separates a kernel fault from quantization loss
    gate_bands("int8 vs int8 jnp reference", bands["int8"],
               bands["int8 jnp reference"], F32_GATE)
    gate_bands("int8 vs f32", bands["int8"], bands["f32"], INT8_GATE)

    eng = f32_eng
    kernel_in_serve_chunk(eng)
    merges_agree(eng._di, eng._snap, wl.queries, wl.ranges)

    # ingest under serving: rows from the same clusters, attributes
    # interleaved with the existing ones, WAL-acked before they apply
    extra = make_vectors(n + N_INGEST, D, seed=SEED)[n:]
    extra_attrs = np.random.default_rng(SEED + 5).uniform(0, n, N_INGEST)
    t0 = time.perf_counter()
    with CompileCounter() as cc:
        ack = eng.submit_ingest(extra, extra_attrs)
        check(ack.accepted == N_INGEST and ack.lsn > 0, "ingest acked via WAL")
        serve_burst(eng, wl.queries, wl.ranges)  # interleaves with ingest
    check(eng.pending_ingest == 0 and len(idx) == n + N_INGEST,
          "ingest applied")
    say(f"ingest: {N_INGEST} rows under serving in "
        f"{time.perf_counter() - t0:.3f} s (WAL lsn {ack.lsn}; "
        f"{cc.count} compiles)")

    vecs = np.concatenate([wl.vectors, extra])
    attrs = np.concatenate([wl.attrs, idx.store.attrs[n:n + N_INGEST]])
    gold = [brute_force(vecs, attrs, q, tuple(r), K)
            for q, r in zip(wl.queries, wl.ranges)]
    host2 = band_recall(host_search(idx, wl.queries, wl.ranges), gold, fr)
    found, wall = serve_burst(eng, wl.queries, wl.ranges)
    grown = band_recall(found, gold, fr)
    say(f"re-serve f32 after ingest ({wall:.3f} s): {fmt_bands(grown)}")
    say(f"host search after ingest: {fmt_bands(host2)}")
    gate_bands("f32 vs host after ingest", grown, host2, F32_GATE)


def four_chips() -> None:
    from repro.core import WoWIndex, make_workload, recall
    from repro.core.device_search import search_batch
    from repro.core.distributed import make_serving_fn
    from repro.core.snapshot import take_snapshot
    from repro.launch.mesh import make_host_mesh

    n = N_SHARDED
    wl = make_workload(n=n, d=D, nq=NQ, seed=SEED, k=K)
    idx = WoWIndex(dim=D, m=16, o=4, seed=SEED)
    t0 = time.perf_counter()
    # set-up on the host: four chips are charged for every second, and the
    # device build is the one-chip run's subject, not this phase's
    idx.insert_batch(wl.vectors, wl.attrs, batch_size=BUILD_BATCH)
    say(f"set-up: {n} rows built on the host in "
        f"{time.perf_counter() - t0:.3f} s")
    snap = take_snapshot(idx)
    mesh = make_host_mesh((4, 1), ("data", "model"))
    serve = make_serving_fn(mesh, snap, k=K, width=WIDTH)
    t0 = time.perf_counter()
    res = serve(wl.queries, wl.ranges)
    ids4 = np.asarray(res.ids)
    say(f"sharded serve (4 x 1 mesh, first call incl. compile): "
        f"{time.perf_counter() - t0:.3f} s")
    one = np.asarray(search_batch(snap, wl.queries, wl.ranges, k=K,
                                  width=WIDTH).ids)
    check(np.array_equal(ids4, one), "sharded ids == one-chip ids")
    held = {s.device for s in res.ids.addressable_shards}
    check(len(held) == 4, "query results spread over 4 devices")
    check(len(serve.device_index.vectors.sharding.device_set) == 4,
          "index replicated on 4 devices")
    rec = np.mean([recall(snap.ids_map[r[r >= 0]], g)
                   for r, g in zip(ids4, wl.gt)])
    say(f"sharded ids == one-chip ids for {NQ} queries; results on "
        f"{len(held)} devices; recall@{K} = {rec:.4f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=1 << 16,
                    help="corpus rows for the one-chip run")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the query-sharded serving phase")
    args = ap.parse_args()

    dev = describe_devices()
    say(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print("no TPU found: chip_smoke.py runs on a TPU only",
              file=sys.stderr)
        raise SystemExit(2)
    say(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        check(dev["count"] == 4, "four devices visible")
        four_chips()
    else:
        one_chip(args.n)
    say(f"total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
