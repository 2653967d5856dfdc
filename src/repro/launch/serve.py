"""Serving launcher: build/load a WoW index and serve batched range-filtered
queries on the device path (optionally on a data-sharded mesh).

    PYTHONPATH=src python -m repro.launch.serve --n 4000 --queries 256
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser(description="repro WoW serving launcher")
    ap.add_argument("--n", type=int, default=4000)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--queries", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--m", type=int, default=16)
    ap.add_argument("--ef-construction", type=int, default=64)
    ap.add_argument("--o", type=int, default=4)
    ap.add_argument("--mesh", default="", help='e.g. "4x2" -> (data, model)')
    ap.add_argument("--backend", default="auto", choices=("auto", "pallas", "ref"),
                    help="distance-kernel dispatch (see repro.kernels.ops)")
    ap.add_argument("--vec-dtype", default="f32",
                    choices=("f32", "int8", "bf16"),
                    help="on-device vector-slab storage: f32 (oracle), int8 "
                         "(per-row f32 scales, 4x less HBM traffic) or bf16 "
                         "(2x); dequant is fused into the Pallas gather "
                         "kernel, so candidate rows never materialize in "
                         "f32 HBM (quantized modes require --pipeline fused)")
    ap.add_argument("--pipeline", default="fused", choices=("fused", "reference"),
                    help="hop pipeline: fused (production) or the pre-refactor "
                         "reference (parity/benchmark oracle)")
    ap.add_argument("--visited", default="bitmap", choices=("bitmap", "hash"),
                    help="visited-set state: exact [B, n/32] bitmap or the "
                         "constant-size double-hashed filter (O(budget), not "
                         "O(n) — the only option at million-vector scale)")
    ap.add_argument("--visited-bits", type=int, default=None,
                    help="hash-filter bits per query (pow2; default sized "
                         "from the search budget at a 2%% FP target)")
    ap.add_argument("--compact", default="",
                    help='ragged-batch compaction schedule "H0,H" (e.g. '
                         '"64,128"): chunk the hop loop and compact '
                         "finished queries out between chunks (single-host "
                         "path only)")
    ap.add_argument("--build-batch", type=int, default=128,
                    help="micro-batch size for batched construction "
                         "(insert_batch, vectorized Alg. 1); 0 = the "
                         "sequential insert loop")
    ap.add_argument("--build-backend", default="numpy",
                    choices=("numpy", "ops", "device", "sharded"),
                    help="insert_batch phase-1 engine: host BLAS (numpy), "
                         "host search + fused gather kernel (ops), the "
                         "accelerator-resident build — jitted hop pipeline "
                         "over the frozen snapshot + delta arena (device) — "
                         "or that build shard_map'd over a device mesh "
                         "(sharded; see --build-shards)")
    ap.add_argument("--build-shards", type=int, default=0,
                    help="with --build-backend sharded: build-mesh size "
                         "(0 = every visible device)")
    ap.add_argument("--ingest", type=int, default=0,
                    help="ingest-while-serve: after the first serve wave, "
                         "stream N extra vectors through insert_batch, "
                         "refresh the snapshot incrementally and re-serve "
                         "the queries")
    ap.add_argument("--adaptive-filter", action="store_true",
                    help="with --visited hash: re-size the visited filter "
                         "for the post-ingest re-serve from the measured "
                         "hop histogram of the first wave (p99 + slack; "
                         "worst-case sizing remains the cold-start default)")
    ap.add_argument("--compact-rows", action="store_true",
                    help="run the tombstone compaction pass "
                         "(WoWIndex.compact_rows) before serving")
    ap.add_argument("--index-dir", default="",
                    help="durable lifecycle root: serve-from-checkpoint cold "
                         "start when the directory holds checkpoints (mmap'd "
                         "slabs, no rebuild), otherwise build the index "
                         "durably (WAL-logged ingest) and checkpoint it there")
    ap.add_argument("--compact-threshold", type=float, default=None,
                    help="background compaction cadence: run compact_rows "
                         "automatically once the tombstone fraction reaches "
                         "this value (checked at insert_batch / checkpoint "
                         "boundaries; logged via repro.core.index)")
    ap.add_argument("--engine", action="store_true",
                    help="serve through the request-lifecycle engine "
                         "(repro.serve.lifecycle): admission queue + "
                         "deadlines + backpressure + degraded-mode search; "
                         "--ingest rides the same scheduler via the "
                         "WAL-backed ingest queue")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="with --engine: open-loop arrival rate in "
                         "queries/s (0 = submit everything immediately, "
                         "i.e. a closed burst)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="with --engine: per-request deadline; requests "
                         "that cannot finish in time complete degraded "
                         "(reduced hop budget), never time out")
    ap.add_argument("--max-wave", type=int, default=64,
                    help="with --engine: widest scheduled wave")
    ap.add_argument("--queue-cap", type=int, default=512,
                    help="with --engine: admission-queue bound; submits "
                         "past it are rejected with a retry-after hint")
    ap.add_argument("--cluster", type=int, default=0,
                    help="replicated serving: run N members (primary + N-1 "
                         "replicas, WAL shipping + quorum-durable ingest "
                         "acks), route the query stream across them, and "
                         "demonstrate a zero-downtime rolling restart "
                         "mid-stream (drain -> checkpoint -> restart -> "
                         "catch-up -> readmit, one member at a time); "
                         "roots live under --index-dir (or a temp dir)")
    ap.add_argument("--cluster-quorum", type=int, default=0,
                    help="with --cluster: members (primary included) that "
                         "must fsync before an ingest ack (0 = majority)")
    ap.add_argument("--trace-compiles", action="store_true",
                    help="print every XLA backend compile to stderr as it "
                         "happens (wowlint compile guard): a compile after "
                         "warmup is a shape-stability bug, visible here as "
                         "a timestamped line instead of a silent p99 spike")
    args = ap.parse_args()

    if args.vec_dtype != "f32" and args.pipeline == "reference":
        ap.error("--vec-dtype int8/bf16 requires --pipeline fused (the "
                 "reference pipeline has no fused-dequant gather)")

    from .device import describe_devices, use_compile_cache

    use_compile_cache()
    dev = describe_devices()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")

    if args.trace_compiles:
        from ..analysis.compile_guard import trace_compiles

        _tracer = trace_compiles("launch.serve")
        _tracer.__enter__()  # left active for the whole process

    import numpy as np

    from ..core import WoWIndex, make_workload, recall
    from ..core.snapshot import take_snapshot

    wl = make_workload(n=args.n, d=args.dim, nq=args.queries, seed=0,
                       k=args.k)
    if args.cluster > 1:
        if args.mesh:
            ap.error("--cluster and --mesh are mutually exclusive")
        _serve_cluster(args, wl, recall)
        return
    build_kw = {}
    if args.build_shards > 0:
        if args.build_backend != "sharded":
            ap.error("--build-shards requires --build-backend sharded")
        build_kw["shards"] = args.build_shards

    idx = None
    snap = None
    if args.index_dir:
        from ..persist import is_durable_dir, load_serving_snapshot, open_durable

        if is_durable_dir(args.index_dir):
            # serve-from-checkpoint cold start: the serving snapshot comes
            # straight off the newest checkpoint's mmap'd slabs — no host
            # index, no graph replay, first query before the slabs page in
            cold_t0 = time.time()
            snap, meta = load_serving_snapshot(args.index_dir)
            print(f"cold start from {args.index_dir}: {snap.n} vectors "
                  f"(checkpoint lsn {meta['lsn']}) mapped in "
                  f"{(time.time()-cold_t0)*1e3:.0f} ms")
        else:
            idx = open_durable(
                args.index_dir,
                create=dict(dim=args.dim, m=args.m,
                            ef_construction=args.ef_construction, o=args.o,
                            seed=0, vec_dtype=args.vec_dtype),
                compact_threshold=args.compact_threshold,
            )
    else:
        idx = WoWIndex(dim=args.dim, m=args.m,
                       ef_construction=args.ef_construction,
                       o=args.o, seed=0,
                       compact_threshold=args.compact_threshold,
                       vec_dtype=args.vec_dtype)
    if idx is not None:
        t0 = time.time()
        if args.build_batch > 0:
            idx.insert_batch(wl.vectors, wl.attrs, batch_size=args.build_batch,
                             backend=args.build_backend, **build_kw)
            how = f"batched/{args.build_backend} (micro-batch {args.build_batch})"
        else:
            for v, a in zip(wl.vectors, wl.attrs):
                idx.insert(v, a)
            how = "sequential"
        if args.index_dir:
            how += ", WAL-logged"
        print(f"indexed {len(idx)} vectors in {time.time()-t0:.1f}s [{how}] "
              f"({idx.graph.num_layers} layers, {idx.memory_bytes()/2**20:.1f} MiB)")
        if args.compact_rows:
            t0 = time.time()
            nrows = idx.compact_rows()
            print(f"compact_rows: {nrows} rows rebuilt in {time.time()-t0:.2f}s")
        if args.index_dir:
            t0 = time.time()
            path = idx.checkpoint(args.index_dir)
            print(f"checkpointed to {path} in {(time.time()-t0)*1e3:.0f} ms")
        snap = take_snapshot(idx)

    compact = None
    if args.compact:
        h0, h1 = (int(x) for x in args.compact.split(","))
        compact = (h0, h1)

    if args.engine:
        if args.mesh:
            ap.error("--engine and --mesh are mutually exclusive (the "
                     "engine schedules waves itself)")
        _serve_engine(args, wl, idx, snap, recall)
        return

    if args.mesh:
        import jax

        from ..core.distributed import make_serving_fn
        from .mesh import make_host_mesh

        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_host_mesh((d, m), ("data", "model"))
        serve = make_serving_fn(mesh, snap, k=args.k, width=args.width,
                                backend=args.backend, pipeline=args.pipeline,
                                visited=args.visited,
                                visited_bits=args.visited_bits,
                                visited_adaptive=args.adaptive_filter,
                                vec_dtype=args.vec_dtype)
        res = serve(wl.queries, wl.ranges)
        if args.adaptive_filter and args.visited == "hash":
            print(f"adaptive visited filter (sharded, psum'd hop histogram): "
                  f"{serve.state['bits']} bits/query after "
                  f"{int(serve.state['hist'].sum())} queries")
    else:
        from ..core.device_search import search_batch

        res = search_batch(snap, wl.queries, wl.ranges, k=args.k,
                           width=args.width, backend=args.backend,
                           pipeline=args.pipeline, visited=args.visited,
                           visited_bits=args.visited_bits, compact=compact,
                           vec_dtype=args.vec_dtype)
    import numpy as np

    ids = np.asarray(res.ids)
    if idx is None and snap is not None:
        print(f"cold-start-to-first-query: "
              f"{(time.time()-cold_t0)*1e3:.0f} ms (load + serve wave)")
    t0 = time.time()
    recs = []
    for i in range(args.queries):
        got = np.asarray([int(snap.ids_map[j]) for j in ids[i] if j >= 0])
        recs.append(recall(got, wl.gt[i]))
    hops = np.asarray(res.hops)
    print(f"served {args.queries} queries: recall@{args.k} = {np.mean(recs):.4f}, "
          f"mean DC = {float(np.mean(np.asarray(res.dc))):.0f}, "
          f"mean hops = {float(np.mean(hops)):.0f}")
    q = np.percentile(hops, [50, 90, 99, 100]).astype(int)
    print(f"hops-to-termination: p50={q[0]} p90={q[1]} p99={q[2]} max={q[3]} "
          f"(ragged batches pay max without --compact)")

    if args.ingest > 0:
        # ingest-while-serve: micro-batch inserts + incremental snapshot
        # refresh (take_snapshot(prev=...): block-copied prefixes + dirty-row
        # scatters, no re-compaction argsort), then re-serve
        from ..core.datasets import make_attrs, make_vectors
        from ..core.device_search import search_batch

        extra_v = make_vectors(args.ingest, args.dim, seed=99)
        extra_a = make_attrs(extra_v, seed=99) + float(np.max(wl.attrs)) + 1.0
        bs = args.build_batch or 128
        if idx is None:
            # cold-started off the checkpoint: ingest needs the live index —
            # run full crash recovery (checkpoint + WAL replay) now and ride
            # the WAL from here on
            from ..persist import open_durable

            t0 = time.time()
            idx = open_durable(args.index_dir,
                               compact_threshold=args.compact_threshold)
            print(f"recovered live index for ingest in {time.time()-t0:.2f}s "
                  f"({len(idx)} vectors, lsn {idx._applied_lsn})")
            snap = None  # checkpoint snapshot may be mmap'd; rebuild below
        t0 = time.time()
        idx.insert_batch(extra_v, extra_a, batch_size=bs,
                         backend=args.build_backend, **build_kw)
        t_ing = time.time() - t0
        t0 = time.time()
        snap = take_snapshot(idx, prev=snap)
        t_snap = time.time() - t0
        print(f"ingested {args.ingest} vectors in {t_ing:.2f}s "
              f"({args.ingest / max(t_ing, 1e-9):.0f} ins/s), "
              f"incremental snapshot refresh {t_snap * 1e3:.0f} ms "
              f"({snap.n} live)")
        v_bits = args.visited_bits
        if args.adaptive_filter and args.visited == "hash":
            from ..core.device_search import visited_filter_bits_measured

            v_bits = visited_filter_bits_measured(hops, args.m)
            print(f"adaptive visited filter: {v_bits} bits/query from the "
                  f"measured hop histogram (p99={q[2]})")
        res2 = search_batch(snap, wl.queries, wl.ranges, k=args.k,
                            width=args.width, backend=args.backend,
                            pipeline=args.pipeline, visited=args.visited,
                            visited_bits=v_bits, compact=compact,
                            vec_dtype=args.vec_dtype)
        ids2 = np.asarray(res2.ids)
        recs2 = []
        for i in range(args.queries):
            got = np.asarray([int(snap.ids_map[j]) for j in ids2[i] if j >= 0])
            recs2.append(recall(got, wl.gt[i]))
        print(f"re-served {args.queries} queries post-ingest: "
              f"recall@{args.k} = {np.mean(recs2):.4f}")
        if args.index_dir:
            # the WAL already made the ingest durable; the incremental
            # checkpoint (O(changed rows)) just shortens the next replay
            t0 = time.time()
            path = idx.checkpoint(args.index_dir)
            print(f"incremental checkpoint to {path} in "
                  f"{(time.time()-t0)*1e3:.0f} ms")


def _serve_cluster(args, wl, recall) -> None:
    """Replicated serving demo: ingest the workload through the primary
    (quorum-durable acks), serve the query stream across every member,
    and run a zero-downtime rolling restart in the middle of it — the
    stream must complete with zero failed queries (degraded is fine)."""
    import os
    import tempfile

    import numpy as np

    from ..serve.cluster import Cluster
    from ..serve.lifecycle import EngineConfig, Rejected

    base = args.index_dir or tempfile.mkdtemp(prefix="wow-cluster-")
    roots = [os.path.join(base, f"member{i}") for i in range(args.cluster)]
    cfg = EngineConfig(
        k=args.k, width=args.width, backend=args.backend,
        visited=args.visited, visited_bits=args.visited_bits,
        adaptive=args.adaptive_filter, max_wave=args.max_wave,
        queue_cap=args.queue_cap,
        default_timeout_s=(args.deadline_ms / 1e3
                           if args.deadline_ms > 0 else None),
        build_backend=args.build_backend,
        vec_dtype=args.vec_dtype,
    )
    quorum = args.cluster_quorum or None
    cluster = Cluster(
        roots,
        create=dict(dim=args.dim, m=args.m,
                    ef_construction=args.ef_construction, o=args.o, seed=0),
        config=cfg, quorum=quorum,
        compact_threshold=args.compact_threshold)
    t0 = time.time()
    bs = max(args.build_batch or 128, 1)
    for s in range(0, args.n, bs):
        cluster.submit_ingest(wl.vectors[s:s + bs], wl.attrs[s:s + bs])
        cluster.step()
    cluster.drain()
    lag = {nid: m.replicator.status().get("lag", 0)
           for nid, m in cluster.members.items() if m.replicator is not None}
    print(f"cluster of {args.cluster} (quorum "
          f"{cluster.quorum}): ingested {args.n} vectors in "
          f"{time.time()-t0:.1f}s, every ack quorum-durable, lag={lag}")
    cluster.warmup()

    replies = []
    rejected = 0
    crid_to_qi: dict[int, int] = {}
    restart_at = args.queries // 3
    rolled = None
    t0 = time.time()
    for i in range(args.queries):
        out = cluster.submit(wl.queries[i], wl.ranges[i])
        if isinstance(out, Rejected):
            rejected += 1
        else:
            crid_to_qi[out.crid] = i
        replies.extend(cluster.step())
        if i == restart_at:
            # the tentpole demo: every member restarts mid-stream; the
            # routing + engine backpressure machinery absorbs it
            t_roll = time.time()
            res = cluster.rolling_restart()
            replies.extend(res["replies"])
            rolled = (res["events"], time.time() - t_roll)
    replies.extend(cluster.drain())
    wall = time.time() - t0

    recs = []
    by_node: dict[str, int] = {}
    degraded = 0
    for cr in replies:
        qi = crid_to_qi.get(cr.crid)
        if qi is None:
            continue
        got = np.asarray([j for j in cr.reply.ids if j >= 0])
        recs.append(recall(got, wl.gt[qi]))
        by_node[cr.node] = by_node.get(cr.node, 0) + 1
        degraded += int(cr.reply.degraded)
    if rolled is not None:
        ev, t_roll = rolled
        print(f"rolling restart mid-stream in {t_roll:.1f}s: "
              + ", ".join(f"{what}:{nid}" for what, nid in ev))
    print(f"served {len(recs)}/{args.queries} queries across "
          f"{by_node} (rejected {rejected}, degraded {degraded}): "
          f"recall@{args.k} = {float(np.mean(recs)):.4f}, "
          f"{len(recs)/max(wall, 1e-9):.0f} QPS")
    lost = args.queries - len(recs) - rejected
    if lost:
        raise SystemExit(f"{lost} queries vanished without a reply — the "
                         f"zero-downtime contract is broken")
    print(f"zero-downtime contract held: every admitted query replied "
          f"(primary now {cluster.primary_id}, "
          f"epoch {cluster.members[cluster.primary_id].replicator.epoch})")


def _serve_engine(args, wl, idx, snap, recall) -> None:
    """Engine-driven serving: admit the workload through the request
    lifecycle (open-loop at ``--rate`` or as a closed burst), drive the
    scheduler to drain, then print per-request latency percentiles +
    QPS (admission->reply) and the shutdown summary."""
    import numpy as np

    from ..serve.lifecycle import EngineConfig, Rejected, ServeEngine

    cfg = EngineConfig(
        k=args.k, width=args.width, backend=args.backend,
        visited=args.visited, visited_bits=args.visited_bits,
        adaptive=args.adaptive_filter, max_wave=args.max_wave,
        queue_cap=args.queue_cap,
        default_timeout_s=(args.deadline_ms / 1e3
                           if args.deadline_ms > 0 else None),
        build_backend=args.build_backend,
        vec_dtype=args.vec_dtype,
    )
    eng = ServeEngine(index=idx, snapshot=snap, config=cfg)
    if args.ingest > 0:
        if idx is None:
            from ..persist import open_durable

            idx = open_durable(args.index_dir,
                               compact_threshold=args.compact_threshold)
            eng = ServeEngine(index=idx, config=cfg)
        from ..core.datasets import make_attrs, make_vectors

        extra_v = make_vectors(args.ingest, args.dim, seed=99)
        extra_a = (make_attrs(extra_v, seed=99)
                   + float(np.max(wl.attrs)) + 1.0)
        ir = eng.submit_ingest(extra_v, extra_a)
        print(f"ingest admitted (durable ack, applies interleave with "
              f"queries): {ir!r}")

    # precompile every wave/compaction bucket before traffic: lazy shape
    # discovery would block a live request behind an XLA compile
    print(f"engine warmup (all wave shapes) in {eng.warmup():.2f} s")

    replies: list = []
    rid_to_qi: dict = {}
    rejected = 0
    period = 1.0 / args.rate if args.rate > 0 else 0.0
    next_t = time.monotonic()
    for i in range(args.queries):
        if period:
            # open-loop arrivals: hold the offered load fixed and keep the
            # scheduler busy between arrivals instead of sleeping idle
            while True:
                now = time.monotonic()
                if now >= next_t:
                    break
                if not eng.idle:
                    replies.extend(eng.step())
                else:
                    time.sleep(min(1e-3, next_t - now))
            next_t += period
        out = eng.submit(wl.queries[i], wl.ranges[i])
        if isinstance(out, Rejected):
            rejected += 1
        else:
            rid_to_qi[out.rid] = i
        if period:
            replies.extend(eng.step())
        # closed burst: no step between submits, so the scheduler sees the
        # whole backlog and assembles full-width waves
    replies.extend(eng.drain())

    recs = []
    for r in replies:
        qi = rid_to_qi.get(r.rid)
        if qi is None:
            continue
        got = np.asarray([j for j in r.ids if j >= 0])
        recs.append(recall(got, wl.gt[qi]))
    s = eng.engine_stats()
    print(f"engine served {s['served']} queries "
          f"(admitted {s['admitted']}, rejected {rejected}, "
          f"degraded {s['degraded']}, expired-in-queue {s['expired']}): "
          f"recall@{args.k} = {float(np.mean(recs)):.4f}")
    print(f"latency admission->reply: p50={s['p50_ms']:.1f} ms "
          f"p95={s['p95_ms']:.1f} ms p99={s['p99_ms']:.1f} ms, "
          f"throughput {s['qps']:.0f} QPS"
          + (f" (offered {args.rate:.0f} QPS open-loop)"
             if period else " (closed burst)"))
    print(f"shutdown summary: waves={s['waves']} chunks={s['chunks']} "
          f"backlog_waves={s['backlog_waves']} queue_peak={s['queue_peak']} "
          f"ingest_batches={s['ingest']['batches']} "
          f"ingest_rows={s['ingest']['rows']} "
          f"applied_lsn={s['applied_lsn']}")
    if args.ingest > 0 and args.index_dir and idx is not None:
        t0 = time.time()
        path = idx.checkpoint(args.index_dir)
        print(f"incremental checkpoint to {path} in "
              f"{(time.time()-t0)*1e3:.0f} ms")


if __name__ == "__main__":
    main()
