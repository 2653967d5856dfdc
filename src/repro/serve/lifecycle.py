"""Production serve engine: a robust request lifecycle over the WoW index.

The closed-loop wave launcher (``repro.launch.serve``) answered "how fast
is the hop loop"; this module answers "what happens to a *request*" — the
JetStream-style engine the ROADMAP's direction 1 calls for, built from
four explicit stages:

**Admission** — ``submit`` places a request in a bounded queue with an
absolute deadline (``timeout_s`` from the injected clock).  When the queue
reaches ``queue_cap`` the request is rejected with a ``retry_after``
estimate derived from the live service rate — backpressure is a first-class
reply, never unbounded queue growth.

**Scheduling** — waves are assembled from the queue head into power-of-two
buckets (one compilation per bucket, exactly like ``search_batch``) and
tracked as slot-based in-flight state.  The hop loop runs as resumable
chunks (``device_search._run_jit_inplace``: one launch that updates the
wave's ``HopState`` in place and returns a packed record of what the host
needs, read back in one transfer); at every
chunk boundary finished requests are harvested and *replied immediately*,
survivors are compacted into smaller buckets, and newly admitted requests
start as fresh waves that interleave round-robin with the stragglers — the
ragged-batch compaction machinery promoted from intra-batch to
cross-request, so a short query never waits on another request's straggler.
Ingest rides the same scheduler through a deficit counter
(``ingest_share``): builds and queries make progress under one loop, and
ingest drains opportunistically when queries are idle.  While waves are
in flight a new wave starts only at full width (``max_wave`` requests
queued and as many slots free), so a backlog — after a host stall, say —
is served by full-width waves and clears at the engine's best rate.

**Execution** — the current jitted hop pipeline, with the two previously
static knobs driven per-wave by the live hop histogram: the hashed visited
filter is re-sized via ``visited_filter_bits_from_hist`` and the chunk
schedule via ``chunk_schedule_from_hist`` (both pow2-quantised so the jit
cache stays warm).  Per-request trajectories are row-independent and
iteration-indexed, so for equal static knobs the engine's results are
bitwise those of a one-shot ``search_batch`` — wave grouping, compaction
and interleaving cannot change any answer (gated in
``tests/test_serve_engine.py``).

**Graceful degradation** — deadlines are enforced at chunk boundaries: a
request that would blow its deadline during the next chunk is harvested
*now* with its best-so-far beam (the sorted result array is a valid
answer prefix at every iteration) and marked ``degraded=True`` — a reduced
hop budget, never a timeout.  A reply that lands past its deadline for any
reason carries the flag too, so "no reply after deadline without
``degraded``" holds by construction.  Requests that expire while still
queued are answered empty-and-degraded.  Load beyond what the engine
serves is shed by admission alone: the queue stops at ``queue_cap`` and
rejects with ``retry_after``; waves never narrow under a backlog, because
a narrower wave serves less at the same host cost per chunk.

**WAL-backed ingest** — ``submit_ingest`` validates rows individually
(bad rows are *rejected*, good rows proceed — the explicit
``IngestResult`` contract), logs every micro-batch through the index's
attached ``repro.persist`` WAL and group-commits them with one fsync
*before* the batch enters the ingest queue: durability order equals
admission order, and the ack means "recoverable", not "applied".  The
scheduler applies queued batches FIFO under the ``_wal_replaying`` guard
(they are already logged) and advances ``_applied_lsn`` per batch; a crash
at ANY point after the ack — including SIGKILL with the whole queue
pending — replays the un-applied suffix from the WAL on the next
``open_durable``, because apply == replay by PR 6's construction.
Auto-compaction only fires when the queue is empty, so live apply order
always equals log order and replay stays bitwise.

Determinism for tests: the clock (``now``) is injectable, and an
``EngineFaultPlan`` (``repro.persist.faultfs``) hooks every chunk and
ingest apply — slow waves become virtual-clock jumps, crashes become
``CrashError`` at exact scheduler points.

**Tracing** — the scheduler marks its host work with
``jax.profiler.TraceAnnotation`` spans, which land in the same profiler
trace as the device's programs and cost a few hundred nanoseconds each
while no trace is captured (arguments are formatted only when one is).
They nest as the code does::

    serve.step                      one scheduler turn
      serve.assemble  wave, n, bucket   queue pop, packing, uploads
        serve.dispatch  program         _init_jit launch (returns early)
      serve.chunk     wave, h, bucket   one hop chunk of one wave
        serve.dispatch  program         _run_jit_inplace launch
        serve.sync                      the chunk's record: its one read
        serve.harvest   n               replies built from the record
        serve.compact                   survivor gather
          serve.dispatch  program       _compact_rows launch

``wave`` is the wave's ordinal (``ServeStats.waves`` at its assembly);
requests and replies carry it too.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.device_search import (
    _MIN_BUCKET,
    _compact_rows,
    _init_jit,
    _pow2ceil,
    _run_jit_inplace,
    chunk_record,
    chunk_schedule_from_hist,
    hop_cfg,
    to_device_index,
    visited_filter_bits_from_hist,
)


# --------------------------------------------------------------------- stats
class ServeStats:
    """Request-lifecycle counters + latency accounting — the one source of
    truth shared by the engine, ``RagPipeline.stats()`` and the benches.

    Latency is admission(arrival)->reply and queue wait is
    admission->dispatch of the request's wave, both recorded in bounded
    reservoirs (the most recent ``reservoir`` samples) so a long-running
    server's percentiles track current behavior at O(1) memory."""

    def __init__(self, reservoir: int = 4096):
        self.submitted = 0
        self.admitted = 0
        self.rejected = 0
        self.served = 0
        self.degraded = 0
        self.expired = 0  # deadline passed while still queued
        self.ingest_batches = 0
        self.ingest_rows = 0
        self.ingest_rejected_rows = 0
        self.ingest_replayed = 0  # applied from a pre-crash WAL suffix
        self.waves = 0
        self.chunks = 0
        self.backlog_waves = 0  # waves assembled while others ran
        self.queue_peak = 0
        self._lat = deque(maxlen=reservoir)
        self._wait = deque(maxlen=reservoir)
        self._t0: float | None = None
        self._t1: float | None = None

    def note_reply(self, now: float, latency_s: float, degraded: bool,
                   wait_s: float = 0.0) -> None:
        self.served += 1
        if degraded:
            self.degraded += 1
        self._lat.append(latency_s)
        self._wait.append(wait_s)
        if self._t0 is None:
            self._t0 = now - latency_s
        self._t1 = now

    def latency_percentiles(self) -> dict:
        if not self._lat:
            return {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0}
        q = np.percentile(np.asarray(self._lat), [50, 95, 99]) * 1e3
        return {"p50_ms": float(q[0]), "p95_ms": float(q[1]),
                "p99_ms": float(q[2])}

    def qps(self) -> float:
        if self._t0 is None or self._t1 is None or self._t1 <= self._t0:
            return 0.0
        return self.served / (self._t1 - self._t0)

    def summary(self) -> dict:
        out = {
            "submitted": self.submitted,
            "admitted": self.admitted,
            "rejected": self.rejected,
            "served": self.served,
            "degraded": self.degraded,
            "expired": self.expired,
            "degraded_fraction": (self.degraded / self.served
                                  if self.served else 0.0),
            "shed_fraction": (self.rejected / self.submitted
                              if self.submitted else 0.0),
            "waves": self.waves,
            "chunks": self.chunks,
            "backlog_waves": self.backlog_waves,
            "queue_peak": self.queue_peak,
            "qps": self.qps(),
            "ingest": {
                "batches": self.ingest_batches,
                "rows": self.ingest_rows,
                "rejected_rows": self.ingest_rejected_rows,
                "replayed": self.ingest_replayed,
            },
        }
        out.update(self.latency_percentiles())
        q = (np.percentile(np.asarray(self._wait), [50, 95]) * 1e3
             if self._wait else (0.0, 0.0))
        out.update(queue_wait_p50_ms=float(q[0]),
                   queue_wait_p95_ms=float(q[1]))
        return out


# ------------------------------------------------------------------ requests
@dataclass
class Request:
    """One admitted query request (engine-internal after ``submit``)."""

    rid: int
    query: np.ndarray  # f32[d]
    rng: tuple[float, float]
    k: int
    deadline: float  # absolute clock time; +inf = none
    arrival_t: float
    wave: int = -1  # ordinal of the wave it runs in; -1 = still queued
    start_t: float | None = None  # clock time its wave's init dispatched


@dataclass
class Reply:
    """The terminal state of a served request.  ``degraded`` means the
    answer was produced under a reduced hop budget (deadline pressure) or
    after its deadline; ``reason`` is None for a full-budget in-deadline
    answer, else ``"deadline"`` (truncated in flight) or
    ``"queue_deadline"`` (expired before execution, ids empty).
    ``wait_s`` is the queue wait, admission to the dispatch of the
    request's wave (its whole latency when it expired in the queue);
    ``wave`` that wave's ordinal (-1 when it never ran)."""

    rid: int
    ids: np.ndarray  # i64[k] external (index) ids, -1 padded
    dists: np.ndarray  # f32[k], +inf padded
    degraded: bool
    reason: str | None
    hops: int
    dc: int
    latency_s: float
    finish_t: float
    wait_s: float = 0.0
    wave: int = -1


@dataclass
class Rejected:
    """Backpressure reply: not admitted; retry after ``retry_after`` s."""

    rid: int
    retry_after: float
    queue_len: int


@dataclass
class Ticket:
    rid: int


class IngestResult:
    """Explicit outcome of one ingest call.

    ``accepted`` rows were committed (synchronous path) or
    logged-and-fsynced for apply (engine path, ``pending=True``);
    ``rejected`` lists ``(row, reason)`` for rows that failed validation —
    the caller always knows exactly which rows are durable, instead of
    inferring a prefix from a mid-stream ``ValueError``.  ``lsn`` is the
    last WAL record covering the accepted rows (0 when not durable).
    Array-like over the committed vertex ids for backward compatibility
    with callers that treated ``add_documents``'s return as the vid array.
    """

    def __init__(self, vids: np.ndarray, accepted: int,
                 rejected: list[tuple[int, str]], lsn: int = 0,
                 pending: bool = False):
        self.vids = np.asarray(vids, dtype=np.int64)
        self.accepted = int(accepted)
        self.rejected = list(rejected)
        self.lsn = int(lsn)
        self.pending = bool(pending)

    def __len__(self) -> int:
        return len(self.vids)

    def __iter__(self):
        return iter(self.vids)

    def __getitem__(self, i):
        return self.vids[i]

    def __array__(self, dtype=None):
        return np.asarray(self.vids, dtype=dtype)

    def __repr__(self) -> str:
        return (f"IngestResult(accepted={self.accepted}, "
                f"rejected={len(self.rejected)}, lsn={self.lsn}, "
                f"pending={self.pending})")


def validate_rows(vectors: np.ndarray, attrs: np.ndarray,
                  dim: int) -> tuple[np.ndarray, list[tuple[int, str]]]:
    """Row-level ingest validation: returns (keep mask, rejected rows).

    The per-row twin of ``WoWIndex._validate_ingest``'s all-or-nothing
    batch gate: a half-bad batch yields an explicit accept/reject split
    instead of an opaque mid-stream ``ValueError``.  A structural mismatch
    (wrong vector dimension) still raises — no row of such a batch is
    interpretable."""
    if vectors.ndim != 2 or vectors.shape[1] != dim:
        raise ValueError(
            f"vectors have dimension "
            f"{vectors.shape[-1] if vectors.ndim else 0}, index expects {dim}"
        )
    ok = np.isfinite(attrs)
    rejected = [(int(i), "non-finite attribute") for i in np.flatnonzero(~ok)]
    vok = np.isfinite(vectors).all(axis=1)
    rejected += [(int(i), "non-finite vector component")
                 for i in np.flatnonzero(ok & ~vok)]
    rejected.sort()
    return ok & vok, rejected


# -------------------------------------------------------------------- config
@dataclass
class EngineConfig:
    """Static engine knobs.  Search knobs mirror ``search_batch``; the
    lifecycle knobs bound queue memory (``queue_cap``, beyond which
    admission rejects), wave shape (``max_wave``/``max_slots``) and ingest
    fairness (``ingest_share`` = fraction of scheduler turns ingest may
    consume while queries are pending; 0.5 = strict alternation)."""

    k: int = 10
    width: int = 64
    backend: str = "auto"
    vec_dtype: str = "f32"  # device vector-slab storage mode (serving)
    visited: str = "bitmap"
    visited_bits: int | None = None
    merge: str = "auto"
    max_hops: int | None = None
    adaptive: bool = True  # hist-driven filter + chunk resizing
    chunk: tuple[int, int] = (8, 8)  # cold-start schedule
    hist_window: int = 16  # rolling per-wave histograms (matches RagPipeline)
    max_wave: int = 64
    max_slots: int = 256
    queue_cap: int = 512
    default_timeout_s: float | None = None
    ingest_share: float = 0.5
    ingest_batch: int = 128
    build_backend: str = "numpy"

    def __post_init__(self):
        from ..core.store import VEC_DTYPES

        if self.vec_dtype not in VEC_DTYPES:
            raise ValueError(
                f"vec_dtype must be one of {VEC_DTYPES}, "
                f"got {self.vec_dtype!r}"
            )
        if not 0.0 <= self.ingest_share <= 1.0:
            raise ValueError("ingest_share must be in [0, 1]")
        if self.queue_cap < 1 or self.max_wave < 1 or self.max_slots < 1:
            raise ValueError("queue_cap/max_wave/max_slots must be >= 1")


@dataclass(eq=False)  # identity equality: fields hold arrays
class _Wave:
    """Slot-based in-flight state of one admitted wave."""

    st: object  # HopState (device)
    cfg: object  # HopCfg
    di: object  # DeviceIndex the wave was launched against
    ids_map: np.ndarray  # snapshot id -> external id
    reqs: list  # admitted requests (stable for the wave's lifetime)
    orig: np.ndarray  # slot -> index into reqs, -1 = retired/padding
    dl: np.ndarray  # f64[slots] absolute deadlines (+inf = none)
    chunk: tuple[int, int]
    next_h: int
    t_planned: int = 0
    wid: int = 0  # wave ordinal, shared by its spans and requests


# -------------------------------------------------------------------- engine
class ServeEngine:
    """Single-host serve engine (see the module docstring for the stage
    semantics).  Single-threaded and step-driven: ``submit``/
    ``submit_ingest`` enqueue, ``step()`` advances the scheduler by one
    turn (at most one ingest apply + one hop chunk) and returns the
    replies it produced, ``drain()`` steps until idle.  The driving loop
    (launcher, bench, test) owns the thread — determinism is the point:
    every fault-plan and virtual-clock test replays exactly.

    ``index`` enables ingest and snapshot refresh; a bare ``snapshot``
    serves queries only (the serve-from-checkpoint cold start).  When the
    index has a WAL attached (``repro.persist.open_durable``), ingest
    admission is durable: acked batches survive any crash.
    """

    def __init__(self, index=None, snapshot=None,
                 config: EngineConfig | None = None, now=None,
                 fault_plan=None, stats: ServeStats | None = None):
        if index is None and snapshot is None:
            raise ValueError("ServeEngine needs an index or a snapshot")
        self.index = index
        self.config = config or EngineConfig()
        self.stats = stats or ServeStats()
        self.fault_plan = fault_plan
        self._now = now or time.monotonic
        self._snap = snapshot
        # key by the snapshot's OWN stamp (not index.mutations): a handed-in
        # snapshot may be stale, and the first wave must notice and refresh
        self._snap_key = snapshot.stamp if snapshot is not None else None
        self._di = (
            to_device_index(snapshot, vec_dtype=self.config.vec_dtype)
            if snapshot is not None else None
        )
        self._queue: deque[Request] = deque()
        self._ingest_q: deque[tuple[int | None, np.ndarray, np.ndarray]] = (
            deque()
        )
        self._waves: list[_Wave] = []
        self._rr = 0  # round-robin cursor over in-flight waves
        self._next_rid = 0
        self._ingest_credit = 0.0
        self._recent_hists: deque = deque(maxlen=self.config.hist_window)
        self._hop_s = 0.0  # EWMA wall seconds per hop chunk-iteration
        self._wave_s = 0.0  # EWMA wall seconds per executed chunk

    # ---------------------------------------------------------- introspection
    @property
    def queue_len(self) -> int:
        return len(self._queue)

    @property
    def pending_ingest(self) -> int:
        return len(self._ingest_q)

    @property
    def in_flight(self) -> int:
        return sum(int(np.sum(w.orig >= 0)) for w in self._waves)

    @property
    def idle(self) -> bool:
        return not (self._queue or self._waves or self._ingest_q)

    def hop_histogram(self) -> np.ndarray | None:
        """Rolling hop histogram over the last ``hist_window`` waves."""
        if not self._recent_hists:
            return None
        H = max(h.shape[0] for h in self._recent_hists)
        out = np.zeros(H, np.int64)
        for h in self._recent_hists:
            out[: h.shape[0]] += h
        return out

    def engine_stats(self) -> dict:
        """Live scheduler state + the ``ServeStats`` summary."""
        out = self.stats.summary()
        out.update(
            queue_len=self.queue_len,
            in_flight=self.in_flight,
            pending_ingest=self.pending_ingest,
            applied_lsn=(self.index._applied_lsn
                         if self.index is not None else 0),
            chunk_schedule=list(self._chunk_schedule()),
            visited_bits=self._visited_bits(),
        )
        return out

    # -------------------------------------------------------------- admission
    def submit(self, query: np.ndarray, rng, k: int | None = None,
               timeout_s: float | None = None):
        """Admit one query request.  Returns a ``Ticket`` or a
        ``Rejected`` carrying the retry-after estimate."""
        now = self._now()
        cfg = self.config
        self.stats.submitted += 1
        rid = self._next_rid
        self._next_rid += 1
        qlen = len(self._queue)
        if qlen >= cfg.queue_cap:
            self.stats.rejected += 1
            return Rejected(rid=rid, retry_after=self._retry_after(),
                            queue_len=qlen)
        if timeout_s is None:
            timeout_s = cfg.default_timeout_s
        deadline = now + timeout_s if timeout_s is not None else np.inf
        k = int(k) if k is not None else cfg.k
        if k > cfg.k:
            raise ValueError(f"k={k} exceeds the engine's configured "
                             f"k={cfg.k} (beam harvest width)")
        self._queue.append(Request(
            rid=rid, query=np.asarray(query, np.float32),
            rng=(float(rng[0]), float(rng[1])), k=k, deadline=deadline,
            arrival_t=now,
        ))
        self.stats.admitted += 1
        self.stats.queue_peak = max(self.stats.queue_peak, len(self._queue))
        return Ticket(rid=rid)

    #: retry_after ceiling: a hint above this means the EWMA was poisoned
    #: (virtual-clock jump, pathological chunk) — clients should re-probe,
    #: not sleep for minutes on a transient estimate
    RETRY_AFTER_MAX_S = 30.0
    _RETRY_AFTER_COLD_S = 0.05  # one-chunk floor before any chunk ran

    def _retry_after(self) -> float:
        """Backpressure hint: the time to drain half the queue at the
        observed service rate (chunk EWMA), floored at one chunk.

        Always a bounded positive float: on a cold start the EWMA is 0
        (no chunk has run), and a fault-plan virtual-clock jump can drive
        it non-finite — either would otherwise hand clients a 0/inf/NaN
        retry hint (0 = immediate hammer-retry loop, inf/NaN = never)."""
        per_wave = self._wave_s
        if not np.isfinite(per_wave) or per_wave <= 0.0:
            per_wave = self._RETRY_AFTER_COLD_S
        waves_ahead = (len(self._queue) / (2.0 * self.config.max_wave)
                       + len(self._waves))
        hint = max(per_wave, waves_ahead * per_wave)
        if not np.isfinite(hint) or hint <= 0.0:
            hint = self._RETRY_AFTER_COLD_S
        return float(min(hint, self.RETRY_AFTER_MAX_S))

    # ----------------------------------------------------------------- ingest
    def submit_ingest(self, vectors: np.ndarray, attrs) -> IngestResult:
        """Admit an ingest batch: per-row validation, WAL group commit
        (log every micro-batch, one fsync), then queue for apply.  The
        returned result is the durability ack — accepted rows survive any
        subsequent crash; application happens asynchronously under the
        scheduler (``pending=True``)."""
        if self.index is None:
            raise RuntimeError(
                "ingest needs a live index (engine was built from a bare "
                "snapshot; recover the index first)"
            )
        vectors = np.asarray(vectors, np.float32)
        if vectors.ndim == 1:
            vectors = vectors.reshape(1, -1)
        attrs = np.asarray(attrs, np.float64).reshape(-1)
        if len(vectors) != len(attrs):
            raise ValueError(f"{len(vectors)} vectors vs {len(attrs)} attrs")
        keep, rejected = validate_rows(vectors, attrs, self.index.dim)
        self.stats.ingest_rejected_rows += len(rejected)
        vectors, attrs = vectors[keep], attrs[keep]
        wal = self.index._wal
        lsn = self.index._applied_lsn
        bs = self.config.ingest_batch
        staged = []
        for s in range(0, len(attrs), bs):
            vs, as_ = vectors[s : s + bs], attrs[s : s + bs]
            if wal is not None:
                # group commit: append now, one fsync below acks them all
                lsn = wal.log_insert(vs, as_,
                                     backend=self.config.build_backend,
                                     device_width=None, shards=None,
                                     fsync=False)
                staged.append((lsn, vs, as_))
            else:
                staged.append((None, vs, as_))
        if wal is not None and staged:
            wal.sync()  # durability barrier: everything above is now acked
        self._ingest_q.extend(staged)
        self.stats.ingest_batches += len(staged)
        self.stats.ingest_rows += len(attrs)
        return IngestResult(
            vids=np.empty(0, np.int64), accepted=len(attrs),
            rejected=rejected, lsn=lsn if wal is not None else 0,
            pending=True,
        )

    def _apply_ingest_one(self) -> None:
        """Apply the oldest queued (already-logged) ingest micro-batch.
        The record stays queued until the apply commits, so a fault-plan
        crash here loses nothing: the batch is in the WAL and replays."""
        if self.fault_plan is not None:
            self.fault_plan.on_ingest_apply()
        lsn, vs, as_ = self._ingest_q[0]
        idx = self.index
        if lsn is not None:
            # already logged at admission: apply must not re-log
            idx._wal_replaying = True
            try:
                idx.insert_batch(vs, as_, batch_size=max(len(as_), 1),
                                 backend=self.config.build_backend)
            finally:
                idx._wal_replaying = False
            idx._applied_lsn = lsn
        else:
            idx.insert_batch(vs, as_, batch_size=max(len(as_), 1),
                             backend=self.config.build_backend)
        self._ingest_q.popleft()
        if not self._ingest_q:
            # the cadence check is deferred until the queue is empty so a
            # triggered COMPACT record lands after every already-logged
            # insert — live apply order must equal log order for replay
            idx._maybe_auto_compact()

    # -------------------------------------------------------------- scheduler
    def step(self) -> list[Reply]:
        """One scheduler turn: expire stale queued requests, give ingest
        its fair share, assemble a wave if there is capacity, run one hop
        chunk of one in-flight wave.  Returns the replies produced."""
        with TraceAnnotation("serve.step"):
            now = self._now()
            replies: list[Reply] = []
            self._expire_queued(now, replies)
            if self._ingest_q:
                self._ingest_credit += self.config.ingest_share
                if (self._ingest_credit >= 1.0
                        or not (self._queue or self._waves)):
                    self._ingest_credit = max(0.0, self._ingest_credit - 1.0)
                    self._apply_ingest_one()
            free = self.config.max_slots - self.in_flight
            # batching policy: while waves are in flight, a new wave starts
            # only at full width — arrivals accumulate, and slots free up —
            # since a chunk costs the host about the same at any width, so
            # narrow waves would serve a backlog at a lower rate; once the
            # engine is idle, take whatever is queued.  Cannot starve: when
            # the last wave retires, the next step assembles a partial wave
            # unconditionally.
            full = min(self.config.max_wave, self.config.max_slots)
            if self._queue and (
                not self._waves or min(len(self._queue), free) >= full
            ):
                self._assemble_wave(free)
            if self._waves:
                replies.extend(self._run_chunk())
            return replies

    def drain(self, max_steps: int = 1_000_000) -> list[Reply]:
        """Step until idle; the step bound turns a scheduler deadlock into
        a loud failure instead of a hang."""
        replies: list[Reply] = []
        for _ in range(max_steps):
            if self.idle:
                return replies
            replies.extend(self.step())
        raise RuntimeError(
            f"engine failed to drain within {max_steps} steps "
            f"(queue={self.queue_len}, in_flight={self.in_flight}, "
            f"ingest={self.pending_ingest})"
        )

    # ------------------------------------------------------------- internals
    def _expire_queued(self, now: float, replies: list[Reply]) -> None:
        if not self._queue:
            return
        keep: deque[Request] = deque()
        for req in self._queue:
            if req.deadline < now:
                self.stats.expired += 1
                replies.append(self._reply(
                    req, np.full(req.k, -1, np.int64),
                    np.full(req.k, np.inf, np.float32), hops=0, dc=0,
                    now=now, degraded=True, reason="queue_deadline",
                ))
            else:
                keep.append(req)
        self._queue = keep

    def _refresh_snapshot(self) -> None:
        if self.index is None:
            if self._snap is None:
                raise RuntimeError("no serving snapshot")
            return
        key = self.index.mutations
        if self._di is None or self._snap is None or self._snap_key != key:
            from ..core.snapshot import take_snapshot

            self._snap = take_snapshot(self.index, prev=self._snap)
            self._di = to_device_index(
                self._snap, vec_dtype=self.config.vec_dtype
            )
            self._snap_key = key

    def _visited_bits(self) -> int | None:
        cfg = self.config
        if cfg.visited != "hash":
            return None
        if cfg.adaptive:
            hist = self.hop_histogram()
            if hist is not None and self._snap is not None:
                return visited_filter_bits_from_hist(hist, self._snap.m)
        return cfg.visited_bits  # None = worst-case budget sizing

    def _chunk_schedule(self) -> tuple[int, int]:
        if self.config.adaptive:
            hist = self.hop_histogram()
            if hist is not None:
                return chunk_schedule_from_hist(hist)
        return self.config.chunk

    def _wave_cfg(self, snap):
        cfg = self.config
        return hop_cfg(
            k=cfg.k, width=cfg.width, m=snap.m, o=snap.o,
            metric="l2" if snap.metric == "l2" else "cosine",
            max_hops=cfg.max_hops, backend=cfg.backend,
            visited=cfg.visited, visited_bits=self._visited_bits(),
            merge=cfg.merge,
        )

    def warmup(self) -> float:
        """Precompile every jit shape the scheduler can assemble under
        the current schedule: each pow2 wave bucket up to ``max_wave``
        x {first chunk, steady chunk}, plus every shrink-compaction
        bucket pair.  Without this a production engine discovers shapes
        *lazily* — e.g. a 16-wide wave only exists once the slot pool
        runs low under sustained load, and that first mid-traffic
        assembly blocks a request behind ~1s of XLA compilation.
        Adaptive engines can still compile new chunk lengths or filter
        sizes as the live histogram shifts; the bucket set itself is
        closed under compaction, so the static case compiles nothing
        after warmup.  Touches no scheduler state (stats, queue,
        histograms) and returns the wall seconds spent.
        """
        t0 = time.perf_counter()
        self._refresh_snapshot()
        di = self._di
        wcfg = self._wave_cfg(self._snap)
        chunk = self._chunk_schedule()
        d = self._snap.vectors.shape[1]
        buckets, B = [], _MIN_BUCKET
        while B < self.config.max_wave:
            buckets.append(B)
            B *= 2
        buckets.append(_pow2ceil(max(self.config.max_wave, _MIN_BUCKET)))
        states = {}
        for B in buckets:
            qp = jnp.zeros((B, d), jnp.float32)
            rp = jnp.tile(jnp.asarray([[1.0, 0.0]], jnp.float32), (B, 1))
            st = _init_jit(di, qp, rp, wcfg)
            for h in dict.fromkeys(chunk):  # (h0, h), deduped
                st, _ = _run_jit_inplace(di, st, wcfg, h)
            states[B] = st  # never donated again: compacted below
        for B in buckets:
            for Bn in buckets:
                if Bn < B:
                    rows = np.arange(Bn)
                    _compact_rows(states[B], jnp.asarray(rows),
                                  jnp.int32(Bn))
        return time.perf_counter() - t0

    def _assemble_wave(self, free: int) -> None:
        cfg = self.config
        take = min(cfg.max_wave, free, len(self._queue))
        if take <= 0:
            return
        wid = self.stats.waves
        Bp = _pow2ceil(max(take, _MIN_BUCKET))
        with TraceAnnotation("serve.assemble", wave=wid, n=take, bucket=Bp):
            self._refresh_snapshot()
            snap, di = self._snap, self._di
            reqs = [self._queue.popleft() for _ in range(take)]
            wcfg = self._wave_cfg(snap)
            chunk = self._chunk_schedule()
            qp = np.zeros((Bp, snap.vectors.shape[1]), np.float32)
            rp = np.tile(np.asarray([[1.0, 0.0]], np.float32), (Bp, 1))
            dl = np.full(Bp, np.inf)
            for i, r in enumerate(reqs):
                qp[i] = r.query
                rp[i] = r.rng
                dl[i] = r.deadline
            qd, rd = jnp.asarray(qp), jnp.asarray(rp)
            start = self._now()
            for r in reqs:
                r.wave, r.start_t = wid, start
            with TraceAnnotation("serve.dispatch", program="_init_jit"):
                st = _init_jit(di, qd, rd, wcfg)
            orig = np.concatenate(
                [np.arange(take), np.full(Bp - take, -1)]
            ).astype(np.int64)
            self._waves.append(_Wave(
                st=st, cfg=wcfg, di=di, ids_map=snap.ids_map, reqs=reqs,
                orig=orig, dl=dl, chunk=chunk, next_h=chunk[0], wid=wid,
            ))
        self.stats.waves += 1
        if len(self._waves) > 1:
            self.stats.backlog_waves += 1

    def _run_chunk(self) -> list[Reply]:
        if self.fault_plan is not None:
            self.fault_plan.on_chunk()
        w = self._waves[self._rr % len(self._waves)]
        h = w.next_h
        with TraceAnnotation("serve.chunk", wave=w.wid, h=h,
                             bucket=len(w.orig)):
            replies = self._chunk(w, h)
        self._rr += 1
        return replies

    def _chunk(self, w: _Wave, h: int) -> list[Reply]:
        t0 = self._now()
        with TraceAnnotation("serve.dispatch", program="_run_jit_inplace"):
            w.st, rec = _run_jit_inplace(w.di, w.st, w.cfg, h)
        with TraceAnnotation("serve.sync"):
            # the chunk boundary's one read: it waits for the chunk
            act, dc, hops, res_i, res_d = chunk_record(np.asarray(rec),
                                                       w.cfg.k)
        now = self._now()
        self.stats.chunks += 1
        w.t_planned += h
        dt = max(now - t0, 0.0)
        if np.isfinite(dt):  # a virtual-clock jump must not poison the EWMAs
            a = 0.3  # EWMA weight: recent chunks dominate the estimates
            self._hop_s = (1 - a) * self._hop_s + a * (dt / h) \
                if self._hop_s else dt / h
            self._wave_s = (1 - a) * self._wave_s + a * dt \
                if self._wave_s else dt

        real = w.orig >= 0
        budget_out = w.t_planned >= w.cfg.max_hops + 1
        finished = real & ~act
        # deadline check: a request that cannot afford the NEXT chunk is
        # harvested now with its best-so-far beam (reduced hop budget);
        # round-robin means a wave waits len(waves) turns for its next
        # chunk, so the lookahead scales with the in-flight wave count
        est_next = self._hop_s * w.chunk[1] * max(len(self._waves), 1)
        blown = real & act & (w.dl < now + est_next)
        harvest = finished | blown | (real & act & budget_out)
        replies: list[Reply] = []
        if harvest.any():
            with TraceAnnotation("serve.harvest", n=int(harvest.sum())):
                hist = np.bincount(hops[harvest], minlength=1)
                self._recent_hists.append(hist.astype(np.int64))
                for slot in np.flatnonzero(harvest):
                    req = w.reqs[w.orig[slot]]
                    truncated = bool(act[slot]) and bool(blown[slot])
                    late = now > req.deadline
                    ids = res_i[slot, : req.k]
                    mapped = np.where(
                        ids >= 0, w.ids_map[np.clip(ids, 0, None)], -1
                    ).astype(np.int64)
                    replies.append(self._reply(
                        req, mapped, res_d[slot, : req.k].copy(),
                        hops=int(hops[slot]), dc=int(dc[slot]), now=now,
                        degraded=truncated or late,
                        reason="deadline" if (truncated or late) else None,
                    ))
        live = real & act & ~harvest
        nlive = int(np.sum(live))
        if nlive == 0:
            self._waves.remove(w)
        else:
            # pow2 buckets (not device_search's 1.5x granularity): engine
            # waves are narrow, so fewer distinct compiled shapes beats
            # tighter padding — a long-running server must not keep
            # discovering new bucket shapes to compile mid-request
            Bn = min(len(w.orig), _pow2ceil(max(nlive, _MIN_BUCKET)))
            rows = np.flatnonzero(live)
            if Bn < len(w.orig):  # bucket shrinks: gather the survivors
                with TraceAnnotation("serve.compact"):
                    idx = np.concatenate(
                        [rows, np.full(Bn - nlive, rows[0])]
                    )
                    with TraceAnnotation("serve.dispatch",
                                         program="_compact_rows"):
                        w.st = _compact_rows(w.st, jnp.asarray(idx),
                                             jnp.int32(nlive))
                    w.orig = np.where(np.arange(Bn) < nlive, w.orig[idx], -1)
                    w.dl = w.dl[idx]
            else:  # same bucket: just retire the harvested slots
                w.orig[harvest] = -1
            w.next_h = w.chunk[1]
        return replies

    def _reply(self, req: Request, ids: np.ndarray, dists: np.ndarray,
               hops: int, dc: int, now: float, degraded: bool,
               reason: str | None) -> Reply:
        lat = max(now - req.arrival_t, 0.0)
        start = now if req.start_t is None else req.start_t
        wait = max(start - req.arrival_t, 0.0)
        self.stats.note_reply(now, lat, degraded, wait)
        return Reply(rid=req.rid, ids=ids, dists=dists, degraded=degraded,
                     reason=reason, hops=hops, dc=dc, latency_s=lat,
                     finish_t=now, wait_s=wait, wave=req.wave)
