"""The ingest side of a run, for a cell whose traffic has an ``ingest``
block: the capacity the schedule may use, taps on the engine's ack, apply
and snapshot refresh, and the checks of the guarantee.

The guarantee, as the traffic file states it: a batch that
``submit_ingest`` acked is fsynced in the WAL before the call returns, and
once applied a query finds its rows.  After the drain, and untimed:

* ``unread_acked`` - acked batches whose read-back probe fails.  The probe
  is the batch's first row as the query, with the one-value range
  ``[attr, attr]``, through ``ServeEngine.submit``; it passes when the
  reply's first id has that attribute, the index stores that row bitwise
  as acked, and the distance is within the configuration's ``dist_gap``
  of 0, relative to the row's squared norm;
* ``append_recall_loss`` - one less the mean recall@k of the append
  probes (``traffic.append_probes``: the mix's queries with ranges that
  reach into the appended rows, through ``ServeEngine.submit``) against
  the brute-force neighbours over the corpus plus the acked rows.  The
  one-value probe above finds a row by its attribute alone; only a search
  through the new rows' edges finds their neighbours in a wider range;
* ``lost_acked`` - rows of acked batches that the WAL on disk does not
  hold bitwise (vector and attribute) in a record at or before the ack's
  LSN, read back once the engine is gone.  The log is append-only, so a
  row logged only after its ack (at apply, say) sits in a later record;
* ``unsynced_acked`` - acked batches some row of whose record was not
  durable when ``submit_ingest`` returned: the bytes up to the record's
  end had not been through an ``fsync`` of its file.  ``Taps`` notes, at
  every ``os.fsync``, how far that file was durable, and at every ack a
  copy of those notes.

The WAL is read by this module's own reader of the format that
``repro.persist.wal`` documents, not by the program's.
"""
from __future__ import annotations

import io
import os
import struct
import time
import zlib

import numpy as np

WARM_BATCHES = 2  # applied in set-up: the first apply and refresh

# the WAL's format (repro/persist/wal.py): a segment is a 36-byte header
# starting with the magic, then records of u32 body length, u32 crc32 of
# the body, and a body of u8 type, u64 LSN and the payload.  An insert's
# payload is u32 n, n bytes of JSON, then the vectors' and the
# attributes' .npy images.
SEG_MAGIC = b"WOWWAL01"
SEG_HEADER = 36
T_INSERT = 1


def capacity(n: int) -> int:
    """Rows the device index holds before its padded shapes grow: the
    power of two at or above ``n`` (``to_device_index``).  Rows and
    distinct attributes alike, and every appended row has its own."""
    return 1 << max(n - 1, 0).bit_length()


def check_capacity(n: int, appended: int) -> None:
    """Refuse a run whose appends would grow the padded shapes, which
    would compile inside the window."""
    cap = capacity(n)
    if n + appended > cap:
        raise ValueError(f"{n} + {appended} appended rows exceed the "
                         f"device capacity of {cap} rows")


class Taps:
    """Taps on this run's engine, installed after the set-up.

    * every ``submit_ingest``: how far each file was durable when it
      returned, keyed by the ack's LSN (``synced_at_ack``);
    * ``os.fsync`` / ``os.fdatasync`` of the process, until ``close``: the
      size of the file synced (``synced``);
    * the index's ``insert_batch`` (one ingest apply): host time;
    * the engine's snapshot refresh, on each call made after the index
      mutated: host time, and the bytes of host arrays it handed to
      ``jnp.asarray``, ``jnp.array`` or ``jax.device_put``.
    """

    def __init__(self, engine):
        import jax
        import jax.numpy as jnp

        self.applied = 0
        self.apply_s = 0.0
        self.refreshes = 0
        self.refresh_s = 0.0
        self.refresh_bytes = 0
        self.synced: dict = {}  # (st_dev, st_ino) -> bytes durable
        self.synced_at_ack: dict = {}  # ack LSN -> copy of synced
        self._restore = []
        index = engine.index
        insert = index.insert_batch
        refresh = engine._refresh_snapshot
        submit = engine.submit_ingest
        published = [index.mutations]

        def acked(vectors, attrs):
            res = submit(vectors, attrs)
            self.synced_at_ack[int(res.lsn)] = dict(self.synced)
            return res

        def timed_insert(*args, **kw):
            t0 = time.perf_counter()
            try:
                return insert(*args, **kw)
            finally:
                self.apply_s += time.perf_counter() - t0
                self.applied += 1

        def counted(put, tree: bool):
            def upload(x, *args, **kw):
                out = put(x, *args, **kw)
                leaves = [(x, out)]
                if tree:  # device_put takes and gives a pytree
                    leaves = zip(jax.tree_util.tree_leaves(x),
                                 jax.tree_util.tree_leaves(out))
                self.refresh_bytes += sum(o.nbytes for i, o in leaves
                                          if not isinstance(i, jax.Array))
                return out
            return upload

        def timed_refresh():
            if index.mutations == published[0]:
                return refresh()
            published[0] = index.mutations
            puts = [(jnp, "asarray"), (jnp, "array"), (jax, "device_put")]
            real = [getattr(mod, name) for mod, name in puts]
            for (mod, name), fn in zip(puts, real):
                setattr(mod, name, counted(fn, name == "device_put"))
            t0 = time.perf_counter()
            try:
                return refresh()
            finally:
                self.refresh_s += time.perf_counter() - t0
                self.refreshes += 1
                for (mod, name), fn in zip(puts, real):
                    setattr(mod, name, fn)

        def durable(sync):
            def tapped(fd):
                st = os.fstat(fd if isinstance(fd, int) else fd.fileno())
                sync(fd)
                key = (st.st_dev, st.st_ino)
                self.synced[key] = max(self.synced.get(key, 0), st.st_size)
            return tapped

        engine.submit_ingest = acked
        index.insert_batch = timed_insert
        engine._refresh_snapshot = timed_refresh
        for name in ("fsync", "fdatasync"):
            if hasattr(os, name):
                self._restore.append((name, getattr(os, name)))
                setattr(os, name, durable(getattr(os, name)))

    def close(self) -> None:
        """Give ``os`` its own ``fsync`` back; idempotent."""
        while self._restore:
            name, fn = self._restore.pop()
            setattr(os, name, fn)

    def counters(self) -> dict:
        return {"ingest_applied": self.applied,
                "ingest_apply_s": self.apply_s,
                "refreshes": self.refreshes, "refresh_s": self.refresh_s,
                "refresh_bytes": self.refresh_bytes}


def warm(engine, feed) -> None:
    """Apply the set-up batches of ``feed``, one at a time."""
    for vs, as_ in zip(feed.vectors, feed.attrs):
        res = engine.submit_ingest(vs, as_)
        if res.accepted != len(as_):
            raise RuntimeError(f"set-up batch not acked: {res!r}")
        engine.drain()


def index_rows(vectors, attrs, warm_feed, feed, log):
    """-> (vectors, attributes) of the rows the index holds after a sound
    run, in id order: the corpus, the set-up batches, then the acked
    batches of the window in the order of their acks."""
    d = vectors.shape[1]
    acked = np.flatnonzero(log.acked)
    return (np.concatenate([vectors, warm_feed.vectors.reshape(-1, d),
                            feed.vectors[acked].reshape(-1, d)]),
            np.concatenate([attrs, warm_feed.attrs.ravel(),
                            feed.attrs[acked].ravel()]))


def summary_line(feed, log, visible_ms, counters: dict, n: int) -> str:
    """The window's ingest, for standard error: acks, lag from due to
    applied, the timed applies and refreshes, rows against capacity."""
    ack = log.ack_s[log.acked] * 1e3
    vis = visible_ms if visible_ms.size else np.zeros(1)
    applies = max(counters["ingest_applied"], 1)
    refreshes = max(counters["refreshes"], 1)
    rows = feed.attrs.shape[1]
    return (f"ingest: {len(log.acked)} batches of {rows} rows offered, "
            f"{log.acked.sum()} acked, {np.sum(~np.isnan(log.visible_t))} "
            f"seen applied; ack p50 {np.median(ack) if ack.size else 0:.3f}"
            f" / max {np.max(ack, initial=0):.3f} ms; due to applied p50 "
            f"{np.median(vis):.3f} / p95 {np.percentile(vis, 95):.3f} / max "
            f"{np.max(vis):.3f} ms; {counters['ingest_applied']} applies, "
            f"{counters['ingest_apply_s'] * 1e3 / applies:.3f} ms each; "
            f"{counters['refreshes']} refreshes, "
            f"{counters['refresh_s'] * 1e3 / refreshes:.3f} ms and "
            f"{counters['refresh_bytes'] / refreshes / 1e6:.3f} MB each; "
            f"rows {n + WARM_BATCHES * rows + feed.attrs.size} of the "
            f"device capacity {capacity(n)}")


def unread_acked(engine, feed, log, dist_gap: float) -> int:
    """Acked batches whose read-back probe fails (see the module)."""
    from repro.serve.lifecycle import Ticket

    store = engine.index.store
    acked = np.flatnonzero(log.acked)
    rid = {}
    for j in acked:
        a = float(feed.attrs[j, 0])
        t = engine.submit(feed.vectors[j, 0], (a, a))
        if isinstance(t, Ticket):
            rid[t.rid] = j
    got = {rep.rid: rep for rep in engine.drain()}
    failed = len(acked) - len(rid)
    for r, j in rid.items():
        rep = got.get(r)
        q = feed.vectors[j, 0]
        ok = rep is not None and len(rep.ids) > 0 and rep.ids[0] >= 0
        if ok:
            i = int(rep.ids[0])
            ok = (i < store.n and store.attrs[i] == feed.attrs[j, 0]
                  and np.asarray(store.vectors[i], np.float32).tobytes()
                  == q.tobytes()
                  and abs(float(rep.dists[0]))
                  <= dist_gap * float(np.dot(q, q)))
        failed += not ok
    return failed


def wal_inserts(wal_dir: str):
    """Yield ``(lsn, vectors, attrs, file, end)`` for each insert record
    of the WAL segments in ``wal_dir``: ``file`` is ``(st_dev, st_ino)``
    of its segment and ``end`` the offset just past the record.  A segment
    is read up to its first record that does not check out."""
    for name in sorted(os.listdir(wal_dir)):
        path = os.path.join(wal_dir, name)
        with open(path, "rb") as f:
            data = f.read()
        if not data.startswith(SEG_MAGIC):
            continue
        st = os.stat(path)
        off = SEG_HEADER
        while off + 8 <= len(data):
            length, crc = struct.unpack_from("<II", data, off)
            body = data[off + 8:off + 8 + length]
            if len(body) < max(length, 9) or zlib.crc32(body) != crc:
                break
            rtype, lsn = struct.unpack_from("<BQ", body)
            off += 8 + length
            if rtype == T_INSERT:
                (n,) = struct.unpack_from("<I", body, 9)
                buf = io.BytesIO(body[13 + n:])
                vs = np.load(buf, allow_pickle=False)
                yield (lsn, vs, np.load(buf, allow_pickle=False),
                       (st.st_dev, st.st_ino), off)


def wal_numbers(wal_dir: str, feed, log, synced_at_ack: dict) -> dict:
    """``lost_acked`` and ``unsynced_acked`` (see the module) of the WAL
    in ``wal_dir``."""
    logged = {}  # attribute bytes -> (lsn, vector bytes, file, end)
    for lsn, vs, as_, file, end in wal_inserts(wal_dir):
        for v, a in zip(vs, as_):
            logged.setdefault(a.tobytes(), (lsn, v.tobytes(), file, end))
    lost = unsynced = 0
    for j in np.flatnonzero(log.acked):
        synced = synced_at_ack.get(int(log.lsn[j]), {})
        durable = True
        for v, a in zip(feed.vectors[j], feed.attrs[j]):
            lsn, got, file, end = logged.get(a.tobytes(), (None,) * 4)
            ok = lsn is not None and lsn <= log.lsn[j] and got == v.tobytes()
            lost += not ok
            durable = durable and ok and synced.get(file, -1) >= end
        unsynced += not durable
    return {"lost_acked": lost, "unsynced_acked": unsynced}
