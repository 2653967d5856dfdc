"""wowlint: per-pass fixture violations, clean-tree gate, suppressions,
baseline mechanics, and the runtime compile guard — including the
shape-stable-ingest regression: ServeEngine serves a post-growth wave
with ZERO new compiles after ``warmup()``."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.analysis.engine import lint_paths, lint_repo, report_dead

REPO = Path(__file__).resolve().parents[1]


def _fixture(tmp_path, name, code):
    p = tmp_path / name
    p.write_text(textwrap.dedent(code))
    return p


def _names(findings):
    return {f.pass_name for f in findings}


# ------------------------------------------------------------ pass fixtures

JIT_PURITY_BAD = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def hop(x):
        if x > 0:                 # branch on tracer
            return np.asarray(x)  # host transfer
        for v in x:               # python loop over tracer
            x = x + v
        return float(jnp.sum(x))  # host sync
"""

JIT_PURITY_CLEAN = """
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np

    @functools.partial(jax.jit, static_argnames=("n",))
    def hop(x, n):
        if n > 3:                      # static arg: legal
            x = x + float(n)           # float() of a static: legal
        for _ in range(n):             # loop over static: legal
            x = helper(x, n)
        B, = x.shape                   # .shape is static
        if B > 8:
            x = x[:8]
        return jnp.where(x > 0, x, 0.0)

    def helper(x, n):
        w = np.arange(n)               # static arg from call site
        return x * w.sum()
"""

JIT_PURITY_CALLEE = """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def root(x):
        return helper(x)

    def helper(y):
        if y.sum() > 0:   # tainted via call-site propagation
            return y
        return -y
"""

SHAPE_BAD = """
    import numpy as np

    def assemble(take):
        wave_cap = 100          # non-pow2 sizing literal
        buf = np.zeros((48, 4)) # 48 = 1.5*32 half-step: legal
        pad = np.empty(0)       # empty: legal
        return wave_cap, buf, pad
"""

DTYPE_BAD = """
    import numpy as np

    def distances(vectors, q):
        dists = np.zeros(8, dtype=np.float64)       # distance-named f64
        vec16 = vectors.astype(np.float16)          # distance value f16
        attrs = np.zeros(8, dtype=np.float64)       # order keys: legal
        return dists, vec16, attrs
"""

DTYPE_QUANT_BAD = """
    import numpy as np

    def serve(q_vectors, scales, vectors):
        deq_vec = q_vectors.astype(np.float32)   # host-side dequant: finding
        scales = scales.astype(np.float16)       # scales must stay f32
        q_vectors = vectors.astype(np.int8)      # quantization: legal
        q_slab = np.zeros((4, 4), dtype=np.bfloat16)  # quant storage: legal
        return deq_vec, scales, q_vectors, q_slab
"""

DONATION_BAD = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def scatter(dst, idx, rows):
        return dst.at[idx].set(rows)

    def update(buf, idx, rows):
        out = scatter(buf, idx, rows)
        return out + buf          # buf was donated: dead reference

    def update_ok(buf, idx, rows):
        buf = scatter(buf, idx, rows)   # same-statement rebind: safe
        return buf + 1

    def update_nested(buf, idx, rows, span):
        with span:
            out = scatter(buf, idx, rows)
        return out + buf          # donated inside the block: dead reference

    def update_nested_ok(buf, idx, rows, span):
        with span:
            buf, n = scatter(buf, idx, rows), 1   # rebind in the block: safe
        for _ in range(n):
            buf = scatter(buf, idx, rows)
        return buf + 1
"""

DURABILITY_BAD = """
    class Ingest:
        def submit(self, wal, recs):
            for r in recs:
                wal.append("I", r, fsync=False)
            return len(recs)      # ack before wal.sync(): lost-write window

        def submit_ok(self, wal, recs):
            for r in recs:
                wal.append("I", r, fsync=False)
            wal.sync()
            return len(recs)
"""

REPLICATION_BAD = """
    class Primary:
        def append(self, rec):
            self.replicator.ship(rec)
            self.peer.send_ack(rec.lsn)   # ack while the ship is in flight

        def append_ok(self, rec):
            self.replicator.ship(rec)
            self.replicator.await_quorum(rec.lsn)
            self.peer.send_ack(rec.lsn)   # quorum-durable: clean

        def fence(self, msg):
            if msg.epoch <= self.epoch:   # non-strict: equal epoch passes
                return False
            return True
"""

_FIXTURES = {
    "jit-purity": JIT_PURITY_BAD,
    "shape-discipline": SHAPE_BAD,
    "dtype-drift": DTYPE_BAD,
    "donation-safety": DONATION_BAD,
    "durability-ordering": DURABILITY_BAD,
    "replication-ordering": REPLICATION_BAD,
}


@pytest.mark.parametrize("pass_name", sorted(_FIXTURES))
def test_pass_catches_seeded_violation(tmp_path, pass_name):
    p = _fixture(tmp_path, "bad.py", _FIXTURES[pass_name])
    findings = lint_paths([p], passes=[pass_name])
    assert findings, f"{pass_name} missed its seeded violation"
    assert _names(findings) == {pass_name}


def test_dtype_drift_quantized_slab_rules(tmp_path):
    """The quantized-arena rules: casting a q-slab back to f32 outside the
    kernel scope and any non-f32 scale cast are findings; quantization
    casts (into int8/bf16) and quantized storage creation are legal."""
    p = _fixture(tmp_path, "bad.py", DTYPE_QUANT_BAD)
    findings = lint_paths([p], passes=["dtype-drift"])
    msgs = " | ".join(f.message for f in findings)
    assert "host-side dequant" in msgs
    assert "scales must stay float32" in msgs
    assert len(findings) == 2, [f.message for f in findings]


def test_jit_purity_finds_each_violation_kind(tmp_path):
    p = _fixture(tmp_path, "bad.py", JIT_PURITY_BAD)
    msgs = " | ".join(f.message for f in lint_paths([p]))
    assert "`if` on a traced value" in msgs
    assert "np.asarray" in msgs
    assert "loop over a traced value" in msgs
    assert "float() on a traced value" in msgs


def test_jit_purity_static_args_are_clean(tmp_path):
    p = _fixture(tmp_path, "clean.py", JIT_PURITY_CLEAN)
    assert lint_paths([p], passes=["jit-purity"]) == []


def test_jit_purity_taint_propagates_to_callees(tmp_path):
    p = _fixture(tmp_path, "callee.py", JIT_PURITY_CALLEE)
    findings = lint_paths([p], passes=["jit-purity"])
    assert any("helper" in f.message for f in findings)


def test_donation_safe_rebind_not_flagged(tmp_path):
    p = _fixture(tmp_path, "don.py", DONATION_BAD)
    findings = lint_paths([p], passes=["donation-safety"])
    lines = DONATION_BAD.splitlines()
    # the two unsafe ones are the only findings, nested rebinds included
    assert sorted(f.line for f in findings) == [
        i for i, t in enumerate(lines, 1) if "dead reference" in t]


def test_replication_ack_and_epoch_rules_fire_separately(tmp_path):
    p = _fixture(tmp_path, "rep.py", REPLICATION_BAD)
    findings = lint_paths([p], passes=["replication-ordering"])
    msgs = [f.message for f in findings]
    # exactly one of each: append_ok's barriered ack is clean
    assert sum("quorum barrier" in m for m in msgs) == 1
    assert sum("non-strict epoch" in m for m in msgs) == 1


def test_durability_barrier_clears_pending(tmp_path):
    p = _fixture(tmp_path, "dur.py", DURABILITY_BAD)
    findings = lint_paths([p], passes=["durability-ordering"])
    lines = {f.line for f in findings}
    assert len(findings) == 1  # submit_ok's synced return is clean
    bad_line = next(i for i, t in enumerate(
        DURABILITY_BAD.splitlines(), 1) if "lost-write window" in t)
    assert lines == {bad_line}


# ------------------------------------------------- suppressions + baseline

def test_inline_suppression(tmp_path):
    code = SHAPE_BAD.replace(
        "wave_cap = 100",
        "wave_cap = 100  # wowlint: disable=shape-discipline")
    p = _fixture(tmp_path, "sup.py", code)
    assert lint_paths([p], passes=["shape-discipline"]) == []


def test_baseline_filters_accepted_findings(tmp_path):
    from repro.analysis.findings import load_baseline, save_baseline

    p = _fixture(tmp_path, "bad.py", SHAPE_BAD)
    findings = lint_paths([p], passes=["shape-discipline"])
    assert findings
    bl = tmp_path / "baseline.json"
    save_baseline(bl, findings)
    accepted = load_baseline(bl)
    assert all(f.key() in accepted for f in findings)
    left = [f for f in findings if f.key() not in accepted]
    assert left == []


# ------------------------------------------------------- whole-tree gates

def test_shipped_tree_lints_clean():
    assert lint_repo() == [], "src/repro must lint clean (or be baselined)"


def test_no_dead_modules_in_surface():
    assert report_dead() == []


def test_cli_fails_on_seeded_violation(tmp_path):
    # the CLI is jax-free in lint mode, so 5 subprocesses stay cheap
    for pass_name, code in _FIXTURES.items():
        p = _fixture(tmp_path, f"{pass_name}.py", code)
        res = subprocess.run(
            [sys.executable, "-m", "repro.analysis", "--fail-on-findings",
             "--pass", pass_name, str(p)],
            capture_output=True, text=True,
            env={**os.environ,
                 "PYTHONPATH": str(REPO / "src")},
        )
        assert res.returncode == 1, (pass_name, res.stdout, res.stderr)
        assert pass_name in res.stdout


def test_real_tree_roots_and_traced_set():
    """The call graph must see the repo's actual jit boundaries."""
    from repro.analysis.callgraph import RepoIndex
    from repro.analysis.engine import surface_files

    idx = RepoIndex(surface_files())
    roots = {f.qualname for f in idx.functions.values() if f.jit_root}
    assert "repro.core.device_search:_run_jit" in roots
    assert "repro.core.device_search:_run_jit_inplace" in roots
    assert "repro.core.device_search:_init_jit" in roots
    assert any("kernels.gather_distance" in r for r in roots)  # pallas
    traced = idx.traced_functions()
    assert "repro.core.device_search:_hop_body" in traced
    assert "repro.core.device_search:_landing_and_entry" in traced
    # host drivers must NOT be in the traced set
    assert not any(q.endswith(":warmup") for q in traced)


# ------------------------------------------------------- compile guard

def test_compile_counter_counts_once_then_cached():
    import jax
    import jax.numpy as jnp

    from repro.analysis import CompileCounter

    @jax.jit
    def f(x):
        return jnp.dot(x, x)

    x = jnp.arange(6, dtype=jnp.float32)
    with CompileCounter() as cold:
        f(x).block_until_ready()
    with CompileCounter() as warm:
        f(x).block_until_ready()
    assert cold.count >= 1
    assert warm.count == 0


def test_zero_compiles_after_warmup_across_ingest_growth():
    """The shape-stable-ingest gate: after ``warmup()``, serving a wave,
    growing the index by an ingest batch, and serving the refreshed
    snapshot must compile NOTHING — pow2 row padding keeps the grown
    snapshot on the warmed executables.  The gate covers every program
    the engine launches, its in-place chunk entry among them."""
    from repro.analysis import CompileCounter
    from repro.core import WoWIndex, make_workload
    from repro.core.device_search import _run_jit_inplace
    from repro.serve.lifecycle import EngineConfig, ServeEngine

    wl = make_workload(n=520, d=12, nq=16, seed=3, k=5, with_gt=False)
    idx = WoWIndex(dim=12, m=12, ef_construction=48, o=4, seed=0)
    idx.insert_batch(wl.vectors, wl.attrs, batch_size=128, backend="numpy")

    cfg = EngineConfig(k=5, width=16, max_wave=8, adaptive=False,
                       visited="bitmap", build_backend="numpy")
    eng = ServeEngine(index=idx, config=cfg)
    warmed = _run_jit_inplace._cache_size()
    eng.warmup()
    assert _run_jit_inplace._cache_size() > warmed  # warmup compiled it

    def serve_wave(n0):
        tickets = []
        for i in range(8):
            tickets.append(eng.submit(wl.queries[i], wl.ranges[i]))
        replies = eng.drain()
        assert len(replies) == 8
        return replies

    with CompileCounter("post-warmup") as cc:
        serve_wave(0)
        # ingest growth: 520 -> 640 rows, same pow2 snapshot capacity
        rng = np.random.default_rng(11)
        extra_v = rng.normal(size=(120, 12)).astype(np.float32)
        extra_a = (np.arange(120) / 120.0 + float(np.max(wl.attrs)) + 1.0)
        res = eng.submit_ingest(extra_v, extra_a)
        assert res.accepted == 120
        eng.drain()  # applies the ingest micro-batches
        assert len(idx) == 520 + 120
        serve_wave(1)  # post-growth wave on the refreshed snapshot
    assert cc.count == 0, (
        f"{cc.count} XLA compile(s) after warmup — ingest growth changed "
        f"a compiled shape (pow2 snapshot padding regressed)")


def test_padded_device_index_matches_unpadded_results():
    """Pow2 row padding must be invisible: device search over a padded
    index returns bitwise the ids/dists of the tight index."""
    import jax.numpy as jnp

    from repro.core import WoWIndex, make_workload
    from repro.core.device_search import (
        DeviceIndex,
        device_search,
        to_device_index,
    )
    from repro.core.snapshot import take_snapshot

    wl = make_workload(n=300, d=12, nq=12, seed=5, k=5, with_gt=False)
    idx = WoWIndex(dim=12, m=12, ef_construction=48, o=4, seed=0)
    idx.insert_batch(wl.vectors, wl.attrs, batch_size=128, backend="numpy")
    snap = take_snapshot(idx)
    di_pad = to_device_index(snap)
    assert di_pad.vectors.shape[0] == 512  # 300 -> pow2
    di_tight = DeviceIndex(
        vectors=jnp.asarray(snap.vectors, jnp.float32),
        sq_norms=jnp.asarray(snap.sq_norms, jnp.float32),
        attrs=jnp.asarray(snap.attrs, jnp.float32),
        neighbors=jnp.asarray(snap.neighbors, jnp.int32),
        uvals=jnp.asarray(snap.uvals, jnp.float32),
        uval_rep=jnp.asarray(snap.uval_rep, jnp.int32),
    )
    kw = dict(k=5, width=16, m=snap.m, o=snap.o, metric=snap.metric)
    r_pad = device_search(di_pad, wl.queries, wl.ranges, **kw)
    r_tight = device_search(di_tight, wl.queries, wl.ranges, **kw)
    np.testing.assert_array_equal(np.asarray(r_pad.ids),
                                  np.asarray(r_tight.ids))
    np.testing.assert_array_equal(np.asarray(r_pad.dists),
                                  np.asarray(r_tight.dists))
