"""The corpus of a configuration: its vectors and attributes, the cached
build of its index, and the fresh per-run copy that a run serves from.

The generators are copies of ``core/datasets.py`` (``make_vectors``,
``make_attrs(kind="random")``): the benchmark keeps its own, so that a
later change to the program cannot change the data it is measured on.
``corpus_vectors(cfg)`` equals ``make_vectors(n, d, data_seed)`` bit for
bit (``bench/tests/test_corpus.py``).

A build takes minutes on the chip (the host edge commit of
``insert_batch`` runs about 139 rows/s at d = 128), so a run builds only
when ``<cache>/corpus-<digest>`` is missing.  The digest covers every file
under ``src/`` and the configuration's ``corpus`` and ``build`` blocks;
two configurations that serve one corpus differently share one build.
Runs never write to the cached build: each copies it into
``<cache>/run`` and serves and logs there.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

SRC = Path(__file__).resolve().parents[1] / "src"


def cluster_centers(corpus: dict) -> tuple[np.random.Generator, np.ndarray]:
    """The data seed's generator and cluster centers, drawn in
    ``make_vectors``'s order (centers first)."""
    rng = np.random.default_rng(corpus["data_seed"])
    centers = rng.normal(size=(corpus["clusters"], corpus["d"]))
    return rng, centers.astype(np.float32) * 4.0


def draw_rows(rng: np.random.Generator, centers: np.ndarray,
              count: int) -> np.ndarray:
    """``count`` rows from the clusters: a center plus unit noise."""
    assign = rng.integers(0, len(centers), size=count)
    noise = rng.normal(size=(count, centers.shape[1])).astype(np.float32)
    return (centers[assign] + noise).astype(np.float32)


def corpus_vectors(corpus: dict) -> np.ndarray:
    rng, centers = cluster_centers(corpus)
    return draw_rows(rng, centers, corpus["n"])


def corpus_attrs(corpus: dict) -> np.ndarray:
    """The random regime: a permutation of 0..n-1 (``make_attrs``)."""
    if corpus["attrs"] != "random":
        raise ValueError(f"unknown attribute regime {corpus['attrs']!r}")
    rng = np.random.default_rng(corpus["data_seed"] + 1)
    return rng.permutation(corpus["n"]).astype(np.float64)


def source_digest(cfg: dict) -> str:
    """Digest of every file under ``src/`` and of the corpus and build
    blocks of ``cfg``: the key of a cached build."""
    h = hashlib.sha256()
    for path in sorted(SRC.rglob("*")):
        if (not path.is_file() or "__pycache__" in path.parts
                or path.suffix == ".pyc"):
            continue
        h.update(path.relative_to(SRC).as_posix().encode())
        h.update(b"\0")
        h.update(path.read_bytes())
        h.update(b"\0")
    block = {"corpus": cfg["corpus"], "build": cfg["build"]}
    h.update(json.dumps(block, sort_keys=True).encode())
    return h.hexdigest()[:16]


def build_dir(cfg: dict, cache: Path) -> Path:
    return cache / f"corpus-{source_digest(cfg)}"


def build_corpus(cfg: dict, dest: Path) -> None:
    """Generate the corpus, build its index with ``insert_batch`` and
    checkpoint it into ``dest``.  Prints the build time and compile count.

    The build logs no WAL: the checkpoint alone holds it, so a run's
    ``open_durable`` starts a fresh log at LSN 1, and the cache stays one
    checkpoint in size."""
    from repro.analysis.compile_guard import CompileCounter
    from repro.core import WoWIndex

    corpus, build = cfg["corpus"], cfg["build"]
    tmp = dest.with_name(dest.name + ".tmp")
    shutil.rmtree(tmp, ignore_errors=True)
    t0 = time.perf_counter()
    vectors, attrs = corpus_vectors(corpus), corpus_attrs(corpus)
    idx = WoWIndex(dim=corpus["d"], m=build["m"], o=build["o"],
                   ef_construction=build["ef_construction"],
                   metric=corpus["metric"], seed=corpus["data_seed"])
    with CompileCounter() as cc:
        idx.insert_batch(vectors, attrs, batch_size=build["batch_size"],
                         backend=build["backend"])
    idx.checkpoint(str(tmp), incremental=False)
    secs = time.perf_counter() - t0
    os.replace(tmp, dest)
    print(f"build: {corpus['n']} rows in {secs:.3f} s = "
          f"{corpus['n'] / secs:.1f} rows/s (backend {build['backend']}, "
          f"batch {build['batch_size']}, {idx.graph.num_layers} layers; "
          f"{cc.count} compiles, {cc.total_secs:.3f} s compiling) -> "
          f"{dest.name}", file=sys.stderr, flush=True)


def cached_build(cfg: dict, cache: Path) -> Path:
    """The cached build of ``cfg``'s corpus, built first when missing."""
    dest = build_dir(cfg, cache)
    if not dest.is_dir():
        dest.parent.mkdir(parents=True, exist_ok=True)
        build_corpus(cfg, dest)
    return dest


def fresh_run_dir(build: Path, cache: Path) -> Path:
    """A fresh copy of the cached ``build``, for this run's WAL and state;
    the caller removes it at exit."""
    run = cache / "run"
    shutil.rmtree(run, ignore_errors=True)
    shutil.copytree(build, run)
    return run
