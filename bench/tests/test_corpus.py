"""The benchmark's copy of the generators, and the key of the build cache."""
import numpy as np

from bench import corpus

CORPUS = {"n": 3000, "d": 12, "data_seed": 4, "clusters": 32, "attrs": "random",
          "metric": "l2"}
CFG = {"corpus": CORPUS, "build": {"m": 8}}


def test_corpus_equals_the_program_generators():
    from repro.core.datasets import make_attrs, make_vectors

    v = corpus.corpus_vectors(CORPUS)
    np.testing.assert_array_equal(v, make_vectors(3000, 12, seed=4))
    np.testing.assert_array_equal(corpus.corpus_attrs(CORPUS),
                                  make_attrs(v, kind="random", seed=4))


def test_digest_follows_src_and_build_block(tmp_path, monkeypatch):
    src = tmp_path / "src"
    (src / "pkg").mkdir(parents=True)
    (src / "pkg" / "a.py").write_text("x = 1\n")
    monkeypatch.setattr(corpus, "SRC", src)
    d0 = corpus.source_digest(CFG)
    (src / "pkg" / "__pycache__").mkdir()
    (src / "pkg" / "__pycache__" / "a.cpython-312.pyc").write_bytes(b"\0")
    assert corpus.source_digest(CFG) == d0
    (src / "pkg" / "a.py").write_text("x = 2\n")
    d1 = corpus.source_digest(CFG)
    assert d1 != d0
    assert corpus.source_digest({"corpus": CORPUS, "build": {"m": 9}}) != d1
