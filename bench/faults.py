"""Faults planted in the program's ingest path, for the tests of the checks
and for ``control.py``'s readings on the chip.  Each must come out not
correct.

    plant("no-edges", setattr)   # or a test's monkeypatch.setattr

* ``no-edges`` - every ``insert_batch`` stores its rows and attributes
  but leaves the new rows without out-edges at every layer: the one-value
  probe still finds each row by its attribute, only a search through the
  new rows fails (``append_recall_loss``);
* ``no-sync`` - ``WalWriter.sync``, the engine's group-commit barrier, does
  nothing: every batch is logged, and acked before it is durable
  (``unsynced_acked``).
"""
from __future__ import annotations

FAULTS = ("no-edges", "no-sync")


def plant(name: str, put) -> None:
    """Plant fault ``name`` through ``put(owner, attribute, value)``."""
    if name == "no-edges":
        from repro.core.graph import PAD
        from repro.core.index import WoWIndex

        insert = WoWIndex.insert_batch

        def edgeless(self, *args, **kw):
            ids = insert(self, *args, **kw)
            graph = self.graph
            for layer, count in zip(graph.layers, graph.counts):
                layer[ids] = PAD
                count[ids] = 0
            graph.version += 1
            return ids

        put(WoWIndex, "insert_batch", edgeless)
    elif name == "no-sync":
        from repro.persist.wal import WalWriter

        put(WalWriter, "sync", lambda self: None)
    else:
        raise ValueError(f"unknown fault {name!r}; known: {FAULTS}")
