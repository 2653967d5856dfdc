"""Finds a cell's pieces by name: its configuration, traffic mix, cell file
and per-layer metric readers, each in a file of its own.

    <root>/configs/<config>.json     sizes, build and engine settings, limits
    <root>/traffic/<mix>.json        arrival and request parameters, and
                                     the ingest stream's with its limits
    <root>/workloads/<cell>.json     config, traffic, rates and metric names
    <root>/metrics/<metric>.py       UNIT and read(ctx) -> float | None
    <root>/peaks.json                chip peaks keyed by device_kind

A later cell, mix or metric is a new file here and an entry in
``BENCHMARK.json``; no code changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Layout:
    """Where the data files and the caches live.  Tests point ``root`` at
    a copy that holds files of their own, and ``cache`` at a temp dir."""

    root: Path = BENCH
    cache: Path = BENCH / ".cache"

    def _json(self, kind: str, name: str) -> dict:
        path = self.root / kind / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
        return json.loads(path.read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def workload(self, name: str) -> dict:
        return self._json("workloads", name)

    def reader(self, metric: str):
        """The module of a per-layer metric: ``UNIT`` and ``read(ctx)``."""
        path = self.root / "metrics" / f"{metric}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no metric reader named {metric!r} "
                                    f"({path})")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def peaks(self, device_kind: str) -> dict:
        """Peaks of one chip; a kind that is not in the table is an error."""
        table = json.loads((self.root / "peaks.json").read_text())
        if device_kind not in table["chips"]:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"peaks.json (known: {sorted(table['chips'])})")
        return table["chips"][device_kind]


@dataclass
class Cell:
    """One cell with its pieces resolved."""

    name: str
    spec: dict
    config: dict
    traffic: dict

    @classmethod
    def load(cls, layout: Layout, name: str) -> "Cell":
        spec = layout.workload(name)
        return cls(name=name, spec=spec, config=layout.config(spec["config"]),
                   traffic=layout.traffic(spec["traffic"]))

    @property
    def rate(self) -> float:
        return float(self.spec["rate_qps"])

    @property
    def ingest_rate(self) -> float:
        """Ingest batches per second, in a cell whose mix appends."""
        return float(self.spec["ingest_batches_per_s"])
