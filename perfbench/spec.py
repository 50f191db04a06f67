"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A configuration is the file ``BENCHMARK.json`` names for it
(``configs/<config>.json``), a traffic mix is ``traffic/<traffic>.json`` and
a per-layer metric is ``metrics/<name>.py`` (a module with ``read(ctx)``),
under the benchmark's directory.  Adding a configuration, a cell or a metric
therefore adds files and entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[tuple] = None    # None: every cell

    def applies_to(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple
    per_layer: tuple


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics(entries) -> tuple:
    out = []
    for e in entries:
        wl = e.get("workloads")
        out.append(Metric(name=e["name"], unit=e["unit"],
                          workloads=tuple(wl) if wl else None))
    return tuple(out)


class Benchmark:
    """``BENCHMARK.json`` at ``root`` plus the files it names under
    ``bench_dir`` (``<root>/perfbench`` unless given)."""

    def __init__(self, root: Path, bench_dir: Optional[Path] = None):
        self.root = Path(root)
        self.bench_dir = Path(bench_dir) if bench_dir else BENCH_DIR
        self.spec = _load_json(self.root / "BENCHMARK.json")
        self.end_to_end = _metrics(self.spec["end_to_end"])
        self.per_layer = _metrics(self.spec["per_layer"])

    def config(self, name: str) -> dict:
        entry = next(c for c in self.spec["configs"] if c["name"] == name)
        cfg = _load_json(self.root / entry["file"])
        cfg.setdefault("name", name)
        return cfg

    def traffic(self, name: str) -> dict:
        t = _load_json(self.bench_dir / "traffic" / f"{name}.json")
        t.setdefault("name", name)
        return t

    def cell(self, name: str) -> Cell:
        entries = [w for w in self.spec["workloads"] if w["name"] == name]
        if not entries:
            known = ", ".join(w["name"] for w in self.spec["workloads"])
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(known: {known})")
        w = entries[0]
        return Cell(
            name=name, config=self.config(w["config"]),
            traffic=self.traffic(w["traffic"]), chips=int(w["chips"]),
            end_to_end=tuple(m for m in self.end_to_end
                             if m.applies_to(name)),
            per_layer=tuple(m for m in self.per_layer if m.applies_to(name)))

    def reader(self, metric: str) -> Callable:
        """``read(ctx)`` of ``metrics/<metric>.py``."""
        path = self.bench_dir / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"perfbench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
