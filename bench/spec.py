"""``BENCHMARK.json`` and the files it names, resolved by name.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
Its configuration file, its traffic file and the reader of each per-layer
metric it reports are found from names alone, so a later cell, mix or metric
is added with new files and new entries, never by editing one that is there.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

ROOT = Path(__file__).resolve().parents[1]

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}\Z")


@dataclass(frozen=True)
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names loaded."""

    name: str
    chips: int
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic file's contents
    end_to_end: tuple[dict, ...]  # metric entries this cell reports
    per_layer: tuple[dict, ...]
    root: Path

    @property
    def kind(self) -> str:
        """``write`` or ``read``: which loop of :mod:`bench.harness` runs it."""
        return self.traffic["loop"]

    def reader(self, metric: str) -> Callable:
        """The ``read`` function of ``bench/metrics/<metric>.py``."""
        return load_reader(self.root, metric)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with its files loaded by name."""
    root = Path(root)
    bench = load_benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if len(entries) != 1:
        raise KeyError(f"no workload {workload!r} in {root / 'BENCHMARK.json'}")
    entry = entries[0]
    (cfg_entry,) = [c for c in bench["configs"] if c["name"] == entry["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{entry['traffic']}.json").read_text())
    e2e = tuple(m for m in bench["end_to_end"] if _applies(m, workload))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(
        m for m in bench["per_layer"]
        if _applies(m, workload) and m["moves"] in reported
    )
    return Cell(
        name=workload, chips=int(entry["chips"]), config=config, traffic=traffic,
        end_to_end=e2e, per_layer=per_layer, root=root,
    )


def reader_path(root: Path, metric: str) -> Path:
    """``bench/metrics/<metric>.py``; where that is missing, a metric named
    ``<base>.<suffix>`` (one quantity split by the end-to-end metric it
    moves) is read by ``bench/metrics/<base>.py``."""
    metrics = Path(root) / "bench" / "metrics"
    path = metrics / f"{metric}.py"
    if not path.is_file() and "." in metric:
        path = metrics / f"{metric.rsplit('.', 1)[0]}.py"
    return path


def load_reader(root: Path, metric: str) -> Callable:
    """Import the metric's reader by path (metric names may hold dots,
    which a module name may not) and return its ``read``."""
    path = reader_path(root, metric)
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
