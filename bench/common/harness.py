"""Finds a cell's files by the names in ``BENCHMARK.json`` and runs it.

Nothing here knows a configuration, a traffic mix or a per-layer metric
by name:

* a configuration is the directory of its ``file``: ``config.json`` (the
  sizes as run, and the ``driver`` that runs them), ``system.py`` (how
  the program is built from it) and ``reference.py`` (its plain
  reference, weights or data made from the seed, and the control);
* a traffic mix is ``bench/traffic/<traffic>.json``, read by
  :mod:`bench.common.traffic`;
* a per-layer metric is ``bench/layers/<metric>.py`` with ``read(readings)``;
* a cell's limits on what ``correct`` compares are
  ``bench/limits/<workload>.json``;
* a driver is ``bench/common/<driver>.py`` with ``run(cell)``.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path (names may hold dots
    and dashes, which ``import`` refuses)."""
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json; "
                   f"known: {[e['name'] for e in entries]}")


def metrics_of(bench: dict, workload: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    ones: every metric that lists the cell, or lists no cells."""
    kind = "per_layer" if traced else "end_to_end"
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


@dataclass
class Cell:
    """Everything a driver needs to run one cell once."""

    root: Path
    workload: dict
    config: dict
    config_dir: Path
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list = field(default_factory=list)
    recorder: Any = None        # trace.Recorder of a traced run

    @property
    def chips(self) -> int:
        return self.workload["chips"]

    def module(self, name: str) -> ModuleType:
        return load_module(self.config_dir / f"{name}.py")


@dataclass
class Readings:
    """What per-layer readers read: the reduced trace of the traced
    window (or None), the driver's counts, the configuration, the mix
    and the chip's peaks."""

    trace: Any
    counts: dict
    config: dict
    traffic: dict
    peak: dict
    chips: int


@dataclass
class Outcome:
    """What a driver returns from one run."""

    attempted: int
    failed: int
    metrics: dict           # end-to-end metric name -> value
    checks: dict            # compared name -> (value, limit or None)
    notes: dict             # printed on earlier lines, never a metric
    memory_peak_bytes: int
    readings: Readings | None = None

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(
            lim is not None and v <= lim for v, lim in self.checks.values())


def make_cell(root: Path, workload: str, seed: int, seconds: float,
              trace: bool, t_start: float | None = None) -> tuple[dict, Cell]:
    bench = load_json(root / "BENCHMARK.json")
    wl = _named(bench["workloads"], workload, "workload")
    entry = _named(bench["configs"], wl["config"], "configuration")
    cfg_file = root / entry["file"]
    limits_file = root / "bench" / "limits" / f"{workload}.json"
    cell = Cell(
        root=root, workload=wl, config=load_json(cfg_file),
        config_dir=cfg_file.parent,
        traffic=load_json(root / "bench" / "traffic" / f"{wl['traffic']}.json"),
        limits=load_json(limits_file) if limits_file.exists() else {},
        seed=seed, seconds=seconds, trace=trace,
        t_start=time.perf_counter() if t_start is None else t_start)
    return bench, cell


def limit(cell: Cell, name: str) -> float | None:
    """The cell's limit on compared number ``name``; None where the
    cell's limits file sets none, which no run passes."""
    entry = cell.limits.get(name)
    return None if entry is None else float(entry["limit"])


def driver(cell: Cell) -> ModuleType:
    return importlib.import_module(f"bench.common.{cell.config['driver']}")


def read_layers(root: Path, metrics: list[dict], readings: Readings,
                log) -> dict:
    """Run each per-layer metric's reader; a reader that finds nothing
    returns None and its metric is left out."""
    out = {}
    for m in metrics:
        got = load_module(root / "bench" / "layers" / f"{m['name']}.py").read(
            readings)
        note = None
        if isinstance(got, tuple):
            got, note = got
        if got is None:
            log(f"{m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(got), "unit": m["unit"]}
        if note:
            log(f"{m['name']}: {note}")
    return out
