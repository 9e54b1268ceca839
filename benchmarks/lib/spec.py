"""What one run is: BENCHMARK.json's entry for a workload, with the
configuration file and the traffic file it names. Everything that belongs
to one cell, configuration, mix or metric is found by name from here."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
METRIC_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


class SpecError(Exception):
    """BENCHMARK.json, or a file it names, does not say what a run needs."""


def load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{path}: {e}") from e


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    group: str  # "end_to_end" | "per_layer"
    entry: dict

    def applies_to(self, workload: str) -> bool:
        cells = self.entry.get("workloads")
        return cells is None or workload in cells


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    config_file: str
    traffic: dict         # the traffic file
    metrics: tuple        # every Metric of this cell, both groups
    run_seconds: int
    bench_dir: str = BENCH_DIR

    def metrics_of(self, group: str) -> list:
        return [m for m in self.metrics if m.group == group]


def load_cell(workload: str, benchmark_json: str | None = None,
              bench_dir: str = BENCH_DIR) -> Cell:
    """The cell `workload`. `benchmark_json` and `bench_dir` default to this
    checkout's; a test hands in a temporary directory to show that a new
    cell is files and one entry."""
    bj = load_json(benchmark_json or os.path.join(ROOT, "BENCHMARK.json"))
    entry = next((w for w in bj.get("workloads", ())
                  if w.get("name") == workload), None)
    if entry is None:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(has: {[w.get('name') for w in bj.get('workloads', ())]})")
    cfg_entry = next((c for c in bj.get("configs", ())
                      if c.get("name") == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {workload!r} names config "
                        f"{entry['config']!r}, which BENCHMARK.json lacks")
    root = os.path.dirname(bench_dir)
    config_file = os.path.join(root, cfg_entry["file"])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     entry["traffic"] + ".json"))
    metrics = tuple(
        Metric(m["name"], m["unit"], group, m)
        for group in METRIC_DIRS for m in bj.get(group, ())
        if Metric(m["name"], m["unit"], group, m).applies_to(workload))
    return Cell(workload, int(entry["chips"]), load_json(config_file),
                config_file, traffic, metrics, int(bj["run_seconds"]),
                bench_dir)


def load_reader(cell: Cell, metric: Metric):
    """The metric's own file, `<bench_dir>/<group dir>/<name>.py`, which has
    `read(ctx) -> float | None`. A name split by a suffix because it moves a
    different end-to-end metric in different cells (`x.lat`, `x.thr`) shares
    the reader of its stem, `x.py`, unless it has a file of its own."""
    folder = os.path.join(cell.bench_dir, METRIC_DIRS[metric.group])
    stem = metric.name.rsplit(".", 1)[0]
    path = next((p for p in (os.path.join(folder, metric.name + ".py"),
                             os.path.join(folder, stem + ".py"))
                 if os.path.exists(p)), None)
    if path is None:
        raise SpecError(f"metric {metric.name!r} has no reader "
                        f"({metric.name}.py or {stem}.py) in {folder}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric.name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} has no read(ctx)")
    return mod
