"""Resolve a cell by its name in BENCHMARK.json to its data files.

A cell is data: `workloads[i]` names a configuration and a traffic mix;
`benchmark/configs/<configuration>.json` holds the sizes as they are run
and names the family, `benchmark/traffic/<traffic>.json` the batch and
lengths, `benchmark/limits/<cell>.json` the limits of the comparison that
decides `correct`, `benchmark/families/<family>.py` everything about the
model, `benchmark/layer_metrics/<metric>.py` one reader per per-layer
metric. A later PR adds files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


class Cell:
    def __init__(self, name):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            self.benchmark = json.load(f)
        rows = [w for w in self.benchmark["workloads"] if w["name"] == name]
        if not rows:
            raise SystemExit(
                f"no workload {name!r} in BENCHMARK.json; there are "
                f"{[w['name'] for w in self.benchmark['workloads']]}")
        self.name = name
        self.row = rows[0]
        self.chips = int(self.row["chips"])
        entry = [c for c in self.benchmark["configs"]
                 if c["name"] == self.row["config"]][0]
        with open(os.path.join(ROOT, entry["file"])) as f:
            self.config = json.load(f)
        self.traffic = _load("traffic", self.row["traffic"] + ".json")
        self.limits = _load("limits", name + ".json")
        self.family = importlib.import_module(
            "benchmark.families." + self.config["family"])

    def limits_for(self, rehearsal):
        """The comparison's limits: the cell's own, read on the chip at
        the cell's size, or — in a rehearsal — those read on the CPU at
        the rehearsal's size (the file's "rehearsal" group)."""
        return self.limits["rehearsal"] if rehearsal else self.limits

    def _reports(self, metric):
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.benchmark["end_to_end"] if self._reports(m)]

    def per_layer(self):
        return [m for m in self.benchmark["per_layer"] if self._reports(m)]


def layer_metric_reader(name):
    """The `read(ctx)` of benchmark/layer_metrics/<name>.py ('.' and '-'
    in a metric's name map to '_')."""
    mod = importlib.import_module(
        "benchmark.layer_metrics." + name.replace(".", "_").replace("-", "_"))
    return mod.read
