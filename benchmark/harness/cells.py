"""Finds a cell's files by the names ``BENCHMARK.json`` gives.

Nothing here knows a cell, a mix, a configuration or a metric by name:
a later PR adds files and entries and edits none that is there.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(os.path.join(REPO, path), encoding="utf-8") as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _load("BENCHMARK.json")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration's file, as it is run
    traffic: dict           # the mix's file
    workload: dict          # the cell's own file: item, tolerances, why
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)

    @property
    def runner(self) -> str:
        return self.config["runner"]

    def sized(self, rehearsal: bool) -> "Cell":
        """The cell as it runs: a rehearsal lays each file's ``rehearsal``
        group over its sizes, a chip run takes the file as written."""
        if not rehearsal:
            return self
        over = lambda d: {**d, **d.get("rehearsal", {})}  # noqa: E731
        return Cell(self.name, self.chips, over(self.config),
                    over(self.traffic), over(self.workload),
                    self.end_to_end, self.per_layer)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    bench = load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {name!r}; it has "
                       f"{[w['name'] for w in bench['workloads']]}")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    # the lists are read as written, as the driver reads them; that a
    # per-layer metric's cells are among those of the metric it moves is
    # tests/test_contract.py's to hold
    layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name=name, chips=int(entry["chips"]),
                config=_load(cfg["file"]),
                traffic=_load(f"benchmark/traffic/{entry['traffic']}.json"),
                workload=_load(f"benchmark/workloads/{name}.json"),
                end_to_end=e2e, per_layer=layer)


def load_runner(name: str):
    return importlib.import_module(f"benchmark.runners.{name}")


def load_reader(kind: str, metric: str):
    """``benchmark/<kind>/<metric>.py`` -> its ``read(run)``."""
    return importlib.import_module(f"benchmark.{kind}.{metric}").read
