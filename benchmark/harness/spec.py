"""Finds what a cell is made of by the names in `BENCHMARK.json`.

The harness is driven by data: a later PR adds files and entries and edits no
file that is there. So nothing here lists cells, configurations, paths or
metrics. A cell's entry names its configuration and traffic; the
configuration's file names its `family`, the traffic's file its `path`; a
metric's entry is its own name. Each name is a file:

    <dir>/configs/<config>.json      (the entry's `file`)
    <dir>/traffic/<traffic>.json
    <dir>/families/<family>.py       state, batch, FLOPs, reference check
    <dir>/paths/<path>.py            how a step is driven
    <dir>/end_to_end/<metric>.py     one reader per end-to-end metric
    <dir>/layer_metrics/<metric>.py  one reader per per-layer metric

where `<dir>` is any directory under `paths` of the `BENCHMARK.json` read,
and then this package's own (so that a cell kept elsewhere, such as a test's
tiny one, can use the families and paths that are here).
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from dataclasses import dataclass

PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PACKAGE_DIR)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple   # the cell's metric entries of BENCHMARK.json
    per_layer: tuple
    dirs: tuple         # where this cell's files and modules are looked up


def _find(dirs, *parts) -> str:
    for d in dirs:
        path = os.path.join(d, *parts)
        if os.path.exists(path):
            return path
    raise FileNotFoundError(
        f"no {os.path.join(*parts)} under any of {list(dirs)}")


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", (cell,))


def load_cell(workload: str, root: str = REPO) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(there are: {sorted(entries)})")
    entry = entries[workload]
    dirs = tuple(os.path.join(root, p) for p in bench["paths"])
    if PACKAGE_DIR not in dirs:
        dirs += (PACKAGE_DIR,)
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = _read_json(os.path.join(root, config_entry["file"]))
    traffic = _read_json(_find(dirs, "traffic", entry["traffic"] + ".json"))
    end_to_end = tuple(m for m in bench["end_to_end"]
                       if _in_cell(m, workload))
    reported = {m["name"] for m in end_to_end}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _in_cell(m, workload) and m["moves"] in reported)
    return Cell(name=workload, chips=entry["chips"], config=config,
                traffic=traffic, end_to_end=end_to_end, per_layer=per_layer,
                dirs=dirs)


def load_module(kind: str, name: str, dirs):
    """The module `<dir>/<kind>/<name>.py` of the first `<dir>` that has it."""
    path = _find(dirs, kind, name + ".py")
    if os.path.dirname(os.path.dirname(path)) == PACKAGE_DIR:
        return importlib.import_module(f"benchmark.{kind}.{name}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_added.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
