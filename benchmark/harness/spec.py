"""Finds a cell's files by the names in BENCHMARK.json.

  workloads[].name            -> benchmark/workloads/<name>.json  (traffic mix
                                 and the driver that plays it)
  workloads[].config          -> configs[].file (the deployment's sizes)
  per_layer[].name            -> benchmark/metrics/<name>.json, which names
                                 its reader ``benchmark/readers/<reader>.py``
  end_to_end[] / per_layer[]  -> a metric belongs to a cell when it has no
                                 ``workloads`` key or lists the cell there

A later PR adds a cell, a configuration or a per-layer metric by adding such
files and BENCHMARK.json entries; no file here is edited for it.
"""

from __future__ import annotations

import importlib
import json
import os
from dataclasses import dataclass

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict  # the configuration's file
    mix: dict  # the traffic mix's file
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]  # BENCHMARK.json entries + their metric file
    run_seconds: int


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, rehearsal: bool = False) -> Cell:
    """The cell as BENCHMARK.json and its files define it. ``rehearsal``
    lays benchmark/rehearsal/<name>.json over them: tiny sizes for the CPU,
    which run.py never lets pass for a result."""
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    rows = [w for w in bench["workloads"] if w["name"] == name]
    if not rows:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json ({known})")
    row = rows[0]
    cfg_row = next(c for c in bench["configs"] if c["name"] == row["config"])
    per_layer = []
    for m in bench["per_layer"]:
        if _in_cell(m, name):
            body = _load(os.path.join(BENCH_DIR, "metrics", m["name"] + ".json"))
            per_layer.append({**m, **body})
    config = _load(os.path.join(ROOT, cfg_row["file"]))
    mix = _load(os.path.join(BENCH_DIR, "workloads", name + ".json"))
    if rehearsal:
        tiny = _load(os.path.join(BENCH_DIR, "rehearsal", name + ".json"))
        config.update(tiny.get("config", {}))
        mix.update(tiny.get("mix", {}))
    return Cell(
        name=name,
        chips=row["chips"],
        config=config,
        mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, name)],
        per_layer=per_layer,
        run_seconds=bench["run_seconds"],
    )


def load_driver(mix: dict):
    """The driver module a traffic mix names: benchmark/drivers/<name>.py."""
    return importlib.import_module(f"benchmark.drivers.{mix['driver']}")


def load_reader(metric: dict):
    return importlib.import_module(f"benchmark.readers.{metric['reader']}")
