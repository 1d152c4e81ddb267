"""Drive the rest of a run without the harness's look for a chip."""

import json
import os
import time

from benchmark import run as bench_run
from benchmark.harness import spec

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# cells whose files are here and tested but which BENCHMARK.json does not
# list yet (PERF.md, Open questions): cell -> its configuration
UNLISTED = {"solo-kvstore-load": "solo-kvstore"}


def _unlisted_cell(name: str) -> spec.Cell:
    load = lambda *p: json.load(open(os.path.join(spec.BENCH_DIR, *p)))  # noqa: E731
    config = load("configs", UNLISTED[name] + ".json")
    mix = load("workloads", name + ".json")
    tiny = load("rehearsal", name + ".json")
    config.update(tiny.get("config", {}))
    mix.update(tiny.get("mix", {}))
    return spec.Cell(name=name, chips=1, config=config, mix=mix,
                     end_to_end=[], per_layer=[], run_seconds=30)


def run_cell(name: str, seed: int, seconds: float, control: str = ""):
    if name in UNLISTED:
        cell = _unlisted_cell(name)
    else:
        cell = spec.load_cell(name, rehearsal=True)
    return bench_run.execute(
        cell, seed, seconds, False, CPU, control=control,
        t_process=time.monotonic(),
    )


def device_only_checks(result: dict) -> dict:
    """Checks that can only hold on the chip (lanes through the device
    verifier) are left out of a CPU test's verdict."""
    return {k: v for k, v in result["checks"].items()
            if k != "lanes_not_through_verifier"}


def correct_on_cpu(result: dict) -> bool:
    return all(c["value"] <= c["limit"]
               for c in device_only_checks(result).values())
