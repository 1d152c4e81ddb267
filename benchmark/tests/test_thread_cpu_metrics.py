"""The ten per-layer metrics of the interpreter lock's demand (PR 36) read
the series the program renders, by their exact names, through
``counter_ratio``; from a program without those series (the parent) they
read 0, and a rehearsed run of each vote cell prints them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import counters, spec
from benchmark.readers import counter_ratio
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs.metrics import NodeMetrics

CELLS = {"round": "vote1000-jitter", "ext": "qa175ve-jitter"}
BASES = {
    "receive_cpu_ms_per_height": "consensus receive routine",
    "drain_cpu_pct": "consensus receive routine",
    "peer_routine_cpu_ms_per_height": "consensus reactor",
    "profiler_cpu_ms_per_height": "process (observability planes)",
    "process_cpu_ms_per_height": "process (all threads)",
}
NAMES = [f"{b}.{s}" for s in CELLS for b in BASES]
HEIGHTS = 4
# seconds of CPU by role over the window, and the drains' CPU and wall
ROLES = {
    "cs-receive": 1.5, "gossip-data": 0.8, "gossip-votes": 0.6,
    "maj23": 0.1, "mempool-bcast": 0.4, "evidence-bcast": 0.3,
    "prof-sampler": 0.05, "cs-commit-writer": 0.2, "bench-feeder": 1.0,
}
DRAIN_CPU, DRAIN_WALL = 1.2, 3.0


def _metric(name: str) -> dict:
    cell = CELLS[name.rsplit(".", 1)[1]]
    (entry,) = [m for m in spec.load_cell(cell).per_layer
                if m["name"] == name]
    return entry


class _Ctx:
    def __init__(self, delta):
        self.counters = delta


def _recorded() -> dict:
    """A window's deltas as a node renders them: heights, drains, roles."""
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        before = counters._prom(m.registry)
        for _ in range(HEIGHTS):
            m.consensus_vote_phase_seconds.labels("height").observe(0.7)
        m.consensus_vote_phase_seconds.labels("drain").observe(DRAIN_WALL)
        libmetrics.observe_drain_cpu(int(DRAIN_CPU * 1e9))
        for role, s in ROLES.items():
            m.thread_cpu_seconds.labels(role).inc(s)
        return counters.delta(before, counters._prom(m.registry))
    finally:
        libmetrics.pop_node_metrics(m)


def _parent_like() -> dict:
    """The same window from a program that renders none of the new
    series: the heights and drains are there, the CPU is not."""
    return {k: v for k, v in _recorded().items()
            if "cpu_seconds" not in k}


@pytest.mark.parametrize("name", NAMES)
def test_metric_is_listed_for_its_cell_on_the_ratio_reader(name):
    m = _metric(name)
    assert m["reader"] == "counter_ratio"
    assert m["source"] == "program_counter"
    assert m["layer"] == BASES[name.rsplit(".", 1)[0]]
    assert m["moves"] == "sigs_per_s"
    assert m["workloads"] == [CELLS[name.rsplit(".", 1)[1]]]
    assert "reads 0 because the series is missing" in m["what"]


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_reader_finds_the_series_by_their_exact_names(suffix):
    ctx = _Ctx(_recorded())
    got = {b: counter_ratio.read(_metric(f"{b}.{suffix}"), ctx)
           for b in BASES}
    per = 1000.0 / HEIGHTS
    assert got["receive_cpu_ms_per_height"] == pytest.approx(1.5 * per)
    assert got["drain_cpu_pct"] == pytest.approx(100 * DRAIN_CPU / DRAIN_WALL)
    assert got["peer_routine_cpu_ms_per_height"] == pytest.approx(
        (0.8 + 0.6 + 0.1 + 0.4 + 0.3) * per)
    assert got["profiler_cpu_ms_per_height"] == pytest.approx(0.05 * per)
    assert got["process_cpu_ms_per_height"] == pytest.approx(
        sum(ROLES.values()) * per)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_series_reads_zero(name):
    assert counter_ratio.read(_metric(name), _Ctx(_parent_like())) == 0.0


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_rehearse_prints_the_five_metrics(suffix):
    root = spec.ROOT
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELLS[suffix], "--rehearse", "--seed", "2147483661",
         "--seconds", "3", "--trace", "1"],
        capture_output=True, text=True, timeout=900, cwd=root,
        env={k: v for k, v in os.environ.items()
             if not k.startswith("COMETBFT_TPU_")},
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal_correct"] is True, line["checks"]
    for b in BASES:
        assert line["metrics"][f"{b}.{suffix}"]["value"] > 0, b
    assert line["metrics"][f"drain_cpu_pct.{suffix}"]["value"] <= 100
