"""The two per-layer metrics of the published round state (PR 32) read
series the program renders, by their exact names, and a rehearsed run of
the cell prints both."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import counters, spec
from benchmark.readers import counter_ratio
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs.metrics import NodeMetrics

CELL = "vote1000-jitter"
NAMES = ["round_state_published_read_pct.round",
         "state_mutex_wait_ms_per_vote.round"]


def _metric(name: str) -> dict:
    (entry,) = [m for m in spec.load_cell(CELL).per_layer if m["name"] == name]
    return entry


@pytest.mark.parametrize("name", NAMES)
def test_metric_is_listed_for_the_cell_on_the_ratio_reader(name):
    m = _metric(name)
    assert m["reader"] == "counter_ratio"
    assert m["layer"] == "consensus receive routine"
    assert m["moves"] == "sigs_per_s" and m["workloads"] == [CELL]


def test_reader_finds_both_series_by_their_exact_names():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        before = counters._prom(m.registry)
        libmetrics.observe_round_state_reads("published", 99)
        libmetrics.observe_round_state_reads("locked", 1)
        m.consensus_drain_items_total.labels("vote").inc(2000)
        m.lock_wait.labels("consensus.state").inc(0.5)
        m.lock_wait.labels("consensus.wal._mtx").inc(7.0)  # not this one's
        delta = counters.delta(before, counters._prom(m.registry))
    finally:
        libmetrics.pop_node_metrics(m)

    class Ctx:
        counters = delta

    assert counter_ratio.read(_metric(NAMES[0]), Ctx) == pytest.approx(99.0)
    assert counter_ratio.read(_metric(NAMES[1]), Ctx) == pytest.approx(0.25)


def test_program_without_the_counter_reports_nothing():
    class Ctx:
        counters = {
            'prom.cometbft_tpu_consensus_drain_items_total{kind="vote"}': 10.0}

    assert counter_ratio.read(_metric(NAMES[0]), Ctx) is None


def test_rehearse_prints_both_metrics():
    root = spec.ROOT
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seed", "2147483659",
         "--seconds", "2", "--trace", "1"],
        capture_output=True, text=True, timeout=600, cwd=root,
        env={k: v for k, v in os.environ.items()
             if not k.startswith("COMETBFT_TPU_")},
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal_correct"] is True, line["checks"]
    assert line["metrics"][NAMES[0]]["value"] >= 99
    assert line["metrics"][NAMES[1]]["value"] >= 0
