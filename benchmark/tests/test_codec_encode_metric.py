"""codec_encode_ms_per_block reads the codec's encode time a synced
block through ``counter_ratio``: the series are the ones a node renders,
the sync loop hands the time to the registry once a block, a program
without the series (the parent) reads 0, and a rehearsed catch-up prints
it."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from benchmark.harness import counters, spec
from benchmark.readers import counter_ratio
from cometbft_tpu.blocksync.reactor import BlocksyncReactor
from cometbft_tpu.libs import jsoncodec
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs.metrics import NodeMetrics
from cometbft_tpu.types import serialization as ser
from cometbft_tpu.types.block import BlockID, Commit, CommitSig, PartSetHeader

CELL = "mixed4096-catchup"
NAME = "codec_encode_ms_per_block"


class _Ctx:
    def __init__(self, delta):
        self.counters = delta


def _metric() -> dict:
    (entry,) = [m for m in spec.load_cell(CELL).per_layer
                if m["name"] == NAME]
    return entry


def _commit(n: int) -> Commit:
    return Commit(
        height=2, round=0, block_id=BlockID(b"\x01" * 32,
                                            PartSetHeader(1, b"\x02" * 32)),
        signatures=[CommitSig(2, bytes([i % 256]) * 20, i, b"\x03" * 64)
                    for i in range(n)],
    )


class _Pool:
    def __init__(self):
        self.popped = 0

    def pop_request(self):
        self.popped += 1


def _reactor(encode_per_block):
    """A sync loop whose verify-and-apply only encodes, as the part set
    and the store do."""
    r = BlocksyncReactor.__new__(BlocksyncReactor)
    r.pool = _Pool()
    r._n_synced = 0
    r._verify_and_apply = lambda first, ext, second: bool(
        encode_per_block())
    return r


def _blocks(r, n: int) -> None:
    first = SimpleNamespace(header=SimpleNamespace(height=5))
    second = SimpleNamespace(last_commit=None)
    for _ in range(n):
        r._apply_first(first, None, second)


def test_metric_is_listed_for_its_cell_on_the_ratio_reader():
    m = _metric()
    assert m["reader"] == "counter_ratio"
    assert m["source"] == "program_counter"
    assert m["layer"] == "block sync"
    assert m["moves"] == "sigs_per_s"
    assert m["workloads"] == [CELL]
    assert "reads 0 because the series is missing" in m["what"]


def test_the_time_reaches_the_registry_once_a_block():
    """Whatever the codec spent since the registry was made is in the
    series after _apply_first, and not before: the encodes add to a
    process-wide sum that only the sync loop hands over."""
    commit = _commit(64)
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        t0 = m._codec_encode_wm
        ser.dumps(commit)
        assert m.codec_encode_seconds.value() == 0.0
        _blocks(_reactor(lambda: ser.dumps(commit)), 3)
        got = m.codec_encode_seconds.value()
        handed = m._codec_encode_wm
        assert t0 < handed <= jsoncodec.encode_ns()
        assert got == pytest.approx((handed - t0) / 1e9)
    finally:
        libmetrics.pop_node_metrics(m)


def test_reader_finds_the_series_by_their_exact_names():
    """The numerator and the denominator are series the registry renders:
    a window of 4 blocks, each encoding a commit, reads the codec's time
    a block."""
    commit = _commit(256)
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        before = counters._prom(m.registry)
        r = _reactor(lambda: ser.dumps(commit))
        _blocks(r, 4)
        delta = counters.delta(before, counters._prom(m.registry))
    finally:
        libmetrics.pop_node_metrics(m)
    metric = _metric()
    for key in metric["numerator"] + metric["denominator"]:
        assert key in delta, key
    assert delta[metric["denominator"][0]] == 4
    got = counter_ratio.read(metric, _Ctx(delta))
    assert got == pytest.approx(
        1000.0 * m.codec_encode_seconds.value() / 4)
    assert got > 0


def test_a_program_without_the_series_reads_zero():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        before = counters._prom(m.registry)
        _blocks(_reactor(lambda: ser.dumps(_commit(8))), 2)
        delta = counters.delta(before, counters._prom(m.registry))
    finally:
        libmetrics.pop_node_metrics(m)
    parent_like = {k: v for k, v in delta.items()
                   if "codec_encode" not in k}
    assert counter_ratio.read(_metric(), _Ctx(parent_like)) == 0.0


def test_rehearse_prints_the_metric():
    root = spec.ROOT
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seed", "2147483677",
         "--seconds", "3", "--trace", "1"],
        capture_output=True, text=True, timeout=900, cwd=root,
        env={k: v for k, v in os.environ.items()
             if not k.startswith("COMETBFT_TPU_")},
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal_correct"] is True, line["checks"]
    assert line["metrics"][NAME]["value"] > 0
