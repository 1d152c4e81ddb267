"""mixed4096-catchup at the rehearsal size: a node of cmd init +
default_new_node with block sync on catches a 16-validator chain (8 ed25519
+ 8 sr25519) up from 4 scripted peers, two of which serve an altered block
each. Sound runs equal the plain reference's walk; the three controls and
faults planted under a whole run come out not correct."""

import time

import pytest

from benchmark import run as bench_run
from benchmark.drivers import blocksync_catchup
from benchmark.harness import spec
from cellrun import CPU, correct_on_cpu, run_cell

CELL = "mixed4096-catchup"
SYNC_METRICS = [
    "block_decode_ms_per_block", "commit_light_ms_per_block",
    "block_validate_ms_per_block", "block_apply_ms_per_block",
    "block_store_ms_per_block", "pool_wait_ms_per_block",
    "device_idle_pct.sync", "pool_cpu_ms_per_block.sync",
]


def _bad(res) -> dict:
    return {k: c["value"] for k, c in res["checks"].items()
            if c["value"] > c["limit"]}


def _run(seed: int, seconds: float = 2.0, control: str = "",
         trace: bool = False, **mix):
    cell = spec.load_cell(CELL, rehearsal=True)
    cell.mix.update(mix)
    return bench_run.execute(cell, seed, seconds, trace, CPU,
                             control=control, t_process=time.monotonic())


@pytest.mark.parametrize("seed", [5, 2**31 + 12345])
def test_sound_run_equals_the_reference(seed):
    res = run_cell(CELL, seed, 2.0)
    assert correct_on_cpu(res), res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"sigs_per_s", "setup_s"}
    # 16 validators: 11 light lanes + 16 LastCommit lanes a block
    sigs = res["metrics"]["sigs_per_s"]["value"] * 2.0
    assert sigs == pytest.approx(27 * res["attempted"])


def test_traced_run_reads_the_sync_metrics():
    """On the CPU every commit check is a host batch: the device's metrics
    read nothing to read (no mixed-tpu lanes), the block-sync phases read
    their spans."""
    res = _run(11, trace=True)
    assert correct_on_cpu(res), res["checks"]
    for name in SYNC_METRICS:
        assert name in res["metrics"], name
    assert res["metrics"]["commit_light_ms_per_block"]["value"] > 0
    assert res["metrics"]["device_lane_pct.sync"]["value"] == 0.0


@pytest.mark.parametrize("control", ["stride8", "trust_all", "ed_only"])
def test_control_is_not_correct(control):
    res = run_cell(CELL, 77, 2.0, control=control)
    assert "walk_mismatches" in _bad(res), res["checks"]


def test_verifier_that_says_yes_is_caught(monkeypatch):
    """The mixed verifier answers every lane sound: the altered blocks are
    applied (their LastCommit stored as a seen commit) where the walk
    refuses them."""
    from cometbft_tpu.crypto import batch as crypto_batch

    def yes(self):
        return True, [True] * len(self)

    monkeypatch.setattr(crypto_batch.MixedBatchVerifier, "verify", yes)
    res = _run(21)
    assert {"walk_mismatches"} <= _bad(res).keys(), res["checks"]


def test_verifier_that_skips_sr25519_is_caught(monkeypatch):
    """The mixed verifier checks its ed25519 lanes and takes every sr25519
    lane as sound: the block with the altered sr25519 lane passes."""
    from cometbft_tpu.crypto import batch as crypto_batch

    inner = crypto_batch.MixedBatchVerifier.verify

    def ed_only(self):
        _, bits = inner(self)
        bits = [ok or t == "sr25519" for ok, t in zip(bits, self._types)]
        return all(bits), bits

    monkeypatch.setattr(crypto_batch.MixedBatchVerifier, "verify", ed_only)
    res = _run(22)
    assert "walk_mismatches" in _bad(res), res["checks"]


def test_missing_stored_commit_is_caught(monkeypatch):
    inner = blocksync_catchup.Driver._stored_commit

    def drop_one(self, h, tip):
        return None if h == tip else inner(self, h, tip)

    monkeypatch.setattr(blocksync_catchup.Driver, "_stored_commit", drop_one)
    res = _run(23)
    assert _bad(res).keys() == {"stored_block_or_commit_faults"}, \
        res["checks"]


def test_exhausted_script_is_caught():
    res = _run(24, knee_sigs_per_s=60)
    assert "script_exhausted_in_window_or_not_settled" in _bad(res), \
        res["checks"]


def test_node_may_reach_the_end_after_the_window(monkeypatch):
    """A traced run's profiler takes minutes to stop, and the peers serve
    on meanwhile: the node may reach the script's end and switch to
    consensus after the window. The checks read the window."""
    from benchmark.harness import tracing

    monkeypatch.setattr(tracing.Tracer, "stop", lambda self: time.sleep(8))
    res = _run(25, knee_sigs_per_s=2000)
    assert correct_on_cpu(res), res["checks"]
