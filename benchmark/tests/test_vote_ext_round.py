"""qa175ve-jitter at the rehearsal size: a node of cmd init +
default_new_node follows a scripted chain of 16 validators with vote
extensions on, fed by 4 scripted peers. Sound runs equal the plain
reference; the three controls and faults planted under a whole run come
out not correct; the arrival script has the same counts on every seed."""

import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import run as bench_run
from benchmark.drivers import vote_ext_script, vote_script
from benchmark.harness import chain as rawchain
from benchmark.harness import spec
from cellrun import CPU, correct_on_cpu, run_cell

CELL = "qa175ve-jitter"
PRECOMMIT = vote_ext_script.PRECOMMIT
EXT_METRICS = [
    "device_lane_pct.ext", "preverify_lanes_per_drain.ext",
    "ext_sig_memo_hit_pct.ext", "ext_verify_ms_per_height",
    "ext_sign_bytes_ms_per_height", "preverify_ms_per_height.ext",
    "vote_admit_ms_per_height.ext", "vote_queue_wait_ms_per_vote.ext",
    "reactor_receive_ms_per_vote.ext", "wal_write_ms_per_height.ext",
    "vote_span_coverage_pct.ext", "device_idle_pct.ext",
]


def _bad(res) -> dict:
    return {k: c["value"] for k, c in res["checks"].items()
            if c["value"] > c["limit"]}


def _run(seed: int, seconds: float = 2.0, control: str = "", **mix):
    cell = spec.load_cell(CELL, rehearsal=True)
    cell.mix.update(mix)
    return bench_run.execute(cell, seed, seconds, False, CPU, control=control,
                             t_process=time.monotonic())


@pytest.mark.parametrize("seed", [5, 2**31 + 12345, 987654321])
def test_sound_run_equals_the_reference(seed):
    res = run_cell(CELL, seed, 2.0)
    assert correct_on_cpu(res), res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # every sound vote of a fed height announced once, no copy counted:
    # 16 validators x 2 votes a height, whatever was delivered twice
    assert res["attempted"] % 32 == 0
    assert set(res["metrics"]) == {"sigs_per_s", "setup_s"}
    assert res["metrics"]["sigs_per_s"]["value"] > 0


@pytest.mark.parametrize("control", ["stride8", "trust_all", "votes_only"])
def test_control_is_not_correct(control):
    res = run_cell(CELL, 77, 2.0, control=control)
    assert _bad(res).keys() == {
        "vote_mismatches", "app_shown_extension_mismatches"}, res["checks"]


def test_verifier_that_says_yes_to_every_extension_is_caught(monkeypatch):
    """The drains' batches come back true on every extension lane (the
    lanes whose message is longer than a vote's sign-bytes): the copy with
    the altered extension signature is shown to the application and
    admitted in the sound original's place."""
    from cometbft_tpu.crypto import batch as crypto_batch

    inner = crypto_batch.Ed25519BatchVerifier.verify

    def yes_to_extensions(self):
        _, bits = inner(self)
        bits = [ok or len(m) > 200 for ok, m in zip(bits, self._msgs)]
        return all(bits), bits

    monkeypatch.setattr(
        crypto_batch.Ed25519BatchVerifier, "verify", yes_to_extensions)
    res = run_cell(CELL, 78, 2.0)
    assert {"vote_mismatches", "app_shown_extension_mismatches"} <= \
        _bad(res).keys(), res["checks"]


def test_altered_extension_in_a_stored_extended_commit_is_caught(monkeypatch):
    from cometbft_tpu.store import BlockStore

    inner = BlockStore.load_block_extended_commit

    def altered(self, height):
        ec = inner(self, height)
        if ec is not None:
            k = next(i for i, es in enumerate(ec.extended_signatures)
                     if es.extension)
            es = ec.extended_signatures[k]
            ec.extended_signatures[k] = dataclasses.replace(
                es, extension=rawchain.flip_bit(es.extension, 77))
        return ec

    monkeypatch.setattr(BlockStore, "load_block_extended_commit", altered)
    res = run_cell(CELL, 81, 2.0)
    assert _bad(res).keys() == {"stored_commit_faults"}, res["checks"]


def test_missing_extended_commit_is_caught(monkeypatch):
    from cometbft_tpu.store import BlockStore

    monkeypatch.setattr(BlockStore, "load_block_extended_commit",
                        lambda self, height: None)
    res = run_cell(CELL, 83, 2.0)
    assert _bad(res).keys() == {"stored_commit_faults"}, res["checks"]


def test_app_call_for_a_refused_extension_is_caught(monkeypatch):
    """A node that asks the application first and checks the extension's
    signature after: the copy with the altered extension signature is
    still refused, and the application has seen it."""
    from cometbft_tpu.consensus.state import ConsensusState

    inner = ConsensusState._verify_extension_signature

    def app_first(self, vote, pub_key, memo):
        self.block_exec.verify_vote_extension(vote, self.state)
        inner(self, vote, pub_key, memo)

    monkeypatch.setattr(
        ConsensusState, "_verify_extension_signature", app_first)
    res = run_cell(CELL, 84, 2.0)
    assert _bad(res).keys() == {"app_shown_extension_mismatches"}, \
        res["checks"]


def test_exhausted_script_is_caught():
    res = _run(79, 2.0, list_over_knee=0.01)
    assert "script_exhausted_or_node_lost" in _bad(res), res["checks"]


def test_timeout_acted_on_is_caught(monkeypatch):
    """A node that needs a timeout to get on is not following the chain:
    here every proposal comes 50 ms late and the propose timeout is 5 ms."""
    from cometbft_tpu.config import ConsensusConfig
    from cometbft_tpu.consensus.state import ConsensusState

    inner = ConsensusState.set_proposal_from_peer

    def late(self, proposal, peer_id):
        time.sleep(0.05)
        inner(self, proposal, peer_id)

    monkeypatch.setattr(ConsensusConfig, "propose_timeout",
                        lambda self, round_: 0.005)
    monkeypatch.setattr(ConsensusState, "set_proposal_from_peer", late)
    res = _run(80, 2.0)
    assert "timeouts_acted_on" in _bad(res), res["checks"]


# --- the arrival script ----------------------------------------------------

MIX = {"peers": 50, "duplicate_share": 0.05, "bad_votes_per_wave": 1,
       "bad_extensions_per_wave": 1, "burst_mean": 16, "burst_cap": 128,
       "wave_span_ms": 80.0}


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 3_000_000_011])
@pytest.mark.parametrize("msg_type", vote_ext_script.TYPES)
def test_wave_has_the_same_counts_on_every_seed(seed, msg_type):
    n = 175
    deliveries, bursts, ext_altered = vote_ext_script.wave(
        seed, 9, msg_type, n, MIX)
    kinds = [k for k, _pos, _peer in deliveries]
    assert kinds.count(vote_script.SOUND) == n
    assert kinds.count(vote_script.DUPLICATE) == 9
    n_ext = 1 if msg_type == PRECOMMIT else 0
    assert kinds.count(vote_script.MANGLED) == 1 + n_ext
    assert len(ext_altered) == n_ext
    at = {}
    for i, (kind, pos, peer) in enumerate(deliveries):
        at.setdefault((kind, pos), []).append((i, peer))
    assert ext_altered <= {pos for k, pos in at if k == vote_script.MANGLED}
    for (kind, pos), [(i, peer)] in at.items():
        (j, sound_peer), = at[(vote_script.SOUND, pos)]
        if kind == vote_script.DUPLICATE:
            assert i > j and peer != sound_peer
        elif kind == vote_script.MANGLED:
            assert i < j and peer != sound_peer
    offsets = [off for _s, off in bursts]
    assert offsets[0] == 0.0 and offsets[-1] == pytest.approx(0.080)
    assert deliveries != vote_ext_script.wave(seed + 1, 9, msg_type, n, MIX)[0]


def test_extensions_are_seeded_and_distinct_a_vote():
    a = vote_ext_script.extension(7, 3, 11, 2048)
    assert len(a) == 2048 and a == vote_ext_script.extension(7, 3, 11, 2048)
    assert len({a, vote_ext_script.extension(7, 3, 12, 2048),
                vote_ext_script.extension(7, 4, 11, 2048),
                vote_ext_script.extension(8, 3, 11, 2048)}) == 4


def test_rehearse_prints_every_new_metric():
    root = spec.ROOT
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--rehearse", "--seed", "11", "--seconds", "2",
         "--trace", "1"],
        capture_output=True, text=True, timeout=600, cwd=root,
        env={k: v for k, v in os.environ.items()
             if not k.startswith("COMETBFT_TPU_")},
    )
    assert proc.returncode == 3, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert "correct" not in line
    assert line["rehearsal_correct"] is True, line["checks"]
    for name in EXT_METRICS:
        assert name in line["metrics"], name
    assert line["metrics"]["vote_span_coverage_pct.ext"]["value"] >= 90
    assert line["metrics"]["ext_sig_memo_hit_pct.ext"]["value"] >= 90
