"""vote1000-jitter at the rehearsal size: a node of cmd init +
default_new_node follows a scripted chain of 16 validators fed by 4
scripted peers. Sound runs equal the plain reference; the controls and
faults planted under a whole run come out not correct; the arrival script
has the same counts on every seed; the reference says what a correct node
admits."""

import dataclasses
import json
import os
import subprocess
import sys
import time

import pytest

from benchmark import run as bench_run
from benchmark.drivers import vote_script
from benchmark.harness import chain as rawchain
from benchmark.harness import spec
from benchmark.reference import ed25519_oracle as oracle
from benchmark.reference import vote_round_ref as ref
from cellrun import CPU, correct_on_cpu, run_cell

CELL = "vote1000-jitter"
NEW_METRICS = [
    "device_lane_pct.round", "preverify_lanes_per_drain.round",
    "vote_queue_wait_ms_per_vote", "preverify_ms_per_height",
    "vote_sign_bytes_ms_per_height", "vote_admit_ms_per_height",
    "sig_memo_hit_pct.round", "reactor_receive_ms_per_vote",
    "wal_write_ms_per_height.round", "vote_span_coverage_pct.round",
    "device_idle_pct.round",
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


@pytest.mark.parametrize("control", ["stride8", "trust_all"])
def test_control_is_not_correct(control):
    res = run_cell(CELL, 77, 2.0, control=control)
    assert _bad(res).keys() == {"vote_mismatches"}, res["checks"]


def test_verifier_that_answers_yes_is_caught(monkeypatch):
    """Every batch the receive routine pre-verifies comes back all true:
    the mangled copies are admitted in the sound votes' place."""
    from cometbft_tpu.crypto import batch as crypto_batch

    def yes(self):
        return True, [True] * len(self)

    monkeypatch.setattr(crypto_batch.Ed25519BatchVerifier, "verify", yes)
    res = run_cell(CELL, 78, 2.0)
    assert _bad(res).get("vote_mismatches", 0) > 0, res["checks"]


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


def test_altered_vote_in_a_stored_commit_is_caught(monkeypatch):
    from cometbft_tpu.store import BlockStore

    inner = BlockStore.load_block_commit

    def altered(self, height):
        commit = inner(self, height)
        if commit is not None:
            cs = commit.signatures[3]
            commit.signatures[3] = dataclasses.replace(
                cs, signature=rawchain.flip_bit(cs.signature, 77))
        return commit

    monkeypatch.setattr(BlockStore, "load_block_commit", altered)
    res = run_cell(CELL, 81, 2.0)
    assert _bad(res).keys() == {"stored_commit_faults"}, res["checks"]


def test_dropped_vote_is_caught(monkeypatch):
    """One prevote in three hundred never reaches the inbox."""
    from cometbft_tpu.consensus.state import ConsensusState

    inner = ConsensusState.add_vote_from_peer
    seen = {"n": 0}

    def lossy(self, vote, peer_id):
        seen["n"] += 1
        if vote.msg_type == 1 and seen["n"] % 300 == 0:
            return
        inner(self, vote, peer_id)

    monkeypatch.setattr(ConsensusState, "add_vote_from_peer", lossy)
    res = run_cell(CELL, 82, 2.0)
    assert res["failed"] > 0
    assert {"vote_mismatches", "has_vote_faults"} & _bad(res).keys(), \
        res["checks"]


# --- the arrival script ----------------------------------------------------

MIX = {"peers": 50, "duplicate_share": 0.05, "bad_vote_share": 0.005,
       "burst_mean": 16, "burst_cap": 128, "wave_span_ms": 80.0}


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 3_000_000_011])
def test_wave_has_the_same_counts_on_every_seed(seed):
    n = 1000
    deliveries, bursts = vote_script.wave(seed, 9, ref.PRECOMMIT, n, MIX)
    kinds = [k for k, _pos, _peer in deliveries]
    assert kinds.count(vote_script.SOUND) == n
    assert kinds.count(vote_script.DUPLICATE) == 50
    assert kinds.count(vote_script.MANGLED) == 5
    at = {}
    for i, (kind, pos, peer) in enumerate(deliveries):
        at.setdefault((kind, pos), []).append((i, peer))
    assert sorted(pos for k, pos in at if k == vote_script.SOUND) == \
        list(range(n))
    for (kind, pos), [(i, peer)] in at.items():
        assert 0 <= peer < 50
        (j, sound_peer), = at[(vote_script.SOUND, pos)]
        if kind == vote_script.DUPLICATE:
            assert i > j and peer != sound_peer
        elif kind == vote_script.MANGLED:
            assert i < j and peer != sound_peer
    # bursts cover the deliveries in order; gaps span the wave
    starts = [s for s, _off in bursts]
    assert starts[0] == 0 and starts == sorted(set(starts))
    sizes = [b - a for a, b in zip(starts, starts[1:] + [len(deliveries)])]
    assert max(sizes) <= 128 and 8 < sum(sizes) / len(sizes) < 32
    offsets = [off for _s, off in bursts]
    assert offsets[0] == 0.0 and offsets == sorted(offsets)
    assert offsets[-1] == pytest.approx(0.080)
    # another seed moves the order
    assert deliveries != vote_script.wave(seed + 1, 9, ref.PRECOMMIT, n, MIX)[0]


# --- the reference ---------------------------------------------------------


def _signed(n=4, height=3):
    block = (b"\x01" * 32, 5, b"\x02" * 32)
    keys = [oracle.keypair(rawchain.seed_bytes(1, "k", i)) for i in range(n)]
    pubkeys = [pk for _sk, pk in keys]
    templates = {t: ref.vote_template(t, "c", height, 0, *block)
                 for t in vote_script.TYPES}
    votes = {}
    for t in vote_script.TYPES:
        for i, (sk, _pk) in enumerate(keys):
            ts = 1_700_000_000_000_000_000 + i
            votes[(t, i)] = (t, i, ts, sk.sign(ref.sign_bytes(templates[t], ts)))
    return block, pubkeys, templates, votes


def test_reference_admits_the_sound_original_after_a_mangled_copy():
    _block, pubkeys, templates, votes = _signed()
    t, i, ts, sig = votes[(1, 2)]
    mangled = (t, i, ts, rawchain.flip_bit(sig, 9))
    deliveries = [votes[(1, 0)], mangled, votes[(1, 0)], votes[(1, 2)],
                  votes[(2, 1)]]
    got, verified = ref.admitted(deliveries, templates, pubkeys)
    assert got == {(1, 0, votes[(1, 0)][3]), (1, 2, sig),
                   (2, 1, votes[(2, 1)][3])}
    assert verified == 4  # the second copy of (1, 0) is known, not checked
    trusting, _ = ref.admitted(deliveries, templates, pubkeys,
                               lambda lanes: [True] * len(lanes))
    assert (1, 2, mangled[3]) in trusting and (1, 2, sig) not in trusting


def test_reference_counts_a_stored_commits_faults():
    _block, pubkeys, templates, votes = _signed()
    commit = [(i, votes[(2, i)][2], votes[(2, i)][3]) for i in range(4)]
    tpl = templates[ref.PRECOMMIT]
    assert ref.commit_faults(commit, tpl, pubkeys, 10) == 0
    assert ref.commit_faults(commit[:3], tpl, pubkeys, 10) == 0  # 30 of 40
    assert ref.commit_faults(commit[:2], tpl, pubkeys, 10) == 1  # no quorum
    bad = list(commit)
    bad[1] = (1, bad[1][1], rawchain.flip_bit(bad[1][2], 3))
    assert ref.commit_faults(bad, tpl, pubkeys, 10) == 1
    # a prevote's signature is not a precommit's
    swapped = [(0, votes[(1, 0)][2], votes[(1, 0)][3])] + commit[1:]
    assert ref.commit_faults(swapped, tpl, pubkeys, 10) == 1


def test_reference_sign_bytes_are_the_programs():
    """The generator signs the reference's bytes and the node verifies
    them with its own: both encoders agree on both vote types."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import BlockID, PartSetHeader

    block = (b"\x07" * 32, 6, b"\x08" * 32)
    bid = BlockID(block[0], PartSetHeader(block[1], block[2]))
    for t in vote_script.TYPES:
        tpl = ref.vote_template(t, "bench-vote1000", 12, 0, *block)
        ts = vote_script.vote_timestamps(12, t, 8)[5]
        assert ref.sign_bytes(tpl, ts) == canonical.vote_sign_bytes(
            "bench-vote1000", t, 12, 0, bid, ts)


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
    for name in NEW_METRICS:
        assert name in line["metrics"], name
    assert line["metrics"]["vote_span_coverage_pct.round"]["value"] >= 90
