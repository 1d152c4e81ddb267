"""``qa175-relayers-backfill`` at test size on the CPU: a sound run is
correct, both controls come out not correct, and each thing the check
guards (an exhausted request list, a cache that answers, an absorbed fault,
a verifier that stops checking) comes out not correct when planted."""

import time

import pytest

from benchmark import run as bench_run
from benchmark.harness import spec
from cellrun import CPU

CELL = "qa175-relayers-backfill"


def run(seed: int, seconds: float = 2.0, control: str = "", **mix):
    cell = spec.load_cell(CELL, rehearsal=True)
    cell.mix.update(mix)
    return bench_run.execute(cell, seed, seconds, False, CPU,
                             control=control, t_process=time.monotonic())


def bad_checks(result: dict) -> set:
    return {k for k, c in result["checks"].items() if c["value"] > c["limit"]}


def test_sound_run_is_correct():
    res = run(2**31 + 54321)
    assert not bad_checks(res), res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 20
    assert res["metrics"]["sigs_per_s"]["value"] > 0


@pytest.mark.parametrize("control", ["stride8", "trust_all"])
def test_control_is_not_correct(control):
    res = run(91, control=control)
    assert bad_checks(res) == {"verdict_mismatches"}, res["checks"]


def test_exhausted_request_list_is_not_correct():
    res = run(92, knee_replies_per_s=2)
    assert bad_checks(res) == {"request_list_exhausted"}, res["checks"]


def test_cache_that_answers_is_caught(monkeypatch):
    """Two requests over the same pair of heights: the second is answered
    by the result cache, which this traffic must never allow."""
    from benchmark.drivers import light_backfill

    plan = light_backfill.Driver._plan

    def twice(self, heights, n_requests, n_warm):
        requests, warm = plan(self, heights, n_requests, n_warm)
        sound = [r for r in requests if r["kind"] == "sound"]
        requests[requests.index(sound[1])] = dict(sound[0])
        return requests, warm

    monkeypatch.setattr(light_backfill.Driver, "_plan", twice)
    res = run(93)
    assert "cache_answers" in bad_checks(res), res["checks"]


def test_absorbed_dispatch_fault_is_caught(monkeypatch):
    """A fault the dispatch layer absorbs and serves around (a staging or
    prestage fault, a Pallas flavour retired) leaves the answers right and
    the run not correct: the cell must not pass on a degraded path."""
    from cometbft_tpu.ops import verify as ov
    from benchmark.drivers import light_backfill

    ask = light_backfill.Driver._ask
    state = {"calls": 0}

    def faulting(self, req):
        state["calls"] += 1
        if state["calls"] == 40:  # past the warm-up's requests
            ov._note_fault("prestage", RuntimeError("planted"))
        return ask(self, req)

    monkeypatch.setattr(light_backfill.Driver, "_ask", faulting)
    res = run(94)
    assert bad_checks(res) == {"dispatch_faults"}, res["checks"]


def test_verifier_that_answers_yes_is_caught(monkeypatch):
    from cometbft_tpu.crypto import batch as crypto_batch

    def yes(self):
        return True, [True] * len(self)

    monkeypatch.setattr(crypto_batch.Ed25519BatchVerifier, "verify", yes)
    res = run(95)
    assert {"verdict_mismatches", "lanes_counted_minus_needed"} <= \
        bad_checks(res), res["checks"]


def test_trusting_reference_matches_lanes_by_address():
    """The +1/3 check walks the commit's lanes and takes those whose address
    is in the trusted set: a trusted set that lacks some signers counts
    later lanes, and names the commit's lane when one is altered."""
    from benchmark.harness import chain as rawchain
    from benchmark.reference import skipping_ref

    vals = rawchain.make_validators(5, "val", 12)
    commit = rawchain.sign_commits(
        vals, "c", [(7, b"\x01" * 32, 1, b"\x02" * 32)])[7]
    every = skipping_ref.verify_commit_trusting(
        commit, vals.addresses, vals.addresses, vals.pubkeys, 10, 1, 3)
    assert every == (("accept", None), 5)
    # trusted set = the odd positions only: 6 validators, needs > 20 -> 3
    odd_a, odd_p = vals.addresses[1::2], vals.pubkeys[1::2]
    got = skipping_ref.verify_commit_trusting(
        commit, vals.addresses, odd_a, odd_p, 10, 1, 3)
    assert got == (("accept", None), 3)
    bad = rawchain.tamper(commit, [3], 5)
    got = skipping_ref.verify_commit_trusting(
        bad, vals.addresses, odd_a, odd_p, 10, 1, 3)
    assert got == (("reject", 3), 3)
    none = skipping_ref.verify_commit_trusting(
        commit, vals.addresses, [b"x" * 20] * 3, vals.pubkeys[:3], 10, 1, 3)
    assert none == (("power", None), 0)


def test_program_that_donates_staged_buffers_is_refused_before_setup(
        monkeypatch):
    """A lane arena of two slots a shape, the older handed to the next
    staging (what ops/verify had before PR 26): the cell exits 5 before any
    set-up. With no arena the probe finds nothing and the run goes on."""
    import jax.numpy as jnp
    from cometbft_tpu.ops import verify as ov
    from benchmark.drivers import light_backfill

    class TwoSlots:
        def __init__(self):
            self.slots = []

        def stage(self, kind, rows):
            if len(self.slots) == 2:
                self.slots.pop(0).delete()
            self.slots.append(jnp.asarray(rows))
            return self.slots[-1]

    light_backfill.preflight_staging()
    monkeypatch.setattr(ov, "_LANE_ARENA", TwoSlots(), raising=False)
    monkeypatch.setattr(ov, "_lane_arena_enabled", lambda: True,
                        raising=False)
    with pytest.raises(SystemExit) as refused:
        run(96)
    assert refused.value.code == light_backfill.EXIT_CANNOT_SERVE
