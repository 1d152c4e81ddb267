"""Cross-caller verify coalescer (crypto/coalesce.py): flush triggers,
shutdown drain, per-ticket failure isolation, behavioral identity of
coalesced vote admission, the warmed-burst no-recompile contract, the
adaptive host/device crossover (crypto/batch.AdaptiveCrossover), and
the MixedBatchVerifier edge cases that ride along this PR.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import coalesce
from cometbft_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs.metrics import NodeMetrics
from cometbft_tpu.types import canonical
from cometbft_tpu.types.block import BlockID, PartSetHeader
from cometbft_tpu.types.priv_validator import MockPV
from cometbft_tpu.types.validator_set import Validator, ValidatorSet
from cometbft_tpu.types.vote import Vote, VoteError
from cometbft_tpu.types.vote_set import ConflictingVoteError, VoteSet

pytestmark = pytest.mark.quick

CHAIN_ID = "coalesce-test-chain"


def _lanes(n: int, seed: int = 1):
    """(pub_objs, raw_pubkeys, msgs, sigs), all valid."""
    pvs = [
        Ed25519PrivKey.from_seed((seed * 100 + i).to_bytes(32, "big"))
        for i in range(n)
    ]
    msgs = [b"lane-%d-%d" % (seed, i) for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    pubs = [pv.pub_key() for pv in pvs]
    return pubs, [p.data for p in pubs], msgs, sigs


@pytest.fixture
def metrics():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    yield m
    libmetrics.pop_node_metrics(m)


def _coalescer(**kw):
    kw.setdefault("device", False)
    co = coalesce.VerifyCoalescer(**kw)
    co.start()
    return co


class TestFlushTriggers:
    def test_size_flush_does_not_wait_for_deadline(self, metrics):
        # a 60 s window would time the test out if size didn't flush
        co = _coalescer(window_us=60_000_000, max_lanes=4)
        try:
            _, pks, msgs, sigs = _lanes(4)
            bits = co.submit(pks, msgs, sigs).result(timeout=10)
            assert bits == [True] * 4
            assert (
                metrics.coalesce_flushes.labels("size").value() >= 1
            )
        finally:
            co.stop()

    def test_deadline_flush_serves_a_lone_lane(self, metrics):
        co = _coalescer(window_us=20_000, max_lanes=1 << 20)
        try:
            _, pks, msgs, sigs = _lanes(1, seed=2)
            bits = co.submit(pks, msgs, sigs).result(timeout=10)
            assert bits == [True]
            assert (
                metrics.coalesce_flushes.labels("deadline").value() >= 1
            )
            assert metrics.coalesce_window_lanes._n >= 1
        finally:
            co.stop()

    def test_invalid_lane_is_false_not_an_error(self):
        co = _coalescer(window_us=1_000, max_lanes=8)
        try:
            _, pks, msgs, sigs = _lanes(3, seed=3)
            sigs[1] = sigs[0]  # wrong message for that key
            bits = co.submit(pks, msgs, sigs).result(timeout=10)
            assert bits == [True, False, True]
        finally:
            co.stop()

    def test_device_window_matches_host_verdicts(self):
        # XLA-CPU exercises the real device staging path; one corrupted
        # lane must flip only its own bit (bucket padding untouched).
        # min_device_lanes pinned low: the default defers to the live
        # crossover, which correctly keeps 8-lane windows on host.
        co = _coalescer(
            window_us=60_000_000, max_lanes=8, device=True,
            min_device_lanes=1,
        )
        try:
            _, pks, msgs, sigs = _lanes(8, seed=4)
            sigs[5] = bytes(64)
            bits = co.submit(pks, msgs, sigs).result(timeout=120)
            assert bits == [True] * 5 + [False] + [True] * 2
            assert co.device_windows == 1
        finally:
            co.stop()


class TestFailureIsolation:
    def test_exception_in_one_submit_fails_only_that_ticket(self, metrics):
        co = _coalescer(window_us=20_000, max_lanes=8)
        try:
            _, pks, msgs, sigs = _lanes(3, seed=5)
            bad = co.submit([pks[0]], [None], [sigs[0]])  # msg coerces -> TypeError
            good = co.submit(pks[1:3], msgs[1:3], sigs[1:3])
            assert good.result(timeout=10) == [True, True]
            with pytest.raises(TypeError):
                bad.result(timeout=10)
            assert (
                metrics.coalesce_flushes.labels("deadline").value() >= 1
            )
        finally:
            co.stop()


class TestShutdownDrain:
    def test_drain_delivers_every_pending_future(self):
        # a window/size pair that can never flush on its own: only the
        # drain can resolve these tickets
        co = _coalescer(window_us=60_000_000, max_lanes=1 << 20)
        _, pks, msgs, sigs = _lanes(6, seed=6)
        sigs[2] = bytes(64)
        tickets = [
            co.submit([pks[i]], [msgs[i]], [sigs[i]]) for i in range(6)
        ]
        assert not any(t.done() for t in tickets)
        co.stop()  # blocks until the drain resolved everything
        assert all(t.done() for t in tickets)
        bits = [t.result(timeout=0.1)[0] for t in tickets]
        assert bits == [True, True, False, True, True, True]

    def test_submit_after_stop_raises_and_helpers_fall_back(self):
        co = _coalescer(window_us=1_000, max_lanes=8)
        coalesce.push_active(co)
        try:
            pubs, pks, msgs, sigs = _lanes(1, seed=7)
            co.stop()
            with pytest.raises(coalesce.CoalescerStoppedError):
                co.submit(pks, msgs, sigs)
            # the routed helper must still answer, on the host path
            assert coalesce.verify_signature(pubs[0], msgs[0], sigs[0])
            assert not coalesce.verify_signature(pubs[0], b"x", sigs[0])
        finally:
            coalesce.pop_active(co)

    def test_concurrent_submitters_all_resolve_on_stop(self):
        co = _coalescer(window_us=60_000_000, max_lanes=1 << 20)
        pubs, pks, msgs, sigs = _lanes(8, seed=8)
        results: dict[int, list] = {}

        def submit_and_wait(i):
            t = co.submit([pks[i]], [msgs[i]], [sigs[i]])
            results[i] = t.result(timeout=30)

        threads = [
            threading.Thread(target=submit_and_wait, args=(i,), daemon=True)
            for i in range(8)
        ]
        for t in threads:
            t.start()
        # wait until every submit landed before draining
        deadline = threading.Event()
        for _ in range(200):
            if co._pending_lanes == 8:
                break
            deadline.wait(0.01)
        co.stop()
        for t in threads:
            t.join(timeout=10)
        assert sorted(results) == list(range(8))
        assert all(v == [True] for v in results.values())


class TestInflightRescue:
    """A window popped from _pending but not yet materialized lives in
    neither the queue nor any caller's hands — the rescue paths must
    resolve its tickets when the executor faults or wedges."""

    def test_rescue_resolves_undone_tickets_from_wire(self):
        co = coalesce.VerifyCoalescer(device=False)  # never started
        _, pks, msgs, sigs = _lanes(3, seed=21)
        sigs[1] = bytes(64)
        t1, t2 = coalesce._Ticket(2), coalesce._Ticket(1)
        fl = coalesce._Inflight(
            None, None, [(t1, 0, 2), (t2, 2, 1)], 3, "size", 0.0,
            (pks, msgs, sigs),
        )
        t2.resolve([True])  # concurrently-resolved ticket is skipped
        co._rescue_inflight(fl)
        assert t1.result(timeout=0.1) == [True, False]
        assert t2.result(timeout=0.1) == [True]

    def test_executor_fault_after_dispatch_resolves_tickets(
        self, monkeypatch
    ):
        # _launch hands back an in-flight window; _finish then blows up
        # without resolving anything — the loop's rescue must still
        # answer the submitters (on host, same verdicts)
        def fake_launch(self, groups, lanes, reason):
            pubkeys, msgs, sigs, staged = self._stage(groups)
            return coalesce._Inflight(
                lambda: None, None, staged, lanes, reason, 0.0,
                (pubkeys, msgs, sigs),
            )

        def boom(self, fl):
            raise RuntimeError("post-dispatch fault")

        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_launch", fake_launch
        )
        monkeypatch.setattr(coalesce.VerifyCoalescer, "_finish", boom)
        co = _coalescer(window_us=1_000, max_lanes=4)
        try:
            _, pks, msgs, sigs = _lanes(2, seed=22)
            sigs[1] = bytes(64)
            bits = co.submit(pks, msgs, sigs).result(timeout=10)
            assert bits == [True, False]
        finally:
            co.stop()

    def test_stop_rescues_window_wedged_in_materialization(
        self, monkeypatch
    ):
        # the executor blocks inside the window's materializer (a device
        # stall); on_stop's join times out and the safety net resolves
        # the in-flight tickets instead of leaving submitters hanging
        release = threading.Event()

        def fake_launch(self, groups, lanes, reason):
            pubkeys, msgs, sigs, staged = self._stage(groups)

            def wedge():
                release.wait()
                return np.ones(lanes, bool)

            return coalesce._Inflight(
                wedge, np.ones(lanes, bool), staged, lanes, reason, 0.0,
                (pubkeys, msgs, sigs),
            )

        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_launch", fake_launch
        )
        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_JOIN_TIMEOUT_S", 0.2
        )
        co = _coalescer(window_us=1_000, max_lanes=2)
        try:
            _, pks, msgs, sigs = _lanes(2, seed=23)
            ticket = co.submit(pks, msgs, sigs)
            # wait for the executor to pop + dispatch the window
            for _ in range(200):
                if co._inflights:
                    break
                threading.Event().wait(0.01)
            assert co._inflights
            co.stop()  # join times out at 0.2 s, rescue kicks in
            assert ticket.done()
            assert ticket.result(timeout=0.1) == [True, True]
        finally:
            release.set()

    def test_stop_rescues_window_wedged_in_launch(self, monkeypatch):
        # the executor wedges INSIDE _launch — the window is out of
        # _pending but in neither _inflights slot; only the staging
        # mirror makes its tickets reachable by the shutdown net
        release = threading.Event()

        def wedged_launch(self, groups, lanes, reason):
            release.wait()
            return None

        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_launch", wedged_launch
        )
        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_JOIN_TIMEOUT_S", 0.2
        )
        co = _coalescer(window_us=1_000, max_lanes=2)
        try:
            _, pks, msgs, sigs = _lanes(2, seed=25)
            sigs[1] = bytes(64)
            ticket = co.submit(pks, msgs, sigs)
            for _ in range(200):
                if co._staging is not None:
                    break
                threading.Event().wait(0.01)
            assert co._staging is not None
            co.stop()  # join times out, the staging rescue resolves
            assert ticket.done()
            assert ticket.result(timeout=0.1) == [True, False]
        finally:
            release.set()

    def test_stop_rescues_both_double_buffer_slots(self, monkeypatch):
        # window N wedged in materialization WHILE window N+1 is
        # already dispatched: both live outside _pending, both must be
        # rescued by the shutdown safety net
        release = threading.Event()
        both_submitted = threading.Event()

        def fake_launch(self, groups, lanes, reason):
            both_submitted.wait(5)
            pubkeys, msgs, sigs, staged = self._stage(groups)

            def wedge():
                release.wait()
                return np.ones(lanes, bool)

            return coalesce._Inflight(
                wedge, np.ones(lanes, bool), staged, lanes, reason, 0.0,
                (pubkeys, msgs, sigs),
            )

        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_launch", fake_launch
        )
        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_JOIN_TIMEOUT_S", 0.2
        )
        co = _coalescer(window_us=1_000, max_lanes=2)
        try:
            _, pks, msgs, sigs = _lanes(4, seed=24)
            t1 = co.submit(pks[:2], msgs[:2], sigs[:2])
            t2 = co.submit(pks[2:], msgs[2:], sigs[2:])
            both_submitted.set()
            for _ in range(500):
                if len(co._inflights) == 2:
                    break
                threading.Event().wait(0.01)
            assert len(co._inflights) == 2
            co.stop()
            assert t1.done() and t2.done()
            assert t1.result(timeout=0.1) == [True, True]
            assert t2.result(timeout=0.1) == [True, True]
        finally:
            release.set()


class TestWedgeContainment:
    """A wedged or dead executor must degrade the coalescer to the host
    path, never freeze callers: one result-bound stall trips the
    cooldown breaker (queued groups go to a host rescue, one caller
    re-probes after the cooldown), and an executor death no handler
    could catch still unroutes and drains."""

    def test_result_timeout_trips_breaker(self, monkeypatch):
        release = threading.Event()

        def wedged_launch(self, groups, lanes, reason):
            release.wait()
            return None

        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_launch", wedged_launch
        )
        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_JOIN_TIMEOUT_S", 0.2
        )
        monkeypatch.setattr(coalesce, "_RESULT_TIMEOUT_S", 0.2)
        co = _coalescer(window_us=1_000, max_lanes=2)
        coalesce.push_active(co)
        try:
            _, pks, msgs, sigs = _lanes(1, seed=26)
            # first caller pays the bound once, then trips the breaker
            assert co.try_verify(pks, msgs, sigs) is None
            assert co._accepting and not co.routable()  # tripped, alive
            # unrouted for the cooldown: later callers fall back
            # instantly
            assert coalesce.active() is None
            assert coalesce.verify_signature(
                Ed25519PubKey(pks[0]), msgs[0], sigs[0]
            )
            # a group queued behind the wedged executor is handed to
            # the next trip's host rescue, not leaked for the cooldown
            t2 = co.submit(pks, msgs, sigs)
            co._trip()
            assert t2.result(2.0) == [True]
        finally:
            coalesce.pop_active(co)
            release.set()
            co.stop()

    def test_probe_single_flight_after_cooldown(self):
        co = _coalescer(window_us=1_000, max_lanes=4)
        coalesce.push_active(co)
        try:
            co._trip()
            assert coalesce.active() is None  # tripped: unrouted
            co._tripped_until = time.monotonic() - 0.01  # cooldown over
            # active() is a PURE query — is-routed checks must not
            # consume the single-flight probe (a commit walk calls it
            # twice before any verify runs)
            assert coalesce.active() is co
            assert coalesce.active() is co
            # only a routed verify claims the probe; one winner, and
            # concurrent claimers stay on host until its verdict
            assert co._claim_probe()
            assert not co._claim_probe()
            assert coalesce.active() is None  # deadline pushed forward
            # the probe's successful verify re-arms routing for all
            co._tripped_until = time.monotonic() - 0.01
            pubs, pks, msgs, sigs = _lanes(1, seed=29)
            assert co.try_verify(pks, msgs, sigs) == [True]
            assert co._tripped_until == 0.0
            assert co.routable() and coalesce.active() is co
        finally:
            coalesce.pop_active(co)
            co.stop()

    def test_breaker_rearms_after_cooldown(self, monkeypatch):
        monkeypatch.setattr(coalesce, "_TRIP_COOLDOWN_S", 0.15)
        co = _coalescer(window_us=1_000, max_lanes=4)
        coalesce.push_active(co)
        try:
            pubs, pks, msgs, sigs = _lanes(1, seed=28)
            co._trip()
            assert not co.routable()
            assert coalesce.active() is None
            # tripped routing still answers correctly via host fallback
            assert coalesce.verify_signature(pubs[0], msgs[0], sigs[0])
            # a direct submit is still served: the breaker gates
            # routing, and this executor is alive
            t = co.submit(pks, msgs, sigs)
            assert t.result(2.0) == [True]
            time.sleep(0.2)
            # cooldown over: routing resumes through the live executor
            assert co.routable() and coalesce.active() is co
            assert co.try_verify(pks, msgs, sigs) == [True]
            assert co.windows >= 1
        finally:
            coalesce.pop_active(co)
            co.stop()

    def test_executor_death_unroutes_and_drains(self, monkeypatch):
        submitted = threading.Event()

        def dying_collect(self, block):
            submitted.wait(5)
            # BaseException: escapes the loop's `except Exception`, so
            # only the finally stands between the tickets and a hang
            raise SystemExit("executor killed")

        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_collect", dying_collect
        )
        co = _coalescer()
        try:
            _, pks, msgs, sigs = _lanes(2, seed=27)
            sigs[0] = bytes(64)
            ticket = co.submit(pks, msgs, sigs)
            submitted.set()
            co._thread.join(timeout=5)
            assert not co._thread.is_alive()
            assert not co._accepting
            assert ticket.done()
            assert ticket.result(timeout=0.1) == [False, True]
        finally:
            submitted.set()
            co.stop()


def _make_valset(n):
    pvs = [
        MockPV(Ed25519PrivKey.from_seed((900 + i).to_bytes(32, "big")))
        for i in range(n)
    ]
    vals = ValidatorSet(
        [Validator(pv.get_pub_key(), voting_power=10) for pv in pvs]
    )
    by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
    ordered = [by_addr[bytes(v.address)] for v in vals.validators]
    return vals, ordered


def _block_id(tag: int = 1) -> BlockID:
    return BlockID(
        hash=bytes([tag]) * 32,
        part_set_header=PartSetHeader(total=1, hash=bytes(32)),
    )


def _vote_corpus(vals, pvs):
    """A mixed valid/invalid admission corpus: valid votes, corrupted
    signatures, wrong-address relays, equivocations, duplicates."""
    bid = _block_id(1)
    votes = []
    base_ns = 1_700_000_000_000_000_000
    for idx, (val, pv) in enumerate(zip(vals.validators, pvs)):
        v = Vote(
            msg_type=canonical.PREVOTE_TYPE,
            height=5,
            round=0,
            block_id=bid,
            timestamp_ns=base_ns + idx,
            validator_address=val.address,
            validator_index=idx,
        )
        pv.sign_vote(CHAIN_ID, v, sign_extension=False)
        votes.append(v)
    import dataclasses

    # invalid votes FIRST, while their slots are still empty — once a
    # valid vote occupies a slot, a corrupted re-send trips the
    # same-block-different-signature VoteSetError before any signature
    # check runs, and this corpus wants the signature path exercised
    corpus: list[Vote] = []
    # corrupted signature for validator 0
    corpus.append(dataclasses.replace(votes[0], signature=bytes(64)))
    # address-spoofed relay: validator 1's validly signed bytes claimed
    # under validator 2's slot (sign bytes don't bind the address — the
    # signature check against validator 2's key must reject it)
    corpus.append(
        dataclasses.replace(
            votes[1],
            validator_index=2,
            validator_address=vals.validators[2].address,
        )
    )
    corpus.extend(votes)
    # equivocation: validator 3 signs a different block
    other = Vote(
        msg_type=canonical.PREVOTE_TYPE,
        height=5,
        round=0,
        block_id=_block_id(2),
        timestamp_ns=base_ns + 3,
        validator_address=vals.validators[3].address,
        validator_index=3,
    )
    pvs[3].sign_vote(CHAIN_ID, other, sign_extension=False)
    corpus.append(other)
    # exact duplicate
    corpus.append(votes[4])
    return corpus


def _admit_all(corpus, vals):
    """(added, error-type-name) per vote through single add_vote."""
    vs = VoteSet(CHAIN_ID, 5, 0, canonical.PREVOTE_TYPE, vals)
    out = []
    for vote in corpus:
        try:
            out.append((vs.add_vote(vote), None))
        except (VoteError, ConflictingVoteError, Exception) as e:
            out.append((False, type(e).__name__))
    return out


class TestVoteAdmissionIdentity:
    """Acceptance: per-vote admission through the coalescer is
    behaviorally identical to host verification — same accept/reject
    decision and the same error class for every vote of a mixed
    valid/invalid corpus."""

    def test_add_vote_same_decisions_with_and_without_coalescer(self):
        vals, pvs = _make_valset(8)
        corpus = _vote_corpus(vals, pvs)
        baseline = _admit_all(corpus, vals)
        co = _coalescer(window_us=2_000, max_lanes=64)
        coalesce.push_active(co)
        try:
            routed = _admit_all(corpus, vals)
        finally:
            coalesce.pop_active(co)
            co.stop()
        assert routed == baseline
        # the corpus actually exercised every class
        kinds = {k for _, k in baseline}
        assert "VoteError" in kinds and "ConflictingVoteError" in kinds
        assert (True, None) in baseline and (False, None) in baseline

    def test_add_votes_batch_same_decisions(self):
        vals, pvs = _make_valset(6)
        corpus = _vote_corpus(vals, pvs)

        def run():
            vs = VoteSet(CHAIN_ID, 5, 0, canonical.PREVOTE_TYPE, vals)
            added, errs = vs.add_votes_batch(corpus)
            return added, [type(e).__name__ if e else None for e in errs]

        baseline = run()
        co = _coalescer(window_us=2_000, max_lanes=64)
        coalesce.push_active(co)
        try:
            routed = run()
        finally:
            coalesce.pop_active(co)
            co.stop()
        assert routed == baseline

    def test_commit_verification_through_coalescer(self):
        from cometbft_tpu.types import validation

        vals, pvs = _make_valset(4)
        bid = _block_id(1)
        from tests.helpers import sign_commit

        commit = sign_commit(CHAIN_ID, vals, pvs, 5, 0, bid)
        co = _coalescer(window_us=2_000, max_lanes=64)
        coalesce.push_active(co)
        try:
            validation.verify_commit(CHAIN_ID, vals, bid, 5, commit)
            # corrupt one signature: same error as the unrouted path
            import dataclasses

            bad = dataclasses.replace(
                commit,
                signatures=[
                    dataclasses.replace(commit.signatures[0],
                                        signature=bytes(64))
                ]
                + list(commit.signatures[1:]),
            )
            with pytest.raises(validation.VerificationError):
                validation.verify_commit(CHAIN_ID, vals, bid, 5, bad)
        finally:
            coalesce.pop_active(co)
            co.stop()


class TestFirstInvalidIndexIdentity:
    def test_deferred_invalid_still_named_before_inline_failure(
        self, monkeypatch
    ):
        """verifyCommitSingle names the FIRST invalid signature in walk
        order. With a coalescer routed, eligible lanes defer while
        ineligible keys verify inline — an inline failure at a later
        index must not usurp an earlier deferred invalid."""
        import dataclasses

        from cometbft_tpu.types import validation
        from tests.helpers import sign_commit

        vals, pvs = _make_valset(5)
        bid = _block_id(1)
        commit = sign_commit(CHAIN_ID, vals, pvs, 5, 0, bid)
        sigs = list(commit.signatures)
        for i in (1, 3):  # 1 stays eligible (defers); 3 goes inline
            sigs[i] = dataclasses.replace(sigs[i], signature=bytes(64))
        bad = dataclasses.replace(commit, signatures=sigs)
        ineligible = bytes(vals.validators[3].pub_key.data)
        real_eligible = coalesce.eligible
        monkeypatch.setattr(
            coalesce,
            "eligible",
            lambda pk: bytes(pk.data) != ineligible and real_eligible(pk),
        )

        def run() -> str:
            needed = vals.total_voting_power() * 2 // 3
            with pytest.raises(validation.VerificationError) as ei:
                validation._verify_single(
                    CHAIN_ID, vals, bad, needed,
                    count_all=True, by_index=True,
                )
            return str(ei.value)

        baseline = run()
        assert "(#1)" in baseline
        co = _coalescer(window_us=2_000, max_lanes=64)
        coalesce.push_active(co)
        try:
            routed = run()
        finally:
            coalesce.pop_active(co)
            co.stop()
        assert routed == baseline

    def test_deferred_invalid_still_named_before_double_vote(self):
        """A later double-vote raise must not usurp an earlier deferred
        invalid signature either: unrouted, the walk raises wrong
        signature at the earlier index and never reaches the duplicate."""
        import dataclasses

        from cometbft_tpu.types import validation
        from tests.helpers import sign_commit

        vals, pvs = _make_valset(5)
        bid = _block_id(1)
        commit = sign_commit(CHAIN_ID, vals, pvs, 5, 0, bid)
        sigs = list(commit.signatures)
        sigs[1] = dataclasses.replace(sigs[1], signature=bytes(64))
        sigs[4] = sigs[2]  # validator #2 votes twice (idx 2 and 4)
        bad = dataclasses.replace(commit, signatures=sigs)

        def run() -> str:
            needed = vals.total_voting_power() * 2 // 3
            with pytest.raises(validation.VerificationError) as ei:
                validation._verify_single(
                    CHAIN_ID, vals, bad, needed,
                    count_all=True, by_index=False,
                )
            return str(ei.value)

        baseline = run()
        assert "(#1)" in baseline
        co = _coalescer(window_us=2_000, max_lanes=64)
        coalesce.push_active(co)
        try:
            routed = run()
        finally:
            coalesce.pop_active(co)
            co.stop()
        assert routed == baseline


class TestCoalescedConsensusNet:
    def test_four_validator_net_commits_through_coalescer(self):
        """A real in-process consensus burst with the coalescer routed:
        proposal checks and vote admission flow through coalesced
        windows (host-window mode for CPU speed) and the net still
        commits — the end-to-end form of the behavioral-identity
        contract."""
        from tests import helpers

        genesis, pvs = helpers.make_genesis(4)
        co = _coalescer(window_us=500, max_lanes=64)
        coalesce.push_active(co)
        nodes = [helpers.make_consensus_node(genesis, pv) for pv in pvs]
        helpers.wire_perfect_gossip(nodes)
        try:
            for cs, _ in nodes:
                cs.start()
            assert helpers.wait_for_height(nodes[0][1], 2, timeout=60)
        finally:
            for cs, parts in nodes:
                helpers.stop_node(cs, parts)
            coalesce.pop_active(co)
            co.stop()
        assert co.windows > 0, "burst never flushed a coalesced window"


class TestNoRecompileCoalescedBurst:
    def test_warmed_coalesced_burst_compiles_nothing(self):
        """Acceptance: zero new XLA compiles in a warmed coalesced
        burst — windows pad to the same fixed shape buckets as every
        other launch, so steady-state micro-batches never retrigger
        XLA compilation."""
        from cometbft_tpu.libs import devstats

        co = _coalescer(
            window_us=60_000_000, max_lanes=8, device=True,
            min_device_lanes=1,
        )
        devstats.enable()
        try:
            _, pks, msgs, sigs = _lanes(8, seed=9)
            # warm: one full window (compile + arena build land here)
            assert co.submit(pks, msgs, sigs).result(timeout=300) == (
                [True] * 8
            )
            compiles0 = devstats.compile_count()
            from cometbft_tpu.ops import verify as ov

            builds0 = ov._PUBKEY_CACHE.builds
            for _ in range(4):
                bits = co.submit(pks, msgs, sigs).result(timeout=120)
                assert bits == [True] * 8
            assert devstats.compile_count() == compiles0, (
                "coalesced burst recompiled after warm-up"
            )
            assert ov._PUBKEY_CACHE.builds == builds0
            assert co.device_windows >= 5
        finally:
            devstats.disable()
            co.stop()


class TestAdaptiveCrossover:
    def test_uncalibrated_returns_none(self):
        xo = cbatch.AdaptiveCrossover()
        assert xo.threshold() is None
        xo.observe_host(100, 0.01)
        assert xo.threshold() is None  # device side still empty

    def test_crossover_solves_floor_over_rate(self):
        xo = cbatch.AdaptiveCrossover()
        # host 100 us/lane (no floor); device 50 ms floor + 2 us/lane
        for _ in range(xo.MIN_SAMPLES + 1):
            xo.observe_host(100, 100 * 100e-6)
            xo.observe_host(400, 400 * 100e-6)
            xo.observe_device(128, 0.05 + 128 * 2e-6)
            xo.observe_device(1024, 0.05 + 1024 * 2e-6)
        t = xo.threshold()
        expect = 0.05 / (100e-6 - 2e-6)
        assert t is not None and abs(t - expect) / expect < 0.05, (t, expect)

    def test_host_per_call_overhead_lands_in_floor_not_rate(self):
        # the dominant host feed is tiny coalescer windows whose fixed
        # per-call cost must calibrate as a host FLOOR — folding it into
        # the per-lane rate would drag the crossover far below the host
        # MSM's true win region. host 1 ms/call + 100 us/lane, device
        # 50 ms floor + 2 us/lane: true crossover (50-1)/0.098 = 500,
        # while a pure-rate host model fed 1-8-lane windows would
        # answer well below it (overhead-inflated per-lane rates).
        xo = cbatch.AdaptiveCrossover()
        for _ in range(xo.MIN_SAMPLES + 1):
            for n in (1, 2, 4, 8):
                xo.observe_host(n, 1e-3 + n * 100e-6)
            xo.observe_device(128, 0.05 + 128 * 2e-6)
            xo.observe_device(1024, 0.05 + 1024 * 2e-6)
        t = xo.threshold()
        expect = (0.05 - 1e-3) / (100e-6 - 2e-6)
        assert t is not None and abs(t - expect) / expect < 0.05, (t, expect)

    def test_host_faster_at_every_size_routes_to_host(self):
        # device per-lane cost above the host rate even with zero
        # floor: host wins at EVERY batch size, so the crossover must
        # answer the clamp ceiling (keep batches on host), not the floor
        xo = cbatch.AdaptiveCrossover()
        for _ in range(xo.MIN_SAMPLES + 1):
            xo.observe_host(100, 100 * 100e-6)  # 100 us/lane
            xo.observe_host(400, 400 * 100e-6)
            xo.observe_device(128, 128 * 200e-6)  # 200 us/lane, no floor
            xo.observe_device(1024, 1024 * 200e-6)
        assert xo.threshold() == xo.HI

    def test_clamps_and_degenerate_fit(self):
        xo = cbatch.AdaptiveCrossover()
        for _ in range(xo.MIN_SAMPLES + 1):
            xo.observe_host(50, 50 * 1e-3)  # absurdly slow host
            xo.observe_host(200, 200 * 1e-3)
            xo.observe_device(256, 0.001)  # single-size device samples
        assert xo.threshold() == xo.LO  # clamped at the floor
        xo2 = cbatch.AdaptiveCrossover()
        for _ in range(xo2.MIN_SAMPLES + 1):
            xo2.observe_host(50, 50 * 1e-9)  # host faster than light
            xo2.observe_host(200, 200 * 1e-9)
            xo2.observe_device(256, 10.0)
        assert xo2.threshold() == xo2.HI

    def test_host_batch_threshold_respects_seed_and_pin(self, monkeypatch):
        # the (monkeypatchable) module seed answers on the CPU backend
        monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", 123)
        assert cbatch.host_batch_threshold() == 123
        # an attached accelerator has a static seed of its own ...
        monkeypatch.undo()
        from cometbft_tpu.libs import accel

        monkeypatch.setattr(accel, "accelerator_backend_live", lambda: True)
        assert (
            cbatch.host_batch_threshold()
            == cbatch._ACCEL_HOST_BATCH_THRESHOLD
        )
        # ... which an operator env pin, or a patched seed, overrides
        monkeypatch.setattr(cbatch, "_ENV_PINNED", True)
        assert cbatch.host_batch_threshold() == cbatch.HOST_BATCH_THRESHOLD
        monkeypatch.setattr(cbatch, "_ENV_PINNED", False)
        monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", 123)
        assert cbatch.host_batch_threshold() == 123

    def test_post_optimization_device_profile_converges_below_256(
        self, monkeypatch
    ):
        # THE device-floor acceptance stand-in for host-only
        # containers: feed the live fit synthetic (lanes, seconds)
        # samples shaped like the post-optimization device profile —
        # per-window fixed cost down to ~2 ms (persistent lane arenas,
        # overlapped d2h, narrowed dtypes, small-grid jits) against the
        # measured ~28 us/lane host RLC rate — and the calibrated
        # crossover must land under 256 lanes, where the coalescer's
        # real steady-state windows (100-150 validator commits) live.
        monkeypatch.setenv("COMETBFT_TPU_ADAPTIVE_THRESHOLD", "1")
        monkeypatch.setattr(cbatch, "_ENV_PINNED", False)
        xo = cbatch.AdaptiveCrossover()
        for _ in range(xo.MIN_SAMPLES + 1):
            for n in (8, 16, 32, 64, 128, 256):
                xo.observe_host(n, 5e-6 + n * 28e-6)
            for n in (64, 128, 256, 512, 1024, 2048):
                xo.observe_device(n, 2e-3 + n * 1e-6)
        t = xo.threshold()
        assert t is not None and t < 256, t
        fit = xo.fit_summary()
        assert fit["crossover_lanes"] == t
        assert fit["device_floor_s"] == pytest.approx(2e-3, rel=0.1)
        assert fit["host_rate_s_per_lane"] == pytest.approx(
            28e-6, rel=0.1
        )

    def test_reset_refits_from_scratch(self):
        # a stepped device profile (staging arenas toggled, kernel
        # swap) must be able to drop stale samples instead of decaying
        # through hundreds of windows
        xo = cbatch.AdaptiveCrossover()
        for _ in range(xo.MIN_SAMPLES + 1):
            xo.observe_host(200, 200 * 100e-6)
            xo.observe_device(128, 0.05 + 128 * 2e-6)
            xo.observe_device(1024, 0.05 + 1024 * 2e-6)
        assert xo.threshold() is not None
        xo.reset()
        assert xo.threshold() is None
        assert xo.fit_summary()["host_samples"] == 0


class TestRouteHoldsStill:
    """Two questions, two answers: a batch smaller than a window shares
    one whenever a coalescer is routed, whatever the device/host cut
    reads; and nothing a process measures moves that cut."""

    @pytest.mark.parametrize("cut", [2, 64, 96, 117, 118, 768, 16384])
    def test_117_lane_batch_shares_a_window_at_every_cut(
        self, metrics, monkeypatch, cut
    ):
        monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", cut)
        pubs, _pks, msgs, sigs = _lanes(117, seed=21)
        co = _coalescer(window_us=1_000)
        coalesce.push_active(co)
        try:
            bv = cbatch.Ed25519BatchVerifier()
            for p, m, s in zip(pubs, msgs, sigs):
                bv.add(p, m, s)
            ok, bits = bv.verify()
        finally:
            coalesce.pop_active(co)
            co.stop()
        assert ok and bits == [True] * 117
        assert co.tickets == 1 and co.windows == 1
        sigs_by = metrics.verify_batch_sigs.labels
        assert sigs_by("ed25519-coalesce").value() == 117
        assert sigs_by("ed25519-tpu").value() == 0
        assert sigs_by("ed25519-host").value() == 0
        assert metrics.coalesce_lanes.labels("host").value() == 117
        assert metrics.coalesce_queue_wait_seconds._n == 1

    def test_a_window_sized_batch_does_not_queue(self, metrics, monkeypatch):
        monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", 1 << 20)
        pubs, _pks, msgs, sigs = _lanes(8, seed=22)
        co = _coalescer(window_us=1_000, max_lanes=8)
        coalesce.push_active(co)
        try:
            bv = cbatch.Ed25519BatchVerifier()
            for p, m, s in zip(pubs, msgs, sigs):
                bv.add(p, m, s)
            assert bv.verify() == (True, [True] * 8)
        finally:
            coalesce.pop_active(co)
            co.stop()
        assert co.tickets == 0
        assert metrics.verify_batch_sigs.labels("ed25519-host").value() == 8

    def test_cut_does_not_move_on_outlying_samples(self, monkeypatch):
        from cometbft_tpu.crypto import host_batch
        from cometbft_tpu.libs import accel

        monkeypatch.setattr(accel, "accelerator_backend_live", lambda: True)
        before = cbatch.host_batch_threshold()
        real = host_batch.verify_many

        def slow(pks, msgs, sigs):  # a host pass 100x its usual time
            time.sleep(0.2)
            return real(pks, msgs, sigs)

        monkeypatch.setattr(host_batch, "verify_many", slow)
        _, pks, msgs, sigs = _lanes(4, seed=23)
        co = _coalescer(window_us=500)
        try:
            assert co.submit(pks, msgs, sigs).result(10) == [True] * 4
            monkeypatch.setattr(host_batch, "verify_many", real)
            for _ in range(6):
                assert co.submit(pks, msgs, sigs).result(10) == [True] * 4
        finally:
            co.stop()
        assert cbatch.host_batch_threshold() == before
        assert before == cbatch._ACCEL_HOST_BATCH_THRESHOLD


class TestWindowSpans:
    def test_queue_wait_and_window_form_one_tree_across_threads(self):
        from cometbft_tpu.libs import trace as libtrace

        was = libtrace.enabled()
        libtrace.enable()
        libtrace.reset()
        _, pks, msgs, sigs = _lanes(3, seed=24)
        co = _coalescer(window_us=1_000)
        try:
            with libtrace.span("caller.request") as outer:
                assert co.submit(pks, msgs, sigs).result(10) == [True] * 3
        finally:
            co.stop()
            recs = libtrace.ring_dump()
            libtrace.reset()
            if not was:
                libtrace.disable()
        by_name = {r["name"]: r for r in recs if r["kind"] == "span"}
        wait, win = by_name["coalesce.queue_wait"], by_name["coalesce.window"]
        assert wait["parent"] == outer.id and wait["window"] == win["span"]
        assert wait["thread"] == "verify-coalescer" and wait["lanes"] == 3
        fb = by_name["verify.fallback"]
        assert fb["parent"] == win["span"] and fb["route"] == "host"
        assert fb["backend"] == "ed25519-coalesce" and fb["lanes"] == 3
        assert (win["lanes"], win["tickets"], win["route"]) == (3, 1, "host")
        assert win["start_ns"] <= fb["start_ns"]
        assert fb["start_ns"] + fb["dur_ns"] <= win["start_ns"] + win["dur_ns"]


class TestReadbackDrain:
    """The readback drain thread: dispatched windows materialize on a
    dedicated thread IN SUBMISSION ORDER while the executor packs and
    dispatches the next window — execute of window N+1 overlaps the
    d2h of window N — and the rescue paths still reach every ticket
    when either thread faults."""

    def test_tickets_resolve_in_submission_order(self, monkeypatch):
        # Window 1's device result is SLOW, window 2's instant: FIFO
        # drain must still resolve window 1's tickets first. The gate
        # event releases window 1 only after window 2 has been
        # DISPATCHED — which simultaneously pins the overlap property
        # (the executor launched N+1 while N's readback was pending).
        gate = threading.Event()
        dispatched: list[int] = []
        resolved: list[int] = []
        seq_by_groups: dict[int, int] = {}

        def fake_launch(self, groups, lanes, reason):
            pubkeys, msgs, sigs, staged = self._stage(groups)
            seq = len(dispatched) + 1
            dispatched.append(seq)
            seq_by_groups[id(staged)] = seq

            def finish(seq=seq):
                if seq == 1:
                    gate.wait(10)
                return np.ones(lanes, bool)

            return coalesce._Inflight(
                finish, np.ones(lanes, bool), staged, lanes, reason,
                0.0, (pubkeys, msgs, sigs),
            )

        real_rb = coalesce.VerifyCoalescer._resolve_bits

        def tracking_rb(self, staged, bits, reason, backend, **kw):
            seq = seq_by_groups.get(id(staged))
            if seq is not None:
                resolved.append(seq)
            real_rb(self, staged, bits, reason, backend, **kw)

        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_launch", fake_launch
        )
        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_resolve_bits", tracking_rb
        )
        co = _coalescer(window_us=1_000, max_lanes=2, max_inflight=2)
        try:
            _, pks, msgs, sigs = _lanes(4, seed=31)
            t1 = co.submit(pks[:2], msgs[:2], sigs[:2])
            # wait for window 1 to be dispatched before submitting
            # window 2, so the two flushes cannot merge
            for _ in range(200):
                if dispatched:
                    break
                time.sleep(0.01)
            t2 = co.submit(pks[2:], msgs[2:], sigs[2:])
            # the executor must dispatch window 2 while window 1 is
            # still materializing on the drain thread
            for _ in range(500):
                if len(dispatched) == 2:
                    break
                time.sleep(0.01)
            assert dispatched == [1, 2], (
                "executor never overlapped window 2's dispatch with "
                "window 1's readback"
            )
            assert not t1.done() and not t2.done()
            gate.set()
            assert t1.result(timeout=10) == [True, True]
            assert t2.result(timeout=10) == [True, True]
            assert resolved == [1, 2], resolved
        finally:
            gate.set()
            co.stop()

    def test_drain_finish_fault_rescues_that_window_only(
        self, monkeypatch
    ):
        # _finish raising on the drain thread (not the executor) must
        # host-rescue THAT window's tickets from the retained wire and
        # leave the loop alive for the next window
        calls: list[int] = []

        def fake_launch(self, groups, lanes, reason):
            pubkeys, msgs, sigs, staged = self._stage(groups)
            return coalesce._Inflight(
                lambda: np.ones(lanes, bool), np.ones(lanes, bool),
                staged, lanes, reason, 0.0, (pubkeys, msgs, sigs),
            )

        real_finish = coalesce.VerifyCoalescer._finish

        def flaky_finish(self, fl):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("drain-side fault")
            return real_finish(self, fl)

        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_launch", fake_launch
        )
        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_finish", flaky_finish
        )
        co = _coalescer(window_us=1_000, max_lanes=2)
        try:
            _, pks, msgs, sigs = _lanes(4, seed=32)
            sigs[1] = bytes(64)
            # window 1: drain _finish faults -> host rescue, real
            # verdicts (including the corrupted lane's False)
            bits = co.submit(pks[:2], msgs[:2], sigs[:2]).result(
                timeout=10
            )
            assert bits == [True, False]
            # window 2: the drain thread survived and finishes normally
            bits = co.submit(pks[2:], msgs[2:], sigs[2:]).result(
                timeout=10
            )
            assert bits == [True, True]
        finally:
            co.stop()

    def test_depth_bound_blocks_the_executor(self, monkeypatch):
        # with max_inflight=1 the executor may not dispatch window 2
        # until window 1 fully materialized
        gate = threading.Event()
        dispatched: list[int] = []

        def fake_launch(self, groups, lanes, reason):
            pubkeys, msgs, sigs, staged = self._stage(groups)
            dispatched.append(len(dispatched) + 1)

            def finish():
                gate.wait(10)
                return np.ones(lanes, bool)

            return coalesce._Inflight(
                finish, np.ones(lanes, bool), staged, lanes, reason,
                0.0, (pubkeys, msgs, sigs),
            )

        monkeypatch.setattr(
            coalesce.VerifyCoalescer, "_launch", fake_launch
        )
        co = _coalescer(window_us=1_000, max_lanes=2, max_inflight=1)
        try:
            _, pks, msgs, sigs = _lanes(4, seed=33)
            t1 = co.submit(pks[:2], msgs[:2], sigs[:2])
            for _ in range(200):
                if dispatched:
                    break
                time.sleep(0.01)
            t2 = co.submit(pks[2:], msgs[2:], sigs[2:])
            time.sleep(0.3)  # give a buggy executor time to overrun
            assert dispatched == [1], (
                "depth bound 1 must serialize dispatches"
            )
            gate.set()
            assert t1.result(timeout=10) == [True, True]
            assert t2.result(timeout=10) == [True, True]
            assert dispatched == [1, 2]
        finally:
            gate.set()
            co.stop()


class TestMixedBatchVerifierEdges:
    def test_empty_verifier_verifies_vacuously(self):
        bv = cbatch.MixedBatchVerifier()
        assert len(bv) == 0
        ok, bits = bv.verify()
        assert ok is True and bits == []

    def test_all_sr25519_matches_dedicated_backend(self):
        from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey

        keys = [
            Sr25519PrivKey(i.to_bytes(32, "little")) for i in range(1, 5)
        ]
        msgs = [b"sr-%d" % i for i in range(4)]
        sigs = [k.sign(m) for k, m in zip(keys, msgs)]
        sigs[2] = bytes(64)  # one invalid lane

        mixed = cbatch.MixedBatchVerifier()
        dedicated = cbatch.Sr25519BatchVerifier()
        for k, m, s in zip(keys, msgs, sigs):
            mixed.add(k.pub_key(), m, s)
            dedicated.add(k.pub_key(), m, s)
        ok_m, bits_m = mixed.verify()
        ok_d, bits_d = dedicated.verify()
        assert (ok_m, list(bits_m)) == (ok_d, list(bits_d))
        assert list(bits_m) == [True, True, False, True]

    def test_malformed_ed_lane_fails_only_itself(self):
        _, pks, msgs, sigs = _lanes(3, seed=11)
        bv = cbatch.MixedBatchVerifier()
        for pk, m, s in zip(pks, msgs, sigs):
            bv.add(Ed25519PubKey(pk), m, s)
        # truncate one signature AFTER add(): the lane-admission filter
        # (_ed_lane_idxs) must reject it without poisoning the batch
        bv._sigs[1] = b"\x01" * 10
        ok, bits = bv.verify()
        assert not ok
        assert list(bits) == [True, False, True]
