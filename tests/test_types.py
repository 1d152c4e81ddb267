"""Types layer: canonical sign bytes (golden vectors), blocks, validator
sets, vote sets, commit verification.

Golden byte vectors reproduced from the reference test suite
(types/vote_test.go:63-155 TestVoteSignBytesTestVectors) — the canonical
encodings are consensus-critical and must match byte-for-byte.
"""

import pytest

from cometbft_tpu.crypto import Ed25519PrivKey
from cometbft_tpu.types import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
    ConflictingVoteError,
    Data,
    Header,
    MockPV,
    NIL_BLOCK_ID,
    NotEnoughVotingPowerError,
    PartSetHeader,
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    PartSet,
    Validator,
    ValidatorSet,
    VerificationError,
    Version,
    Vote,
    VoteSet,
    verify_commit,
    verify_commit_light,
    verify_commit_light_trusting,
    Fraction,
)
from cometbft_tpu.types import canonical, proto
from cometbft_tpu.types.vote import Proposal

from helpers import HAVE_CRYPTOGRAPHY


# --- canonical sign bytes ----------------------------------------------------


class TestSignBytesGoldenVectors:
    """types/vote_test.go:63-155."""

    def test_zero_vote(self):
        got = canonical.vote_sign_bytes("", 0, 0, 0, NIL_BLOCK_ID, proto.ZERO_TIME_NS)
        want = bytes(
            [0xD, 0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF,
             0xFF, 0xFF, 0x1]
        )
        assert got == want

    def test_precommit(self):
        got = canonical.vote_sign_bytes(
            "", PRECOMMIT_TYPE, 1, 1, NIL_BLOCK_ID, proto.ZERO_TIME_NS
        )
        want = bytes(
            [0x21, 0x8, 0x2,
             0x11, 0x1, 0, 0, 0, 0, 0, 0, 0,
             0x19, 0x1, 0, 0, 0, 0, 0, 0, 0,
             0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF,
             0xFF, 0x1]
        )
        assert got == want

    def test_prevote(self):
        got = canonical.vote_sign_bytes(
            "", PREVOTE_TYPE, 1, 1, NIL_BLOCK_ID, proto.ZERO_TIME_NS
        )
        assert got[1:3] == bytes([0x8, 0x1])
        assert len(got) == 0x21 + 1

    def test_no_type_with_chain_id(self):
        got = canonical.vote_sign_bytes(
            "test_chain_id", 0, 1, 1, NIL_BLOCK_ID, proto.ZERO_TIME_NS
        )
        want = bytes(
            [0x2E,
             0x11, 0x1, 0, 0, 0, 0, 0, 0, 0,
             0x19, 0x1, 0, 0, 0, 0, 0, 0, 0,
             0x2A, 0xB, 0x8, 0x80, 0x92, 0xB8, 0xC3, 0x98, 0xFE, 0xFF, 0xFF,
             0xFF, 0x1,
             0x32, 0xD]
        ) + b"test_chain_id"
        assert got == want

    def test_vote_proposal_not_equal(self):
        v = canonical.vote_sign_bytes("", 0, 1, 1, NIL_BLOCK_ID, proto.ZERO_TIME_NS)
        p = canonical.proposal_sign_bytes(
            "", 1, 1, 0, NIL_BLOCK_ID, proto.ZERO_TIME_NS
        )
        assert v != p


# --- block / header ----------------------------------------------------------


def _pv_set(n, power=10):
    pvs = [MockPV(Ed25519PrivKey.from_seed(bytes([i + 1]) * 32)) for i in range(n)]
    vals = ValidatorSet(
        [Validator(pub_key=pv.get_pub_key(), voting_power=power) for pv in pvs]
    )
    by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
    ordered = [by_addr[v.address] for v in vals.validators]
    return ordered, vals


def _block_id(seed=b"\xaa"):
    return BlockID(
        hash=seed * 32, part_set_header=PartSetHeader(total=1, hash=seed * 32)
    )


def _make_commit(chain_id, height, round_, block_id, pvs, vals, *, nil_idx=(),
                 absent_idx=(), bad_sig_idx=()):
    sigs = []
    for i, pv in enumerate(pvs):
        if i in absent_idx:
            sigs.append(CommitSig.absent())
            continue
        bid = NIL_BLOCK_ID if i in nil_idx else block_id
        vote = Vote(
            msg_type=PRECOMMIT_TYPE,
            height=height,
            round=round_,
            block_id=bid,
            timestamp_ns=1_700_000_000_000_000_000 + i,
            validator_address=vals.validators[i].address,
            validator_index=i,
        )
        pv.sign_vote(chain_id, vote, sign_extension=False)
        if i in bad_sig_idx:
            vote.signature = vote.signature[:-1] + bytes(
                [vote.signature[-1] ^ 1]
            )
        sigs.append(vote.commit_sig())
    return Commit(height=height, round=round_, block_id=block_id, signatures=sigs)


class TestHeaderAndBlock:
    def test_header_hash_deterministic(self):
        h = Header(
            version=Version(block=11, app=1),
            chain_id="test",
            height=3,
            time_ns=1_700_000_000_000_000_000,
            last_block_id=_block_id(),
            last_commit_hash=b"\x01" * 32,
            data_hash=b"\x02" * 32,
            validators_hash=b"\x03" * 32,
            next_validators_hash=b"\x04" * 32,
            consensus_hash=b"\x05" * 32,
            app_hash=b"\x06" * 32,
            last_results_hash=b"\x07" * 32,
            evidence_hash=b"\x08" * 32,
            proposer_address=b"\x09" * 20,
        )
        h1, h2 = h.hash(), h.hash()
        assert h1 == h2 and len(h1) == 32
        # any field change changes the hash
        from dataclasses import replace

        assert replace(h, height=4).hash() != h1
        assert replace(h, chain_id="other").hash() != h1
        assert replace(h, app_hash=b"\x0a" * 32).hash() != h1

    def test_header_hash_nil_without_validators_hash(self):
        h = Header(
            version=Version(),
            chain_id="t",
            height=1,
            time_ns=0,
            last_block_id=NIL_BLOCK_ID,
            last_commit_hash=b"",
            data_hash=b"",
            validators_hash=b"",
            next_validators_hash=b"",
            consensus_hash=b"",
            app_hash=b"",
            last_results_hash=b"",
            evidence_hash=b"",
            proposer_address=b"\x01" * 20,
        )
        assert h.hash() is None

    def test_part_set_roundtrip(self):
        data = bytes(range(256)) * 700  # ~ 3 parts at 64KB
        ps = PartSet.from_data(data)
        assert ps.is_complete()
        ps2 = PartSet(ps.header)
        for i in range(ps.header.total):
            assert ps2.add_part(ps.get_part(i))
        assert ps2.assemble() == data

    def test_part_set_rejects_tampered_part(self):
        from cometbft_tpu.types.part_set import PartSetError

        data = b"x" * 100000
        ps = PartSet.from_data(data)
        part = ps.get_part(0)
        part.bytes_ = b"y" + part.bytes_[1:]
        ps2 = PartSet(ps.header)
        with pytest.raises(Exception):
            ps2.add_part(part)


# --- validator set -----------------------------------------------------------


class TestValidatorSet:
    def test_ordering_power_desc_address_asc(self):
        pvs, vals = _pv_set(5)
        powers = [v.voting_power for v in vals.validators]
        assert powers == sorted(powers, reverse=True)

    def test_proposer_rotation_is_fair(self):
        _, vals = _pv_set(3)
        counts = {}
        vs = vals
        for _ in range(300):
            p = vs.get_proposer().address
            counts[p] = counts.get(p, 0) + 1
            vs = vs.copy_increment_proposer_priority(1)
        # equal power => each proposes ~100 times
        assert all(90 <= c <= 110 for c in counts.values()), counts

    def test_proposer_rotation_weighted(self):
        pv1 = MockPV(Ed25519PrivKey.from_seed(b"\x01" * 32))
        pv2 = MockPV(Ed25519PrivKey.from_seed(b"\x02" * 32))
        vals = ValidatorSet(
            [
                Validator(pub_key=pv1.get_pub_key(), voting_power=1),
                Validator(pub_key=pv2.get_pub_key(), voting_power=3),
            ]
        )
        counts = {}
        vs = vals
        for _ in range(400):
            p = vs.get_proposer().address
            counts[p] = counts.get(p, 0) + 1
            vs = vs.copy_increment_proposer_priority(1)
        heavy = counts[bytes(pv2.get_pub_key().address())]
        assert 280 <= heavy <= 320, counts

    def test_hash_changes_with_power(self):
        _, vals = _pv_set(3)
        h1 = vals.hash()
        vals.validators[0].voting_power += 1
        assert vals.hash() != h1

    def test_update_add_remove(self):
        pvs, vals = _pv_set(3)
        new_pv = MockPV(Ed25519PrivKey.from_seed(b"\x42" * 32))
        vals.update_with_change_set(
            [Validator(pub_key=new_pv.get_pub_key(), voting_power=5)]
        )
        assert len(vals) == 4
        assert vals.has_address(bytes(new_pv.get_pub_key().address()))
        # remove it again
        vals.update_with_change_set(
            [Validator(pub_key=new_pv.get_pub_key(), voting_power=0)]
        )
        assert len(vals) == 3
        with pytest.raises(ValueError):
            vals.update_with_change_set(
                [Validator(pub_key=new_pv.get_pub_key(), voting_power=0)]
            )


# --- the validator set's kept root (ValidatorSet.hash) ----------------------


def _leaves_root(vals):
    """What a set without a memo computes: the reference's definition."""
    from cometbft_tpu.crypto import merkle

    return merkle.hash_from_byte_slices([v.bytes() for v in vals.validators])


def _other_key(i=0x70):
    return MockPV(Ed25519PrivKey.from_seed(bytes([i]) * 32)).get_pub_key()


def _set_power(vals):
    vals.validators[1].voting_power += 7


def _replace_validator(vals):
    vals.validators[2] = Validator(pub_key=_other_key(), voting_power=10)


def _append_validator(vals):
    vals.validators.append(Validator(pub_key=_other_key(), voting_power=10))


def _remove_validator(vals):
    del vals.validators[0]


def _swap_pub_key(vals):
    vals.validators[3].pub_key = _other_key()


def _swap_two_validators(vals):
    v = vals.validators
    v[0], v[1] = v[1], v[0]


def _new_list_object(vals):
    vals.validators = [v.copy() for v in vals.validators[:-1]]


@pytest.fixture
def hash_counts():
    """{result: calls} of types_valset_hash_total, on a registry of this
    test's own."""
    from cometbft_tpu.libs import metrics as libmetrics

    m = libmetrics.NodeMetrics()
    libmetrics.push_node_metrics(m)

    def read():
        return {
            r: int(m.valset_hash_total.labels(r).value())
            for r in ("computed", "reused")
        }

    yield read
    libmetrics.pop_node_metrics(m)


@pytest.fixture
def no_tree_after(monkeypatch):
    """Call it, and from then on building a Merkle tree fails the test."""
    from cometbft_tpu.crypto import merkle

    def refuse(_items):
        raise AssertionError("the root was computed again")

    return lambda: monkeypatch.setattr(
        merkle, "hash_from_byte_slices", refuse)


class TestValidatorSetRootMemo:
    def test_hit_returns_what_a_fresh_set_computes(self, hash_counts):
        pvs, vals = _pv_set(5)
        first = vals.hash()
        assert hash_counts() == {"computed": 1, "reused": 0}
        again = vals.hash()
        assert hash_counts() == {"computed": 1, "reused": 1}
        fresh = ValidatorSet(
            [Validator(pub_key=pv.get_pub_key(), voting_power=10)
             for pv in pvs]
        )
        assert fresh._root_memo is None
        assert again == first == fresh.hash() == _leaves_root(vals)
        assert isinstance(again, bytes) and len(again) == 32

    @pytest.mark.parametrize("change", [
        _set_power, _replace_validator, _append_validator,
        _remove_validator, _swap_pub_key, _swap_two_validators,
        _new_list_object,
    ])
    def test_change_in_place_gives_the_new_root(self, hash_counts, change):
        _, vals = _pv_set(5)
        old = vals.hash()
        change(vals)
        got = vals.hash()
        assert got == _leaves_root(vals)
        assert got != old
        assert hash_counts() == {"computed": 2, "reused": 0}
        # and the new root is the one kept from here on
        assert vals.hash() == got
        assert hash_counts() == {"computed": 2, "reused": 1}

    def test_change_and_change_back_gives_the_old_root(self, hash_counts):
        _, vals = _pv_set(4)
        old = vals.hash()
        vals.validators[0].voting_power += 1
        assert vals.hash() != old
        vals.validators[0].voting_power -= 1
        assert vals.hash() == old == _leaves_root(vals)
        # one root is kept, the last: the way back is computed too
        assert hash_counts() == {"computed": 3, "reused": 0}

    def test_equal_key_of_another_object_is_the_same_leaf(
        self, hash_counts, no_tree_after
    ):
        """The witness compares keys by their bytes (the key classes are
        frozen dataclasses), as a leaf does."""
        _, vals = _pv_set(4)
        root = vals.hash()
        no_tree_after()
        for v in vals.validators:
            v.pub_key = type(v.pub_key)(bytes(v.pub_key.bytes()))
        assert vals.hash() == root
        assert hash_counts() == {"computed": 1, "reused": 1}

    def test_unhashable_key_type_keeps_no_root(self):
        class _Bls:
            type = "bls12381"

            def bytes(self):
                return b"\x01" * 48

        _, vals = _pv_set(3)
        vals.hash()
        vals.validators[1].pub_key = _Bls()
        with pytest.raises(ValueError):
            vals.hash()
        with pytest.raises(ValueError):  # no stale root the second time
            vals.hash()

    def test_update_with_change_set_gives_the_fresh_sets_root(
        self, hash_counts
    ):
        pvs, vals = _pv_set(4)
        old = vals.hash()
        vals.update_with_change_set([
            Validator(pub_key=_other_key(), voting_power=5),
            Validator(pub_key=pvs[0].get_pub_key(), voting_power=25),
        ])
        assert vals._root_memo is None
        fresh = ValidatorSet([
            Validator(pub_key=v.pub_key, voting_power=v.voting_power)
            for v in vals.validators
        ])
        got = vals.hash()
        assert got == fresh.hash() == _leaves_root(vals) != old
        assert hash_counts()["reused"] == 0

    @pytest.mark.parametrize("derive", [
        lambda vs: vs.copy(),
        lambda vs: vs.copy_increment_proposer_priority(3),
        lambda vs: vs.copy().copy_increment_proposer_priority(1),
    ], ids=["copy", "copy_increment", "copy_of_copy"])
    def test_copies_hit_without_recomputing(
        self, hash_counts, monkeypatch, derive
    ):
        from cometbft_tpu.crypto import merkle

        _, vals = _pv_set(5)
        root = vals.hash()
        calls = []
        real = merkle.hash_from_byte_slices
        monkeypatch.setattr(
            merkle, "hash_from_byte_slices",
            lambda items: calls.append(len(items)) or real(items),
        )
        cp = derive(vals)
        assert cp.hash() == root
        assert not calls
        assert hash_counts() == {"computed": 1, "reused": 1}
        # the copy's validators are its own: a change there is seen
        # there and not in the original
        cp.validators[0].voting_power += 1
        assert cp.hash() == real([v.bytes() for v in cp.validators]) != root
        assert calls == [5]
        assert vals.hash() == root
        assert calls == [5]

    def test_priorities_are_not_hashed_and_keep_the_root(
        self, hash_counts, no_tree_after
    ):
        _, vals = _pv_set(5)
        root = vals.hash()
        no_tree_after()
        vals.increment_proposer_priority(7)
        vals.rescale_priorities(1)
        vals.validators[0].proposer_priority = 12345
        assert vals.hash() == root
        assert hash_counts() == {"computed": 1, "reused": 1}

    def test_round_trip_through_serialization_carries_no_memo(
        self, hash_counts
    ):
        from cometbft_tpu.types import serialization

        _, vals = _pv_set(4)
        cold = serialization.dumps(vals)
        root = vals.hash()
        assert vals._root_memo is not None
        warm = serialization.dumps(vals)
        assert warm == cold  # the memo is never written
        back = serialization.loads(warm)
        assert isinstance(back, ValidatorSet)
        assert back._root_memo is None
        assert back.hash() == root
        assert hash_counts() == {"computed": 2, "reused": 0}

    def test_two_threads_on_one_cold_set_get_one_root(self, hash_counts):
        import threading

        _, vals = _pv_set(64)
        gate = threading.Barrier(2)
        got, failed = [], []

        def worker():
            try:
                gate.wait(timeout=30)
                for _ in range(20):
                    got.append(vals.hash())
            except Exception as e:  # read on the test's thread
                failed.append(e)

        threads = [threading.Thread(target=worker) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not failed, failed
        assert len(got) == 40 and set(got) == {_leaves_root(vals)}
        counts = hash_counts()
        assert counts["computed"] in (1, 2)  # a race costs one duplicate
        assert counts["computed"] + counts["reused"] == 40


# --- commit verification (hot path) -----------------------------------------


CHAIN_ID = "test-chain"


class TestVerifyCommit:
    def test_happy_path_batch(self):
        pvs, vals = _pv_set(4)
        bid = _block_id()
        commit = _make_commit(CHAIN_ID, 5, 0, bid, pvs, vals)
        verify_commit(CHAIN_ID, vals, bid, 5, commit)
        verify_commit_light(CHAIN_ID, vals, bid, 5, commit)
        verify_commit_light_trusting(CHAIN_ID, vals, commit, Fraction(1, 3))

    def test_bad_signature_rejected(self):
        pvs, vals = _pv_set(4)
        bid = _block_id()
        commit = _make_commit(
            CHAIN_ID, 5, 0, bid, pvs, vals, bad_sig_idx={2}
        )
        with pytest.raises(VerificationError, match="wrong signature"):
            verify_commit(CHAIN_ID, vals, bid, 5, commit)

    def test_insufficient_power(self):
        pvs, vals = _pv_set(4)
        bid = _block_id()
        # 2 of 4 sign => 20/40 <= 2/3
        commit = _make_commit(
            CHAIN_ID, 5, 0, bid, pvs, vals, absent_idx={0, 1}
        )
        with pytest.raises(NotEnoughVotingPowerError):
            verify_commit(CHAIN_ID, vals, bid, 5, commit)

    def test_nil_votes_counted_but_not_tallied(self):
        pvs, vals = _pv_set(4)
        bid = _block_id()
        # 3 commit votes + 1 nil: power 30/40 > 2/3 — must pass and verify
        # the nil vote's signature too (VerifyCommit checks all).
        commit = _make_commit(CHAIN_ID, 5, 0, bid, pvs, vals, nil_idx={3})
        verify_commit(CHAIN_ID, vals, bid, 5, commit)
        # but a bad nil-vote signature still fails the full check
        commit2 = _make_commit(
            CHAIN_ID, 5, 0, bid, pvs, vals, nil_idx={3}, bad_sig_idx={3}
        )
        with pytest.raises(VerificationError, match="wrong signature"):
            verify_commit(CHAIN_ID, vals, bid, 5, commit2)
        # ...while the light check ignores non-commit votes entirely
        verify_commit_light(CHAIN_ID, vals, bid, 5, commit2)

    def test_wrong_height_or_block(self):
        pvs, vals = _pv_set(4)
        bid = _block_id()
        commit = _make_commit(CHAIN_ID, 5, 0, bid, pvs, vals)
        with pytest.raises(VerificationError):
            verify_commit(CHAIN_ID, vals, bid, 6, commit)
        with pytest.raises(VerificationError):
            verify_commit(CHAIN_ID, vals, _block_id(b"\xbb"), 5, commit)

    def test_light_trusting_different_valset(self):
        pvs, vals = _pv_set(6)
        bid = _block_id()
        commit = _make_commit(CHAIN_ID, 5, 0, bid, pvs, vals)
        # trusted set = subset of 4 (overlap enough for 1/3 trust level)
        subset = ValidatorSet(
            [
                Validator(pub_key=v.pub_key, voting_power=v.voting_power)
                for v in vals.validators[:4]
            ]
        )
        verify_commit_light_trusting(CHAIN_ID, subset, commit, Fraction(1, 3))

    def test_single_fallback_below_threshold(self):
        pvs, vals = _pv_set(1)
        bid = _block_id()
        commit = _make_commit(CHAIN_ID, 5, 0, bid, pvs, vals)
        # 1 signature < batchVerifyThreshold => single-verify path
        verify_commit(CHAIN_ID, vals, bid, 5, commit)


# --- vote set ----------------------------------------------------------------


def _vote(vals, pvs, i, bid, *, h=3, r=0, t=PREVOTE_TYPE, ts=0):
    v = Vote(
        msg_type=t,
        height=h,
        round=r,
        block_id=bid,
        timestamp_ns=ts or 1_700_000_000_000_000_000,
        validator_address=vals.validators[i].address,
        validator_index=i,
    )
    pvs[i].sign_vote(CHAIN_ID, v, sign_extension=False)
    return v


class TestVoteSet:
    def test_two_thirds_latch(self):
        pvs, vals = _pv_set(4)
        vs = VoteSet(CHAIN_ID, 3, 0, PREVOTE_TYPE, vals)
        bid = _block_id()
        assert vs.add_vote(_vote(vals, pvs, 0, bid))
        assert vs.add_vote(_vote(vals, pvs, 1, bid))
        assert vs.two_thirds_majority() is None
        assert vs.add_vote(_vote(vals, pvs, 2, bid))
        assert vs.two_thirds_majority() == bid

    def test_duplicate_vote_not_added(self):
        pvs, vals = _pv_set(4)
        vs = VoteSet(CHAIN_ID, 3, 0, PREVOTE_TYPE, vals)
        v = _vote(vals, pvs, 0, _block_id())
        assert vs.add_vote(v)
        assert not vs.add_vote(v)

    def test_conflicting_vote_raises(self):
        pvs, vals = _pv_set(4)
        vs = VoteSet(CHAIN_ID, 3, 0, PREVOTE_TYPE, vals)
        assert vs.add_vote(_vote(vals, pvs, 0, _block_id(b"\xaa")))
        with pytest.raises(ConflictingVoteError):
            vs.add_vote(_vote(vals, pvs, 0, _block_id(b"\xbb")))

    def test_conflicting_vote_admitted_after_peer_maj23(self):
        pvs, vals = _pv_set(4)
        vs = VoteSet(CHAIN_ID, 3, 0, PREVOTE_TYPE, vals)
        bid_b = _block_id(b"\xbb")
        assert vs.add_vote(_vote(vals, pvs, 0, _block_id(b"\xaa")))
        vs.set_peer_maj23("peer1", bid_b)
        assert vs.add_vote(_vote(vals, pvs, 0, bid_b))

    def test_invalid_signature_rejected(self):
        pvs, vals = _pv_set(4)
        vs = VoteSet(CHAIN_ID, 3, 0, PREVOTE_TYPE, vals)
        v = _vote(vals, pvs, 0, _block_id())
        v.signature = bytes(64)
        from cometbft_tpu.types.vote import VoteError

        with pytest.raises(VoteError):
            vs.add_vote(v)

    def test_batched_ingest_matches_sequential(self):
        pvs, vals = _pv_set(6)
        bid = _block_id()
        votes = [_vote(vals, pvs, i, bid) for i in range(6)]
        votes[2].signature = bytes(64)  # invalid
        vs = VoteSet(CHAIN_ID, 3, 0, PREVOTE_TYPE, vals)
        added, errors = vs.add_votes_batch(votes)
        assert added == [True, True, False, True, True, True]
        assert errors[2] is not None  # bad signature surfaced, not swallowed
        assert all(e is None for i, e in enumerate(errors) if i != 2)
        assert vs.two_thirds_majority() == bid

    def test_make_commit(self):
        pvs, vals = _pv_set(4)
        vs = VoteSet(CHAIN_ID, 3, 0, PRECOMMIT_TYPE, vals)
        bid = _block_id()
        for i in range(3):
            vs.add_vote(_vote(vals, pvs, i, bid, t=PRECOMMIT_TYPE))
        commit = vs.make_commit()
        assert commit.block_id == bid
        assert commit.signatures[3].block_id_flag == BLOCK_ID_FLAG_ABSENT
        verify_commit(CHAIN_ID, vals, bid, 3, commit)


class TestProposal:
    def test_sign_and_validate(self):
        pv = MockPV(Ed25519PrivKey.from_seed(b"\x05" * 32))
        p = Proposal(
            height=2,
            round=1,
            pol_round=-1,
            block_id=_block_id(),
            timestamp_ns=1_700_000_000_000_000_000,
        )
        pv.sign_proposal(CHAIN_ID, p)
        p.validate_basic()
        assert pv.get_pub_key().verify_signature(
            p.sign_bytes(CHAIN_ID), p.signature
        )


class TestVerifyCommitMixedKeys:
    """A heterogeneous (ed25519 + sr25519) validator set batches through
    crypto_batch.MixedBatchVerifier — one launch — where the reference
    falls back to per-signature verifies (types/validation.go:170-176)."""

    def _mixed_pv_set(self, n_ed, n_sr, power=10):
        from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey

        pvs = [
            MockPV(Ed25519PrivKey.from_seed(bytes([i + 1]) * 32))
            for i in range(n_ed)
        ] + [
            MockPV(Sr25519PrivKey.from_seed(bytes([i + 101]) * 32))
            for i in range(n_sr)
        ]
        vals = ValidatorSet(
            [
                Validator(pub_key=pv.get_pub_key(), voting_power=power)
                for pv in pvs
            ]
        )
        by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
        ordered = [by_addr[v.address] for v in vals.validators]
        return ordered, vals

    def test_mixed_commit_batches_and_verifies(self):
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.types import validation

        pvs, vals = self._mixed_pv_set(3, 3)
        assert crypto_batch.supports_commit_batch(vals)
        assert validation._should_batch_verify(
            vals, _make_commit(CHAIN_ID, 5, 0, _block_id(), pvs, vals)
        )
        bid = _block_id()
        commit = _make_commit(CHAIN_ID, 5, 0, bid, pvs, vals)
        verify_commit(CHAIN_ID, vals, bid, 5, commit)

    def test_mixed_commit_bad_signature_attributed(self):
        pvs, vals = self._mixed_pv_set(3, 3)
        bid = _block_id()
        commit = _make_commit(
            CHAIN_ID, 5, 0, bid, pvs, vals, bad_sig_idx={4}
        )
        with pytest.raises(VerificationError, match="wrong signature"):
            verify_commit(CHAIN_ID, vals, bid, 5, commit)


class TestValidatorKeyWireScope:
    """The tendermint.crypto.PublicKey oneof carries ed25519 (1),
    secp256k1 (2) and sr25519 (3, as Tendermint v0.35's keys.proto
    numbers it): a mixed ed25519 + sr25519 set has a hash and a genesis;
    any other key type is refused at genesis with a clear message instead
    of crashing the FSM at the first validator-set hash."""

    def test_valset_hash_encodes_sr25519_as_field_3(self):
        from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey
        from cometbft_tpu.types.validator_set import pubkey_proto_encode

        pk = Sr25519PrivKey.from_seed(b"\x09" * 32).pub_key()
        assert pubkey_proto_encode(pk) == b"\x1a\x20" + pk.data
        vs = ValidatorSet([Validator(pub_key=pk, voting_power=1)])
        other = ValidatorSet([Validator(
            pub_key=Sr25519PrivKey.from_seed(b"\x0c" * 32).pub_key(),
            voting_power=1)])
        assert len(vs.hash()) == 32 and vs.hash() != other.hash()

    def test_genesis_accepts_sr25519_and_rejects_unknown_types(self):
        from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey
        from cometbft_tpu.types.genesis import (
            GenesisDoc,
            GenesisValidator,
        )

        pv = Sr25519PrivKey.from_seed(b"\x0a" * 32)
        doc = GenesisDoc(
            chain_id="wire-scope",
            genesis_time_ns=1,
            validators=[
                GenesisValidator(pub_key=pv.pub_key(), power=10)
            ],
        )
        doc.validate_and_complete()
        back = GenesisDoc.from_json(doc.to_json())
        assert back.validators[0].pub_key == pv.pub_key()

        class _Bls:
            type = "bls12381"

            def address(self):
                return b"\x01" * 20

            def bytes(self):
                return b"\x00" * 48

        doc = GenesisDoc(
            chain_id="wire-scope",
            genesis_time_ns=1,
            validators=[GenesisValidator(pub_key=_Bls(), power=10)],
        )
        with pytest.raises(ValueError, match="not wire-encodable"):
            doc.validate_and_complete()

    @pytest.mark.skipif(
        not HAVE_CRYPTOGRAPHY,
        reason="secp256k1/OpenSSL key types need the cryptography wheel",
    )
    def test_genesis_accepts_secp256k1_validator(self):
        from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
        from cometbft_tpu.types.genesis import (
            GenesisDoc,
            GenesisValidator,
        )

        pv = Secp256k1PrivKey.from_seed(b"\x0b" * 32)
        doc = GenesisDoc(
            chain_id="wire-scope",
            genesis_time_ns=1,
            validators=[
                GenesisValidator(pub_key=pv.pub_key(), power=10)
            ],
        )
        doc.validate_and_complete()  # proto-encodable: accepted
        assert doc.validator_set().hash()
