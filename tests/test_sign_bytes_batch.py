"""One encoding of a commit's sign-bytes, and one ``add_many``, under the
commit walk (types/validation._verify_batch).

The per-lane encoder (``canonical.vote_sign_bytes`` through
``Commit.vote_sign_bytes``) and the per-lane walk stay here as the
references: the batched encoder (native/edbatch.cpp through
``canonical.vote_sign_bytes_many``) gives the same bytes lane for lane, and
the verifier sees the same (key, message, signature) sequence, the same
early stop and the same errors as it did lane by lane. The benchmark's own
encoder (benchmark/reference/canonical.py), which imports nothing of the
program, is the second oracle for the bytes."""

import dataclasses
import random
from array import array

import pytest

from benchmark.reference import canonical as ref_canonical
from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import host_batch
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey
from cometbft_tpu.types import canonical, proto, validation
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
)
from cometbft_tpu.types.priv_validator import MockPV
from cometbft_tpu.types.validator_set import Validator, ValidatorSet
from cometbft_tpu.types.vote import Vote

CHAIN_ID = "sign-bytes-chain"
BASE_NS = 1_700_000_000_000_000_000
SECOND = 1_000_000_000
BLOCK_ID = BlockID(
    hash=bytes(range(32)),
    part_set_header=PartSetHeader(total=3, hash=bytes(range(32, 64))),
)


def _commit(timestamps, flags=None, chain_height=77, round_=1) -> Commit:
    """A commit whose lanes carry ``timestamps``; the bytes under test do
    not depend on keys or signatures."""
    flags = flags or [BLOCK_ID_FLAG_COMMIT] * len(timestamps)
    sigs = [
        CommitSig.absent()
        if flag == BLOCK_ID_FLAG_ABSENT
        else CommitSig(flag, i.to_bytes(20, "big"), ts, bytes([i % 251]) * 64)
        for i, (ts, flag) in enumerate(zip(timestamps, flags))
    ]
    return Commit(chain_height, round_, BLOCK_ID, sigs)


def _reference_bytes(commit: Commit, chain_id: str, idxs) -> list[bytes]:
    """The benchmark's encoder, from the wire format alone."""
    for_block = ref_canonical.vote_template(
        chain_id, commit.height, commit.round, commit.block_id.hash,
        commit.block_id.part_set_header.total,
        commit.block_id.part_set_header.hash,
    )
    # the reference writes templates for a block only; a vote for nil
    # leaves field 4 out (canonical.proto: a nil block id is omitted)
    for_nil = (
        ref_canonical._varint_field(1, ref_canonical.PRECOMMIT)
        + ref_canonical._sfixed64_field(2, commit.height)
        + ref_canonical._sfixed64_field(3, commit.round),
        for_block[1],
    )
    return [
        ref_canonical.vote_sign_bytes(
            for_block
            if commit.signatures[i].block_id_flag == BLOCK_ID_FLAG_COMMIT
            else for_nil,
            commit.signatures[i].timestamp_ns,
        )
        for i in idxs
    ]


def _assert_same_bytes(commit, chain_id, idxs):
    got = commit.vote_sign_bytes_many(chain_id, idxs)
    per_lane = [commit.vote_sign_bytes(chain_id, i) for i in idxs]
    assert got == per_lane
    assert got == _reference_bytes(commit, chain_id, idxs)
    assert all(type(sb) is bytes for sb in got)
    return got


@pytest.mark.parametrize("lanes", [1, 2, 59, 117, 6667])
def test_lane_counts_of_the_listed_cells(lanes):
    rng = random.Random(lanes)
    commit = _commit(
        [BASE_NS + rng.randrange(3 * SECOND) for _ in range(lanes)])
    _assert_same_bytes(commit, CHAIN_ID, range(lanes))


# name -> timestamps of one commit's lanes, in lane order
TIMESTAMP_CASES = {
    "timestamp_0": [0, BASE_NS, 0],
    "nanos_0": [BASE_NS, BASE_NS + SECOND, 5 * SECOND],
    # 1- and 5-byte nanos, and 4 lengths between, interleaved: the lanes
    # of several length classes come back in lane order
    "nanos_of_every_length": [
        BASE_NS + n for n in (
            1, 1 << 28, 127, 999_999_999, 128, (1 << 28) - 1, 0,
            1 << 14, (1 << 21) - 1, 1 << 21, 5, 1 << 29,
        )
    ],
    "negative": [-1, -SECOND, -BASE_NS, BASE_NS, -(1 << 63), -SECOND - 1],
    "seconds_of_every_length": [
        s * SECOND + 7 for s in (
            1, 127, 128, 1 << 14, 1 << 21, 1 << 28, 1 << 33,
        )
    ] + [(1 << 63) - 1],
    "int64_ends": [-(1 << 63), (1 << 63) - 1, -(1 << 63) + SECOND, 0],
}


@pytest.mark.parametrize("case", sorted(TIMESTAMP_CASES))
def test_timestamp_encodings(case):
    timestamps = TIMESTAMP_CASES[case]
    commit = _commit(timestamps)
    got = _assert_same_bytes(commit, CHAIN_ID, range(len(timestamps)))
    if case == "timestamp_0":
        # field 5 is emitted with an empty Timestamp body
        assert b"\x2a\x00" in got[0]


@pytest.mark.parametrize("chain_len", [40, 50, 200, 20_000])
def test_long_chain_id_lengthens_the_prefix(chain_len):
    """A body of 128 bytes or more takes a two-byte length prefix (three
    from 16,384), and lanes on both sides of 128 may share a commit."""
    chain_id = "c" * chain_len
    commit = _commit([BASE_NS + 1, 0, BASE_NS + (1 << 28), SECOND])
    got = _assert_same_bytes(commit, chain_id, range(4))
    bodies = {len(sb) - len(proto.uvarint(len(sb))) for sb in got}
    assert len(bodies) > 1


def test_timestamp_beyond_int64_is_handed_back():
    """Go's zero time, an absent CommitSig's timestamp, is under int64
    nanoseconds: the batched encoder declines the commit's lanes whole,
    and the walk then encodes them one by one."""
    commit = _commit([BASE_NS, proto.ZERO_TIME_NS, BASE_NS + 1])
    assert commit.vote_sign_bytes_many(CHAIN_ID, [0, 1, 2]) is None
    assert commit.vote_sign_bytes_many(CHAIN_ID, [0, 2]) is not None
    for t in (1 << 63, -(1 << 63) - 1, 1 << 70):
        assert _commit([t]).vote_sign_bytes_many(CHAIN_ID, [0]) is None


def test_commit_nil_and_absent_lanes_under_verify_commit():
    """verify_commit takes every lane that is not absent: a lane for the
    block signs the commit's block id, a nil lane the nil block id."""
    flags = [
        BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_ABSENT,
        BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_NIL,
        BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT,
    ]
    commit = _commit([BASE_NS + 300_000_000 * i for i in range(8)], flags)
    taken = [i for i, f in enumerate(flags) if f != BLOCK_ID_FLAG_ABSENT]
    got = _assert_same_bytes(commit, CHAIN_ID, taken)
    assert len(set(got)) == len(taken)
    # all nil: no lane for the block at all
    _assert_same_bytes(commit, CHAIN_ID, [1, 4, 5])
    # an absent lane, asked for, signs nil at Go's zero time: handed back
    assert commit.vote_sign_bytes_many(CHAIN_ID, range(8)) is None


def test_indexes_need_not_be_contiguous_or_ordered():
    rng = random.Random(5)
    commit = _commit([BASE_NS + rng.randrange(SECOND) for _ in range(64)])
    _assert_same_bytes(commit, CHAIN_ID, [3, 4, 9, 17, 18, 40, 63])
    _assert_same_bytes(commit, CHAIN_ID, [63, 0, 17, 17, 2])
    assert commit.vote_sign_bytes_many(CHAIN_ID, []) == []


def test_unknown_flag_raises_as_the_per_lane_encoder_does():
    commit = _commit([BASE_NS, BASE_NS + 1], [BLOCK_ID_FLAG_COMMIT, 9])
    with pytest.raises(ValueError, match="unknown BlockIDFlag 9"):
        commit.vote_sign_bytes(CHAIN_ID, 1)
    with pytest.raises(ValueError, match="unknown BlockIDFlag 9"):
        commit.vote_sign_bytes_many(CHAIN_ID, [0, 1])


def test_native_rows_come_in_the_packers_layout():
    """host_batch.vote_sign_bytes: a MsgColumn, one blob and n + 1
    offsets, the msgs/offs pair host_batch.pack_wire and
    pack_challenges read in place."""
    timestamps = TIMESTAMP_CASES["nanos_of_every_length"] + [0, -1]
    prefix, suffix = canonical._vote_template(
        CHAIN_ID, canonical.PRECOMMIT_TYPE, 77, 1, BLOCK_ID)
    column = host_batch.vote_sign_bytes(
        prefix, suffix, array("q", timestamps))
    assert isinstance(column, host_batch.MsgColumn)
    blob, offs = column.blob, column.offs
    assert type(blob) is bytes and offs.typecode == "Q"
    assert len(offs) == len(timestamps) + 1
    assert offs[0] == 0 and offs[-1] == len(blob)
    assert [blob[a:b] for a, b in zip(offs, offs[1:])] == [
        canonical.vote_sign_bytes(
            CHAIN_ID, canonical.PRECOMMIT_TYPE, 77, 1, BLOCK_ID, t)
        for t in timestamps
    ]


def test_single_vote_encoder_is_unmoved():
    """canonical.vote_sign_bytes against bytes written out by hand."""
    got = canonical.vote_sign_bytes(
        "c", canonical.PRECOMMIT_TYPE, 1, 0, None, 3 * SECOND + 4)
    assert got == bytes.fromhex("14" "0802" "110100000000000000" "2a04"
                                "0803" "1004" "3201" "63")


# --- the walk ---------------------------------------------------------------


class _Recorder(cbatch.BatchVerifier):
    """Takes lanes through ``add`` alone (``add_many`` is the base class's
    loop over it) and answers with the bits it was given."""

    def __init__(self, bits=None):
        self.lanes: list[tuple] = []
        self.bits = bits

    def add(self, pub_key, msg, signature):
        self.lanes.append((pub_key, bytes(msg), bytes(signature)))

    def __len__(self):
        return len(self.lanes)

    def verify(self):
        bits = self.bits or [True] * len(self.lanes)
        return all(bits), bits


def _per_lane_walk(chain_id, vals, commit, needed, ignore, count, count_all,
                   by_index, bv):
    """The walk as it stood before the batched encoder, kept as the
    reference: one sign-bytes call and one ``bv.add`` a lane."""
    seen: dict[int, int] = {}
    batch_sig_idxs: list[int] = []
    tallied = 0
    for idx, cs in enumerate(commit.signatures):
        if ignore(cs):
            continue
        if by_index:
            val = vals.validators[idx]
        else:
            val_idx, val = vals.get_by_address(cs.validator_address)
            if val is None:
                continue
            if val_idx in seen:
                raise validation.VerificationError(
                    f"double vote from validator {val_idx} "
                    f"({seen[val_idx]} and {idx})"
                )
            seen[val_idx] = idx
        bv.add(val.pub_key, commit.vote_sign_bytes(chain_id, idx),
               cs.signature)
        batch_sig_idxs.append(idx)
        if count(cs):
            tallied += val.voting_power
        if not count_all and tallied > needed:
            break
    if tallied <= needed:
        raise validation.NotEnoughVotingPowerError(got=tallied, needed=needed)
    ok, valid_sigs = bv.verify()
    for i, sig_ok in enumerate(valid_sigs):
        if not sig_ok:
            idx = batch_sig_idxs[i]
            raise validation.VerificationError(
                f"wrong signature (#{idx}): "
                f"{commit.signatures[idx].signature.hex()}"
            )


def _not_for_block(cs):
    return cs.block_id_flag != BLOCK_ID_FLAG_COMMIT


# facade -> (ignore, count, count_all, by_index) of the per-lane walk
WALKS = {
    "verify_commit": (
        lambda cs: cs.block_id_flag == BLOCK_ID_FLAG_ABSENT,
        lambda cs: cs.block_id_flag == BLOCK_ID_FLAG_COMMIT, True, True),
    "verify_commit_light": (_not_for_block, lambda cs: True, False, True),
    "verify_commit_light_trusting": (
        _not_for_block, lambda cs: True, False, False),
}


def _call(facade, vals, commit, trust=validation.DEFAULT_TRUST_LEVEL):
    if facade == "verify_commit_light_trusting":
        return validation.verify_commit_light_trusting(
            CHAIN_ID, vals, commit, trust)
    return getattr(validation, facade)(
        CHAIN_ID, vals, commit.block_id, commit.height, commit)


def _needed(facade, vals, trust=validation.DEFAULT_TRUST_LEVEL):
    if facade == "verify_commit_light_trusting":
        return vals.total_voting_power() * trust.numerator // trust.denominator
    return vals.total_voting_power() * 2 // 3


def _outcome(fn):
    """What a walk did to its caller: None, or the error's type and text
    (and a shortfall's two numbers)."""
    try:
        fn()
    except validation.NotEnoughVotingPowerError as e:
        return ("not enough", e.got, e.needed, str(e))
    except validation.VerificationError as e:
        return ("refused", str(e))
    return None


def _both_walks(monkeypatch, facade, vals, commit, bits=None):
    """(outcome, lanes) of the program's walk and of the per-lane walk."""
    new = _Recorder(bits)
    monkeypatch.setattr(
        cbatch, "create_commit_batch_verifier", lambda _vals: new)
    new_outcome = _outcome(lambda: _call(facade, vals, commit))
    old = _Recorder(bits)
    ignore, count, count_all, by_index = WALKS[facade]
    old_outcome = _outcome(lambda: _per_lane_walk(
        CHAIN_ID, vals, commit, _needed(facade, vals), ignore, count,
        count_all, by_index, old))
    return (new_outcome, new.lanes), (old_outcome, old.lanes)


def _signed(n_vals, seed, powers=None):
    """(validator set, signed commit) with distinct per-lane timestamps
    whose nanos take 1 to 5 varint bytes."""
    rng = random.Random(seed)
    pvs = [
        MockPV(Ed25519PrivKey.from_seed(rng.randbytes(32)))
        for _ in range(n_vals)
    ]
    vals = ValidatorSet([
        Validator(pv.get_pub_key(),
                  voting_power=(powers[i] if powers else 10))
        for i, pv in enumerate(pvs)
    ])
    by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
    sigs = []
    for idx, val in enumerate(vals.validators):
        vote = Vote(
            msg_type=canonical.PRECOMMIT_TYPE, height=9, round=0,
            block_id=BLOCK_ID,
            timestamp_ns=BASE_NS + rng.choice(
                (3, 200, 70_000, 9_000_000, 800_000_000)) * (idx + 1) % SECOND,
            validator_address=val.address, validator_index=idx,
        )
        by_addr[bytes(val.address)].sign_vote(
            CHAIN_ID, vote, sign_extension=False)
        sigs.append(vote.commit_sig())
    return vals, Commit(9, 0, BLOCK_ID, sigs), by_addr


def _with_lane(commit, idx, **changes):
    sigs = list(commit.signatures)
    sigs[idx] = dataclasses.replace(sigs[idx], **changes)
    return dataclasses.replace(commit, signatures=sigs)


def _nil_lane(commit, idx, by_addr):
    """Lane ``idx`` re-signed as a precommit for nil."""
    cs = commit.signatures[idx]
    vote = Vote(
        msg_type=canonical.PRECOMMIT_TYPE, height=commit.height,
        round=commit.round, block_id=BlockID(), timestamp_ns=cs.timestamp_ns,
        validator_address=cs.validator_address, validator_index=idx,
    )
    by_addr[bytes(cs.validator_address)].sign_vote(
        CHAIN_ID, vote, sign_extension=False)
    return _with_lane(
        commit, idx, block_id_flag=BLOCK_ID_FLAG_NIL,
        signature=vote.signature)


@pytest.mark.parametrize("engine", ["native", "none"])
@pytest.mark.parametrize("seed", [11, 12])
@pytest.mark.parametrize("facade", sorted(WALKS))
def test_verifier_sees_the_per_lane_walks_sequence(
    monkeypatch, facade, seed, engine
):
    """Absent and nil lanes in the commit, unequal powers: the same lanes
    in the same order, and the same early-stop lane; also on a machine
    without the native engine, where the walk goes lane by lane."""
    if engine == "none":
        monkeypatch.setattr(host_batch, "vote_sign_bytes", lambda *a: None)
    else:
        assert host_batch.available()
    rng = random.Random(seed)
    powers = [rng.randrange(1, 40) for _ in range(24)]
    vals, commit, by_addr = _signed(24, seed, powers)
    commit = _with_lane(commit, 2, block_id_flag=BLOCK_ID_FLAG_ABSENT,
                        validator_address=b"", signature=b"",
                        timestamp_ns=proto.ZERO_TIME_NS)
    commit = _nil_lane(commit, 5, by_addr)
    if facade == "verify_commit_light_trusting":
        # another set than the one that signed: a third of it unknown to
        # the commit, in another order
        keep = [v for i, v in enumerate(vals.validators) if i % 3]
        vals = ValidatorSet(
            [Validator(v.pub_key, v.voting_power) for v in keep]
            + [Validator(
                MockPV(Ed25519PrivKey.from_seed(bytes([i]) * 32))
                .get_pub_key(), voting_power=15) for i in range(1, 6)]
        )
    (new_outcome, new_lanes), (old_outcome, old_lanes) = _both_walks(
        monkeypatch, facade, vals, commit)
    assert new_outcome is None and old_outcome is None
    assert new_lanes == old_lanes
    assert len(new_lanes) > 3
    if facade != "verify_commit":
        assert len(new_lanes) < len(vals)  # the walk stopped early


@pytest.mark.parametrize("facade", sorted(WALKS))
def test_shortfall_reads_the_same_got_and_needed(monkeypatch, facade):
    vals, commit, _ = _signed(12, 21)
    for idx in range(1, 12):  # one lane for the block is left
        commit = _with_lane(commit, idx, block_id_flag=BLOCK_ID_FLAG_ABSENT,
                            validator_address=b"", signature=b"",
                            timestamp_ns=proto.ZERO_TIME_NS)
    (new_outcome, new_lanes), (old_outcome, old_lanes) = _both_walks(
        monkeypatch, facade, vals, commit)
    assert new_outcome == old_outcome
    assert new_outcome[:3] == ("not enough", 10, _needed(facade, vals))
    assert new_lanes == old_lanes


def test_double_vote_is_refused_with_the_same_words(monkeypatch):
    vals, commit, _ = _signed(12, 31)
    commit = _with_lane(commit, 3, **{
        f.name: getattr(commit.signatures[1], f.name)
        for f in dataclasses.fields(CommitSig)
    })
    (new_outcome, _), (old_outcome, _) = _both_walks(
        monkeypatch, "verify_commit_light_trusting", vals, commit)
    assert new_outcome == old_outcome
    assert new_outcome == ("refused", "double vote from validator 1 (1 and 3)")


def test_double_vote_past_the_early_stop_is_never_reached(monkeypatch):
    vals, commit, _ = _signed(12, 32)
    commit = _with_lane(commit, 11, **{
        f.name: getattr(commit.signatures[1], f.name)
        for f in dataclasses.fields(CommitSig)
    })
    (new_outcome, new_lanes), (old_outcome, old_lanes) = _both_walks(
        monkeypatch, "verify_commit_light_trusting", vals, commit)
    assert new_outcome is None and old_outcome is None
    assert new_lanes == old_lanes and len(new_lanes) == 5


@pytest.mark.parametrize("where", ["first", "middle", "last"])
@pytest.mark.parametrize("facade", sorted(WALKS))
def test_altered_lane_is_named_by_its_commit_index(facade, where):
    """The real verifier on a commit with one altered signature among the
    verified lanes, and an absent lane before it so that the lane's
    place in the batch is not its index in the commit."""
    vals, commit, _ = _signed(16, 41)
    commit = _with_lane(commit, 1, block_id_flag=BLOCK_ID_FLAG_ABSENT,
                        validator_address=b"", signature=b"",
                        timestamp_ns=proto.ZERO_TIME_NS)
    rec = _Recorder()
    ignore, count, count_all, by_index = WALKS[facade]
    _per_lane_walk(CHAIN_ID, vals, commit, _needed(facade, vals), ignore,
                   count, count_all, by_index, rec)
    sent = [
        i for i, cs in enumerate(commit.signatures)
        if any(cs.signature == lane[2] for lane in rec.lanes)
    ]
    assert len(sent) == len(rec.lanes) >= 5
    idx = {"first": sent[0], "middle": sent[len(sent) // 2],
           "last": sent[-1]}[where]
    sig = bytearray(commit.signatures[idx].signature)
    sig[7] ^= 0x20
    bad = _with_lane(commit, idx, signature=bytes(sig))
    assert _outcome(lambda: _call(facade, vals, bad)) == (
        "refused", f"wrong signature (#{idx}): {bytes(sig).hex()}")
    assert _outcome(lambda: _call(facade, vals, commit)) is None


# --- add_many ---------------------------------------------------------------


def _ed_keys(n):
    return [
        Ed25519PrivKey.from_seed(bytes([i + 1]) * 32).pub_key()
        for i in range(n)
    ]


def _sr_keys(n):
    return [
        Sr25519PrivKey.from_seed(bytes([i + 101]) * 32).pub_key()
        for i in range(n)
    ]


VERIFIERS = {
    "ed25519": (cbatch.Ed25519BatchVerifier, lambda: _ed_keys(5),
                lambda: _sr_keys(1)[0]),
    "sr25519": (cbatch.Sr25519BatchVerifier, lambda: _sr_keys(5),
                lambda: _ed_keys(1)[0]),
    "mixed": (cbatch.MixedBatchVerifier,
              lambda: _ed_keys(3) + _sr_keys(2), lambda: object()),
}


def _held(bv):
    return {
        name: list(getattr(bv, name))
        for name in ("_types", "_pubkeys", "_msgs", "_sigs")
        if hasattr(bv, name)
    }


@pytest.mark.parametrize("kind", sorted(VERIFIERS))
def test_add_many_is_repeated_add(kind):
    cls, keys, _ = VERIFIERS[kind]
    keys = keys()
    # a bytearray and a memoryview are copied to bytes, as add does
    msgs = [b"m0", bytearray(b"m1"), memoryview(b"m2"), b"", b"m4" * 90]
    sigs = [bytes([i]) * 64 for i in range(5)]
    one_by_one, at_once = cls(), cls()
    for triple in zip(keys, msgs, sigs):
        one_by_one.add(*triple)
    at_once.add_many(keys[:2], msgs[:2], sigs[:2])
    at_once.add_many([], [], [])
    at_once.add_many(keys[2:], msgs[2:], sigs[2:])
    assert _held(at_once) == _held(one_by_one)
    assert len(at_once) == len(one_by_one) == 5
    assert all(type(m) is bytes for m in at_once._msgs)


@pytest.mark.parametrize("kind", sorted(VERIFIERS))
def test_add_many_refuses_a_foreign_key_as_add_does(kind):
    cls, keys, foreign = VERIFIERS[kind]
    keys, foreign = keys(), foreign()
    with pytest.raises(TypeError) as by_add:
        cls().add(foreign, b"m", bytes(64))
    bv = cls()
    with pytest.raises(TypeError) as by_add_many:
        bv.add_many(keys[:2] + [foreign], [b"m"] * 3, [bytes(64)] * 3)
    assert str(by_add_many.value) == str(by_add.value)
    assert len(bv) == 0  # checked before any lane is taken


@pytest.mark.parametrize("kind", sorted(VERIFIERS))
def test_add_many_wants_a_message_and_a_signature_per_key(kind):
    cls, keys, _ = VERIFIERS[kind]
    bv = cls()
    with pytest.raises(ValueError):
        bv.add_many(keys(), [b"m"] * 4, [bytes(64)] * 5)
    assert len(bv) == 0


def test_base_class_add_many_loops_over_add():
    rec = _Recorder()
    keys = _ed_keys(3)
    rec.add_many(keys, [b"a", b"b", b"c"], [b"1", b"2", b"3"])
    assert rec.lanes == [
        (keys[0], b"a", b"1"), (keys[1], b"b", b"2"), (keys[2], b"c", b"3")]
