"""ValidatorSet.get_by_address answers from an address index built on the
first lookup: the same answer as upstream's scan (GetByAddress: the first
index, or (-1, None)) after every kind of change to the set, and no map
built by construction, copy or update_with_change_set."""

import pytest

from cometbft_tpu.crypto import Ed25519PrivKey
from cometbft_tpu.types import (
    BLOCK_ID_FLAG_COMMIT,
    BlockID,
    Commit,
    CommitSig,
    Fraction,
    MockPV,
    PartSetHeader,
    PRECOMMIT_TYPE,
    Validator,
    ValidatorSet,
    VerificationError,
    Vote,
    verify_commit_light_trusting,
)
from cometbft_tpu.types import serialization

CHAIN = "addr-index"


def _key(i: int):
    return MockPV(Ed25519PrivKey.from_seed(bytes([i]) * 32)).get_pub_key()


def _set(n: int = 9, seed: int = 1) -> ValidatorSet:
    return ValidatorSet([
        Validator(pub_key=_key(seed + i), voting_power=10 + i % 3)
        for i in range(n)
    ])


def _scan(vals: ValidatorSet, address: bytes):
    for i, v in enumerate(vals.validators):
        if v.address == address:
            return i, v
    return -1, None


def _looked_up(vals: ValidatorSet) -> ValidatorSet:
    vals.get_by_address(vals.validators[0].address)
    assert vals._addr_memo is not None
    return vals


# each case: (the set to ask, whether it must hold no map yet)
def _fresh():
    return _set(), True


def _copy_of_unasked():
    return _set().copy(), True


def _copy_of_asked():
    cp = _looked_up(_set()).copy()
    return cp, False


def _copy_then_swap_in_copy():
    vals = _looked_up(_set())
    cp = vals.copy()
    cp.validators[2] = Validator(pub_key=_key(0x70), voting_power=10)
    # the original's map is the original's: still the scan's answer
    for v in vals.validators:
        assert vals.get_by_address(v.address) == _scan(vals, v.address)
    return cp, False


def _update_adds_removes_powers():
    vals = _looked_up(_set())
    vals.update_with_change_set([
        Validator(pub_key=_key(0x60), voting_power=5),  # added
        Validator(pub_key=_key(0x61), voting_power=30),  # added
        Validator(pub_key=vals.validators[1].pub_key, voting_power=0),
        Validator(pub_key=vals.validators[3].pub_key, voting_power=40),
    ])
    return vals, True


def _replace_in_place():
    vals = _looked_up(_set())
    vals.validators[2] = Validator(pub_key=_key(0x70), voting_power=10)
    return vals, False


def _swap_two_in_place():
    vals = _looked_up(_set())
    v = vals.validators
    v[0], v[4] = v[4], v[0]
    return vals, False


def _append_in_place():
    vals = _looked_up(_set())
    vals.validators.append(Validator(pub_key=_key(0x71), voting_power=10))
    return vals, False


def _remove_in_place():
    vals = _looked_up(_set())
    del vals.validators[0]
    return vals, False


def _address_rewritten():
    vals = _looked_up(_set())
    vals.validators[5].address = bytes(range(20))
    return vals, False


def _repeated_address():
    vals = _looked_up(_set())
    vals.validators[6].address = vals.validators[2].address
    return vals, False


def _proposer_priority_moves():
    vals = _looked_up(_set())
    vals.increment_proposer_priority(5)
    vals.copy_increment_proposer_priority(3)
    return vals, False


def _decoded():
    vals = _looked_up(_set())
    return serialization.loads(serialization.dumps(vals)), True


CASES = [
    _fresh, _copy_of_unasked, _copy_of_asked, _copy_then_swap_in_copy,
    _update_adds_removes_powers, _replace_in_place, _swap_two_in_place,
    _append_in_place, _remove_in_place, _address_rewritten,
    _repeated_address, _proposer_priority_moves, _decoded,
]


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__[1:])
def test_lookup_answers_as_the_scan(case):
    vals, unbuilt = case()
    if unbuilt:
        assert vals._addr_memo is None, "the map was built before a lookup"
    asked = [v.address for v in vals.validators]
    asked += [bytes(20), b"\xff" * 20, _key(0x7F).address()]
    for _round in range(2):  # the kept map answers the second round
        for address in asked:
            got = vals.get_by_address(bytes(address))
            want = _scan(vals, bytes(address))
            assert got[0] == want[0] and got[1] is want[1], address.hex()
            assert vals.has_address(bytes(address)) == (want[0] >= 0)
    assert vals.get_by_address(b"\x01" * 20) == (-1, None)
    index = vals.address_index()
    assert index == {
        a: i for i, a in reversed(list(enumerate(
            v.address for v in vals.validators)))
    }


def test_a_validator_signing_twice_ends_the_walk_by_address():
    """The trusting check looks signers up by address: a validator whose
    signature comes twice ends the walk with upstream's double-vote error
    (types/validation.go verifyCommitBatch), before any lane is verified."""
    pvs = [MockPV(Ed25519PrivKey.from_seed(bytes([i + 1]) * 32))
           for i in range(4)]
    vals = ValidatorSet([Validator(pub_key=pv.get_pub_key(), voting_power=10)
                         for pv in pvs])
    by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
    bid = BlockID(hash=b"\xaa" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\xaa" * 32))
    sigs = []
    for i, v in enumerate(vals.validators[:3]):
        vote = Vote(msg_type=PRECOMMIT_TYPE, height=3, round=0, block_id=bid,
                    timestamp_ns=1_700_000_000_000_000_000 + i,
                    validator_address=v.address, validator_index=i)
        by_addr[v.address].sign_vote(CHAIN, vote, sign_extension=False)
        sigs.append(vote.commit_sig())
    first = sigs[0]
    sigs[2] = CommitSig(BLOCK_ID_FLAG_COMMIT, first.validator_address,
                        first.timestamp_ns, first.signature)
    commit = Commit(height=3, round=0, block_id=bid, signatures=sigs)
    with pytest.raises(VerificationError, match="double vote from validator 0"):
        verify_commit_light_trusting(CHAIN, vals, commit, Fraction(2, 3))
