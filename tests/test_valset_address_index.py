"""ValidatorSet.get_by_address answers from an address index built on the
first lookup: the same answer as upstream's scan (GetByAddress: the first
index, or (-1, None)) after every kind of change to the set, and no map
built by construction, copy or update_with_change_set.

A commit check's batch backend (crypto/batch.supports_commit_batch and
create_commit_batch_verifier) is picked from the key types the set keeps
behind a witness of its keys (ValidatorSet.key_types): the same answer as
a scan of every key after every kind of change, and the same verdicts,
errors and routes from a set checked many times as from a fresh one."""

from dataclasses import dataclass
from types import SimpleNamespace

import pytest

from cometbft_tpu.crypto import Ed25519PrivKey
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.types import (
    BLOCK_ID_FLAG_COMMIT,
    BlockID,
    Commit,
    CommitSig,
    Fraction,
    MockPV,
    NotEnoughVotingPowerError,
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


# --- the key types that pick a commit check's backend ----------------------


@dataclass(frozen=True)
class _UnbatchableKey:
    """A key of a type that no batch backend takes."""

    data: bytes
    type = "unbatchable"

    def address(self) -> bytes:
        return self.data[:20]


def _sr_key(i: int):
    from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey

    return Sr25519PrivKey.from_seed(bytes([i]) * 32).pub_key()


def _mixed_set() -> ValidatorSet:
    return ValidatorSet(
        [Validator(pub_key=_key(1 + i), voting_power=10) for i in range(4)]
        + [Validator(pub_key=_sr_key(0x40 + i), voting_power=10)
           for i in range(3)]
    )


def _asked(vals: ValidatorSet) -> ValidatorSet:
    vals.key_types()
    assert vals._types_memo is not None
    return vals


_ED = "Ed25519BatchVerifier"
_SR = "Sr25519BatchVerifier"
_MIXED = "MixedBatchVerifier"


def _refused(types: str) -> str:
    return f"batch verification unsupported for key types {types}"


# each case: (the set to ask, whether its next read must scan the types,
# the backend the factory gives or the factory's ValueError)
def _ed_only():
    return _set(), True, _ED


def _ed_and_sr():
    return _mixed_set(), True, _MIXED


def _sr_only():
    return ValidatorSet([Validator(pub_key=_sr_key(0x50 + i),
                                   voting_power=10) for i in range(3)]
                        ), True, _SR


def _unbatchable():
    vals = ValidatorSet([
        Validator(pub_key=_UnbatchableKey(bytes([i]) * 32), voting_power=10)
        for i in range(1, 4)
    ])
    return vals, True, _refused("['unbatchable']")


def _unbatchable_beside_ed():
    vals = _set()
    vals.validators.append(Validator(
        pub_key=_UnbatchableKey(b"\x01" * 32), voting_power=10))
    return vals, True, _refused("['ed25519', 'unbatchable']")


def _asked_again():
    return _asked(_set()), False, _ED


def _key_replaced_in_place():
    vals = _asked(_set())
    vals.validators[2].pub_key = _sr_key(0x60)
    return vals, True, _MIXED


def _key_replaced_by_an_equal_key():
    vals = _asked(_set())
    old = vals.validators[2].pub_key
    vals.validators[2].pub_key = type(old)(bytes(old.data))
    return vals, False, _ED


def _validator_appended():
    vals = _asked(_set())
    vals.validators.append(Validator(pub_key=_sr_key(0x61), voting_power=1))
    return vals, True, _MIXED


def _validators_removed():
    vals = _asked(_mixed_set())
    vals.validators[:] = [v for v in vals.validators
                          if v.pub_key.type == "ed25519"]
    return vals, True, _ED


def _validators_reordered():
    vals = _asked(_mixed_set())
    v = vals.validators
    v[0], v[-1] = v[-1], v[0]
    return vals, True, _MIXED


def _emptied():
    vals = _asked(_set())
    vals.validators.clear()
    return vals, True, _refused("[]")


def _updated_with_change_set():
    vals = _asked(_set())
    vals.update_with_change_set([
        Validator(pub_key=_sr_key(0x62), voting_power=5),
        Validator(pub_key=vals.validators[1].pub_key, voting_power=0),
    ])
    assert vals._types_memo is None
    return vals, True, _MIXED


def _copy_of_asked():
    return _asked(_mixed_set()).copy(), False, _MIXED


def _copy_of_unasked():
    return _mixed_set().copy(), True, _MIXED


def _copy_then_key_replaced_in_copy():
    vals = _asked(_set())
    cp = vals.copy()
    cp.validators[0].pub_key = _sr_key(0x63)
    # the original's keys are the original's: its profile still holds
    assert crypto_batch.create_commit_batch_verifier(vals).__class__ \
        .__name__ == _ED
    return cp, True, _MIXED


def _decoded():
    vals = _asked(_mixed_set())
    back = serialization.loads(serialization.dumps(vals))
    assert back._types_memo is None
    return back, True, _MIXED


KEY_TYPE_CASES = [
    _ed_only, _ed_and_sr, _sr_only, _unbatchable, _unbatchable_beside_ed,
    _asked_again, _key_replaced_in_place, _key_replaced_by_an_equal_key,
    _validator_appended, _validators_removed, _validators_reordered,
    _emptied, _updated_with_change_set, _copy_of_asked, _copy_of_unasked,
    _copy_then_key_replaced_in_copy, _decoded,
]


@pytest.fixture
def key_type_counts():
    """{result: calls} of types_valset_key_types_total, on a registry of
    this test's own."""
    m = libmetrics.NodeMetrics()
    libmetrics.push_node_metrics(m)

    def read():
        return {
            r: int(m.valset_key_types_total.labels(r).value())
            for r in ("computed", "reused")
        }

    yield read
    libmetrics.pop_node_metrics(m)


def _backend(vals) -> str:
    try:
        return type(crypto_batch.create_commit_batch_verifier(vals)).__name__
    except ValueError as e:
        return str(e)


@pytest.mark.parametrize("case", KEY_TYPE_CASES, ids=lambda c: c.__name__[1:])
def test_backend_answers_as_the_scan(case, key_type_counts):
    vals, must_scan, want = case()
    # not a ValidatorSet: the same validators, scanned on every call
    plain = SimpleNamespace(validators=list(vals.validators))
    before = key_type_counts()
    for _round in range(2):  # the kept profile answers the second round
        for asked in (vals, plain):
            assert crypto_batch.supports_commit_batch(asked) == (
                want in (_ED, _SR, _MIXED))
            assert _backend(asked) == want
    after = key_type_counts()
    computed = after["computed"] - before["computed"]
    assert computed == int(must_scan)
    assert after["reused"] - before["reused"] == 4 - computed
    assert vals.key_types() == frozenset(
        getattr(v.pub_key, "type", None) for v in vals.validators)


# --- verdicts at width: a set checked again and again ----------------------

_N = 1000
_TS = 1_700_000_000_000_000_000


@pytest.fixture(scope="module")
def wide_chain():
    """2,000 ed25519 signers, a trusted set of the first 1,000, and the
    signed commits of three 1,000-validator sets that share 0, 300 and
    400 validators with it."""
    pvs = [MockPV(Ed25519PrivKey.from_seed(i.to_bytes(32, "big")))
           for i in range(1, 2 * _N + 1)]
    by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
    trusted = [Validator(pub_key=pv.get_pub_key(), voting_power=10)
               for pv in pvs[:_N]]
    bid = BlockID(hash=b"\xbb" * 32,
                  part_set_header=PartSetHeader(total=1, hash=b"\xbb" * 32))
    commits = {}
    for shared, lo in (("none", _N), ("300", _N - 300), ("400", _N - 400)):
        signers = ValidatorSet([
            Validator(pub_key=pv.get_pub_key(), voting_power=10)
            for pv in pvs[lo:lo + _N]
        ])
        sigs = []
        for i, v in enumerate(signers.validators):
            vote = Vote(msg_type=PRECOMMIT_TYPE, height=7, round=0,
                        block_id=bid, timestamp_ns=_TS + i,
                        validator_address=v.address, validator_index=i)
            by_addr[v.address].sign_vote(CHAIN, vote, sign_extension=False)
            sigs.append(vote.commit_sig())
        commits[shared] = Commit(height=7, round=0, block_id=bid,
                                 signatures=sigs)
    return trusted, commits


def _trusted_lanes(vals: ValidatorSet, commit: Commit) -> list[int]:
    index = vals.address_index()
    return [i for i, cs in enumerate(commit.signatures)
            if cs.validator_address in index]


def _scenarios(vals: ValidatorSet, commits: dict) -> dict:
    """name -> (commit, the error the trusting check must raise or None)."""
    needed = 10 * _N // 3
    ok = commits["400"]
    lanes = _trusted_lanes(vals, ok)
    # a validator's second signature inside the walk's cut
    first, dup_at = lanes[0], lanes[4]
    double = Commit(height=7, round=0, block_id=ok.block_id,
                    signatures=list(ok.signatures))
    double.signatures[dup_at] = ok.signatures[first]
    val_idx = vals.address_index()[ok.signatures[first].validator_address]
    # a counted lane's signature altered
    bad_at = lanes[9]
    bad = Commit(height=7, round=0, block_id=ok.block_id,
                 signatures=list(ok.signatures))
    sig = ok.signatures[bad_at]
    altered = sig.signature[:-1] + bytes([sig.signature[-1] ^ 1])
    bad.signatures[bad_at] = CommitSig(
        BLOCK_ID_FLAG_COMMIT, sig.validator_address, sig.timestamp_ns,
        altered)
    return {
        "disjoint": (commits["none"], NotEnoughVotingPowerError(
            got=0, needed=needed)),
        "shares_300": (commits["300"], NotEnoughVotingPowerError(
            got=3000, needed=needed)),
        "double_vote": (double, VerificationError(
            f"double vote from validator {val_idx} ({first} and {dup_at})")),
        "wrong_signature": (bad, VerificationError(
            f"wrong signature (#{bad_at}): {altered.hex()}")),
        "passes": (ok, None),
    }


def _outcome(vals, commit, made: list):
    """(error class, message, got/needed, backend, its route) of one
    trusting check."""
    made.clear()
    err = None
    try:
        verify_commit_light_trusting(CHAIN, vals, commit, Fraction(1, 3))
    except VerificationError as e:
        err = e
    (bv,) = made
    return (type(err), str(err), getattr(err, "got", None),
            getattr(err, "needed", None), type(bv).__name__, bv.route)


def test_trusting_verdicts_and_routes_hold_on_a_set_checked_again(
        wide_chain, key_type_counts, monkeypatch):
    """At 1,000 validators, checks refused for trust, a double vote, a
    wrong signature named by index and a check that passes give the same
    errors, tallies, backends and routes, three rounds over, on one set
    object as on a fresh set each time; the one set scans its key types
    once."""
    validators, commits = wide_chain
    made: list = []
    factory = crypto_batch.create_commit_batch_verifier

    def recording(vals):
        bv = factory(vals)
        made.append(bv)
        return bv

    monkeypatch.setattr(crypto_batch, "create_commit_batch_verifier",
                        recording)
    kept = ValidatorSet(validators)
    scenarios = _scenarios(kept, commits)
    before = key_type_counts()
    seen = {name: [_outcome(kept, commit, made) for _round in range(3)]
            for name, (commit, _want) in scenarios.items()}
    after = key_type_counts()
    assert after["computed"] - before["computed"] == 1
    assert after["reused"] - before["reused"] == 2 * 3 * len(scenarios) - 1
    for name, (commit, want) in scenarios.items():
        fresh = _outcome(ValidatorSet(validators), commit, made)
        assert seen[name] == [fresh] * 3, name
        assert fresh[4] == _ED, name
        if want is None:
            assert fresh[0] is type(None) and fresh[5] is not None, name
            continue
        assert fresh[:2] == (type(want), str(want)), name
        if isinstance(want, NotEnoughVotingPowerError):
            assert fresh[2:4] == (want.got, want.needed), name
            assert fresh[5] is None, name  # refused before any lane ran


def test_key_types_reuse_metric_reads_the_counter(key_type_counts):
    """key_types_reuse_pct.bisect through the benchmark's own snapshot,
    delta, reader and cell loading: three checks of one set read its key
    types six times and scan them once; a window without a check, or a
    program without the series, has nothing to read."""
    from benchmark.harness import counters, spec
    from benchmark.readers import counter_ratio

    name = "key_types_reuse_pct.bisect"
    cell = spec.load_cell("rot10k-bisect")
    (metric,) = [m for m in cell.per_layer if m["name"] == name]
    assert (metric["layer"], metric["moves"], metric["workloads"],
            metric["source"]) == ("batch dispatch and crossover",
                                  "sigs_per_s", ["rot10k-bisect"],
                                  "program_counter")
    vals = _set()
    before = counters.snapshot()
    for _check in range(3):
        crypto_batch.supports_commit_batch(vals)
        crypto_batch.create_commit_batch_verifier(vals)
    after = counters.snapshot()
    window = SimpleNamespace(counters=counters.delta(before, after))
    assert counter_ratio.read(metric, window) == pytest.approx(500 / 6)
    idle = SimpleNamespace(counters=counters.delta(after, after))
    assert counter_ratio.read(metric, idle) is None
    parent_like = {k: v for k, v in window.counters.items()
                   if "valset_key_types" not in k}
    assert counter_ratio.read(
        metric, SimpleNamespace(counters=parent_like)) is None
