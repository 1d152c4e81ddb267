"""The storage/wire codec (libs/jsoncodec) writes, byte for byte, what the
generic walk it encoded with before its per-type plans wrote: the walk is
kept here as the oracle, every class registered on the shared codec is
encoded both ways from a seeded instance, and three large encodings carry
pinned SHA-256 digests. Plans never cache a failure, registering empties
them, and the encode time a top-level call adds is counted once."""

import dataclasses
import hashlib
import json
import random
import sys
import threading
from enum import IntEnum
from types import SimpleNamespace

import pytest

from cometbft_tpu.blocksync import messages as bmsgs
from cometbft_tpu.consensus import messages as cmsgs
from cometbft_tpu.consensus import wal as cwal
from cometbft_tpu.consensus.round_state import RoundStep
from cometbft_tpu.crypto.host_batch import MsgColumn
from cometbft_tpu.crypto.keys import Address, Ed25519PubKey
from cometbft_tpu.crypto.merkle import Proof
from cometbft_tpu.crypto.secp256k1 import Secp256k1PubKey
from cometbft_tpu.crypto.sr25519 import Sr25519PubKey
from cometbft_tpu.libs import jsoncodec
from cometbft_tpu.libs.bits import BitArray
from cometbft_tpu.libs.jsoncodec import Codec
from cometbft_tpu.p2p.pex import reactor as pex
from cometbft_tpu.state import indexer
from cometbft_tpu.statesync import messages as smsgs
from cometbft_tpu.abci import types as abci
from cometbft_tpu.types import evidence as ev
from cometbft_tpu.types import params as tparams
from cometbft_tpu.types import serialization as ser
from cometbft_tpu.types.block import (
    Block,
    BlockID,
    BlockMeta,
    Commit,
    CommitSig,
    Data,
    ExtendedCommit,
    ExtendedCommitSig,
    Header,
    PartSetHeader,
    Version,
)
from cometbft_tpu.types.light_block import LightBlock, SignedHeader
from cometbft_tpu.types.part_set import Part
from cometbft_tpu.types.validator_set import Validator, ValidatorSet
from cometbft_tpu.types.vote import Proposal, Vote

CODEC = ser.codec
assert cwal.wal_codec is CODEC  # the WAL's records nest the types' own


# -- the oracle: the generic walk, one type test after another per value --


def oracle_encode(codec, v):
    adapter = codec._adapters_by_cls.get(type(v))
    if adapter is not None:
        tag, enc, _ = adapter
        return {"__a": tag, "v": oracle_encode(codec, enc(v))}
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        name = type(v).__name__
        if name not in codec._types:
            raise TypeError(f"unregistered dataclass {name}")
        d = {"__t": name}
        for f in dataclasses.fields(v):
            if f.name.startswith("_"):
                continue
            d[f.name] = oracle_encode(codec, getattr(v, f.name))
        return d
    if isinstance(v, bytes):
        return {"__b": v.hex()}
    if isinstance(v, bool) or v is None:
        return v
    if isinstance(v, IntEnum):
        return int(v)
    if isinstance(v, (int, float, str)):
        return v
    if isinstance(v, (list, tuple)):
        return [oracle_encode(codec, x) for x in v]
    if isinstance(v, dict):
        return {
            "__d": [
                [oracle_encode(codec, k), oracle_encode(codec, x)]
                for k, x in v.items()
            ]
        }
    raise TypeError(f"cannot encode {type(v).__name__}")


def oracle_dumps(codec, v) -> bytes:
    return json.dumps(
        oracle_encode(codec, v), separators=(",", ":")
    ).encode()


# -- seeded instances ------------------------------------------------------


def _b(r, n):
    return r.randbytes(n)


def _bid(r):
    return BlockID(_b(r, 32), PartSetHeader(r.randrange(1, 40), _b(r, 32)))


def _sig(r, flag):
    if flag == 1:  # absent: every field at its zero
        return CommitSig()
    return CommitSig(
        flag, _b(r, 20), 1_700_000_000_000_000_000 + r.getrandbits(40),
        _b(r, 64),
    )


def _commit(r, n=7):
    flags = [r.choice((1, 2, 2, 2, 3)) for _ in range(n)]
    return Commit(
        height=r.randrange(2, 10**6), round=r.randrange(3),
        block_id=_bid(r), signatures=[_sig(r, f) for f in flags],
    )


def _header(r, height=None):
    return Header(
        version=Version(11, r.randrange(5)), chain_id="chain-" + _b(r, 4).hex(),
        height=height or r.randrange(2, 10**6),
        time_ns=1_700_000_000_000_000_000 + r.getrandbits(40),
        last_block_id=_bid(r), last_commit_hash=_b(r, 32),
        data_hash=_b(r, 32), validators_hash=_b(r, 32),
        next_validators_hash=_b(r, 32), consensus_hash=_b(r, 32),
        app_hash=_b(r, 32), last_results_hash=_b(r, 32),
        evidence_hash=b"", proposer_address=_b(r, 20),
    )


def _block(r, n_sigs=7, n_txs=3):
    return Block(
        header=_header(r), data=Data(txs=[_b(r, 40) for _ in range(n_txs)]),
        evidence=[], last_commit=_commit(r, n_sigs),
    )


def _key(r, i):
    kind = i % 3
    if kind == 0:
        return Ed25519PubKey(_b(r, 32))
    if kind == 1:
        return Sr25519PubKey(_b(r, 32))
    return Secp256k1PubKey(b"\x02" + _b(r, 32))


def _valset(r, n=5, kinds=3):
    return ValidatorSet([
        Validator(_key(r, i % kinds), r.randrange(1, 1000)) for i in range(n)
    ])


def _vote(r, ext=False):
    return Vote(
        msg_type=r.choice((1, 2)), height=r.randrange(1, 10**6),
        round=r.randrange(3), block_id=_bid(r),
        timestamp_ns=1_700_000_000_000_000_000 + r.getrandbits(40),
        validator_address=_b(r, 20), validator_index=r.randrange(175),
        signature=_b(r, 64),
        extension=_b(r, 2048) if ext else b"",
        extension_signature=_b(r, 64) if ext else b"",
    )


def _bits(r, n=37):
    return BitArray.from_indices(n, [i for i in range(n) if r.random() < 0.4])


def _proof(r):
    return Proof(total=4, index=r.randrange(4), leaf_hash=_b(r, 32),
                 aunts=[_b(r, 32) for _ in range(2)])


def _exec_result(r):
    return abci.ExecTxResult(
        code=r.randrange(3), data=_b(r, 8), log="ok", info="",
        gas_wanted=r.randrange(100), gas_used=r.randrange(100),
        events=[abci.Event(type="transfer", attributes=[
            abci.EventAttribute(key="amount", value="12", index=True),
            abci.EventAttribute(key="memo", value="", index=False),
        ])],
        codespace="",
    )


def _ext_commit(r):
    c = _commit(r, 5)
    return ExtendedCommit(
        height=c.height, round=c.round, block_id=c.block_id,
        extended_signatures=[
            ExtendedCommitSig(
                cs, _b(r, 16) if cs.block_id_flag == 2 else b"",
                _b(r, 64) if cs.block_id_flag == 2 else b"",
            )
            for cs in c.signatures
        ],
    )


def _light_block(r):
    return LightBlock(SignedHeader(_header(r), _commit(r)), _valset(r))


# one seeded instance of every class registered on the shared codec
REGISTERED = {
    "Proof": _proof,
    "PartSetHeader": lambda r: PartSetHeader(r.randrange(1, 9), _b(r, 32)),
    "BlockID": _bid,
    "Version": lambda r: Version(11, r.randrange(9)),
    "Header": _header,
    "CommitSig": lambda r: _sig(r, 2),
    "Commit": _commit,
    "Data": lambda r: Data(txs=[_b(r, 30), b""]),
    "Block": _block,
    "BlockMeta": lambda r: BlockMeta(_bid(r), r.randrange(10**6), _header(r),
                                     r.randrange(50)),
    "ExtendedCommitSig": lambda r: ExtendedCommitSig(
        _sig(r, 2), _b(r, 20), _b(r, 64)),
    "ExtendedCommit": _ext_commit,
    "Part": lambda r: Part(r.randrange(4), _b(r, 300), _proof(r)),
    "Vote": lambda r: _vote(r, ext=True),
    "Proposal": lambda r: Proposal(
        height=r.randrange(1, 10**6), round=1, pol_round=-1,
        block_id=_bid(r), timestamp_ns=r.getrandbits(60),
        signature=_b(r, 64)),
    "Validator": lambda r: Validator(_key(r, 1), r.randrange(1, 99), -7),
    "BlockParams": lambda r: tparams.BlockParams(r.randrange(1, 10**7), -1),
    "EvidenceParams": lambda r: tparams.EvidenceParams(
        r.randrange(1, 10**5), r.getrandbits(50), r.randrange(10**6)),
    "ValidatorParams": lambda r: tparams.ValidatorParams(
        ("ed25519", "sr25519")),
    "VersionParams": lambda r: tparams.VersionParams(r.randrange(9)),
    "ABCIParams": lambda r: tparams.ABCIParams(r.randrange(9)),
    "ConsensusParams": lambda r: tparams.ConsensusParams(),
    "DuplicateVoteEvidence": lambda r: ev.DuplicateVoteEvidence(
        _vote(r), _vote(r), r.randrange(10**4), r.randrange(100),
        r.getrandbits(60)),
    "LightClientAttackEvidence": lambda r: ev.LightClientAttackEvidence(
        _light_block(r), r.randrange(1, 99),
        [Validator(_key(r, 0), 5)], r.randrange(10**4), r.getrandbits(60)),
    "SignedHeader": lambda r: SignedHeader(_header(r), _commit(r)),
    "LightBlock": _light_block,
    "Event": lambda r: _exec_result(r).events[0],
    "EventAttribute": lambda r: abci.EventAttribute("k", "v", True),
    "ExecTxResult": _exec_result,
    "EndHeightMessage": lambda r: cwal.EndHeightMessage(r.randrange(10**6)),
    "MsgInfo": lambda r: cwal.MsgInfo(cmsgs.VoteMessage(_vote(r)), "peer-1"),
    "TimeoutInfo": lambda r: cwal.TimeoutInfo(
        r.random(), r.randrange(10**6), 0, RoundStep.PREVOTE_WAIT),
    "ProposalMessage": lambda r: cmsgs.ProposalMessage(
        REGISTERED["Proposal"](r)),
    "BlockPartMessage": lambda r: cmsgs.BlockPartMessage(
        r.randrange(10**6), 0, REGISTERED["Part"](r)),
    "VoteMessage": lambda r: cmsgs.VoteMessage(_vote(r)),
    "NewRoundStepMessage": lambda r: cmsgs.NewRoundStepMessage(
        r.randrange(10**6), 0, RoundStep.PRECOMMIT, 3, 0),
    "NewValidBlockMessage": lambda r: cmsgs.NewValidBlockMessage(
        r.randrange(10**6), 1, PartSetHeader(3, _b(r, 32)), _bits(r, 3),
        False),
    "ProposalPOLMessage": lambda r: cmsgs.ProposalPOLMessage(
        r.randrange(10**6), 0, _bits(r)),
    "HasVoteMessage": lambda r: cmsgs.HasVoteMessage(
        r.randrange(10**6), 0, 2, r.randrange(175)),
    "VoteSetMaj23Message": lambda r: cmsgs.VoteSetMaj23Message(
        r.randrange(10**6), 0, 1, _bid(r)),
    "VoteSetBitsMessage": lambda r: cmsgs.VoteSetBitsMessage(
        r.randrange(10**6), 0, 2, _bid(r), _bits(r)),
    "PexRequestMessage": lambda r: pex.PexRequestMessage(),
    "PexAddrsMessage": lambda r: pex.PexAddrsMessage(
        [f"{_b(r, 20).hex()}@10.0.0.{i}:26656" for i in range(3)]),
    "TxRecord": lambda r: indexer.TxRecord(
        r.randrange(10**6), 0, _b(r, 50), _exec_result(r), _b(r, 32)),
    "SnapshotsRequestMessage": lambda r: smsgs.SnapshotsRequestMessage(),
    "SnapshotsResponseMessage": lambda r: smsgs.SnapshotsResponseMessage(
        r.randrange(10**6), 1, 4, _b(r, 32), _b(r, 10)),
    "ChunkRequestMessage": lambda r: smsgs.ChunkRequestMessage(
        r.randrange(10**6), 1, 2),
    "ChunkResponseMessage": lambda r: smsgs.ChunkResponseMessage(
        r.randrange(10**6), 1, 2, _b(r, 100), False),
    "StatusRequestMessage": lambda r: bmsgs.StatusRequestMessage(),
    "StatusResponseMessage": lambda r: bmsgs.StatusResponseMessage(
        r.randrange(10**6), 1),
    "BlockRequestMessage": lambda r: bmsgs.BlockRequestMessage(
        r.randrange(10**6)),
    "BlockResponseMessage": lambda r: bmsgs.BlockResponseMessage(
        _block(r), _ext_commit(r)),
    "NoBlockResponseMessage": lambda r: bmsgs.NoBlockResponseMessage(
        r.randrange(10**6)),
}


class _Items(list):
    """A list subclass: encodes as the list it is."""


class _Text(str):
    pass


def block4096(r):
    """A synced block of the mixed 4,096-validator chain's shape: its
    LastCommit holds 4,096 slots, commits, nils and absents mixed."""
    return _block(r, n_sigs=4096, n_txs=8)


def valset4096(r):
    """A mixed ed25519 + sr25519 4,096-validator set, proposer chosen."""
    return _valset(r, n=4096, kinds=2)


def wal_vote_record(r):
    """One framed WAL record of a peer's vote with its extension."""
    return cwal.WAL._frame(
        cwal.MsgInfo(cmsgs.VoteMessage(_vote(r, ext=True)), "peer-7"))


# values beyond one instance a class: the adapters, the type tests' edges
EDGES = {
    "block4096": block4096,
    "valset4096": valset4096,
    "key_ed25519": lambda r: _key(r, 0),
    "key_sr25519": lambda r: _key(r, 1),
    "key_secp256k1": lambda r: _key(r, 2),
    "valset_proposer": _valset,
    "valset_no_proposer": lambda r: ValidatorSet([]),
    "bit_array": _bits,
    "bit_array_empty": lambda r: BitArray(0),
    "commit_sig_absent": lambda r: CommitSig(),
    "commit_sig_nil": lambda r: _sig(r, 3),
    "int_enum": lambda r: RoundStep.COMMIT,
    "bool_and_int": lambda r: [True, 1, False, 0],
    "none": lambda r: None,
    "tuple": lambda r: (1, _b(r, 3), ("x", None)),
    "dict": lambda r: {_b(r, 4): [1, 2], "k": {3: None}, 5: _bid(r)},
    "list_subclass": lambda r: _Items([_b(r, 2), 7, _Items()]),
    "str_subclass": lambda r: _Text("tx"),
    "address": lambda r: Address(_b(r, 20)),
    "float": lambda r: [r.random(), -0.0, 1e300],
    "text": lambda r: ["", "é \"\\", "\x00"],
    "empty": lambda r: [[], (), {}, b""],
    "big_int": lambda r: [2**70, -(2**63)],
}

CASES = sorted(REGISTERED) + sorted(EDGES)
_BUILT: dict = {}


def sample(name: str):
    """The case's value, built once from a seed of its own."""
    if name not in _BUILT:
        build = REGISTERED.get(name) or EDGES[name]
        seed = int.from_bytes(hashlib.sha256(name.encode()).digest()[:8])
        _BUILT[name] = build(random.Random(seed))
    return _BUILT[name]


def test_every_registered_class_has_a_case():
    assert set(CODEC._types) == set(REGISTERED)
    adapted = {type(sample(n)) for n in EDGES}
    assert set(CODEC._adapters_by_cls) <= adapted


@pytest.mark.parametrize("name", CASES)
def test_dumps_is_the_walks_bytes(name):
    v = sample(name)
    want = oracle_dumps(CODEC, v)
    assert CODEC.dumps(v) == want
    assert CODEC.dumps(v) == want  # again, every plan now built
    assert json.dumps(CODEC.encode(v), separators=(",", ":")).encode() == want


def _same(a, b) -> bool:
    """Equal by value: a ValidatorSet by its validators and proposer, a
    dataclass field by field as its own __eq__ would (ValidatorSet has
    none, so a dataclass holding one is walked)."""
    if isinstance(a, ValidatorSet):
        return (isinstance(b, ValidatorSet)
                and _same(a.validators, b.validators)
                and _same(a.proposer, b.proposer))
    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        return type(a) is type(b) and all(
            _same(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a) if f.compare
        )
    if isinstance(a, (list, tuple)):
        return (type(a) is type(b) or isinstance(a, list)) and len(a) == len(
            b) and all(map(_same, a, b))
    return a == b


@pytest.mark.parametrize(
    "name", [n for n in CASES if n not in ("tuple", "str_subclass")]
)
def test_loads_gives_back_the_value(name):
    """Tuples outside a tuple-hinted field come back as lists, as they
    always did; those two cases compare their re-encodings instead."""
    v = sample(name)
    assert _same(CODEC.loads(CODEC.dumps(v)), v)


@pytest.mark.parametrize("name", ["tuple", "str_subclass"])
def test_loads_gives_back_the_encoding(name):
    v = sample(name)
    assert CODEC.dumps(CODEC.loads(CODEC.dumps(v))) == CODEC.dumps(v)


# SHA-256 of each encoding as the codec wrote it before it kept plans
PINNED = {
    "block4096":
        "10983c7c4506a3c317fb99754723947600e74f53dc5a0acf9fb167bd101b627a",
    "valset4096":
        "d34a0de4e8f6e2d9358fb480baddd8b172fd2a8d411005cdcf1f3c0b2f05fe39",
    "wal_vote_record":
        "837f2c6b47caf3c8dc3b1730560bdcfb77d3231de2beb9f7d661a855a0b7902e",
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_pinned_digest(name):
    build = {"block4096": block4096, "valset4096": valset4096,
             "wal_vote_record": wal_vote_record}[name]
    seed = int.from_bytes(hashlib.sha256(name.encode()).digest()[:8])
    v = build(random.Random(seed))
    raw = v if isinstance(v, bytes) else CODEC.dumps(v)
    assert hashlib.sha256(raw).hexdigest() == PINNED[name]


# -- failures are never cached; registering empties the plans -------------


@dataclasses.dataclass
class _Loose:
    a: int
    b: bytes = b""
    _cache: object = None


@dataclasses.dataclass
class _Holder:
    inner: object


@pytest.mark.parametrize("call", ["encode", "dumps"])
@pytest.mark.parametrize("value", [
    _Loose(1), [1, _Loose(2)], object(), {1, 2},
    MsgColumn.joined([b"ab", b"c"]), bytearray(b"x"), _Loose,
], ids=["dataclass", "nested", "object", "set", "msg_column", "bytearray",
        "class"])
def test_what_cannot_be_encoded_raises_every_time(call, value):
    c = Codec()
    c.register(_Holder)
    with pytest.raises(TypeError) as want:
        oracle_encode(c, value)
    for _ in range(2):
        with pytest.raises(TypeError) as got:
            getattr(c, call)(value)
        assert str(got.value) == str(want.value)
    with pytest.raises(TypeError):
        c.dumps(_Holder(value))


@pytest.mark.parametrize("call", ["encode", "dumps"])
def test_register_after_a_failed_encode(call):
    c = Codec()
    with pytest.raises(TypeError):
        getattr(c, call)(_Loose(1, b"\x01"))
    c.register(_Loose)
    got = getattr(c, call)(_Loose(1, b"\x01", "kept out"))
    want = {"__t": "_Loose", "a": 1, "b": {"__b": "01"}}
    assert got == (json.dumps(want, separators=(",", ":")).encode()
                   if call == "dumps" else want)


def test_register_adapter_replaces_a_built_plan():
    c = Codec()
    c.register(_Loose)
    assert c.encode(_Loose(3)) == {"__t": "_Loose", "a": 3, "b": {"__b": ""}}
    c.register_adapter(_Loose, "loose", lambda x: x.a, lambda a: _Loose(a))
    assert c.encode(_Loose(3)) == {"__a": "loose", "v": 3}
    assert c.encode([_Loose(4)]) == oracle_encode(c, [_Loose(4)])


def test_a_same_named_class_encodes_as_the_walk_does():
    """The walk asked for a registered NAME; so does the plan."""
    c = Codec()
    c.register(_Loose)
    Other = dataclasses.make_dataclass("_Loose", [("z", int)])
    assert c.dumps(Other(9)) == oracle_dumps(c, Other(9))


# -- the encode-time counter ------------------------------------------------


def _ticking(monkeypatch, step=1000):
    """jsoncodec's clock, advanced ``step`` ns a reading on each thread
    that called ``mine()``; still on every other thread, so an encode
    elsewhere in the process adds nothing. Returns ``mine``."""
    local = threading.local()

    def perf_counter_ns():
        if not getattr(local, "mine", False):
            return 0
        local.t += step
        return local.t

    def mine():
        local.mine, local.t = True, 0

    monkeypatch.setattr(jsoncodec, "time",
                        SimpleNamespace(perf_counter_ns=perf_counter_ns))
    mine()
    return mine


@pytest.mark.parametrize("call", ["encode", "dumps"])
@pytest.mark.parametrize("name", ["Block", "LightBlock", "MsgInfo"])
def test_one_top_level_call_adds_one_calls_time(monkeypatch, call, name):
    v = sample(name)
    _ticking(monkeypatch)
    before = jsoncodec.encode_ns()
    getattr(CODEC, call)(v)
    assert jsoncodec.encode_ns() - before == 1000


def test_a_failed_encode_still_counts_its_time(monkeypatch):
    _ticking(monkeypatch, step=7)
    before = jsoncodec.encode_ns()
    with pytest.raises(TypeError):
        CODEC.dumps([1, object()])
    assert jsoncodec.encode_ns() - before == 7


def test_threads_that_encode_at_once_lose_no_time(monkeypatch):
    """The sum takes no lock: 8 threads encoding at once, the interpreter
    handing its lock over every microsecond, add every call's time."""
    mine = _ticking(monkeypatch)
    v = sample("Commit")
    n_threads, calls = 8, 1500

    def work():
        mine()
        for _ in range(calls):
            CODEC.dumps(v)

    before = jsoncodec.encode_ns()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert jsoncodec.encode_ns() - before == 1000 * n_threads * calls
