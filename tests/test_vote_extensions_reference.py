"""Vote extensions on the consensus path, against the benchmark's plain
reference (benchmark/reference/vote_ext_ref: own CanonicalVoteExtension
encoder, the ``cryptography`` oracle, nothing of the program): a node that
is no validator follows one scripted height of a 16-validator chain with
``abci.vote_extensions_enable_height = 1`` through its receive routine's
drains. Sound votes, altered vote signatures, altered extension
signatures, missing extension signatures and second copies, before and
after the commit, with the drains on either side of the verify cut.

Beside it: ``_add_vote``'s pre-app check reads the drain's memo (a hit
submits no lone coalescer ticket, ``False`` never reaches the
application, no entry verifies singly); a vote's extension sign-bytes are
encoded once; the genesis file carries ``consensus_params``.
"""

from __future__ import annotations

import json
import os
import random

import pytest

import helpers
from benchmark.drivers import adapters, vote_ext_script
from benchmark.harness import chain as rawchain
from benchmark.reference import ed25519_oracle as oracle
from benchmark.reference import vote_ext_ref as ref
from cometbft_tpu.consensus import RoundStep, TimeoutInfo
from cometbft_tpu.consensus.messages import (
    BlockPartMessage, ProposalMessage, VoteMessage,
)
from cometbft_tpu.consensus.state import EVENT_VOTE
from cometbft_tpu.consensus.wal import MsgInfo
from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import coalesce as ccoalesce
from cometbft_tpu.crypto import host_batch
from cometbft_tpu.crypto.keys import Ed25519PubKey
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import trace as libtrace
from cometbft_tpu.libs.metrics import NodeMetrics
from cometbft_tpu.ops import verify as ov
from cometbft_tpu.types import GenesisDoc, GenesisValidator, canonical
from cometbft_tpu.types import serialization as ser
from cometbft_tpu.types.block import BlockID
from cometbft_tpu.types.params import ABCIParams, ConsensusParams
from cometbft_tpu.types.part_set import PartSet
from cometbft_tpu.types.vote import Proposal, Vote, VoteError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAIN_ID = "ext-ref-chain"
N_VALS, EXT_BYTES = 16, 96
PREVOTE, PRECOMMIT = ref.PREVOTE, ref.PRECOMMIT
NEW_HEIGHT_TOCK = TimeoutInfo(0.0, 1, 0, int(RoundStep.NEW_HEIGHT))
EXT_METRICS = [
    "device_lane_pct.ext", "preverify_lanes_per_drain.ext",
    "ext_sig_memo_hit_pct.ext", "ext_verify_ms_per_height",
    "ext_sign_bytes_ms_per_height", "preverify_ms_per_height.ext",
    "vote_admit_ms_per_height.ext", "vote_queue_wait_ms_per_vote.ext",
    "reactor_receive_ms_per_vote.ext", "wal_write_ms_per_height.ext",
    "vote_span_coverage_pct.ext",
]


@pytest.fixture
def metrics():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    yield m
    libmetrics.pop_node_metrics(m)


@pytest.fixture(params=[4, 96], ids=["over_the_cut", "under_the_cut"])
def cut(request, monkeypatch):
    """Batches of ``cut`` lanes and more take the device verifier's entry,
    which answers here from the host engine: the route is what is under
    test, not the kernel. At 16 validators a drain is 16 to ~45 lanes:
    over a cut of 4, under the accelerator's own 96."""
    def verify_batch(pubkeys, msgs, sigs):
        bits = host_batch.verify_many(
            list(pubkeys), [bytes(m) for m in msgs], list(sigs))
        return all(bits), bits

    monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", request.param)
    monkeypatch.setattr(ov, "verify_batch", verify_batch)
    return request.param


class Net:
    """A seeded 16-validator chain with extensions on, a node that is no
    validator of it, and height 1's block, proposal and signed votes."""

    def __init__(self, seed: int):
        self.seed = seed
        self.raw = rawchain.make_validators(seed, "val", N_VALS)
        self.sks = [
            oracle.keypair(rawchain.seed_bytes(seed, "val", k))[0]
            for k in self.raw.key_index
        ]
        doc = GenesisDoc(
            chain_id=CHAIN_ID, genesis_time_ns=rawchain.BASE_TIME_NS,
            consensus_params=ConsensusParams(
                abci=ABCIParams(vote_extensions_enable_height=1)),
            validators=[
                GenesisValidator(pub_key=Ed25519PubKey(pk),
                                 power=rawchain.VOTING_POWER)
                for pk in self.raw.pubkeys
            ],
        )
        doc.validate_and_complete()
        # as a node reads it: from the genesis file's text
        self.genesis = GenesisDoc.from_json(doc.to_json())
        self.cs, self.parts = helpers.make_consensus_node(self.genesis, None)
        self.shown: dict = {}
        app = self.parts["app"]
        inner = app.verify_vote_extension

        def watched(req):
            i = self.raw.addresses.index(req.validator_address)
            self.shown[i] = self.shown.get(i, 0) + 1
            return inner(req)

        app.verify_vote_extension = watched
        self.admitted: list = []
        self.cs.evsw.add_listener_for_event(
            "test", EVENT_VOTE, lambda v: self.admitted.append((
                v.msg_type, v.validator_index, v.signature,
                v.extension_signature)))
        state = self.cs.state
        proposer = state.validators.get_proposer().address
        block = state.make_block(
            1, [], None, [], proposer, rawchain.BASE_TIME_NS + 10**9)
        self.block_parts = PartSet.from_data(ser.dumps(block))
        self.block_id = BlockID(block.hash(), self.block_parts.header)
        psh = self.block_id.part_set_header
        self.block = (self.block_id.hash, psh.total, psh.hash)
        self.proposal = Proposal(1, 0, -1, self.block_id,
                                 rawchain.BASE_TIME_NS + 10**9)
        self.proposal.signature = self.sks[
            self.raw.addresses.index(proposer)
        ].sign(self.proposal.sign_bytes(CHAIN_ID))
        self.tpls = ref.templates(CHAIN_ID, 1, self.block)

    def stop(self):
        helpers.stop_node(self.cs, self.parts)

    def delivery(self, t: int, i: int, nil: bool = False, ext=None):
        """Validator ``i``'s sound vote as the reference reads it."""
        ts = rawchain.BASE_TIME_NS + 10**9 + t * 10**8 + 1_000 * i
        sig = self.sks[i].sign(ref.sign_bytes(self.tpls[(t, nil)], ts))
        ext_sig = b""
        if ext is None:
            ext = (vote_ext_script.extension(self.seed, 1, i, EXT_BYTES)
                   if t == PRECOMMIT and not nil else b"")
        if t == PRECOMMIT and not nil:
            ext_sig = self.sks[i].sign(
                ref.extension_sign_bytes(CHAIN_ID, 1, 0, ext))
        return (t, i, ts, sig, ext, ext_sig, nil)

    def vote(self, d) -> Vote:
        t, i, ts, sig, ext, ext_sig, nil = d
        return Vote(t, 1, 0, BlockID() if nil else self.block_id, ts,
                    self.raw.addresses[i], i, sig, ext, ext_sig)

    def items(self, deliveries):
        return [("peer", MsgInfo(VoteMessage(self.vote(d)), "peer-a"))
                for d in deliveries]

    def open_height(self):
        """Round 0 of height 1 with the whole proposed block in hand."""
        items = [("timeout", NEW_HEIGHT_TOCK),
                 ("peer", MsgInfo(ProposalMessage(self.proposal), "peer-a"))]
        items += [("peer", MsgInfo(BlockPartMessage(1, 0, p), "peer-a"))
                  for p in self.block_parts.parts]
        assert self.cs._process_batch(items) is False
        assert self.cs.rs.proposal_block is not None


def _flip(sig: bytes, bit: int) -> bytes:
    return rawchain.flip_bit(sig, bit)


def _script(net: Net):
    """One height's arrivals: (prevote drain, precommit drains). Before
    the commit (the 11th precommit admitted of 16): a copy with an
    altered vote signature, one with an altered extension signature and
    one with its extension signature missing, each ahead of its sound
    original, and a second copy of a vote held. After it: the same three
    kinds of copy and a second copy among the late precommits."""
    rng = random.Random(rawchain.seed_bytes(net.seed, "order"))
    order = list(range(N_VALS))
    rng.shuffle(order)
    sound = {t: {i: net.delivery(t, i) for i in range(N_VALS)}
             for t in (PREVOTE, PRECOMMIT)}

    def bad_sig(d):
        return d[:3] + (_flip(d[3], rng.randrange(512)),) + d[4:]

    def bad_ext(d):
        return d[:5] + (_flip(d[5], rng.randrange(512)),) + d[6:]

    def no_ext_sig(d):
        return d[:5] + (b"",) + d[6:]

    pv = sound[PREVOTE]
    prevotes = [pv[order[0]], bad_sig(pv[order[1]]), pv[order[1]],
                pv[order[0]]] + [pv[i] for i in order[2:]]
    pc = sound[PRECOMMIT]
    early, late = order[:11], order[11:]
    first = [pc[early[0]], bad_sig(pc[early[1]]), bad_ext(pc[early[2]]),
             no_ext_sig(pc[early[3]]), pc[early[0]]]
    first += [pc[i] for i in early[1:]]
    second = [bad_ext(pc[late[0]]), pc[late[0]], bad_sig(pc[late[1]]),
              pc[late[1]], no_ext_sig(pc[late[2]]), pc[late[2]],
              pc[late[0]], pc[early[4]]] + [pc[i] for i in late[3:]]
    return prevotes, first, second


def _stored_extended(net: Net):
    ec = net.parts["block_store"].load_block_extended_commit(1)
    assert ec is not None and ec.height == 1 and ec.round == 0
    psh = ec.block_id.part_set_header
    assert (ec.block_id.hash, psh.total, psh.hash) == net.block
    return [
        (i, es.commit_sig.timestamp_ns, es.commit_sig.signature,
         es.extension, es.extension_signature)
        for i, es in enumerate(ec.extended_signatures)
        if es.commit_sig.signature
    ]


@pytest.mark.parametrize("seed", [33, 2**31 + 175])
@pytest.mark.parametrize("drains", ["one_precommit_drain", "two"])
def test_node_admits_shows_and_stores_what_the_reference_does(
    cut, metrics, capfd, seed, drains
):
    net = Net(seed)
    try:
        prevotes, first, second = _script(net)
        net.open_height()
        assert net.cs._process_batch(net.items(prevotes)) is False
        if drains == "two":
            # the commit falls at the end of the first drain
            assert net.cs._process_batch(net.items(first)) is False
            assert net.cs.rs.height == 2
            assert net.cs._process_batch(net.items(second)) is False
        else:
            # ... or in the middle of the one drain that holds them all
            assert net.cs._process_batch(net.items(first + second)) is False
        deliveries = prevotes + first + second
        want, want_shown, needed = ref.walk(
            CHAIN_ID, 1, deliveries, net.tpls, net.raw.pubkeys,
            rawchain.VOTING_POWER)
        assert len(net.admitted) == len(set(net.admitted))
        assert set(net.admitted) == want
        assert len(want) == 2 * N_VALS  # every sound original, no copy
        assert net.shown == want_shown
        # the application saw the 11 early validators' extensions, two of
        # them twice (a second copy; a copy whose vote signature is
        # altered and whose extension signature is sound), and none of
        # the late ones
        assert sum(want_shown.values()) == 13 and len(want_shown) == 11
        scripted = [d[4:6] for d in
                    (net.delivery(PRECOMMIT, i) for i in range(N_VALS))]
        stored = _stored_extended(net)
        assert len(stored) == 11
        assert ref.extended_commit_faults(
            CHAIN_ID, 1, stored, net.tpls[(PRECOMMIT, False)],
            net.raw.pubkeys, rawchain.VOTING_POWER, scripted) == 0
        # every lane of every drain on the one side of the cut
        lanes = metrics.consensus_preverify_lanes_total
        routed = {r: lanes.labels(r).value() for r in ("device", "host")}
        side = "device" if cut == 4 else "host"
        n_ext_lanes = sum(1 for d in first + second if d[5])
        assert routed == {"device": 0, "host": 0,
                          side: len(deliveries) + n_ext_lanes}
        assert needed <= routed[side]
        # the memo answered every extension signature it held. Two it
        # did not hold: the early copy without one (never a lane), and
        # the second copy that came in the drain of its original, whose
        # entry admission had popped (the late copy without a signature
        # the VoteSet refuses unchecked, as it does late second copies)
        checks = metrics.extension_sig_checks_total
        assert checks.labels("verified_singly").value() == 2
        assert checks.labels("memo").value() > 2 * 11
        assert net.cs.rs.last_commit.sig_memo == {}
    finally:
        net.stop()
        capfd.readouterr()  # the refused arrivals' tracebacks


def test_reference_refuses_extension_data_where_none_may_be():
    """A prevote or a nil precommit carrying an extension or an extension
    signature is refused by the reference, as the program's own
    validate_basic refuses it before the vote reaches a VoteSet."""
    net = Net(5)
    try:
        sk = net.sks[3]
        sound = net.delivery(PREVOTE, 3)
        with_ext = sound[:4] + (b"x", b"") + sound[6:]
        with_sig = sound[:4] + (b"", sk.sign(b"y")) + sound[6:]
        nil_pc = net.delivery(PRECOMMIT, 3, nil=True)
        nil_ext = nil_pc[:4] + (b"x", sk.sign(b"y"), True)
        got, shown, _ = ref.walk(
            CHAIN_ID, 1, [with_ext, with_sig, nil_ext, sound, nil_pc],
            net.tpls, net.raw.pubkeys, rawchain.VOTING_POWER)
        assert got == {(PREVOTE, 3, sound[3], b""),
                       (PRECOMMIT, 3, nil_pc[3], b"")}
        assert shown == {}
        for d in (with_ext, with_sig, nil_ext):
            with pytest.raises(VoteError):
                net.vote(d).validate_basic()
        for d in (sound, nil_pc):
            net.vote(d).validate_basic()
    finally:
        net.stop()


def test_reference_extension_sign_bytes_are_the_programs():
    for height, round_, ext in ((1, 0, b""), (12, 0, b"\x01" * 2048),
                                (2**40, 3, b"abc")):
        assert ref.extension_sign_bytes("bench-qa175ve", height, round_,
                                        ext) == \
            canonical.vote_extension_sign_bytes(
                "bench-qa175ve", height, round_, ext)
    block = (b"\x07" * 32, 6, b"\x08" * 32)
    for t in (PREVOTE, PRECOMMIT):
        assert ref.sign_bytes(
            ref.vote_template(t, "c", 9, 0, None), 1_700_000_000_000_000_123
        ) == canonical.vote_sign_bytes(
            "c", t, 9, 0, BlockID(), 1_700_000_000_000_000_123)
        tpl = ref.templates("c", 9, block)[(t, False)]
        assert tpl == ref.vote_template(t, "c", 9, 0, block)


# --- the pre-app check reads the drain's memo --------------------------------


@pytest.fixture
def coalescer():
    """A routed coalescer, as a node has: a single verify is a ticket."""
    co = ccoalesce.VerifyCoalescer(device=False, window_us=100)
    co.start()
    ccoalesce.push_active(co)
    try:
        yield co
    finally:
        ccoalesce.pop_active(co)
        co.stop()


def test_a_preverified_precommit_wave_submits_no_lone_ticket(
    coalescer, metrics
):
    """The drain's one ticket carries every vote and every extension
    lane; the pre-app check and admission read its answers."""
    net = Net(7)
    try:
        net.open_height()
        pv = [net.delivery(PREVOTE, i) for i in range(N_VALS)]
        pc = [net.delivery(PRECOMMIT, i) for i in range(N_VALS)]
        assert net.cs._process_batch(net.items(pv)) is False
        before = coalescer.tickets
        assert net.cs._process_batch(net.items(pc)) is False
        assert coalescer.tickets - before == 1
        assert len(net.admitted) == 2 * N_VALS
        checks = metrics.extension_sig_checks_total
        # 11 pre-app reads before the commit, 16 at admission
        assert checks.labels("memo").value() == 11 + N_VALS
        assert checks.labels("verified_singly").value() == 0
        assert metrics.vote_sig_admissions_total.labels(
            "verified_singly").value() == 0
    finally:
        net.stop()


def _live_precommit(net: Net, i: int = 2):
    net.open_height()
    return net.vote(net.delivery(PRECOMMIT, i))


def _key(net: Net, vote: Vote):
    return (net.raw.pubkeys[vote.validator_index],
            vote.extension_sign_bytes(CHAIN_ID), vote.extension_signature)


def test_a_false_memo_entry_refuses_before_the_application(
    coalescer, metrics
):
    net = Net(8)
    try:
        vote = _live_precommit(net)
        memo = net.cs.rs.votes.sig_memo
        memo[_key(net, vote)] = False
        before = coalescer.tickets
        with pytest.raises(VoteError, match="invalid extension signature"):
            net.cs._add_vote(vote, "peer-a")
        assert net.shown == {} and net.admitted == []
        assert coalescer.tickets == before
        assert _key(net, vote) in memo  # read, not popped
        assert metrics.extension_sig_checks_total.labels(
            "memo").value() == 1
    finally:
        net.stop()


def test_a_true_memo_entry_is_read_here_and_popped_at_admission(
    coalescer, metrics
):
    net = Net(9)
    try:
        vote = _live_precommit(net)
        memo = net.cs.rs.votes.sig_memo
        memo[_key(net, vote)] = True
        memo[(net.raw.pubkeys[2], vote.sign_bytes(CHAIN_ID),
              vote.signature)] = True
        before = coalescer.tickets
        assert net.cs._add_vote(vote, "peer-a") is True
        assert net.shown == {2: 1}
        assert coalescer.tickets == before
        assert memo == {}
        checks = metrics.extension_sig_checks_total
        assert checks.labels("memo").value() == 2
        assert checks.labels("verified_singly").value() == 0
    finally:
        net.stop()


def test_a_memo_hit_never_skips_the_address_binding(coalescer, metrics):
    """A relay that puts another validator's address on a soundly signed
    precommit: both memo entries say yes, and the VoteSet still refuses."""
    net = Net(10)
    try:
        vote = _live_precommit(net)
        memo = net.cs.rs.votes.sig_memo
        memo[_key(net, vote)] = True
        memo[(net.raw.pubkeys[2], vote.sign_bytes(CHAIN_ID),
              vote.signature)] = True
        vote.validator_address = net.raw.addresses[3]
        with pytest.raises(Exception, match="address"):
            net.cs._add_vote(vote, "peer-a")
        assert net.admitted == []
    finally:
        net.stop()


def test_no_memo_entry_verifies_singly(coalescer, metrics):
    net = Net(11)
    try:
        vote = _live_precommit(net)
        before = coalescer.tickets
        assert net.cs._add_vote(vote, "peer-a") is True
        # the pre-app check, then admission's vote + extension pair
        assert coalescer.tickets - before == 3
        assert net.shown == {2: 1}
        checks = metrics.extension_sig_checks_total
        assert checks.labels("verified_singly").value() == 2
        assert checks.labels("memo").value() == 0
        altered = net.vote(net.delivery(PRECOMMIT, 4))
        altered.extension_signature = _flip(altered.extension_signature, 9)
        with pytest.raises(VoteError, match="invalid extension signature"):
            net.cs._add_vote(altered, "peer-a")
        assert net.shown == {2: 1}
    finally:
        net.stop()


def test_extension_sign_bytes_are_encoded_once_a_vote(monkeypatch):
    calls = []
    inner = canonical.vote_extension_sign_bytes

    def counting(*args):
        calls.append(args)
        return inner(*args)

    monkeypatch.setattr(canonical, "vote_extension_sign_bytes", counting)
    vote = Vote(PRECOMMIT, 5, 0, BlockID(), 1, b"\x01" * 20, 0, b"s",
                b"e" * 2048, b"x")
    first = vote.extension_sign_bytes("c")
    assert vote.extension_sign_bytes("c") is first and len(calls) == 1
    assert first == inner("c", 5, 0, b"e" * 2048)
    # whatever the bytes depend on is checked, not assumed
    vote.extension = b"other"
    assert vote.extension_sign_bytes("c") == inner("c", 5, 0, b"other")
    assert vote.extension_sign_bytes("d") == inner("d", 5, 0, b"other")
    vote.round = 1
    assert vote.extension_sign_bytes("d") == inner("d", 5, 1, b"other")
    assert len(calls) == 4
    # the kept encoding is no part of the vote: not compared, not written
    twin = Vote(PRECOMMIT, 5, 1, BlockID(), 1, b"\x01" * 20, 0, b"s",
                b"other", b"x")
    assert twin == vote and ser.dumps(twin) == ser.dumps(vote)
    assert ser.loads(ser.dumps(vote)) == vote


# --- spans and counters ------------------------------------------------------


def test_extension_phases_are_spans_and_events_of_the_drain(metrics):
    libtrace.reset()
    libtrace.enable()
    net = Net(12)
    try:
        net.open_height()
        pc = [net.delivery(PRECOMMIT, i) for i in range(8)]
        libtrace.reset()
        assert net.cs._process_batch(net.items(pc)) is False
        records = libtrace.ring_dump()
    finally:
        net.stop()
        libtrace.disable()
        libtrace.reset()
    spans = {r["name"]: r for r in records if r["kind"] == "span"}
    enc = spans["consensus.ext_sign_bytes"]
    assert enc["lanes"] == 8
    assert enc["parent"] == spans["consensus.sign_bytes"]["span"]
    assert spans["consensus.sign_bytes"]["lanes"] == 16
    events = {r["name"]: r for r in records if r["kind"] == "event"}
    ev = events["consensus.verify_extension"]
    assert ev["span"] == spans["consensus.drain"]["span"]
    assert ev["n"] == 8 and ev["dur_ns"] >= 0
    hist = metrics.consensus_vote_phase_seconds
    assert hist.labels("verify_extension")._sum == pytest.approx(
        ev["dur_ns"] / 1e9)
    assert hist.labels("ext_sign_bytes")._sum <= \
        hist.labels("sign_bytes")._sum
    # verify_extension nests in no other phase: the drain's tiling holds
    tiled = sum(hist.labels(p)._sum for p in (
        "preverify", "wal_write", "verify_extension", "add_vote",
        "vote_step", "publish"))
    assert 0 < tiled <= hist.labels("drain")._sum


@pytest.fixture(scope="module")
def rendered_after_a_height():
    from cometbft_tpu.consensus.reactor import (
        VOTE_CHANNEL, ConsensusReactor, PeerState,
    )
    from test_consensus_vote_spans import _Peer

    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    net = Net(13)
    try:
        net.open_height()
        for t in (PREVOTE, PRECOMMIT):
            net.cs._process_batch(net.items(
                [net.delivery(t, i) for i in range(N_VALS)]))
        peer = _Peer("ef" * 20)
        peer.set("consensus_peer_state", PeerState())
        ConsensusReactor(net.cs).receive(
            VOTE_CHANNEL, peer, ser.dumps(VoteMessage(net.vote(
                net.delivery(PREVOTE, 1)))))
        for phase in ("block_part", "timeout", "queue_wait"):
            m.consensus_vote_phase_seconds.labels(phase).observe(0.0)
        text = m.registry.render()
    finally:
        net.stop()
        libmetrics.pop_node_metrics(m)
    return {line.rpartition(" ")[0] for line in text.splitlines()
            if line and not line.startswith("#")}


@pytest.mark.parametrize("name", EXT_METRICS)
def test_ext_metric_reads_series_that_exist(rendered_after_a_height, name):
    with open(os.path.join(REPO, "benchmark", "metrics", name + ".json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "counter_ratio"
    for key in metric["numerator"] + metric["denominator"]:
        assert key.startswith("prom.cometbft_tpu_")
        series = key[len("prom."):]
        if series.endswith("*"):
            assert any(s.startswith(series[:-1])
                       for s in rendered_after_a_height), key
        else:
            assert series in rendered_after_a_height, key
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry["moves"] == "sigs_per_s"
    assert entry["workloads"] == ["qa175ve-jitter"]


# --- the genesis file --------------------------------------------------------


def test_genesis_file_carries_consensus_params():
    genesis, _pvs = helpers.make_genesis(2)
    text = genesis.to_json()
    assert json.loads(text)["consensus_params"]["abci"] == {
        "vote_extensions_enable_height": 0}
    assert GenesisDoc.from_json(text).consensus_params == \
        genesis.consensus_params
    enabled = GenesisDoc(
        chain_id="c", genesis_time_ns=1, validators=genesis.validators,
        consensus_params=ConsensusParams(
            abci=ABCIParams(vote_extensions_enable_height=7)))
    back = GenesisDoc.from_json(enabled.to_json())
    assert back.consensus_params.abci.vote_extensions_enable_height == 7
    assert back.consensus_params == enabled.consensus_params
    # a file written before the key existed reads as the defaults
    old = json.loads(text)
    del old["consensus_params"]
    assert GenesisDoc.from_json(json.dumps(old)).consensus_params == \
        ConsensusParams()
    with pytest.raises(ValueError):
        ConsensusParams.from_dict({"no_such_section": {}})
