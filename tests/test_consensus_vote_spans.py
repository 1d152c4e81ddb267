"""The consensus vote path's spans and counters (PR 31), and what the
receive routine's drain does to a wave of votes: a drain of any size
admits and refuses exactly what ``add_vote`` one by one does; the batched
sign-bytes of a drain equal the per-vote encoding byte for byte; late
precommits are pre-verified into ``last_commit``'s memo; a switch admits a
peer that came by no connection.
"""

import json
import os
import threading
import types

import pytest

import helpers
from cometbft_tpu.consensus import HeightVoteSet
from cometbft_tpu.consensus.messages import VoteMessage
from cometbft_tpu.consensus.reactor import (
    VOTE_CHANNEL, ConsensusReactor, PeerState,
)
from cometbft_tpu.consensus.state import EVENT_VOTE
from cometbft_tpu.consensus.wal import MsgInfo
from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import host_batch
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.libs import lockprof as liblockprof
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import trace as libtrace
from cometbft_tpu.libs.metrics import NodeMetrics
from cometbft_tpu.ops import verify as ov
from cometbft_tpu.types import GenesisDoc, GenesisValidator, MockPV, canonical
from cometbft_tpu.types import serialization as ser
from cometbft_tpu.types.block import BlockID, PartSetHeader
from cometbft_tpu.types.vote import Vote, votes_sign_bytes
from cometbft_tpu.types.vote_set import VoteSet

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = BlockID(b"\x11" * 32, PartSetHeader(5, b"\x22" * 32))
OTHER = BlockID(b"\x33" * 32, PartSetHeader(300, b"\x44" * 32))
NIL = BlockID()
T0 = 1_700_000_000_000_000_000
CUT = 96  # the accelerator's static cut, here on the CPU
ROUND_METRICS = [
    "device_lane_pct.round", "preverify_lanes_per_drain.round",
    "vote_queue_wait_ms_per_vote", "preverify_ms_per_height",
    "vote_sign_bytes_ms_per_height", "vote_admit_ms_per_height",
    "sig_memo_hit_pct.round", "reactor_receive_ms_per_vote",
    "wal_write_ms_per_height.round", "vote_span_coverage_pct.round",
    "round_state_published_read_pct.round",
    "state_mutex_wait_ms_per_vote.round",
]


@pytest.fixture
def tracer():
    libtrace.reset()
    libtrace.enable()
    yield libtrace
    libtrace.disable()
    libtrace.reset()


@pytest.fixture
def metrics():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    yield m
    libmetrics.pop_node_metrics(m)


@pytest.fixture
def static_cut(monkeypatch):
    """Batches of CUT lanes and more take the device verifier's entry,
    which answers here from the host engine: the route is what is under
    test, not the kernel."""
    def verify_batch(pubkeys, msgs, sigs):
        bits = host_batch.verify_many(
            list(pubkeys), [bytes(m) for m in msgs], list(sigs))
        return all(bits), bits

    monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", CUT)
    monkeypatch.setattr(ov, "verify_batch", verify_batch)


_NETS: dict = {}


def _net(n_vals: int):
    """(genesis, priv vals in validator-set order), made once a size."""
    if n_vals not in _NETS:
        # helpers.make_genesis, for more validators than a byte counts
        pvs = [MockPV(Ed25519PrivKey.from_seed((i + 1).to_bytes(32, "big")))
               for i in range(n_vals)]
        doc = GenesisDoc(
            chain_id=helpers.CHAIN_ID, genesis_time_ns=T0,
            validators=[GenesisValidator(pub_key=pv.get_pub_key(), power=10)
                        for pv in pvs],
        )
        by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
        _NETS[n_vals] = (doc, [by_addr[v.address]
                               for v in doc.validator_set().validators])
    return _NETS[n_vals]


def _vote(pvs, valset, idx, msg_type=canonical.PREVOTE_TYPE, height=1,
          block_id=BLOCK, round_=0):
    v = Vote(
        msg_type=msg_type, height=height, round=round_, block_id=block_id,
        timestamp_ns=T0 + 1_000 * idx + msg_type,
        validator_address=valset.validators[idx].address,
        validator_index=idx,
    )
    pvs[idx].sign_vote(helpers.CHAIN_ID, v, sign_extension=False)
    return v


def _altered(vote):
    sig = bytearray(vote.signature)
    sig[5] ^= 0x10
    return Vote(vote.msg_type, vote.height, vote.round, vote.block_id,
                vote.timestamp_ns, vote.validator_address,
                vote.validator_index, bytes(sig))


def _node(n_vals: int):
    genesis, pvs = _net(n_vals)
    cs, parts = helpers.make_consensus_node(genesis, None)
    return cs, parts, pvs, cs.rs.validators


def _wave(pvs, valset, n: int, msg_type=canonical.PREVOTE_TYPE):
    """``n`` arrivals: sound votes, with every 7th validator's vote first
    arriving altered and every 5th's arriving twice."""
    out = []
    idx = 0
    while len(out) < n:
        v = _vote(pvs, valset, idx, msg_type)
        if idx % 7 == 3:
            out.append(_altered(v))
        out.append(v)
        if idx % 5 == 2:
            out.append(v)
        idx += 1
    return out[:n]


def _items(votes, peer="peer-a"):
    return [("peer", MsgInfo(VoteMessage(v), peer)) for v in votes]


def _one_by_one(valset, votes):
    """What a VoteSet with no memo does with each arrival."""
    sets = HeightVoteSet(helpers.CHAIN_ID, votes[0].height, valset)
    sets.sig_memo = None
    for pair in sets._round_vote_sets.values():
        for vs in pair:
            vs.sig_memo = None
    out = []
    for v in votes:
        try:
            out.append(("added" if sets.add_vote(v, "peer-a") else "held", ""))
        except Exception as e:
            out.append(("refused", type(e).__name__))
    return out


def _drained(cs, votes):
    """What the receive routine's drain does with the same arrivals."""
    out = []
    inner = cs._add_vote

    def recording(vote, peer_id):
        try:
            added = inner(vote, peer_id)
        except Exception as e:
            out.append(("refused", type(e).__name__))
            raise
        out.append(("added" if added else "held", ""))
        return added

    cs._add_vote = recording
    try:
        assert cs._process_batch(_items(votes)) is False
    finally:
        cs._add_vote = inner
    return out


@pytest.mark.parametrize("n_arrivals,n_vals", [
    (1, 8), (95, 128), (96, 128), (1000, 1000),
])
def test_a_drain_admits_what_add_vote_one_by_one_admits(
    static_cut, metrics, capfd, n_arrivals, n_vals
):
    cs, parts, pvs, valset = _node(n_vals)
    try:
        votes = _wave(pvs, valset, n_arrivals)
        want = _one_by_one(valset, votes)
        answers = metrics.vote_sig_admissions_total
        singly0 = answers.labels("verified_singly").value()
        admitted = []
        cs.evsw.add_listener_for_event(
            "test", EVENT_VOTE, lambda v: admitted.append(v))
        got = _drained(cs, votes)
        assert got == want
        assert [v.signature for v in admitted] == [
            v.signature for v, (what, _) in zip(votes, want) if what == "added"
        ]
        lanes = metrics.consensus_preverify_lanes_total
        routed = {r: lanes.labels(r).value() for r in ("device", "host")}
        # one lane a drained vote, all on one side of the cut (a lone
        # vote with no coalescer routed is not pre-verified at all)
        expect = 0 if n_arrivals == 1 else n_arrivals
        side = "device" if n_arrivals >= CUT else "host"
        assert routed == {"device": 0, "host": 0, side: expect}
        memo = answers.labels("memo").value()
        singly = answers.labels("verified_singly").value() - singly0
        # a second copy of a vote held is known and not checked again
        checked = sum(1 for what, _ in want if what != "held")
        assert memo + singly == checked
        assert (memo, singly) == ((0, 1) if n_arrivals == 1
                                  else (checked, 0))
        items = metrics.consensus_drain_items_total
        assert items.labels("vote").value() == n_arrivals
        assert items.labels("other").value() == 0
        # the drain left nothing behind for the height
        assert cs.rs.votes.sig_memo == {}
    finally:
        helpers.stop_node(cs, parts)
        capfd.readouterr()  # the refused arrivals' tracebacks


def _mixed_votes():
    genesis, pvs = _net(8)
    valset = genesis.validator_set()
    votes = []
    for idx in range(8):
        votes.append(_vote(pvs, valset, idx))
        votes.append(_vote(pvs, valset, idx, canonical.PRECOMMIT_TYPE))
    votes.append(_vote(pvs, valset, 1, block_id=NIL))
    votes.append(_vote(pvs, valset, 2, block_id=NIL))
    votes.append(_vote(pvs, valset, 3, block_id=OTHER))  # alone in its group
    votes.append(_vote(pvs, valset, 4, height=2))
    votes.append(_vote(pvs, valset, 5, height=2))
    votes.append(_vote(pvs, valset, 6, round_=1))
    return votes


@pytest.mark.parametrize("take", ["all", "one_group", "single", "none"])
def test_votes_sign_bytes_is_the_per_vote_encoding_byte_for_byte(take):
    votes = _mixed_votes()
    votes = {"all": votes, "one_group": votes[0:16:2], "single": votes[:1],
             "none": []}[take]
    got = votes_sign_bytes(helpers.CHAIN_ID, votes)
    assert len(got) == len(votes)
    for vote, sign_bytes in zip(votes, got):
        assert bytes(sign_bytes) == vote.sign_bytes(helpers.CHAIN_ID)


def test_votes_sign_bytes_without_the_native_encoder(monkeypatch):
    monkeypatch.setattr(canonical, "vote_sign_bytes_many",
                        lambda *a, **k: None)
    votes = _mixed_votes()
    assert votes_sign_bytes(helpers.CHAIN_ID, votes) == [
        v.sign_bytes(helpers.CHAIN_ID) for v in votes]


def _spans():
    return [r for r in libtrace.ring_dump() if r["kind"] == "span"]


def test_spans_of_a_drain_nest_and_phases_tile(static_cut, tracer, metrics,
                                               capfd):
    cs, parts, pvs, valset = _node(128)
    try:
        votes = _wave(pvs, valset, 100)
        for _ in votes:
            cs._vote_enqueued_ns.append(T0)  # as add_vote_from_peer stamps
        libtrace.reset()
        cs._process_batch(_items(votes))
        records = libtrace.ring_dump()
    finally:
        helpers.stop_node(cs, parts)
        capfd.readouterr()
    spans = {r["name"]: r for r in records if r["kind"] == "span"}
    drain = spans["consensus.drain"]
    assert drain["items"] == drain["votes"] == 100
    assert "parent" not in drain
    for child in ("consensus.queue_wait", "consensus.preverify"):
        assert spans[child]["parent"] == drain["span"], child
    assert spans["consensus.queue_wait"]["votes"] == 100
    assert spans["consensus.queue_wait"]["start_ns"] == T0
    pre = spans["consensus.preverify"]
    assert (pre["lanes"], pre["route"]) == (100, "device")
    assert spans["consensus.sign_bytes"]["parent"] == pre["span"]
    assert spans["consensus.sign_bytes"]["lanes"] == 100
    # the phases that recur per item: one event a drain under its span
    events = {r["name"]: r for r in records if r["kind"] == "event"
              and r["name"].startswith("consensus.")
              and r["name"] != "consensus.vote"}
    for name in ("consensus.wal_write", "consensus.add_vote",
                 "consensus.vote_step", "consensus.publish"):
        assert events[name]["span"] == drain["span"], name
        assert events[name]["dur_ns"] >= 0
    assert events["consensus.add_vote"]["n"] == 100
    hist = metrics.consensus_vote_phase_seconds
    tiled = sum(hist.labels(p)._sum for p in (
        "preverify", "wal_write", "add_vote", "vote_step", "publish"))
    whole = hist.labels("drain")._sum
    assert 0 < tiled <= whole
    assert hist.labels("drain")._sum == pytest.approx(drain["dur_ns"] / 1e9)
    assert hist.labels("preverify")._sum == pytest.approx(pre["dur_ns"] / 1e9)
    assert hist.labels("sign_bytes")._sum <= hist.labels("preverify")._sum


def test_late_precommits_are_answered_from_last_commits_memo(
    static_cut, metrics
):
    """After a commit the votes of the height before still arrive: they
    are pre-verified in the drain's launch into the memo last_commit
    shares, not verified one by one."""
    cs, parts, pvs, valset = _node(8)
    try:
        memo: dict = {}
        last = VoteSet(helpers.CHAIN_ID, 1, 0, canonical.PRECOMMIT_TYPE,
                       valset, sig_memo=memo)
        early = [_vote(pvs, valset, i, canonical.PRECOMMIT_TYPE)
                 for i in range(6)]
        for v in early:
            assert last.add_vote(v)
        before = metrics.vote_sig_admissions_total.labels(
            "verified_singly").value()
        with cs._mtx:
            cs.rs.height = 2
            cs.rs.votes = HeightVoteSet(helpers.CHAIN_ID, 2, valset)
            cs.rs.last_commit = last
        late = [_vote(pvs, valset, 6, canonical.PRECOMMIT_TYPE),
                _altered(_vote(pvs, valset, 7, canonical.PRECOMMIT_TYPE)),
                _vote(pvs, valset, 7, canonical.PRECOMMIT_TYPE)]
        cs._process_batch(_items(late))
        answers = metrics.vote_sig_admissions_total
        assert answers.labels("verified_singly").value() == before
        assert answers.labels("memo").value() == 3
        assert last.has_all()
        assert memo == {} and cs.rs.votes.sig_memo == {}
        assert metrics.consensus_preverify_lanes_total.labels(
            "host").value() == 3
    finally:
        helpers.stop_node(cs, parts)


class _Peer:
    """The peer contract the reactors use, and nothing behind it."""

    outbound = False
    persistent = False
    socket_addr = ""

    def __init__(self, pid: str):
        self.id = pid
        self.sent: list = []
        self.running = False
        self._data: dict = {}

    def start(self):
        self.running = True

    def stop(self):
        self.running = False

    def is_running(self):
        return self.running

    def send(self, ch_id, msg):
        self.sent.append((ch_id, msg))
        return True

    try_send = send

    def set(self, key, value):
        self._data[key] = value

    def get(self, key):
        return self._data.get(key)


def test_reactor_receive_is_timed_on_the_peers_thread(tracer, metrics):
    cs, parts, pvs, valset = _node(8)
    try:
        reactor = ConsensusReactor(cs)
        peer = _Peer("ab" * 20)
        peer.set("consensus_peer_state", PeerState())
        wire = ser.dumps(VoteMessage(_vote(pvs, valset, 0)))
        th = threading.Thread(
            target=reactor.receive, args=(VOTE_CHANNEL, peer, wire),
            name="peer-thread")
        th.start()
        th.join()
        (span,) = [s for s in _spans() if s["name"] == "reactor.receive"]
        assert span["thread"] == "peer-thread" and span["ch"] == VOTE_CHANNEL
        hist = metrics.consensus_vote_phase_seconds.labels("receive_vote")
        assert sum(hist._counts) == 1
        assert hist._sum == pytest.approx(span["dur_ns"] / 1e9)
        # the vote is in the inbox with its enqueue stamp beside it
        assert cs._queue.qsize() == 1 and len(cs._vote_enqueued_ns) == 1
    finally:
        helpers.stop_node(cs, parts)


def test_timeouts_are_counted_by_what_they_did(metrics):
    from cometbft_tpu.consensus import RoundStep, TimeoutInfo

    cs, parts, _pvs, _valset = _node(8)
    try:
        with cs._mtx:
            cs._handle_timeout(TimeoutInfo(0.0, 99, 0, int(RoundStep.PROPOSE)))
            cs._handle_timeout(
                TimeoutInfo(0.0, 1, 0, int(RoundStep.NEW_HEIGHT)))
        total = metrics.consensus_timeouts_total
        assert total.labels("stale").value() == 1
        assert total.labels("acted").value() == 1
    finally:
        helpers.stop_node(cs, parts)


def test_batch_verifier_reports_where_it_ran(static_cut):
    genesis, pvs = _net(128)
    valset = genesis.validator_set()
    votes = [_vote(pvs, valset, i) for i in range(CUT)]
    for n, route in ((CUT - 1, "host"), (CUT, "device")):
        bv = cbatch.create_commit_batch_verifier(valset)
        assert bv.route is None
        bv.add_many(
            [valset.validators[v.validator_index].pub_key for v in votes[:n]],
            votes_sign_bytes(helpers.CHAIN_ID, votes[:n]),
            [v.signature for v in votes[:n]],
        )
        ok, bits = bv.verify()
        assert ok and len(bits) == n and bv.route == route


def test_switch_admits_a_peer_that_came_by_no_connection():
    from cometbft_tpu.p2p.base_reactor import ChannelDescriptor, Reactor
    from cometbft_tpu.p2p.switch import Switch, SwitchError

    class Rec(Reactor):
        def __init__(self):
            super().__init__("rec")
            self.calls = []

        def get_channels(self):
            return [ChannelDescriptor(id=0x77, priority=1,
                                      send_queue_capacity=1)]

        def init_peer(self, peer):
            self.calls.append(("init", peer.id, peer.is_running()))

        def add_peer(self, peer):
            self.calls.append(("add", peer.id, peer.is_running()))

        def remove_peer(self, peer, reason):
            self.calls.append(("remove", peer.id, str(reason)))

    transport = types.SimpleNamespace(
        close=lambda: None, accept=lambda: (_ for _ in ()).throw(OSError()))
    sw = Switch(transport)
    rec = sw.add_reactor("rec", Rec())
    peer = _Peer("cd" * 20)
    with pytest.raises(SwitchError):
        sw.admit_peer(peer)  # not running yet
    sw.start()
    try:
        sw.admit_peer(peer)
        assert rec.calls == [("init", peer.id, False), ("add", peer.id, True)]
        with pytest.raises(SwitchError):
            sw.admit_peer(peer)
        sw.try_broadcast(0x77, b"hello")
        assert peer.sent == [(0x77, b"hello")]
        sw.stop_and_remove_peer(peer, "done")
        assert not peer.is_running() and sw.peers() == []
        assert rec.calls[-1] == ("remove", peer.id, "done")
    finally:
        sw.stop()


@pytest.fixture(scope="module")
def rendered_after_a_drain():
    """Series names of the registry the benchmark snapshots, after one
    drain and one received vote."""
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    # as while a node runs: the locks' ledger on, which a drain bridges
    was_profiling = liblockprof.enabled()
    liblockprof.enable()
    cs, parts, pvs, valset = _node(8)
    try:
        cs._process_batch(_items([_vote(pvs, valset, i) for i in range(4)]))
        peer = _Peer("ef" * 20)
        peer.set("consensus_peer_state", PeerState())
        ConsensusReactor(cs).receive(
            VOTE_CHANNEL, peer, ser.dumps(VoteMessage(_vote(pvs, valset, 5))))
        m.consensus_vote_phase_seconds.labels("height").observe(0.1)
        for phase in ("finalize", "block_part", "timeout", "queue_wait"):
            m.consensus_vote_phase_seconds.labels(phase).observe(0.0)
        text = m.registry.render()
    finally:
        helpers.stop_node(cs, parts)
        libmetrics.pop_node_metrics(m)
        if not was_profiling:
            liblockprof.disable()
    return {
        line.rpartition(" ")[0] for line in text.splitlines()
        if line and not line.startswith("#")
    }


@pytest.mark.parametrize("name", ROUND_METRICS)
def test_round_metric_reads_series_that_exist(rendered_after_a_drain, name):
    with open(os.path.join(REPO, "benchmark", "metrics", name + ".json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "counter_ratio"
    for key in metric["numerator"] + metric["denominator"]:
        assert key.startswith("prom.cometbft_tpu_")
        series = key[len("prom."):]
        if series.endswith("*"):
            assert any(s.startswith(series[:-1])
                       for s in rendered_after_a_drain), key
        else:
            assert series in rendered_after_a_drain, key
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry["moves"] == "sigs_per_s"
    assert entry["workloads"] == ["vote1000-jitter"]


def test_wal_write_many_is_write_one_by_one_byte_for_byte(tmp_path):
    from cometbft_tpu.consensus.wal import WAL, TimeoutInfo

    genesis, pvs = _net(8)
    valset = genesis.validator_set()
    msgs = [MsgInfo(VoteMessage(_vote(pvs, valset, i)), "p") for i in range(5)]
    msgs.insert(2, TimeoutInfo(0.5, 1, 0, 3))
    one, many = WAL(str(tmp_path / "one")), WAL(str(tmp_path / "many"))
    for m in msgs:
        one.write(m)
    many.write_many(msgs)
    many.write_many([])
    one.close(), many.close()
    assert (tmp_path / "one").read_bytes() == (tmp_path / "many").read_bytes()


def test_a_drain_logs_every_item_in_order_before_it_is_handled(tmp_path):
    """Runs of current-height peer votes go to the WAL in one write each;
    whatever breaks a run (a timeout, a vote of another height) is logged
    at its own turn; nothing is handled before it is logged."""
    from cometbft_tpu.consensus.wal import WAL, EndHeightMessage, TimeoutInfo

    genesis, pvs = _net(8)
    cs, parts = helpers.make_consensus_node(genesis, None)
    valset = cs.rs.validators
    wal = cs.wal = WAL(str(tmp_path / "wal"))
    votes = [_vote(pvs, valset, i) for i in range(6)]
    other = _vote(pvs, valset, 6, height=2)
    items = (_items(votes[:3]) + [("timeout", TimeoutInfo(0.0, 9, 0, 3))]
             + _items(votes[3:5]) + _items([other]) + _items(votes[5:]))
    writes, handled_at = [], []
    inner_many, inner_add = wal.write_many, cs._add_vote

    def write_many(msgs):
        msgs = list(msgs)
        writes.append(len(msgs))
        inner_many(msgs)

    def add_vote(vote, peer_id):
        handled_at.append(sum(writes))
        return inner_add(vote, peer_id)

    wal.write_many, cs._add_vote = write_many, add_vote
    try:
        cs._process_batch(items)
    finally:
        helpers.stop_node(cs, parts)
    assert writes == [3, 1, 2, 1, 1]  # run, timeout, run, other height, run
    # the k-th handled vote had at least its own frame behind it
    logged_before = [3, 3, 3, 6, 6, 7, 8]
    assert handled_at == logged_before
    logged = [m for m in WAL(str(tmp_path / "wal")).iter_messages()
              if not isinstance(m, EndHeightMessage)]
    assert logged == [payload for _kind, payload in items]
