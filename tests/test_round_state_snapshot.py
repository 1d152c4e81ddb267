"""``ConsensusState.get_round_state()`` without the state mutex: the FSM's
owner publishes a shallow copy of the round state before it lets
'consensus.state' go (``_fsm_region``), and a thread that holds no lock
reads that copy and takes none. A thread inside its own critical section
still reads its own writes. The WAL's record sequence of a scripted
height is pinned beside it: nothing is logged later than before."""

from __future__ import annotations

import dataclasses
import os
import queue
import threading
import time

import pytest

import helpers
from cometbft_tpu.consensus import RoundStep, TimeoutInfo
from cometbft_tpu.consensus.messages import ProposalMessage, VoteMessage
from cometbft_tpu.consensus.reactor import ConsensusReactor
from cometbft_tpu.consensus.round_state import RoundState
from cometbft_tpu.consensus.state import EVENT_NEW_ROUND_STEP
from cometbft_tpu.consensus.wal import WAL, EndHeightMessage, MsgInfo
from cometbft_tpu.libs import lockprof as liblockprof
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import sync as libsync
from cometbft_tpu.libs.metrics import NodeMetrics
from cometbft_tpu.types import canonical

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRAPH_DIR = os.path.join(REPO, "cometbft_tpu", "devtools", "lint", "graph")
FIELDS = [f.name for f in dataclasses.fields(RoundState)]
NEW_HEIGHT_TOCK = TimeoutInfo(0.0, 1, 0, int(RoundStep.NEW_HEIGHT))


@pytest.fixture
def metrics():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    yield m
    libmetrics.pop_node_metrics(m)


def _solo(home=None):
    """A one-validator node, not started: the test is its FSM's owner."""
    genesis, pvs = helpers.make_genesis(1)
    return helpers.make_consensus_node(genesis, pvs[0], home=home)


def _drive(cs, parts, height: int, more_batches: int = 0) -> None:
    """Pump the inbox a batch at a time, as the simnet scheduler does,
    until the block store holds ``height``; then ``more_batches`` more."""
    for _ in range(200):
        if parts["block_store"].height() >= height:
            break
        cs.process_pending(max_batches=1)
    assert parts["block_store"].height() >= height
    for _ in range(more_batches):
        cs.process_pending(max_batches=1)


def _same_fields(a: RoundState, b: RoundState) -> list[str]:
    """Names of the fields in which ``a`` and ``b`` hold other objects."""
    return [f for f in FIELDS if getattr(a, f) is not getattr(b, f)]


def _from_other_thread(fn, timeout: float = 5.0):
    out: list = []
    th = threading.Thread(target=lambda: out.append(fn()), daemon=True)
    th.start()
    th.join(timeout)
    assert not th.is_alive() and out, "the call did not return in time"
    return out[0]


def test_reader_returns_while_another_thread_holds_the_mutex(monkeypatch):
    """... and takes no lock of any kind to do so: every lock of the node
    is built instrumented, and the reader's thread acquires none."""
    taken: list = []
    orig = libsync._order_note_acquired

    def noting(name):
        taken.append((threading.get_ident(), name))
        return orig(name)

    monkeypatch.setattr(libsync, "_order_note_acquired", noting)
    prev = libsync.lock_order_mode()
    libsync.set_lock_order_mode("record")
    try:
        cs, parts = _solo()
    finally:
        libsync.set_lock_order_mode(prev)
    try:
        with cs._mtx:
            cs.rs.round = 5  # not published: the region is still open
            rs, reader = _from_other_thread(
                lambda: (cs.get_round_state(), threading.get_ident()))
        assert rs.round == 0 and rs.height == 1
        assert (threading.get_ident(), "consensus.state") in taken
        assert [name for tid, name in taken if tid == reader] == []
    finally:
        helpers.stop_node(cs, parts)


def _after_init(tmp_path):
    return _solo()


def _after_a_height_through_process_pending(tmp_path):
    cs, parts = _solo()
    cs._queue.put(("timeout", NEW_HEIGHT_TOCK))
    _drive(cs, parts, 2)
    assert cs.rs.height >= 3
    return cs, parts


def _after_a_timeout(tmp_path):
    cs, parts = _solo()
    cs._locked_dispatch("timeout", NEW_HEIGHT_TOCK)
    assert cs.rs.step > RoundStep.NEW_HEIGHT
    return cs, parts


def _after_switch_to_consensus(tmp_path):
    cs, parts = _solo()
    reactor = ConsensusReactor(cs, wait_sync=True)
    cs.sim_driven = True  # no routine of its own: the region is the writer
    reactor.switch_to_consensus(cs.state, skip_wal=True)
    assert cs.is_running() and cs.do_wal_catchup is False
    return cs, parts


def _after_wal_replay_in_on_start(tmp_path):
    home = str(tmp_path / "node")
    cs, parts = _solo(home)
    cs._queue.put(("timeout", NEW_HEIGHT_TOCK))
    # height 1 whole, then height 2's proposal and part logged and
    # handled, its prevote still in the inbox: a crash mid-height
    _drive(cs, parts, 1, more_batches=1)
    assert cs.rs.height == 2 and cs.rs.proposal is not None
    helpers.stop_node(cs, parts)
    cs, parts = _solo(home)
    assert cs.rs.height == 2 and cs.rs.proposal is None
    cs.sim_driven = True
    cs.start()  # on_start replays height 2's records through the handlers
    assert cs.rs.proposal is not None, "the replay handled nothing"
    return cs, parts


@pytest.mark.parametrize("region", [
    _after_init,
    _after_a_height_through_process_pending,
    _after_a_timeout,
    _after_switch_to_consensus,
    _after_wal_replay_in_on_start,
], ids=lambda f: f.__name__.lstrip("_"))
def test_published_copy_equals_the_live_round_state(region, tmp_path):
    cs, parts = region(tmp_path)
    try:
        with cs._mtx:
            published = cs._rs_published
            assert published is not cs.rs
            assert _same_fields(published, cs.rs) == []
        got = _from_other_thread(cs.get_round_state)
        assert got is not published and got is not cs.rs
        assert _same_fields(got, cs.rs) == []
    finally:
        helpers.stop_node(cs, parts)


@pytest.mark.parametrize("hold", ["region", "bare_mutex"])
def test_owner_inside_its_critical_section_reads_its_own_write(hold):
    cs, parts = _solo()
    try:
        with (cs._fsm_region() if hold == "region" else cs._mtx):
            cs.rs.round = 7
            assert cs.get_round_state().round == 7
            # another thread cannot have the mutex now, and sees what the
            # last release showed
            assert _from_other_thread(cs.get_round_state).round == 0
        # a region publishes as it ends; a bare ``with cs._mtx`` is not
        # one (no FSM code writes under it), so nothing new is published
        want = 7 if hold == "region" else 0
        assert _from_other_thread(cs.get_round_state).round == want
    finally:
        helpers.stop_node(cs, parts)


@pytest.mark.parametrize("path", ["published", "locked"])
def test_writing_to_what_was_returned_reaches_nothing(path):
    cs, parts = _solo()
    try:
        def read():
            if path == "published":
                return cs.get_round_state()
            with cs._mtx:
                return cs.get_round_state()

        rs = read()
        rs.round = 99
        rs.validators = None
        assert cs.rs.round == 0 and cs.rs.validators is not None
        assert cs._rs_published.round == 0
        again = read()
        assert again.round == 0 and again.validators is cs.rs.validators
    finally:
        helpers.stop_node(cs, parts)


def test_new_round_step_listener_sees_the_step_it_was_told_of():
    cs, parts = _solo()
    told_and_seen: list = []

    def listener(rs_of_event):
        now = cs.get_round_state()
        told_and_seen.append((
            (rs_of_event.height, rs_of_event.round, int(rs_of_event.step)),
            (now.height, now.round, int(now.step)),
        ))

    cs.evsw.add_listener_for_event("test", EVENT_NEW_ROUND_STEP, listener)
    try:
        cs._queue.put(("timeout", NEW_HEIGHT_TOCK))
        _drive(cs, parts, 2)
        assert len(told_and_seen) >= 8  # 2 heights, 4+ steps each
        # delivery is after the region: the snapshot it published is at
        # the step the event names, or further on, never behind it
        stale = [(t, s) for t, s in told_and_seen if s < t]
        assert stale == []
        # and the last event of a region is exactly the state it left
        assert any(t == s for t, s in told_and_seen)
    finally:
        helpers.stop_node(cs, parts)


def test_reads_are_counted_by_path(metrics):
    cs, parts = _solo()
    try:
        total = metrics.consensus_round_state_reads_total
        start = {p: total.labels(p).value() for p in ("published", "locked")}

        def counted():
            # a call tallies itself with no lock; a drain hands the
            # tally to the registry
            assert cs._process_batch([]) is False
            return {p: total.labels(p).value() - start[p]
                    for p in ("published", "locked")}

        cs.get_round_state()
        _from_other_thread(cs.get_round_state)
        assert counted() == {"published": 2, "locked": 0}
        with cs._mtx:
            cs.get_round_state()
        assert counted() == {"published": 2, "locked": 1}
        # nothing published yet (an object made without __init__'s region)
        cs._rs_published = None
        assert cs.get_round_state().height == cs.rs.height
        assert counted() == {"published": 2, "locked": 2}
        rendered = metrics.registry.render()
        for p in ("published", "locked"):
            assert ("cometbft_tpu_consensus_round_state_reads_total"
                    f'{{path="{p}"}}') in rendered
    finally:
        helpers.stop_node(cs, parts)


def test_a_drain_bridges_the_locks_ledger_into_the_registry(metrics):
    """lock_wait_seconds_total{lock} is written at a scrape, which the
    benchmark's harness never makes: the receive routine bridges the
    profiler's columns once a drain, so a window's two snapshots of the
    registry hold the state mutex's wait."""
    cs, parts = _solo()
    slot = liblockprof.slot_for("consensus.state")
    wait = metrics.lock_wait.labels("consensus.state")
    liblockprof.sample(metrics)  # what this process's locks waited so far
    base = wait.value()
    liblockprof._wait_ns[slot] += 1_500_000
    try:
        assert cs._process_batch([]) is False
        assert wait.value() - base == pytest.approx(0.0015)
        # a second bridge of the same columns adds nothing
        assert cs._process_batch([]) is False
        liblockprof.sample(metrics)
        assert wait.value() - base == pytest.approx(0.0015)
    finally:
        liblockprof._wait_ns[slot] -= 1_500_000
        helpers.stop_node(cs, parts)


def test_wal_records_of_a_scripted_height_keep_their_order(tmp_path):
    """Kinds and order of what one height leaves in the WAL, each record
    written before it is handled and ``#ENDHEIGHT`` last. Holds on the
    program before the published snapshot and after it alike."""
    home = str(tmp_path / "node")
    cs, parts = _solo(home)
    try:
        cs._queue.put(("timeout", NEW_HEIGHT_TOCK))
        _drive(cs, parts, 1)
    finally:
        helpers.stop_node(cs, parts)
    wal = WAL(os.path.join(home, "cs.wal", "wal"))
    try:
        records = list(wal.iter_messages())
    finally:
        wal.close()

    def kind(m):
        if isinstance(m, EndHeightMessage):
            return ("#ENDHEIGHT", m.height)
        if isinstance(m, TimeoutInfo):
            return ("timeout", m.height, m.round, m.step)
        assert isinstance(m, MsgInfo) and m.peer_id == ""
        if isinstance(m.msg, VoteMessage):
            return ("vote", m.msg.vote.height, m.msg.vote.msg_type)
        return (type(m.msg).__name__, m.msg.proposal.height
                if isinstance(m.msg, ProposalMessage) else m.msg.height)

    kinds = [kind(m) for m in records]
    end = kinds.index(("#ENDHEIGHT", 1))
    assert kinds[:end + 1] == [
        ("#ENDHEIGHT", 0),
        ("timeout", 1, 0, int(RoundStep.NEW_HEIGHT)),
        ("ProposalMessage", 1),
        ("BlockPartMessage", 1),
        ("vote", 1, canonical.PREVOTE_TYPE),
        ("vote", 1, canonical.PRECOMMIT_TYPE),
        ("#ENDHEIGHT", 1),
    ]
    # what follows belongs to height 2
    assert all(k[1] == 2 for k in kinds[end + 1:]), kinds[end + 1:]


# -- four nodes, reactors and their per-peer routines live ----------------


class _Peer:
    """The peer contract the reactor uses; what is sent to it is handed
    to the net below."""

    outbound = False
    persistent = False
    socket_addr = ""

    def __init__(self, pid: str, deliver):
        self.id = pid
        self.running = True
        self._deliver = deliver
        self._data: dict = {}

    def is_running(self):
        return self.running

    def send(self, ch_id, msg):
        self._deliver(ch_id, msg)
        return True

    try_send = send

    def set(self, key, value):
        self._data[key] = value

    def get(self, key):
        return self._data.get(key)


class _Net:
    """Reactors that see one another as peers, and nothing else between
    the nodes: proposals, parts and votes travel only because a per-peer
    gossip routine picked them out of a round state it read, as wire
    bytes into the other node's ``ConsensusReactor.receive`` on that
    node's one receive thread (its peers' threads rolled into one)."""

    def __init__(self, nodes):
        n = len(nodes)
        self.errors: list = []
        self.reactors = [ConsensusReactor(cs) for cs, _ in nodes]
        self.inbox = [queue.Queue() for _ in range(n)]
        # peers[i][j]: node j as node i's reactor sees it
        self.peers = [
            {j: _Peer(f"{j:02x}" * 20, self._deliverer(i, j))
             for j in range(n) if j != i}
            for i in range(n)
        ]
        for i in range(n):
            self.reactors[i].switch = self._Switch(self.peers[i])
        self.threads = [
            threading.Thread(target=self._receive, args=(i,), daemon=True,
                             name=f"net-receive-{i}")
            for i in range(n)
        ]

    def _deliverer(self, src: int, dst: int):
        return lambda ch_id, wire: self.inbox[dst].put((ch_id, src, wire))

    class _Switch:
        def __init__(self, peers):
            self.peers = peers

        def try_broadcast(self, ch_id, msg):
            for peer in self.peers.values():
                peer.try_send(ch_id, msg)

    def _receive(self, i: int) -> None:
        while True:
            item = self.inbox[i].get()
            if item is None:
                return
            ch_id, src, wire = item
            try:
                self.reactors[i].receive(ch_id, self.peers[i][src], wire)
            except Exception as e:  # a sanitizer's refusal, too
                self.errors.append(e)

    def start(self):
        # every peer's state first: a node announces its round step as
        # it is given a peer, and the other side must have where to put it
        for i, reactor in enumerate(self.reactors):
            for peer in self.peers[i].values():
                reactor.init_peer(peer)
        for th in self.threads:
            th.start()
        for i, reactor in enumerate(self.reactors):
            reactor.start()
            for peer in self.peers[i].values():
                reactor.add_peer(peer)

    def stop(self):
        for i in range(len(self.reactors)):
            for peer in self.peers[i].values():
                peer.running = False
            self.inbox[i].put(None)
        for th in self.threads:
            th.join(5)


def test_sanitizers_in_enforce_over_a_burst_with_reactor_routines_live(
    monkeypatch, metrics
):
    """Four validators, their reactors' 36 per-peer routines polling the
    round state and every message entering through ``receive``, under the
    lock-order and lockset sanitizers in ``enforce`` against the shipped
    artifacts: no refusal anywhere, also where a routine would swallow it."""
    refused: list = []
    for name in ("_order_check", "lockset_note"):
        orig = getattr(libsync, name)

        def noting(arg, orig=orig):
            try:
                return orig(arg)
            except (libsync.LockOrderError, libsync.LocksetError) as e:
                refused.append(e)
                raise

        monkeypatch.setattr(libsync, name, noting)
    prev_order, prev_set = libsync.lock_order_mode(), libsync.lockset_mode()
    libsync.set_lock_order_mode(
        "enforce", graph_path=os.path.join(GRAPH_DIR, "lockorder.json"))
    libsync.set_lockset_mode(
        "enforce", fields_path=os.path.join(GRAPH_DIR, "fieldguards.json"))
    libsync.reset_locksets()
    genesis, pvs = helpers.make_genesis(4)
    # instrumented locks cost a stack dump an acquire: timeouts a round
    # can be gossiped inside, and routines that leave it the CPU
    from cometbft_tpu import config as cmtconfig

    ms = 1_000_000
    cfg = cmtconfig.test_config()
    cfg.consensus = dataclasses.replace(
        cfg.consensus, peer_gossip_sleep_duration_ns=20 * ms,
        timeout_propose_ns=3_000 * ms, timeout_prevote_ns=1_000 * ms,
        timeout_precommit_ns=1_000 * ms,
    )
    nodes = [helpers.make_consensus_node(genesis, pv, config=cfg)
             for pv in pvs]
    fatals: list = []
    for cs, _ in nodes:
        cs.on_fatal = fatals.append
    net = _Net(nodes)
    try:
        net.start()
        stores = [parts["block_store"] for _, parts in nodes]
        helpers.wait_for_commits(stores, 3, tick=0.02)
        # let every routine come round once more on the final heights
        time.sleep(2 * net.reactors[0]._gossip_sleep)
    finally:
        net.stop()
        for cs, parts in nodes:
            helpers.stop_node(cs, parts)
        libsync.set_lock_order_mode(prev_order)
        libsync.set_lockset_mode(prev_set)
    assert not refused, refused
    assert not fatals and not net.errors, (fatals, net.errors)
    assert len({s.load_block(1).hash() for s in stores}) == 1
    # the state mutex was sampled at its seam, held
    sampled = [held for (field, held) in libsync.observed_locksets()
               if field == "ConsensusState.state"]
    assert sampled and all("consensus.state" in held for held in sampled)
    # the routines and the receive path read the round state, and no
    # read from a thread without the mutex took it
    reads = metrics.consensus_round_state_reads_total
    assert reads.labels("published").value() > 100
    assert reads.labels("locked").value() == 0
