"""A full node following a chain through its consensus reactor: home from
``cmd init``, node from ``default_new_node(cfg)`` as ``cmd start`` builds
it (file stores, the consensus WAL with its fsyncs, every plane on its
default), a genesis of N ed25519 validators of which the node is none, and
scripted peers admitted to its switch that hand it, one serialized message
at a time through ``ConsensusReactor.receive``, each height's proposal,
block parts, prevotes and precommits (drivers/vote_script.py: seeded
order, peers, bursts, gaps, duplicates and mangled copies).

Closed loop by height on one feeder thread: a wave is always delivered to
its end, and height h+1 starts when the node's own NewRoundStep for h+1
has reached the scripted peers. A vote counts when a scripted peer has
received the node's HasVote for it. What the node admitted (its own vote
event, with the signature) and what its stores hold are compared with the
plain reference's walk over the same script (reference/vote_round_ref.py).

The transport is left out (``reduced: p2p_transport``): peers are
in-process objects that keep the peer contract the reactors use; they never
announce a round state of their own, so the node gossips nothing to them.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from argparse import Namespace

from cometbft_tpu.consensus import messages as cmsg
from cometbft_tpu.consensus.reactor import (
    DATA_CHANNEL, STATE_CHANNEL, VOTE_CHANNEL,
)
from cometbft_tpu.consensus.state import EVENT_VOTE
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto.keys import Ed25519PubKey
from cometbft_tpu.ops import verify as ov
from cometbft_tpu.types import serialization as ser
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig,
)
from cometbft_tpu.types.part_set import PartSet
from cometbft_tpu.types.vote import Proposal, Vote

from ..harness import chain as rawchain
from ..harness import spec, stats
from ..reference import ed25519_oracle as oracle
from ..reference import vote_round_ref as ref
from . import adapters, verdicts, vote_script

SIGS = "prom.cometbft_tpu_crypto_verify_batch_sigs_total{"
COALESCE_LANES = "prom.cometbft_tpu_crypto_coalesce_lanes_total{"
_HAS_VOTE = b'{"__t":"HasVoteMessage"'
_ROUND_STEP = b'{"__t":"NewRoundStepMessage"'
TYPES = vote_script.TYPES


EXIT_CANNOT_RUN = 5


def preflight() -> None:
    """This deployment needs a switch that admits a peer that came by no
    connection, and an FSM that counts the timeouts it acts on. A program
    with neither cannot run the cell: said here, before any set-up, by exit
    code 5 and no result line."""
    import sys

    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.p2p.switch import Switch

    missing = [what for what, ok in (
        ("p2p.Switch.admit_peer", hasattr(Switch, "admit_peer")),
        ("consensus_timeouts_total", hasattr(
            libmetrics.node_metrics(), "consensus_timeouts_total")),
    ) if not ok]
    if missing:
        print(f"benchmark: the program lacks {missing}: the cell is not "
              "measured on this program", file=sys.stderr)
        raise SystemExit(EXIT_CANNOT_RUN)


class ScriptedPeer:
    """One neighbour: the peer contract the reactors use
    (id/start/stop/is_running/send/try_send/get/set). What the node sends
    it goes to the net's ``deliver``."""

    persistent = False
    socket_addr = ""

    def __init__(self, net: "ScriptedNet", index: int, outbound: bool):
        self.net = net
        self.index = index
        self.outbound = outbound
        self.id = rawchain.seed_bytes(net.seed, "peer", index)[:20].hex()
        self._data: dict = {}
        self._running = False

    def start(self) -> None:
        self._running = True

    def stop(self) -> None:
        self._running = False
        self.net.stopped.append(self.index)

    def is_running(self) -> bool:
        return self._running

    def send(self, ch_id: int, msg: bytes) -> bool:
        return self.net.deliver(self, ch_id, msg)

    try_send = send

    def set(self, key: str, value) -> None:
        self._data[key] = value

    def get(self, key: str):
        return self._data.get(key)


class ScriptedNet:
    """The scripted neighbours' side of the wire. Peer 0 keeps what the
    node broadcast on the state channel (every peer is sent the same
    bytes): each HasVote with the instant it arrived, and the node's
    round steps, which the feeder waits on."""

    def __init__(self, seed: int, n_peers: int, n_outbound: int):
        self.seed = seed
        self.peers = [
            ScriptedPeer(self, i, i < n_outbound) for i in range(n_peers)
        ]
        self.has_votes: list = []  # (monotonic, wire bytes)
        self.steps: list = []  # (monotonic, height, round, step)
        self.stopped: list = []
        self.height = 0  # the highest height the node announced
        self.cond = threading.Condition()

    def deliver(self, peer: ScriptedPeer, ch_id: int, msg: bytes) -> bool:
        if peer.index or ch_id != STATE_CHANNEL:
            return True
        if msg.startswith(_HAS_VOTE):
            self.has_votes.append((time.monotonic(), msg))
        elif msg.startswith(_ROUND_STEP):
            m = json.loads(msg)
            with self.cond:
                self.steps.append(
                    (time.monotonic(), m["height"], m["round"], m["step"]))
                self.height = max(self.height, m["height"])
                self.cond.notify_all()
        return True

    def wait_height(self, height: int, timeout: float) -> bool:
        with self.cond:
            return self.cond.wait_for(lambda: self.height >= height, timeout)


class Height:
    """One scripted height, ready to hand over."""

    __slots__ = ("block_id", "app_hash", "app_hash_after", "proposer_peer",
                 "data", "waves", "wire", "mangled", "votes")

    def plain_block(self):
        psh = self.block_id.part_set_header
        return (self.block_id.hash, psh.total, psh.hash)

    def deliveries(self):
        """Every delivery as the reference reads it, in arrival order:
        (msg_type, index, timestamp_ns, signature)."""
        out = []
        for t in TYPES:
            for kind, pos, _peer in self.waves[t][0]:
                ts, sig = self.votes[t][pos]
                if kind == vote_script.MANGLED:
                    sig = self.mangled[t][pos][0]
                out.append((t, pos, ts, sig))
        return out


class Driver:
    def __init__(self, cell, seed: int, tracer):
        self.cell, self.seed, self.tracer = cell, seed, tracer
        self.cfg, self.mix = cell.config, cell.mix
        self.chain_id = self.cfg["chain_id"]
        self.marks = stats.Marks()
        self.node = None
        self.home = os.path.join(spec.ROOT, ".bench_home", cell.name)
        self.admitted: list = []  # (height, msg_type, index, signature)
        self.wave_started: dict = {}  # (height, msg_type) -> monotonic
        self.hand_faults: list = []

    # -- set-up ----------------------------------------------------------

    def setup(self, seconds: float) -> None:
        from cometbft_tpu.cmd.__main__ import _config
        from cometbft_tpu.cmd.__main__ import main as cli_main
        from cometbft_tpu.node import default_new_node

        from .served_node import _free_port

        pinned = sorted(k for k in os.environ if k.startswith("COMETBFT_TPU_"))
        if pinned:
            raise RuntimeError(f"{pinned} set: the node runs on its defaults")
        preflight()
        cfg, mix = self.cfg, self.mix
        n = cfg["validators"]
        t = time.monotonic()
        self.raw_vals = rawchain.make_validators(self.seed, "val", n)
        self.vals = adapters.validator_set(self.raw_vals)
        self.n_warm = mix["warmup_heights"]
        per_height = len(TYPES) * n
        self.n_heights = self.n_warm + 2 + int(
            mix["list_over_knee"] * mix["knee_sigs_per_s"] * seconds
            / per_height)
        shutil.rmtree(self.home, ignore_errors=True)
        if cli_main(["--home", self.home, "init"]) != 0:
            raise RuntimeError("cmd init failed")
        node_cfg = _config(Namespace(
            home=self.home, rpc_laddr=f"tcp://127.0.0.1:{_free_port()}",
            p2p_laddr=f"tcp://127.0.0.1:{_free_port()}",
        ))
        if node_cfg.base.db_backend != "file":
            raise RuntimeError("the stores are not file-backed")
        node_cfg.consensus.skip_timeout_commit = cfg["skip_timeout_commit"]
        node_cfg.base.block_sync = cfg["block_sync"]
        genesis = self._genesis()
        with open(node_cfg.base.resolve(node_cfg.base.genesis_file), "w") as f:
            f.write(genesis.to_json())
        t = self.marks.add(f"{n} keys, validator set, cmd init, genesis", t)
        self._build_script(genesis)
        t = self.marks.add(
            f"{self.n_heights} heights scripted: blocks, parts, "
            f"{per_height} votes each signed in a pool", t)
        self._warm_shapes(mix["warm_buckets"])
        t = self.marks.add("key tables, verify shapes of every bucket", t)
        self.node = default_new_node(node_cfg)
        self.node.start()
        if self.node.consensus.priv_validator_pub_key is not None and \
                self.vals.has_address(bytes(
                    self.node.consensus.priv_validator_pub_key.address())):
            raise RuntimeError("the node under test is a validator")
        self.reactor = self.node.consensus_reactor
        self.node.consensus.evsw.add_listener_for_event(
            "benchmark", EVENT_VOTE, self._on_vote)
        self.net = ScriptedNet(self.seed, mix["peers"], mix["peers_outbound"])
        for peer in self.net.peers:
            self.node.switch.admit_peer(peer)
        t = self.marks.add(
            f"node boot, {len(self.net.peers)} scripted peers admitted", t)
        self.next_height = 1
        warm = self._play(time.monotonic() + 600, self.n_warm)
        if warm["lost"] or not self._settle(self.n_warm, 120):
            raise RuntimeError("the node did not follow the warm-up heights")
        if not ov.WARM.wait_idle(600):
            raise RuntimeError("background compiles did not finish")
        if self.tracer.enabled:
            # the ring holds the window's records, not set-up's: a full
            # ring would drop the lanes the kernel's roofline share reads
            from cometbft_tpu.libs import trace as libtrace

            libtrace.reset()
        self.marks.add(f"warm-up: {self.n_warm} heights through the node", t)

    def _genesis(self):
        from cometbft_tpu.types import GenesisDoc, GenesisValidator

        doc = GenesisDoc(
            chain_id=self.chain_id,
            genesis_time_ns=rawchain.BASE_TIME_NS,
            validators=[
                GenesisValidator(pub_key=Ed25519PubKey(pk),
                                 power=rawchain.VOTING_POWER)
                for pk in self.raw_vals.pubkeys
            ],
        )
        doc.validate_and_complete()
        return doc

    def _shadow_executor(self, genesis):
        """A second application and in-memory stores that the script's
        blocks are applied to as they are made: where the header fields
        that depend on execution (app hash, results hash, the rotating
        proposer) come from. Nothing is validated here."""
        from cometbft_tpu import proxy
        from cometbft_tpu.abci.kvstore import KVStoreApplication
        from cometbft_tpu.consensus.replay import Handshaker
        from cometbft_tpu.libs import db as dbm
        from cometbft_tpu.state import (
            BlockExecutor, Store, make_genesis_state,
        )
        from cometbft_tpu.store import BlockStore

        conns = proxy.AppConns(proxy.local_client_creator(
            KVStoreApplication(dbm.MemDB())))
        conns.start()
        store = Store(dbm.MemDB())
        state = make_genesis_state(genesis)
        store.save(state)
        shaker = Handshaker(store, state, BlockStore(dbm.MemDB()), genesis)
        shaker.handshake(conns)
        return BlockExecutor(store, conns.consensus), shaker.state, conns

    def _wire_template(self, msg_type: int, height: int, block_id):
        """The program's own encoding of a vote message of this wave, cut
        around the four fields that differ from vote to vote."""
        ts, idx = 1_111_111_111_111_111_111, 987_654_321
        addr, sig = b"\xa5" * 20, b"\xb6" * 64
        text = ser.dumps(cmsg.VoteMessage(Vote(
            msg_type, height, 0, block_id, ts, addr, idx, sig))).decode()
        parts = []
        for mark in (str(ts), addr.hex(), str(idx), sig.hex()):
            head, found, text = text.partition(mark)
            if not found or mark in text:
                raise RuntimeError("the vote message's encoding has moved")
            parts.append(head)
        parts.append(text)
        return parts

    def _build_script(self, genesis) -> None:
        n = len(self.raw_vals)
        raw = self.raw_vals
        executor, state, conns = self._shadow_executor(genesis)
        position = {a: i for i, a in enumerate(raw.addresses)}
        self.script: dict[int, Height] = {}
        last_commit = None
        try:
            with rawchain.spawn_pool() as pool:
                k = pool._max_workers
                slices = [list(range(n))[i::k] for i in range(k)]
                for h in range(1, self.n_heights + 1):
                    proposer = state.validators.get_proposer().address
                    block = state.make_block(
                        h, [], last_commit, [], proposer,
                        vote_script.block_time_ns(h))
                    parts = PartSet.from_data(ser.dumps(block))
                    bid = BlockID(block.hash(), parts.header)
                    sc = self.script[h] = Height()
                    sc.block_id = bid
                    sc.app_hash = state.app_hash
                    sc.proposer_peer = int.from_bytes(rawchain.seed_bytes(
                        self.seed, "proposer", h)[:4], "big") % self.mix["peers"]
                    sk = oracle.keypair(rawchain.seed_bytes(
                        self.seed, "val", raw.key_index[position[proposer]]))[0]
                    proposal = Proposal(h, 0, -1, bid,
                                        vote_script.block_time_ns(h))
                    proposal.signature = sk.sign(
                        proposal.sign_bytes(self.chain_id))
                    sc.data = [ser.dumps(cmsg.ProposalMessage(proposal))] + [
                        ser.dumps(cmsg.BlockPartMessage(h, 0, p))
                        for p in parts.parts
                    ]
                    wires = {t: self._wire_template(t, h, bid) for t in TYPES}
                    jobs = [
                        (self.seed, "val", sl, raw.key_index, raw.addresses,
                         self.chain_id, h, sc.plain_block(), wires)
                        for sl in slices if sl
                    ]
                    sc.votes = {t: [None] * n for t in TYPES}
                    sc.wire = {t: [None] * n for t in TYPES}
                    for part in pool.map(vote_script.sign_job, jobs):
                        for t, pos, ts, sig, wire in part:
                            sc.votes[t][pos] = (ts, sig)
                            sc.wire[t][pos] = wire
                    sc.waves, sc.mangled = {}, {}
                    for t in TYPES:
                        sc.waves[t] = vote_script.wave(
                            self.seed, h, t, n, self.mix)
                        sc.mangled[t] = {}
                        for kind, pos, _p in sc.waves[t][0]:
                            if kind == vote_script.MANGLED:
                                ts, sig = sc.votes[t][pos]
                                bad = vote_script.mangle(
                                    self.seed, h, t, pos, sig)
                                sc.mangled[t][pos] = (
                                    bad, vote_script.fill_wire(
                                        wires[t], ts, raw.addresses[pos],
                                        pos, bad))
                    self._check_wire(sc, h)
                    last_commit = Commit(
                        height=h, round=0, block_id=bid,
                        signatures=[
                            CommitSig(BLOCK_ID_FLAG_COMMIT, raw.addresses[i],
                                      *sc.votes[ref.PRECOMMIT][i])
                            for i in range(n)
                        ])
                    state, resp = executor.begin_apply(state, bid, block)
                    executor.complete_apply(state, bid, block, resp)
                    sc.app_hash_after = state.app_hash
        finally:
            conns.stop()

    def _check_wire(self, sc: Height, h: int) -> None:
        """The filled template decodes to the vote it stands for."""
        t, pos = ref.PREVOTE, h % len(self.raw_vals)
        ts, sig = sc.votes[t][pos]
        want = cmsg.VoteMessage(Vote(
            t, h, 0, sc.block_id, ts, self.raw_vals.addresses[pos], pos, sig))
        if ser.loads(sc.wire[t][pos]) != want:
            raise RuntimeError("a scripted vote's wire bytes decode otherwise")

    def _warm_shapes(self, buckets) -> None:
        """The key tables of the set and an executable for every bucket a
        drain or a commit check can launch (a cold shape would run on the
        host while it compiles in the background; the window must see
        neither)."""
        crypto_batch.prestage_validators(self.vals)
        for b in buckets:
            ov.WARM.ready(("window", b))
        if not ov.WARM.wait_idle(900):
            raise RuntimeError("verify shapes did not finish compiling")
        if ov.WARM.failed:
            raise RuntimeError(f"verify shapes failed: {ov.WARM.failed}")

    def counters(self) -> dict:
        out = {}
        vc = getattr(self.node, "verify_coalescer", None)
        if vc is not None:
            out["coalescer"] = {
                "windows": vc.windows, "device_windows": vc.device_windows,
                "cold_windows": vc.cold_windows, "trips": vc.trips,
                "tickets": vc.tickets,
            }
        return out

    # -- the feeder ------------------------------------------------------

    def _on_vote(self, vote) -> None:
        """The node's own vote event (the one its reactor turns into a
        HasVote), on its receive routine's thread: what it admitted, with
        the signature."""
        self.admitted.append(
            (vote.height, vote.msg_type, vote.validator_index, vote.signature))

    def _hand(self, ch_id: int, peer: ScriptedPeer, msg: bytes) -> None:
        """As the switch hands a peer's message to a reactor: an error
        stops the peer (Switch._on_peer_receive)."""
        try:
            self.reactor.receive(ch_id, peer, msg)
        except Exception as e:
            self.hand_faults.append(repr(e)[:200])
            self.node.switch.stop_and_remove_peer(peer, e)

    def _feed(self, h: int) -> bool:
        """One height to the node. False if the node never announced it."""
        sc = self.script[h]
        peers = self.net.peers
        if not self.net.wait_height(h, self.mix["height_wait_s"]):
            return False
        for msg in sc.data:
            self._hand(DATA_CHANNEL, peers[sc.proposer_peer], msg)
        for t in TYPES:
            deliveries, bursts = sc.waves[t]
            ends = [b[0] for b in bursts[1:]] + [len(deliveries)]
            wire, mangled = sc.wire[t], sc.mangled[t]
            t0 = time.monotonic()
            self.wave_started[(h, t)] = t0
            for (first, offset), end in zip(bursts, ends):
                wait = t0 + offset - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                for kind, pos, peer in deliveries[first:end]:
                    msg = (mangled[pos][1] if kind == vote_script.MANGLED
                           else wire[pos])
                    self._hand(VOTE_CHANNEL, peers[peer], msg)
        return True

    def _play(self, t_end: float, max_heights: int | None = None) -> dict:
        """Heights from ``next_height`` on, one after the other, until the
        clock passes ``t_end`` at a height's boundary (or ``max_heights``
        are fed). Runs on a thread of its own, as the peers' threads do."""
        out = {"first": self.next_height, "fed": 0, "ran_out": False,
               "lost": False, "error": None, "acted_after_first": None,
               "acted_at_end": None}

        def run() -> None:
            try:
                while time.monotonic() < t_end and (
                        max_heights is None or out["fed"] < max_heights):
                    if self.next_height > self.n_heights:
                        out["ran_out"] = True
                        return
                    if not self._feed(self.next_height):
                        out["lost"] = True
                        return
                    self.next_height += 1
                    out["fed"] += 1
                    if out["fed"] == 1:
                        out["acted_after_first"] = self._timeouts_acted()
            except Exception as e:  # reported on the driver's thread
                out["error"] = e
            finally:
                out["acted_at_end"] = self._timeouts_acted()

        th = threading.Thread(target=run, name="bench-feeder", daemon=True)
        th.start()
        th.join(timeout=max(0.0, t_end - time.monotonic()) + 300)
        if th.is_alive():
            raise RuntimeError("the feeder did not stop")
        if out["error"] is not None:
            raise out["error"]
        return out

    def _settle(self, height: int, timeout: float) -> bool:
        """Wait until the node has gone on from ``height`` and every vote
        of it has been announced."""
        deadline = time.monotonic() + timeout
        if not self.net.wait_height(height + 1, timeout):
            return False
        want = len(TYPES) * len(self.raw_vals)
        while time.monotonic() < deadline:
            if sum(1 for a in reversed(self.admitted[-4 * want:])
                   if a[0] == height) >= want:
                return True
            time.sleep(0.02)
        return False

    # -- the measured window ---------------------------------------------

    def run_window(self, seconds: float) -> dict:
        n_admitted = len(self.admitted)
        n_has = len(self.net.has_votes)
        self.tracer.start()
        t0 = time.monotonic()
        t_end = t0 + seconds
        played = self._play(t_end)
        # from its second height to the feeder's last hand-over the node is
        # fed without a pause, and a timeout it acts on is one it should
        # not have needed. Before and after, it sits waiting for seconds
        # (the end of set-up, the profiler's start and its reading of the
        # trace), about as long as its propose timeout
        acted0 = played["acted_after_first"]
        if acted0 is None:
            acted0 = played["acted_at_end"]
        self.tracer.stop()
        last = played["first"] + played["fed"] - 1
        settled = played["fed"] == 0 or self._settle(
            last, self.mix["drain_seconds"])
        announced: dict = {}
        for seen, msg in self.net.has_votes[n_has:]:
            m = json.loads(msg)
            key = (m["height"], m["msg_type"], m["index"])
            announced.setdefault(key, []).append(seen)
        inside = sum(1 for seen in announced.values() if seen[0] <= t_end)
        n = len(self.raw_vals)
        delivered = played["fed"] * len(TYPES) * n
        # a wave's first hand-over to the HasVote of its last vote
        last_seen: dict = {}
        for (h, t, _i), seen in announced.items():
            last_seen[(h, t)] = max(last_seen.get((h, t), 0.0), seen[0])
        wave_ms = [
            (last_seen[w] - self.wave_started[w]) * 1e3
            for w in last_seen if w in self.wave_started
        ]
        heights_inside = len({
            h for seen, h, _r, _s in self.net.steps if t0 <= seen <= t_end})
        return {
            "end_to_end": {"sigs_per_s": inside / seconds},
            "attempted": delivered,
            "failed": delivered - sum(
                1 for key in announced
                if played["first"] <= key[0] <= last),
            "played": played, "last": last, "settled": settled,
            "announced": announced,
            "admitted": self.admitted[n_admitted:],
            "t0": t0, "t_end": t_end,
            "timeouts_acted": played["acted_at_end"] - acted0,
            "stats": {
                "votes_announced_in_window": inside,
                "heights_fed": played["fed"],
                "heights_announced_in_window": heights_inside,
                "heights_scripted": self.n_heights,
                "height_ms": (seconds * 1e3 / heights_inside
                              if heights_inside else None),
                "wave_consume_ms_p50": stats.percentile(wave_ms, 50),
                "wave_consume_ms_p95": stats.percentile(wave_ms, 95),
            },
            "notes": {"hand_faults": self.hand_faults[:5],
                      "peers_stopped": sorted(set(self.net.stopped))},
        }

    @staticmethod
    def _timeouts_acted() -> float:
        from cometbft_tpu.libs import metrics as libmetrics

        return libmetrics.node_metrics().consensus_timeouts_total.labels(
            "acted").value()

    def close(self) -> None:
        if self.node is not None:
            try:
                self.node.stop()
            finally:
                self.node = None
                shutil.rmtree(self.home, ignore_errors=True)

    # -- correctness -----------------------------------------------------

    def _stored_commits(self, h: int, tip: int):
        """The commits the node's block store holds for ``h``: the one
        block h+1 carried and, at the tip, the one the node itself saw."""
        store = self.node.block_store
        found = []
        for commit in (store.load_block_commit(h),
                       store.load_seen_commit() if h == tip else None):
            if commit is None or commit.height != h:
                continue
            psh = commit.block_id.part_set_header
            found.append((
                (commit.block_id.hash, psh.total, psh.hash),
                [(i, cs.timestamp_ns, cs.signature)
                 for i, cs in enumerate(commit.signatures)
                 if cs.block_id_flag != BLOCK_ID_FLAG_ABSENT],
                commit.round,
            ))
        return found

    def check(self, window: dict, control: str, ctx) -> dict:
        """For every height fed in the window: the votes the node admitted
        against those the reference admits from the same deliveries (both
        ways, signatures included); its HasVotes against its admissions;
        the commits and hashes its stores hold against the reference and
        the script. With ``control`` the control's admissions stand in
        for the node's."""
        played = window["played"]
        first, last = played["first"], window["last"]
        heights = list(range(first, last + 1))
        store = self.node.block_store
        # the node's commit writer saves a block and its state behind the
        # round step that announced the next height
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and heights and (
                store.height() < last
                or self.node.state_store.load().last_block_height < last):
            time.sleep(0.05)
        tip = store.height()
        pubkeys = self.raw_vals.pubkeys
        fn = verdicts.CONTROLS[control] if control else None
        jobs, stored = [], {}
        for h in heights:
            sc = self.script[h]
            stored[h] = self._stored_commits(h, tip)
            jobs.append((
                self.chain_id, h, sc.plain_block(), pubkeys,
                rawchain.VOTING_POWER, sc.deliveries(),
                [(blk, sigs) for blk, sigs, _round in stored[h]], fn,
            ))
        with rawchain.spawn_pool() as pool:
            results = list(pool.map(ref.height_job, jobs))
        got: dict = {h: set() for h in heights}
        twice = 0
        for h, t, idx, sig in window["admitted"]:
            if h in got:
                twice += (t, idx, sig) in got[h]
                got[h].add((t, idx, sig))
        announced = window["announced"]
        vote_mismatches = has_vote_faults = commit_faults = hash_faults = 0
        rounds_above_0 = needed = 0
        for h, (want, stand_in, faults, verified) in zip(heights, results):
            node_set = stand_in if control else got[h]
            vote_mismatches += len(node_set ^ want)
            said = {(t, idx) for (hh, t, idx) in announced if hh == h}
            has_vote_faults += len(said ^ {(t, idx) for t, idx, _s in got[h]})
            has_vote_faults += sum(
                len(seen) - 1 for (hh, _t, _i), seen in announced.items()
                if hh == h)
            commit_faults += faults + (0 if stored[h] else 1)
            rounds_above_0 += sum(1 for _b, _s, r in stored[h] if r != 0)
            meta = store.load_block_meta(h)
            sc = self.script[h]
            if meta is None or meta.block_id != sc.block_id \
                    or meta.header.app_hash != sc.app_hash:
                hash_faults += 1
            # the height's votes, and the full LastCommit of its block
            needed += verified + (len(pubkeys) if h > 1 else 0)
        state = self.node.state_store.load()
        if heights and (state.last_block_height != last or
                        state.app_hash != self.script[last].app_hash_after):
            hash_faults += 1
        rounds_above_0 += sum(
            1 for seen, h, r, _s in self.net.steps
            if r > 0 and first <= h <= last)
        c = ctx.counters
        lone = sum(v for key, v in c.items() if key.startswith(SIGS)
                   and 'backend="ed25519-coalesce"' not in key)
        routed = {key[len(COALESCE_LANES):-1]: v for key, v in c.items()
                  if key.startswith(COALESCE_LANES)}
        counted = lone + sum(routed.values())
        notes = window.setdefault("notes", {})
        notes.update(
            heights_checked=len(heights), admitted_twice=twice,
            lanes_counted=counted, lanes_needed=needed,
            coalesce_lanes_by_route=routed, tip=tip,
        )
        return {
            "vote_mismatches": {"value": vote_mismatches + twice, "limit": 0},
            "has_vote_faults": {"value": has_vote_faults, "limit": 0},
            "stored_commit_faults": {"value": commit_faults, "limit": 0},
            "block_or_app_hash_mismatches": {"value": hash_faults, "limit": 0},
            "lanes_needed_minus_counted": {
                "value": max(0, needed - counted), "limit": 0},
            "timeouts_acted_on": {
                "value": window["timeouts_acted"], "limit": 0},
            "rounds_above_0": {"value": rounds_above_0, "limit": 0},
            "peers_stopped": {
                "value": len(set(self.net.stopped)) + len(self.hand_faults),
                "limit": 0},
            "dispatch_faults": {
                "value": sum(v for key, v in c.items()
                             if key.startswith("faults.")), "limit": 0},
            "compiles_in_window": {
                "value": c.get("devstats.compiles", 0), "limit": 0},
            "script_exhausted_or_node_lost": {
                "value": int(played["ran_out"]) + int(played["lost"])
                + int(not window["settled"]), "limit": 0},
        }
