"""A fresh full node catching up a chain through block sync: home from
``cmd init``, node from ``default_new_node(cfg)`` as ``cmd start`` builds it
(file stores, kvstore, every plane on its default) with ``block_sync`` on, a
genesis of N validators of two schemes (ed25519 and sr25519, interleaved as
their addresses fall) of which the node is none, and scripted peers admitted
to its switch that report the whole chain and answer every ``BlockRequest``
at once, each on its own thread, with pre-encoded ``BlockResponse`` bytes
through ``BlocksyncReactor.receive`` (drivers/sync_script.py: keys, stamps,
the altered blocks).

Closed loop: the node pulls as fast as its own request window lets it. A
block counts when the node's ``apply_block`` for it returned inside the
window (its NewBlock event): the lanes its two commit checks had to verify,
the light check's up to +2/3 and the full LastCommit. What the peers served,
what the node stored and which peers it removed are compared with the plain
reference's walk over the same deliveries (reference/blocksync_ref.py).

The transport is left out (``reduced: p2p_transport``): peers are in-process
objects that keep the peer contract the reactors use.
"""

from __future__ import annotations

import json
import os
import queue
import shutil
import threading
import time
from argparse import Namespace

from cometbft_tpu.blocksync.messages import (
    BlockResponseMessage, NoBlockResponseMessage, StatusResponseMessage,
)
from cometbft_tpu.blocksync.reactor import BLOCKSYNC_CHANNEL
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import host_batch
from cometbft_tpu.crypto import sr25519 as prog_sr
from cometbft_tpu.crypto.keys import Ed25519PubKey
from cometbft_tpu.crypto.sr25519 import Sr25519PubKey
from cometbft_tpu.ops import verify as ov
from cometbft_tpu.types import serialization as ser
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig,
)
from cometbft_tpu.types.part_set import PartSet
from cometbft_tpu.types.validator_set import Validator, ValidatorSet

from ..harness import chain as rawchain
from ..harness import spec, stats
from ..reference import blocksync_ref as ref
from . import sync_script
from .vote_round import EXIT_CANNOT_RUN

SIGS = "prom.cometbft_tpu_crypto_verify_batch_sigs_total{"
_BLOCK_REQUEST = "BlockRequestMessage"
_STATUS_REQUEST = "StatusRequestMessage"


def _sr25519_validator_key() -> bool:
    """Whether a validator set may hold an sr25519 key: its hash
    proto-encodes every key (tendermint.crypto.PublicKey)."""
    from cometbft_tpu.types.validator_set import pubkey_proto_encode

    try:
        pubkey_proto_encode(Sr25519PubKey(bytes(32)))
    except ValueError:
        return False
    return True


def preflight() -> None:
    """This deployment needs a switch that admits a peer that came by no
    connection, sr25519 keys in a validator set and a verifier for a
    mixed set. A program without them cannot run the cell: said here,
    before any set-up, by exit code 5 and no result line."""
    import sys

    from cometbft_tpu.p2p.switch import Switch

    missing = [what for what, ok in (
        ("p2p.Switch.admit_peer", hasattr(Switch, "admit_peer")),
        ("crypto.batch.MixedBatchVerifier",
         hasattr(crypto_batch, "MixedBatchVerifier")),
        ("sr25519 in tendermint.crypto.PublicKey", _sr25519_validator_key()),
    ) if not ok]
    if missing:
        print(f"benchmark: the program lacks {missing}: the cell is not "
              "measured on this program", file=sys.stderr)
        raise SystemExit(EXIT_CANNOT_RUN)


class SyncPeer:
    """One neighbour that has the whole chain: the peer contract the
    reactors use (id/start/stop/is_running/send/try_send/get/set). What
    the node asks of it on the block-sync channel is answered on the
    peer's own thread, as a connection's receive routine would hand it
    over; everything else the node sends it is dropped."""

    persistent = False
    socket_addr = ""

    def __init__(self, net: "SyncNet", index: int, outbound: bool):
        self.net = net
        self.index = index
        self.outbound = outbound
        self.id = rawchain.seed_bytes(net.seed, "peer", index)[:20].hex()
        self._data: dict = {}
        self._running = False
        self._inbox: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._serve, name=f"bench-peer-{index}", daemon=True)

    def start(self) -> None:
        self._running = True
        self._thread.start()

    def stop(self) -> None:
        if self._running:
            self._running = False
            self.net.stopped.append((time.monotonic(), self.index))
        self._inbox.put(None)

    def is_running(self) -> bool:
        return self._running

    def send(self, ch_id: int, msg: bytes) -> bool:
        if ch_id == BLOCKSYNC_CHANNEL:
            m = json.loads(msg)
            if m.get("__t") == _BLOCK_REQUEST:
                self.net.requests.append(
                    (time.monotonic(), m["height"], self.index))
                self._inbox.put(m["height"])
            elif m.get("__t") == _STATUS_REQUEST:
                self._inbox.put(0)
        return True

    try_send = send

    def set(self, key: str, value) -> None:
        self._data[key] = value

    def get(self, key: str):
        return self._data.get(key)

    def _serve(self) -> None:
        while True:
            height = self._inbox.get()
            if height is None:
                return
            if not self._running or not self.net.serving:
                continue
            with self.net.lock:
                self.net.in_flight += 1
            try:
                self.net.hand(self, self.net.answer(self.index, height))
            finally:
                with self.net.lock:
                    self.net.in_flight -= 1


class SyncNet:
    """The scripted peers' side of the wire and its record: every block
    handed to the node, in order, with the peer and whether it was the
    altered copy; every request; every peer the node stopped."""

    def __init__(self, seed: int, n_peers: int, n_outbound: int, driver):
        self.seed = seed
        self.driver = driver
        self.peers = [
            SyncPeer(self, i, i < n_outbound) for i in range(n_peers)]
        self.serving = True
        self.deliveries: list = []  # (monotonic, height, peer, altered)
        self.requests: list = []  # (monotonic, height, peer)
        self.stopped: list = []  # (monotonic, peer)
        self.faults: list = []
        self.in_flight = 0  # answers being handed to the reactor
        self._served_altered: set = set()
        self.lock = threading.Lock()

    def answer(self, index: int, height: int) -> bytes:
        d = self.driver
        if height == 0:
            return d.status_wire
        if not 1 <= height <= d.n_heights:
            return ser.dumps(NoBlockResponseMessage(height=height))
        altered = False
        if height in d.altered_wire:
            with self.lock:
                altered = height not in self._served_altered
                self._served_altered.add(height)
        self.deliveries.append((time.monotonic(), height, index, altered))
        return d.altered_wire[height] if altered else d.script[height].wire

    def hand(self, peer: SyncPeer, msg: bytes) -> None:
        """As the switch hands a peer's message to a reactor: an error
        stops the peer (Switch._on_peer_receive)."""
        node = self.driver.node
        try:
            node.blocksync_reactor.receive(BLOCKSYNC_CHANNEL, peer, msg)
        except Exception as e:
            self.faults.append(repr(e)[:200])
            node.switch.stop_and_remove_peer(peer, e)


class Height:
    """One scripted height: its block's id and wire bytes, the app hash
    its header carries and the one after it, and the commit for it (every
    validator's precommit signature, in set order)."""

    __slots__ = ("block_id", "app_hash", "app_hash_after", "wire", "sigs")

    def plain_block(self):
        psh = self.block_id.part_set_header
        return (self.block_id.hash, psh.total, psh.hash)


class Driver:
    def __init__(self, cell, seed: int, tracer):
        self.cell, self.seed, self.tracer = cell, seed, tracer
        self.cfg, self.mix = cell.config, cell.mix
        self.chain_id = self.cfg["chain_id"]
        self.marks = stats.Marks()
        self.node = None
        self.home = os.path.join(spec.ROOT, ".bench_home", cell.name)
        self.applied: list = []  # (monotonic, height) of NewBlock events
        self._sub = None

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
        t = time.monotonic()
        self._make_validators(cfg["validators_ed25519"],
                              cfg["validators_sr25519"])
        n = len(self.pubkeys)
        self.power = rawchain.VOTING_POWER
        self.light = ref.light_lanes(n, self.power)
        self.lanes_per_block = self.light + n
        self.n_warm = mix["warmup_heights"]
        self.n_heights = self.n_warm + 2 + int(
            mix["list_over_knee"] * mix["knee_sigs_per_s"] * seconds
            / self.lanes_per_block)
        self.plan = sync_script.fault_plan(
            self.seed, self.n_warm, self.schemes, self.light,
            mix["fault_offsets"])
        if self.plan[-1]["height"] + 2 > self.n_heights:
            raise RuntimeError("the script is shorter than its faults")
        shutil.rmtree(self.home, ignore_errors=True)
        if cli_main(["--home", self.home, "init"]) != 0:
            raise RuntimeError("cmd init failed")
        node_cfg = _config(Namespace(
            home=self.home, rpc_laddr=f"tcp://127.0.0.1:{_free_port()}",
            p2p_laddr=f"tcp://127.0.0.1:{_free_port()}",
        ))
        if node_cfg.base.db_backend != "file":
            raise RuntimeError("the stores are not file-backed")
        node_cfg.base.block_sync = cfg["block_sync"]
        genesis = self._genesis()
        path = node_cfg.base.resolve(node_cfg.base.genesis_file)
        with open(path, "w") as f:
            f.write(genesis.to_json())
        self._check_genesis(path)
        t = self.marks.add(
            f"{n} keys ({cfg['validators_sr25519']} sr25519), cmd init, "
            "genesis", t)
        self._build_script(genesis)
        t = self.marks.add(
            f"{self.n_heights} heights scripted: blocks, {n} precommits "
            "each signed", t)
        self._warm_shapes(mix["warm_buckets"])
        t = self.marks.add("ed25519 key tables, verify shapes", t)
        self.node = default_new_node(node_cfg)
        self.node.start()
        pv = self.node.consensus.priv_validator_pub_key
        if pv is not None and self.vals.has_address(bytes(pv.address())):
            raise RuntimeError("the node under test is a validator")
        from cometbft_tpu.types.event_bus import (
            EVENT_NEW_BLOCK, query_for_event,
        )

        self._sub = self.node.event_bus.subscribe(
            "benchmark", query_for_event(EVENT_NEW_BLOCK), capacity=None)
        threading.Thread(target=self._listen, name="bench-newblock",
                         daemon=True).start()
        self.net = SyncNet(self.seed, mix["peers"], mix["peers_outbound"],
                           self)
        for peer in self.net.peers:
            self.node.switch.admit_peer(peer)
        for peer in self.net.peers:
            # what a connected peer does on AddPeer: its own status
            self.net.hand(peer, self.status_wire)
        t = self.marks.add(
            f"node boot, {len(self.net.peers)} scripted peers admitted", t)
        if not self._wait_applied(self.n_warm, mix["warmup_wait_s"]):
            raise RuntimeError("the node did not sync the warm-up heights")
        if not ov.WARM.wait_idle(600):
            raise RuntimeError("background compiles did not finish")
        if self.tracer.enabled:
            # the ring holds the window's records, not set-up's
            from cometbft_tpu.libs import trace as libtrace

            libtrace.reset()
        self.marks.add(f"warm-up: {self.n_warm} heights synced", t)

    def _make_validators(self, n_ed: int, n_sr: int) -> None:
        """Both schemes' keys from the seed; the set's order is ascending
        address (equal powers), so the schemes interleave as their
        addresses fall."""
        seed = self.seed
        keys = [(ref.ED, i, sync_script.ed_pubkey(seed, i))
                for i in range(n_ed)]
        self.sr_x: dict = {}
        for i in range(n_sr):
            x = sync_script.sr_scalar(seed, i)
            self.sr_x[i] = x
            keys.append((ref.SR, i, _ristretto_base_mult(x)))
        vals = ValidatorSet([
            Validator(Ed25519PubKey(pk) if scheme == ref.ED
                      else Sr25519PubKey(pk), voting_power=rawchain.VOTING_POWER)
            for scheme, _i, pk in keys])
        where = {pk: (scheme, i) for scheme, i, pk in keys}
        self.vals = vals
        self.pubkeys = [v.pub_key.data for v in vals.validators]
        self.addresses = [bytes(v.address) for v in vals.validators]
        if self.addresses != sorted(self.addresses):
            raise RuntimeError(
                "the program orders this validator set otherwise than by "
                "ascending address")
        self.schemes = [where[pk][0] for pk in self.pubkeys]
        self.key_of = [where[pk][1] for pk in self.pubkeys]
        # sr25519 signing nonces, R = [r]B, a few a key
        k = sync_script.NONCES_PER_KEY
        self.sr_nonces = {
            i: [(r, _ristretto_base_mult(r)) for r in (
                sync_script.sr_nonce(seed, i, j) for j in range(k))]
            for i in range(n_sr)
        }

    def _genesis(self):
        from cometbft_tpu.types import GenesisDoc, GenesisValidator
        from cometbft_tpu.types.params import (
            ConsensusParams, ValidatorParams,
        )

        doc = GenesisDoc(
            chain_id=self.chain_id,
            genesis_time_ns=rawchain.BASE_TIME_NS,
            validators=[GenesisValidator(pub_key=v.pub_key,
                                         power=v.voting_power)
                        for v in self.vals.validators],
            consensus_params=ConsensusParams(validator=ValidatorParams(
                pub_key_types=(ref.ED, ref.SR))),
        )
        doc.validate_and_complete()
        return doc

    def _check_genesis(self, path: str) -> None:
        """The file reads back, through GenesisDoc, to the set's keys and
        schemes."""
        from cometbft_tpu.types import GenesisDoc

        with open(path) as f:
            doc = GenesisDoc.from_json(f.read())
        got = sorted((v.pub_key.type, v.pub_key.data)
                     for v in doc.validators)
        if got != sorted(zip(self.schemes, self.pubkeys)):
            raise RuntimeError("the genesis file reads back otherwise")

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
        return BlockExecutor(_Unsaved(), conns.consensus), shaker.state, conns

    def _sign_sr(self, h: int, tpl, stamps) -> dict:
        """The sr25519 lanes' precommit signatures of the commit for
        height ``h``: schnorrkel v1, substrate context, nonce h mod
        NONCES_PER_KEY of each key, with the program's batched merlin
        challenges (the reference verifies a sample with its own)."""
        pos = [p for p, s in enumerate(self.schemes) if s == ref.SR]
        nonces = [self.sr_nonces[self.key_of[p]][h % sync_script.NONCES_PER_KEY]
                  for p in pos]
        ks = prog_sr.challenge_scalars_batch(
            [self.pubkeys[p] for p in pos],
            [ref.sign_bytes(tpl, stamps[p]) for p in pos],
            [r_enc + bytes(32) for _r, r_enc in nonces])
        out = {}
        for p, (r, r_enc), k in zip(pos, nonces, ks):
            s = (r + k * self.sr_x[self.key_of[p]]) % ref.L
            s_bytes = bytearray(s.to_bytes(32, "little"))
            s_bytes[31] |= 0x80  # schnorrkel v1 marker
            out[p] = r_enc + bytes(s_bytes)
        return out

    def _build_script(self, genesis) -> None:
        n = len(self.pubkeys)
        executor, state, conns = self._shadow_executor(genesis)
        ed_pos = [p for p, s in enumerate(self.schemes) if s == ref.ED]
        self.script: dict[int, Height] = {}
        self.altered_wire: dict[int, bytes] = {}
        faults = {f["height"]: f for f in self.plan}
        last_commit = None
        wire_head = b'{"__t":"BlockResponseMessage","block":'
        wire_tail = b',"ext_commit":null}'
        try:
            with rawchain.spawn_pool() as pool:
                k = pool._max_workers
                slices = [ed_pos[i::k] for i in range(k)]
                for h in range(1, self.n_heights + 1):
                    proposer = state.validators.get_proposer().address
                    block = state.make_block(
                        h, [], last_commit, [], proposer,
                        sync_script.block_time_ns(h))
                    raw = ser.dumps(block)
                    parts = PartSet.from_data(raw)
                    bid = BlockID(block.hash(), parts.header)
                    sc = self.script[h] = Height()
                    sc.block_id = bid
                    sc.app_hash = state.app_hash
                    sc.wire = wire_head + raw + wire_tail
                    if h == 1 and sc.wire != ser.dumps(BlockResponseMessage(
                            block=block, ext_commit=None)):
                        raise RuntimeError(
                            "the block response's encoding has moved")
                    if h in faults:
                        self.altered_wire[h] = self._altered_block_wire(
                            state, block, last_commit, faults[h], proposer)
                    stamps = sync_script.commit_timestamps(h, n)
                    tpl = ref.template(self.chain_id, h, sc.plain_block())
                    jobs = [(self.seed, [(p, self.key_of[p]) for p in sl],
                             tpl, stamps) for sl in slices if sl]
                    pending = pool.map(sync_script.sign_ed_job, jobs)
                    sigs = self._sign_sr(h, tpl, stamps)
                    for part in pending:
                        sigs.update(part)
                    sc.sigs = [sigs[p] for p in range(n)]
                    last_commit = Commit(
                        height=h, round=0, block_id=bid,
                        signatures=[
                            CommitSig(BLOCK_ID_FLAG_COMMIT,
                                      self.addresses[p], stamps[p],
                                      sc.sigs[p])
                            for p in range(n)
                        ])
                    state, resp = executor.begin_apply(state, bid, block)
                    executor.complete_apply(state, bid, block, resp)
                    sc.app_hash_after = state.app_hash
        finally:
            conns.stop()
        self.status_wire = ser.dumps(StatusResponseMessage(
            height=self.n_heights, base=1))

    def _altered_block_wire(self, state, block, last_commit, fault,
                            proposer) -> bytes:
        """Block ``fault["height"]`` as an altered peer serves it: one
        signature bit flipped in one lane of its LastCommit, every other
        field as the sound block has it (its hash then differs)."""
        lane, bit = fault["lane"], fault["bit"]
        sigs = list(last_commit.signatures)
        cs = sigs[lane]
        sigs[lane] = CommitSig(cs.block_id_flag, cs.validator_address,
                               cs.timestamp_ns,
                               rawchain.flip_bit(cs.signature, bit))
        bad = Commit(height=last_commit.height, round=last_commit.round,
                     block_id=last_commit.block_id, signatures=sigs)
        altered = state.make_block(
            block.header.height, [], bad, [], proposer, block.header.time_ns)
        fault["altered_sig"] = sigs[lane].signature
        return ser.dumps(BlockResponseMessage(block=altered, ext_commit=None))

    def _warm_shapes(self, buckets) -> None:
        """The ed25519 keys' tables and an executable for every bucket a
        commit check launches (a cold shape would compile in the window).
        The sr25519 keys' tables are built by the warm-up heights, as a
        node builds them (prestage_validators skips sr25519 keys)."""
        crypto_batch.prestage_validators(self.vals)
        for b in buckets:
            ov.WARM.ready(("window", b))
        if not ov.WARM.wait_idle(900):
            raise RuntimeError("verify shapes did not finish compiling")
        if ov.WARM.failed:
            raise RuntimeError(f"verify shapes failed: {ov.WARM.failed}")

    def counters(self) -> dict:
        return {"arena": {"builds": ov._PUBKEY_CACHE.builds}}

    # -- the node's side -------------------------------------------------

    def _listen(self) -> None:
        """The node's NewBlock events, each stamped as it arrives: the
        end of its apply_block."""
        sub = self._sub
        while True:
            msg = sub.out.get()
            self.applied.append(
                (time.monotonic(), msg.data.block.header.height))

    def _wait_applied(self, height: int, timeout: float) -> bool:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.applied and self.applied[-1][1] >= height:
                return True
            time.sleep(0.05)
        return False

    def _freeze(self, timeout: float) -> dict:
        """Stop the node asking for blocks (its pool's own stop, as its
        switch to consensus does: no request goes out and none times out)
        and the peers answering, then wait until every answer handed over
        is through the reactor and the node has applied or refused what
        it holds: its tip and the peers it removed unchanged for 2 s.
        Returns the tip, the peers removed and the deliveries, as of
        then."""
        self.node.blocksync_reactor.pool.stop()
        self.net.serving = False
        deadline = time.monotonic() + timeout
        store = self.node.block_store

        def now():
            return (store.height(), len(self.net.stopped),
                    self.net.in_flight)

        last, since = now(), time.monotonic()
        while time.monotonic() < deadline:
            time.sleep(0.1)
            cur = now()
            if cur != last or cur[2]:
                last, since = cur, time.monotonic()
            elif time.monotonic() - since >= 2.0:
                break
        return {"tip": store.height(), "stopped": list(self.net.stopped),
                "deliveries": list(self.net.deliveries),
                "settled": time.monotonic() < deadline}

    # -- the measured window ---------------------------------------------

    def run_window(self, seconds: float) -> dict:
        """The node syncs on its own; the window only reads the clock of
        its NewBlock events. The peers go on serving after it (a traced
        run's profiler takes minutes to stop and be read, and the node
        may reach the script's end and switch to consensus meanwhile,
        which the window does not see): check() freezes the node first."""
        n_applied = len(self.applied)
        self.tracer.start()
        t0 = time.monotonic()
        t_end = t0 + seconds
        time.sleep(max(0.0, t_end - time.monotonic()))
        events = [(seen, h) for seen, h in self.applied[n_applied:]
                  if t0 < seen <= t_end]
        switched = self.node.blocksync_reactor.synced.is_set()
        self.tracer.stop()
        stop_s = time.monotonic() - t_end
        inside = [h for _seen, h in events]
        lanes = sum(self.light + (len(self.pubkeys) if h > 1 else 0)
                    for h in inside)
        gaps = [(b[0] - a[0]) * 1e3 for a, b in zip(events, events[1:])]
        return {
            "end_to_end": {"sigs_per_s": lanes / seconds},
            "attempted": len(inside),
            "failed": 0,
            "inside": inside, "switched": switched,
            "t0": t0, "t_end": t_end,
            "stats": {
                "blocks_applied_in_window": len(inside),
                "block_ms": seconds * 1e3 / len(inside) if inside else None,
                "block_gap_ms_p50": stats.percentile(gaps, 50),
                "block_gap_ms_p95": stats.percentile(gaps, 95),
                "heights_scripted": self.n_heights,
            },
            "notes": {"fault_heights": [f["height"] for f in self.plan],
                      "tracer_stop_s": round(stop_s, 2)},
        }

    def close(self) -> None:
        if self.node is not None:
            try:
                self.node.stop()
            finally:
                self.node = None
                shutil.rmtree(self.home, ignore_errors=True)

    # -- correctness -----------------------------------------------------

    def _commit_lanes(self, h: int, sigs, stamps, count: int):
        return ref.commit_lanes(
            self.chain_id, h, self.script[h].plain_block(), self.schemes,
            self.pubkeys, stamps, sigs, count)

    def _stored_commit(self, h: int, tip: int):
        """The commit the node's store holds for ``h``: the LastCommit of
        block h+1, or at the tip the commit it saw."""
        store = self.node.block_store
        commit = store.load_block_commit(h) if h < tip else \
            store.load_seen_commit()
        if commit is None or commit.height != h:
            return None
        return commit

    @staticmethod
    def _against_walk(walked: dict, tip: int, removed: set,
                      commits: dict):
        """Mismatches between the node, as far as it went, and a walk:
        heights applied, peers removed (a pair is refused with the tip's
        next height first at the latest), seen commits stored from an
        altered block."""
        want_applied = {h for h in walked["applied"] if h <= tip}
        mismatches = len(want_applied ^ set(range(1, tip + 1)))
        want_removed = set()
        for h, p1, p2 in walked["refused"]:
            if h <= tip + 1:
                want_removed.update((p1, p2))
        mismatches += len(removed ^ want_removed)
        # the node's stored commits are compared with the script's below:
        # a walk that stores an altered one disagrees with a sound node
        mismatches += sum(1 for h in commits if h in walked["altered_seen"])
        return mismatches, walked, want_removed

    def check(self, window: dict, control: str, ctx) -> dict:
        """What the node applied, stored and removed against the plain
        reference's walk over what the peers served; a sample of blocks
        and every altered block through the plain oracles. With
        ``control`` the control's verdicts stand in for the oracles' in
        the walk."""
        t_check = time.monotonic()
        frozen = self._freeze(self.mix["settle_seconds"])
        n = len(self.pubkeys)
        tip = frozen["tip"]
        inside = window["inside"]
        deliveries: dict = {}
        for _t, h, peer, altered in frozen["deliveries"]:
            deliveries.setdefault(h, []).append((peer, altered))
        t_frozen = time.monotonic()
        # the altered blocks' light checks, by the oracles and by each
        # control, and the sampled blocks by the oracles
        jobs, what = [], []
        for f in self.plan:
            x = f["height"]
            sigs = list(self.script[x - 1].sigs)
            sigs[f["lane"]] = f["altered_sig"]
            lanes = self._commit_lanes(
                x - 1, sigs, sync_script.commit_timestamps(x - 1, n),
                self.light)
            for name in ("", *ref.CONTROLS):
                jobs.append((lanes, name or None))
                what.append(("altered", name, x))
        store_faults = 0
        sample = ref.sampled(
            lambda *p: rawchain.seed_bytes(self.seed, *p), inside)
        # the commits the store holds around each sampled block and each
        # altered one, and the tip's seen commit; every block's meta
        wanted = {tip}
        for h in sample:
            wanted.update((h - 1, h))
        for f in self.plan:
            wanted.update(range(f["height"] - 2, f["height"] + 2))
        commits = {h: self._stored_commit(h, tip)
                   for h in sorted(wanted) if 1 <= h <= tip}
        for h in sample:
            for ch, count in ((h, self.light), (h - 1, n)):
                c = commits.get(ch)
                if c is None or ch < 1:
                    continue
                lanes = self._commit_lanes(
                    ch, [cs.signature for cs in c.signatures],
                    [cs.timestamp_ns for cs in c.signatures], count)
                for lo in range(0, count, 256):
                    jobs.append((lanes[lo:lo + 256], None))
                    what.append(("sample", ch, count))
        with rawchain.spawn_pool() as pool:
            results = list(pool.map(ref.lanes_job, jobs))
        light_ok: dict = {}
        sample_bits: dict = {}
        for w, bits in zip(what, results):
            if w[0] == "altered":
                light_ok.setdefault(w[1], {})[w[2]] = all(bits)
            else:
                sample_bits.setdefault((w[1], w[2]), []).extend(bits)
        for (_ch, _count), bits in sample_bits.items():
            store_faults += ref.commit_faults(bits, self.power, n)
        removed = {p for _t, p in frozen["stopped"]}
        walks = {name: self._against_walk(
            ref.walk(deliveries, ok), tip, removed, commits)
            for name, ok in light_ok.items()}
        mismatches, walked, want_removed = walks[control]
        store = self.node.block_store
        bad_heights = []
        for h in range(1, tip + 1):
            sc = self.script[h]
            meta = store.load_block_meta(h)
            if meta is None or meta.block_id != sc.block_id \
                    or meta.header.app_hash != sc.app_hash:
                store_faults += 1
                bad_heights.append(h)
        for h, c in commits.items():
            sc = self.script[h]
            if c is None or c.block_id != sc.block_id or [
                    cs.signature for cs in c.signatures] != sc.sigs or [
                    cs.timestamp_ns for cs in c.signatures
            ] != sync_script.commit_timestamps(h, n) or any(
                    cs.block_id_flag != BLOCK_ID_FLAG_COMMIT
                    for cs in c.signatures):
                store_faults += 1
                bad_heights.append(("commit", h))
        state = self.node.state_store.load()
        if state.last_block_height != tip or \
                state.app_hash != self.script[tip].app_hash_after:
            store_faults += 1
        c = ctx.counters
        counted = sum(v for key, v in c.items() if key.startswith(SIGS))
        # the first block's light check may have run before the window
        needed = max(0, sum(
            self.light + (n if h > 1 else 0) for h in inside) - self.light)
        notes = window.setdefault("notes", {})
        notes.update(
            tip=tip, settled=frozen["settled"], bad_heights=bad_heights[:8],
            check_s={"freeze": round(t_frozen - t_check, 2),
                     "rest": round(time.monotonic() - t_frozen, 2)},
            hand_faults=self.net.faults[:5],
            walk_refused=walked["refused"],
            walk_removed=sorted(want_removed),
            removed=sorted(removed), sampled=sample,
            lanes_counted=counted, lanes_needed=needed,
            altered_refused={x: not ok for x, ok in light_ok[""].items()},
            control_mismatches={name: m for name, (m, _w, _r)
                                in walks.items() if name},
        )
        return {
            "walk_mismatches": {"value": mismatches, "limit": 0},
            "stored_block_or_commit_faults": {
                "value": store_faults, "limit": 0},
            "lanes_needed_minus_counted": {
                "value": max(0, needed - counted), "limit": 0},
            "compiles_in_window": {
                "value": c.get("devstats.compiles", 0), "limit": 0},
            "key_tables_built_in_window": {
                "value": c.get("arena.builds", 0), "limit": 0},
            "dispatch_faults": {
                "value": sum(v for key, v in c.items()
                             if key.startswith("faults.")), "limit": 0},
            # the node applies a height once the next one is in: the
            # script's last appliable height applied inside the window
            # means the window ran out of blocks
            "script_exhausted_in_window_or_not_settled": {
                "value": int(max(inside, default=0) >= self.n_heights - 1)
                + int(not frozen["settled"]), "limit": 0},
            "switched_to_consensus_in_window": {
                "value": int(window["switched"]), "limit": 0},
            "hand_faults": {"value": len(self.net.faults), "limit": 0},
        }


class _Unsaved:
    """The shadow executor's state store: the script needs the app's
    hashes and the proposer's rotation, not a record of the states (a
    4,096-validator state takes a third of a second to encode)."""

    def save(self, state) -> None:
        pass

    def save_finalize_block_response(self, height: int, resp) -> None:
        pass


def _ristretto_base_mult(x: int) -> bytes:
    """The ristretto255 encoding of [x]B through the program's native base
    multiplication (data for set-up; the reference decodes it with its
    own)."""
    return prog_sr.ristretto_encode(host_batch.scalar_base_mult(x))
