"""A full node following a chain with vote extensions through its
consensus reactor: vote_round's node, peers, feeder and window (imported,
not edited) on a genesis with ``abci.vote_extensions_enable_height = 1``,
where every non-nil precommit carries an extension and the extension's
signature, and one more kind of altered copy arrives (a precommit whose
extension signature has a flipped bit).

What differs from vote_round: the genesis; the script (each precommit's
seeded extension, signed over the reference's CanonicalVoteExtension
sign-bytes, in the program's own wire encoding); the count (a non-nil
precommit announced is two signatures verified, a prevote one); and the
comparison, against reference/vote_ext_ref.py: admissions with both
signatures, the extensions the application was shown (counted at the
application the node built, a vote at a time), and the extended commit
the block store holds, extensions letter for letter.
"""

from __future__ import annotations

import time

from cometbft_tpu.consensus import messages as cmsg
from cometbft_tpu.crypto.keys import Ed25519PubKey
from cometbft_tpu.types import serialization as ser
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BlockID, Commit, CommitSig,
)
from cometbft_tpu.types.part_set import PartSet
from cometbft_tpu.types.vote import Proposal, Vote

from ..harness import chain as rawchain
from ..reference import ed25519_oracle as oracle
from ..reference import vote_ext_ref as ref
from . import verdicts, vote_ext_script, vote_round, vote_script

TYPES = vote_ext_script.TYPES
PRECOMMIT = ref.PRECOMMIT
SIGS, COALESCE_LANES = vote_round.SIGS, vote_round.COALESCE_LANES

# name -> (stand-in for the vote lanes, for the extension lanes); None =
# the oracle. ``votes_only`` is the step that would tempt a later PR here:
# verify the votes, take every extension signature for sound.
CONTROLS = {
    "stride8": (verdicts.CONTROLS["stride8"],) * 2,
    "trust_all": (verdicts.CONTROLS["trust_all"],) * 2,
    "votes_only": (None, vote_ext_script.take_for_sound),
}


def preflight(genesis) -> None:
    """This deployment is a node started from a genesis file with vote
    extensions enabled. A program whose genesis file does not carry
    ``consensus_params`` builds a node that refuses every extension
    ('unexpected vote extension data') and never commits: said here,
    before any set-up, by exit code 5 and no result line."""
    import sys

    from cometbft_tpu.types import GenesisDoc

    read = GenesisDoc.from_json(genesis.to_json())
    if read.consensus_params != genesis.consensus_params:
        print("benchmark: the program's genesis file drops consensus_params "
              "(abci.vote_extensions_enable_height): the cell is not "
              "measured on this program", file=sys.stderr)
        raise SystemExit(vote_round.EXIT_CANNOT_RUN)


class Height(vote_round.Height):
    """One scripted height; ``exts[pos]`` = (extension, its signature) of
    validator ``pos``'s precommit; ``ext_altered`` = the positions whose
    altered precommit copy has its flipped bit in the extension signature
    (``mangled[PRECOMMIT][pos][0]`` is then that signature, the vote's
    own being sound)."""

    __slots__ = ("exts", "ext_altered")

    def deliveries(self):
        """Every delivery as the reference reads it, in arrival order:
        (msg_type, index, timestamp_ns, signature, extension, extension
        signature, nil)."""
        out = []
        for t in TYPES:
            for kind, pos, _peer in self.waves[t][0]:
                ts, sig = self.votes[t][pos]
                ext, ext_sig = self.exts[pos] if t == PRECOMMIT else (b"", b"")
                if kind == vote_ext_script.MANGLED:
                    if t == PRECOMMIT and pos in self.ext_altered:
                        ext_sig = self.mangled[t][pos][0]
                    else:
                        sig = self.mangled[t][pos][0]
                out.append((t, pos, ts, sig, ext, ext_sig, False))
        return out


class Driver(vote_round.Driver):
    def __init__(self, cell, seed: int, tracer):
        super().__init__(cell, seed, tracer)
        # vote_round's set-up scripts list_over_knee x knee x window /
        # (2 x validators) heights, a vote for a signature; here a height
        # is 3 x validators signatures
        self.mix = dict(self.mix, knee_sigs_per_s=(
            self.mix["knee_sigs_per_s"] * 2.0 / 3.0))
        self.shown: dict = {}  # (height, address) -> VerifyVoteExtension calls

    # -- set-up ----------------------------------------------------------

    def setup(self, seconds: float) -> None:
        super().setup(seconds)
        # the application the node built, watched at its own door: which
        # extensions the node showed it (the warm-up's are not compared)
        app = self.node.proxy_app.consensus.app
        inner, shown = app.verify_vote_extension, self.shown

        def watched(req):
            key = (req.height, req.validator_address)
            shown[key] = shown.get(key, 0) + 1
            return inner(req)

        app.verify_vote_extension = watched

    def _genesis(self):
        from cometbft_tpu.types import GenesisDoc, GenesisValidator
        from cometbft_tpu.types.params import ABCIParams, ConsensusParams

        doc = GenesisDoc(
            chain_id=self.chain_id,
            genesis_time_ns=rawchain.BASE_TIME_NS,
            consensus_params=ConsensusParams(abci=ABCIParams(
                vote_extensions_enable_height=self.cfg[
                    "vote_extensions_enable_height"])),
            validators=[
                GenesisValidator(pub_key=Ed25519PubKey(pk),
                                 power=rawchain.VOTING_POWER)
                for pk in self.raw_vals.pubkeys
            ],
        )
        doc.validate_and_complete()
        preflight(doc)
        return doc

    def _wire_template(self, msg_type: int, height: int, block_id):
        """vote_round's template for a prevote; a precommit's is cut
        around its extension and extension signature too."""
        if msg_type != PRECOMMIT:
            return super()._wire_template(msg_type, height, block_id)
        ts, idx = 1_111_111_111_111_111_111, 987_654_321
        addr, sig = b"\xa5" * 20, b"\xb6" * 64
        ext, ext_sig = b"\xc7" * 40, b"\xd8" * 64
        text = ser.dumps(cmsg.VoteMessage(Vote(
            msg_type, height, 0, block_id, ts, addr, idx, sig, ext,
            ext_sig))).decode()
        parts = []
        for mark in (str(ts), addr.hex(), str(idx), sig.hex(), ext.hex(),
                     ext_sig.hex()):
            head, found, text = text.partition(mark)
            if not found or mark in text:
                raise RuntimeError("the vote message's encoding has moved")
            parts.append(head)
        parts.append(text)
        return parts

    def _build_script(self, genesis) -> None:
        """vote_round's script with each precommit's extension: blocks
        from a shadow executor, votes and extensions signed in a pool, the
        waves with their second copies and altered copies."""
        n, raw, mix = len(self.raw_vals), self.raw_vals, self.mix
        ext_size = self.cfg["extension_bytes"]
        executor, state, conns = self._shadow_executor(genesis)
        position = {a: i for i, a in enumerate(raw.addresses)}
        self.script = {}
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
                        self.seed, "proposer", h)[:4], "big") % mix["peers"]
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
                         self.chain_id, h, sc.plain_block(), wires, ext_size)
                        for sl in slices if sl
                    ]
                    sc.votes = {t: [None] * n for t in TYPES}
                    sc.wire = {t: [None] * n for t in TYPES}
                    sc.exts = [None] * n
                    for part in pool.map(vote_ext_script.sign_job, jobs):
                        for t, pos, ts, sig, ext, ext_sig, wire in part:
                            sc.votes[t][pos] = (ts, sig)
                            sc.wire[t][pos] = wire
                            if t == PRECOMMIT:
                                sc.exts[pos] = (ext, ext_sig)
                    sc.waves, sc.mangled = {}, {}
                    for t in TYPES:
                        *sc.waves[t], altered = vote_ext_script.wave(
                            self.seed, h, t, n, mix)
                        if t == PRECOMMIT:
                            sc.ext_altered = altered
                        sc.mangled[t] = {}
                        for kind, pos, _p in sc.waves[t][0]:
                            if kind != vote_ext_script.MANGLED:
                                continue
                            ts, sig = sc.votes[t][pos]
                            ext, ext_sig = (
                                sc.exts[pos] if t == PRECOMMIT else (b"", b""))
                            if pos in altered:
                                ext_sig = bad = \
                                    vote_ext_script.mangle_extension_signature(
                                        self.seed, h, pos, ext_sig)
                            else:
                                sig = bad = vote_script.mangle(
                                    self.seed, h, t, pos, sig)
                            sc.mangled[t][pos] = (
                                bad, vote_ext_script.fill_wire(
                                    wires[t], ts, raw.addresses[pos], pos,
                                    sig, ext, ext_sig))
                    self._check_wire(sc, h)
                    last_commit = Commit(
                        height=h, round=0, block_id=bid,
                        signatures=[
                            CommitSig(BLOCK_ID_FLAG_COMMIT, raw.addresses[i],
                                      *sc.votes[PRECOMMIT][i])
                            for i in range(n)
                        ])
                    state, resp = executor.begin_apply(state, bid, block)
                    executor.complete_apply(state, bid, block, resp)
                    sc.app_hash_after = state.app_hash
        finally:
            conns.stop()

    def _check_wire(self, sc: Height, h: int) -> None:
        """The filled templates decode to the votes they stand for."""
        super()._check_wire(sc, h)
        pos = h % len(self.raw_vals)
        ts, sig = sc.votes[PRECOMMIT][pos]
        want = cmsg.VoteMessage(Vote(
            PRECOMMIT, h, 0, sc.block_id, ts, self.raw_vals.addresses[pos],
            pos, sig, *sc.exts[pos]))
        if ser.loads(sc.wire[PRECOMMIT][pos]) != want:
            raise RuntimeError("a scripted vote's wire bytes decode otherwise")

    # -- the feeder ------------------------------------------------------

    def _on_vote(self, vote) -> None:
        """What the node admitted, with both signatures."""
        self.admitted.append((
            vote.height, vote.msg_type, vote.validator_index, vote.signature,
            vote.extension_signature))

    # -- the measured window ---------------------------------------------

    def run_window(self, seconds: float) -> dict:
        window = super().run_window(seconds)
        # a vote at a time: one signature a prevote, two a non-nil
        # precommit (its own and its extension's), announced in the window
        inside = sum(
            2 if t == PRECOMMIT else 1
            for (_h, t, _i), seen in window["announced"].items()
            if seen[0] <= window["t_end"])
        window["end_to_end"] = {"sigs_per_s": inside / seconds}
        window["stats"]["sigs_announced_in_window"] = inside
        return window

    # -- correctness -----------------------------------------------------

    def _stored_extended(self, h: int):
        """The extended commit the node's block store holds for ``h``."""
        ec = self.node.block_store.load_block_extended_commit(h)
        if ec is None or ec.height != h:
            return None, 0
        psh = ec.block_id.part_set_header
        return ((ec.block_id.hash, psh.total, psh.hash), [
            (i, es.commit_sig.timestamp_ns, es.commit_sig.signature,
             es.extension, es.extension_signature)
            for i, es in enumerate(ec.extended_signatures)
            if es.commit_sig.block_id_flag != BLOCK_ID_FLAG_ABSENT
        ]), ec.round

    def check(self, window: dict, control: str, ctx) -> dict:
        """For every height fed in the window: the votes the node admitted
        (both signatures) against those the reference admits from the same
        deliveries, both ways; its HasVotes against its admissions; the
        extensions its application was shown against those the reference
        shows, validator by validator; the commits and the extended commit
        its store holds, and the hashes, against the reference and the
        script. With ``control`` the control's admissions and showings
        stand in for the node's."""
        played = window["played"]
        first, last = played["first"], window["last"]
        heights = list(range(first, last + 1))
        store = self.node.block_store
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and heights and (
                store.height() < last
                or self.node.state_store.load().last_block_height < last):
            time.sleep(0.05)
        tip = store.height()
        pubkeys, addresses = self.raw_vals.pubkeys, self.raw_vals.addresses
        jobs, stored, rounds = [], {}, []
        for h in heights:
            sc = self.script[h]
            stored[h] = self._stored_commits(h, tip)
            extended, ec_round = self._stored_extended(h)
            rounds += [r for _b, _s, r in stored[h]] + [ec_round]
            jobs.append((
                self.chain_id, h, sc.plain_block(), pubkeys,
                rawchain.VOTING_POWER, sc.deliveries(),
                [(blk, sigs) for blk, sigs, _round in stored[h]], extended,
                sc.exts, CONTROLS[control] if control else None,
            ))
        with rawchain.spawn_pool() as pool:
            results = list(pool.map(ref.height_job, jobs))
        got: dict = {h: set() for h in heights}
        twice = 0
        for h, *vote in window["admitted"]:
            if h in got:
                twice += tuple(vote) in got[h]
                got[h].add(tuple(vote))
        announced = window["announced"]
        vote_mismatches = has_vote_faults = commit_faults = hash_faults = 0
        app_faults = needed = admitted_extended = shown_total = 0
        for h, (want, want_shown, stand_in, faults, verified) in zip(
                heights, results):
            node_shown = {
                i: self.shown[(h, a)] for i, a in enumerate(addresses)
                if (h, a) in self.shown}
            node_set = got[h]
            if control:
                node_set, node_shown = stand_in
            vote_mismatches += len(node_set ^ want)
            app_faults += sum(
                abs(node_shown.get(i, 0) - want_shown.get(i, 0))
                for i in node_shown.keys() | want_shown.keys())
            shown_total += sum(node_shown.values())
            admitted_extended += sum(
                1 for t, _i, _s, _es in node_set if t == PRECOMMIT)
            said = {(t, idx) for (hh, t, idx) in announced if hh == h}
            has_vote_faults += len(
                said ^ {(t, idx) for t, idx, _s, _es in got[h]})
            has_vote_faults += sum(
                len(seen) - 1 for (hh, _t, _i), seen in announced.items()
                if hh == h)
            commit_faults += faults + (0 if stored[h] else 1)
            meta = store.load_block_meta(h)
            sc = self.script[h]
            if meta is None or meta.block_id != sc.block_id \
                    or meta.header.app_hash != sc.app_hash:
                hash_faults += 1
            # the height's votes and extensions, and its block's LastCommit
            needed += verified + (len(pubkeys) if h > 1 else 0)
        state = self.node.state_store.load()
        if heights and (state.last_block_height != last or
                        state.app_hash != self.script[last].app_hash_after):
            hash_faults += 1
        rounds_above_0 = sum(1 for r in rounds if r != 0) + sum(
            1 for seen, h, r, _s in self.net.steps
            if r > 0 and first <= h <= last)
        c = ctx.counters
        lone = sum(v for key, v in c.items() if key.startswith(SIGS)
                   and 'backend="ed25519-coalesce"' not in key)
        routed = {key[len(COALESCE_LANES):-1]: v for key, v in c.items()
                  if key.startswith(COALESCE_LANES)}
        counted = lone + sum(routed.values())
        window.setdefault("notes", {}).update(
            heights_checked=len(heights), admitted_twice=twice,
            lanes_counted=counted, lanes_needed=needed,
            coalesce_lanes_by_route=routed, tip=tip,
            extensions_shown_to_app=shown_total,
            precommits_admitted=admitted_extended,
        )
        return {
            "vote_mismatches": {"value": vote_mismatches + twice, "limit": 0},
            "has_vote_faults": {"value": has_vote_faults, "limit": 0},
            "app_shown_extension_mismatches": {
                "value": app_faults, "limit": 0},
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
