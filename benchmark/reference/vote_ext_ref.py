"""Plain reference for one height of a consensus round on a chain with
vote extensions (ABCI 2.0: types/vote.go VerifyVoteAndExtension:233,
VerifyExtension:254, consensus/state.go addVote:2207-2215, types/block.go
ExtendedCommit): which delivered votes a correct node admits, which
extensions it shows its application, whether a stored extended commit is
sound, and how many signatures that took. Its own walk over the arrival
script and its own CanonicalVoteExtension encoder beside canonical.py's
CanonicalVote; nothing of the program, no batching, no memo, no device.

proto/tendermint/types/canonical.proto, CanonicalVoteExtension:
  1 extension (bytes)   2 height (sfixed64)   3 round (sfixed64)
  4 chain_id (string)
framed as protoio.MarshalDelimited, zero-valued scalars omitted.

A delivery is ``(msg_type, validator_index, timestamp_ns, signature,
extension, extension_signature, nil)`` in the order the script handed it
to the node; ``nil`` says the vote is for no block. The node, with
extensions enabled at the height:

* refuses a prevote or a nil precommit that carries an extension or an
  extension signature;
* for a non-nil precommit first checks the extension signature against
  the key at the vote's index over the canonical sign-bytes of the
  extension (a missing signature fails); only then shows the extension to
  the application, whatever becomes of the vote afterwards (a second copy
  of a vote held is shown again: consensus/state.go calls the application
  before the VoteSet says it has the vote);
* then admits the vote if and only if no vote of that type from that
  validator was admitted before it and its signature verifies under the
  same key over the canonical sign-bytes of a round-0 vote;
* commits the block the moment the precommits it holds for it carry more
  than two thirds of the power (the script delivers the block and the
  prevotes first), and goes on to the next height. A precommit that
  arrives after that is a late one: it completes the commit the next
  block will carry, so it is admitted on the same two signatures, checked
  together at admission, and the application is not shown its extension
  (consensus/state.go addVote: LastCommit.AddVote); anything else of the
  height is dropped.

So a non-nil precommit is admitted only if both of its signatures verify,
and the application is never shown an extension whose signature has not
verified.
"""

from __future__ import annotations

from . import canonical
from . import ed25519_oracle as oracle
from .vote_round_ref import commit_faults, quorum, sign_bytes

PREVOTE, PRECOMMIT = 1, 2


def extension_sign_bytes(chain_id: str, height: int, round_: int,
                         extension: bytes) -> bytes:
    body = (
        canonical._bytes_field(1, extension)
        + canonical._sfixed64_field(2, height)
        + canonical._sfixed64_field(3, round_)
        + canonical._bytes_field(4, chain_id.encode())
    )
    return canonical._uvarint(len(body)) + body


def vote_template(msg_type: int, chain_id: str, height: int, round_: int,
                  block=None):
    """(prefix, suffix) of a CanonicalVote around its timestamp. ``block``
    is (hash, part-set total, part-set hash), or None for a nil vote,
    whose block id is left out."""
    block_id = b""
    if block is not None:
        block_hash, psh_total, psh_hash = block
        psh = (canonical._varint_field(1, psh_total)
               + canonical._bytes_field(2, psh_hash))
        block_id = (canonical._bytes_field(1, block_hash)
                    + canonical._bytes_field(2, psh, always=True))
    prefix = (
        canonical._varint_field(1, msg_type)
        + canonical._sfixed64_field(2, height)
        + canonical._sfixed64_field(3, round_)
        + canonical._bytes_field(4, block_id)
    )
    return prefix, canonical._bytes_field(6, chain_id.encode())


def templates(chain_id: str, height: int, block) -> dict:
    """The four templates of a round-0 height: (msg_type, nil) -> one."""
    return {
        (t, nil): vote_template(t, chain_id, height, 0,
                                None if nil else block)
        for t in (PREVOTE, PRECOMMIT) for nil in (False, True)
    }


def walk(chain_id: str, height: int, deliveries, tpls, pubkeys, power: int,
         verify_votes=None, verify_extensions=None):
    """The node's answers to ``deliveries`` in order:

    * the ``(msg_type, index, signature, extension_signature)`` it admits;
    * ``{index: n}``, how often it shows the application an extension of
      that validator;
    * how many signatures it had to verify.

    ``verify_votes`` / ``verify_extensions`` (a control) stand in for the
    oracle on the vote lanes / the extension lanes: each is handed its
    lanes at once, as a verifier that takes a drain is."""
    vote_lanes = [
        (pubkeys[idx], sign_bytes(tpls[(t, nil)], ts), sig)
        for t, idx, ts, sig, _e, _es, nil in deliveries
    ]
    extended = [
        k for k, (t, _i, _ts, _s, _e, _es, nil) in enumerate(deliveries)
        if t == PRECOMMIT and not nil
    ]
    ext_lanes = [
        (pubkeys[deliveries[k][1]],
         extension_sign_bytes(chain_id, height, 0, deliveries[k][4]),
         deliveries[k][5])
        for k in extended
    ]
    vote_ok = (verify_votes or oracle.verify_lanes)(vote_lanes)
    ext_ok = dict(zip(
        extended, (verify_extensions or oracle.verify_lanes)(ext_lanes)))
    need = quorum(len(pubkeys), power)
    held: dict = {}
    shown: dict = {}
    verified = for_block = 0
    for k, (t, idx, _ts, sig, ext, ext_sig, nil) in enumerate(deliveries):
        committed = for_block >= need
        if committed and t != PRECOMMIT:
            continue  # the node has gone on: only late precommits count
        if k in ext_ok:
            if not committed:
                # the height is live: the extension's signature, then
                # the application, then the vote
                verified += 1
                if not ext_ok[k]:
                    continue  # the application never sees it
                shown[idx] = shown.get(idx, 0) + 1
        elif ext or ext_sig:
            continue  # extension data where none may be
        have = held.get((t, idx))
        if have is not None and have[0] == sig:
            continue  # a second copy of a vote held: refused unchecked
        verified += 1
        if have is not None or not vote_ok[k]:
            continue
        if committed and k in ext_ok:
            # a late precommit completes the commit the next block will
            # carry: both signatures at admission, no application
            verified += 1
            if not ext_ok[k]:
                continue
        held[(t, idx)] = (sig, ext_sig)
        if t == PRECOMMIT and not nil:
            for_block += power
    admitted = {(t, idx, sig, ext_sig)
                for (t, idx), (sig, ext_sig) in held.items()}
    return admitted, shown, verified


def extended_commit_faults(chain_id: str, height: int, commit, template,
                           pubkeys, power: int, scripted) -> int:
    """Faults of a stored extended commit ``[(index, timestamp_ns,
    signature, extension, extension_signature)]``: a vote signature or an
    extension signature that does not verify, an extension or extension
    signature that is not, letter for letter, the one ``scripted[index]``
    = (extension, extension_signature) the validator signed, plus one if
    the entries sound in both signatures carry no more than two thirds of
    the power."""
    vote_ok = oracle.verify_lanes([
        (pubkeys[idx], sign_bytes(template, ts), sig)
        for idx, ts, sig, _e, _es in commit
    ])
    ext_ok = oracle.verify_lanes([
        (pubkeys[idx], extension_sign_bytes(chain_id, height, 0, ext), es)
        for idx, _ts, _s, ext, es in commit
    ])
    faults = 0
    sound = set()
    for (idx, _ts, _s, ext, es), v_ok, e_ok in zip(commit, vote_ok, ext_ok):
        faults += (not v_ok) + (not e_ok) + ((ext, es) != scripted[idx])
        if v_ok and e_ok:
            sound.add(idx)
    return faults + (len(sound) * power < quorum(len(pubkeys), power))


def height_job(job):
    """Worker (top level, so that a spawned process can run it):
    ``(chain_id, height, block, pubkeys, power, deliveries, commits,
    extended, scripted, control)``. ``block`` = (hash, psh_total,
    psh_hash) of the scripted block; ``commits`` the plain commits the
    node's store holds for this height as ``[(block, [(index,
    timestamp_ns, signature)])]``; ``extended`` its extended commit as
    ``(block, [(index, timestamp_ns, signature, extension,
    extension_signature)])`` or None; ``scripted`` what every validator
    signed, ``[(extension, extension_signature)]`` by index; ``control``
    None or a pair of stand-ins (for the vote lanes, for the extension
    lanes; None in the pair = the oracle). Returns (admitted, shown to the
    application, those two of the control if one is named, faults in the
    stored commits, signatures the height needed)."""
    (chain_id, height, block, pubkeys, power, deliveries, commits, extended,
     scripted, control) = job
    tpls = templates(chain_id, height, block)
    want, shown, verified = walk(
        chain_id, height, deliveries, tpls, pubkeys, power)
    stand_in = None
    if control is not None:
        stand_in = walk(chain_id, height, deliveries, tpls, pubkeys, power,
                        *control)[:2]
    precommit = tpls[(PRECOMMIT, False)]
    faults = 0
    for stored_block, commit in commits:
        if tuple(stored_block) != tuple(block):
            faults += 1
            continue
        faults += commit_faults(commit, precommit, pubkeys, power)
    if extended is None or tuple(extended[0]) != tuple(block):
        faults += 1
    else:
        faults += extended_commit_faults(
            chain_id, height, extended[1], precommit, pubkeys, power,
            scripted)
    return want, shown, stand_in, faults, verified
