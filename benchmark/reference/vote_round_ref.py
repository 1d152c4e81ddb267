"""Plain reference for one height of a consensus round: which delivered
votes a correct node admits, whether a stored commit is sound, and how
many signatures that took (types/vote_set.go AddVote, consensus/state.go
addVote, types/validation.go VerifyCommit), over plain data and the
signature oracle. Its own walk over the arrival script: its own canonical
sign-bytes (prevotes and precommits), index -> key, power tally. Nothing
of the program, no batching, no memo, no device.

A delivery is ``(msg_type, validator_index, timestamp_ns, signature)``
in the order the script handed it to the node. The node must admit a
delivery if and only if no vote of that type from that validator was
admitted before it and its signature verifies under the validator at its
index over the canonical sign-bytes of a round-0 vote for the scripted
block id. So a mangled copy that arrives first is refused and the sound
original after it admitted; a second copy of an admitted vote is refused
as already held.
"""

from __future__ import annotations

from . import canonical
from . import ed25519_oracle as oracle

PREVOTE, PRECOMMIT = 1, 2


def vote_template(msg_type: int, chain_id: str, height: int, round_: int,
                  block_hash: bytes, psh_total: int, psh_hash: bytes):
    """canonical.vote_template for either vote type: (prefix, suffix)
    around the timestamp, from the same field encoders."""
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


def sign_bytes(template, timestamp_ns: int) -> bytes:
    return canonical.vote_sign_bytes(template, timestamp_ns)


def quorum(n_vals: int, power: int) -> int:
    """Least voting power that is more than two thirds of the total."""
    return n_vals * power * 2 // 3 + 1


def admitted(deliveries, templates, pubkeys, verify_lanes=None):
    """The (msg_type, index, signature) triples a node admits, walking
    ``deliveries`` in order, and how many signatures it had to verify
    for that. ``verify_lanes`` (a control) stands in for the oracle: it
    is handed every lane at once, as a verifier that takes a drain is."""
    lanes = [
        (pubkeys[idx], sign_bytes(templates[t], ts), sig)
        for t, idx, ts, sig in deliveries
    ]
    bits = (verify_lanes or oracle.verify_lanes)(lanes)
    held: dict = {}
    verified = 0
    for (t, idx, _ts, sig), ok in zip(deliveries, bits):
        have = held.get((t, idx))
        if have == sig:
            continue  # an exact second copy: refused without a check
        verified += 1
        if have is None and ok:
            held[(t, idx)] = sig
    return {(t, idx, sig) for (t, idx), sig in held.items()}, verified


def commit_faults(commit, template, pubkeys, power: int) -> int:
    """Signatures of a stored commit ``[(index, timestamp_ns, signature)]``
    that do not verify, plus one if the sound ones carry no more than
    two thirds of the power."""
    bits = oracle.verify_lanes([
        (pubkeys[idx], sign_bytes(template, ts), sig)
        for idx, ts, sig in commit
    ])
    bad = sum(1 for ok in bits if not ok)
    sound = {idx for (idx, _t, _s), ok in zip(commit, bits) if ok}
    return bad + (len(sound) * power < quorum(len(pubkeys), power))


def height_job(job):
    """Worker (top level, so that a spawned process can run it):
    ``(chain_id, height, block, pubkeys, power, deliveries, commits,
    control)`` with ``block`` = (hash, psh_total, psh_hash) of the
    scripted block and ``commits`` the commits the node's store holds for
    this height as ``[(block, [(index, timestamp_ns, signature)])]``.
    Returns (admitted triples, those of the control if one is named,
    faults in the stored commits, signatures the height needed)."""
    chain_id, height, block, pubkeys, power, deliveries, commits, control = job
    templates = {
        t: vote_template(t, chain_id, height, 0, *block)
        for t in (PREVOTE, PRECOMMIT)
    }
    want, verified = admitted(deliveries, templates, pubkeys)
    stand_in = None
    if control is not None:
        stand_in, _ = admitted(deliveries, templates, pubkeys, control)
    faults = 0
    for stored_block, commit in commits:
        if tuple(stored_block) != tuple(block):
            faults += 1
            continue
        faults += commit_faults(commit, templates[PRECOMMIT], pubkeys, power)
    return want, stand_in, faults, verified
