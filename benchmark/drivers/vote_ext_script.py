"""The arrival script of a round on a chain with vote extensions, as plain
data: vote_script's waves with one more kind of altered copy (a precommit
whose extension signature has a flipped bit, its vote signature sound),
each precommit's seeded extension, and the pool job that signs a height's
votes and extensions and writes them as wire messages.

Nothing here imports the program or jax, so spawned workers can import it.
Everything is a pure function of ``(seed, ...)``: every seed has the same
counts; the seed moves order, peers, bursts, gaps, lanes, bits and the
extensions' bytes.
"""

from __future__ import annotations

import hashlib

from ..harness import chain as rawchain
from ..reference import vote_ext_ref as ref
from . import vote_script

PREVOTE, PRECOMMIT = ref.PREVOTE, ref.PRECOMMIT
TYPES = (PREVOTE, PRECOMMIT)
SOUND, DUPLICATE, MANGLED = (
    vote_script.SOUND, vote_script.DUPLICATE, vote_script.MANGLED)


def extension(seed: int, height: int, pos: int, size: int) -> bytes:
    """The extension validator ``pos`` signs at ``height``: ``size``
    seeded bytes, another for every vote."""
    return hashlib.shake_256(
        rawchain.seed_bytes(seed, "extension", height, pos)).digest(size)


def sign_job(job):
    """Worker: the votes of both types of the validators at ``positions``
    for one block, signed over the reference's own canonical sign-bytes;
    each precommit with its extension and the extension's signature over
    the reference's CanonicalVoteExtension sign-bytes; written into the
    wire template of their type. Returns ``[(msg_type, position,
    timestamp_ns, signature, extension, extension_signature, wire)]``
    (extension and its signature empty for a prevote)."""
    (seed, tag, positions, key_index, addresses, chain_id, height, block,
     wires, ext_size) = job
    n = len(key_index)
    out = []
    for t in TYPES:
        tpl = ref.vote_template(t, chain_id, height, 0, block)
        stamps = vote_script.vote_timestamps(height, t, n)
        for pos in positions:
            sk = vote_script._secret(seed, tag, key_index[pos])
            sig = sk.sign(ref.sign_bytes(tpl, stamps[pos]))
            ext = ext_sig = b""
            if t == PRECOMMIT:
                ext = extension(seed, height, pos, ext_size)
                ext_sig = sk.sign(
                    ref.extension_sign_bytes(chain_id, height, 0, ext))
            out.append((t, pos, stamps[pos], sig, ext, ext_sig,
                        fill_wire(wires[t], stamps[pos], addresses[pos], pos,
                                  sig, ext, ext_sig)))
    return out


def fill_wire(parts, timestamp_ns: int, address: bytes, index: int,
              signature: bytes, ext: bytes = b"",
              ext_sig: bytes = b"") -> bytes:
    """A vote message's wire bytes from the template the driver cut out of
    the program's own encoding of one: the text around the timestamp, the
    address, the index and the signature and, in a precommit's, around the
    extension and its signature too, in that order."""
    fields = [str(timestamp_ns), address.hex(), str(index), signature.hex()]
    if len(parts) == 7:
        fields += [ext.hex(), ext_sig.hex()]
    out = [parts[0]]
    for value, tail in zip(fields, parts[1:]):
        out += (value, tail)
    return "".join(out).encode()


def wave(seed: int, height: int, msg_type: int, n_vals: int, mix: dict):
    """vote_script.wave with the altered copies given as counts a wave:
    ``bad_votes_per_wave`` copies with a flipped vote-signature bit and,
    in a precommit wave, ``bad_extensions_per_wave`` more whose flipped
    bit is in the extension signature, each first from another peer than
    the sound original that follows. Returns vote_script's ``deliveries``
    and ``bursts`` (both kinds of altered copy are ``MANGLED`` there) and
    the set of positions whose altered copy is of the second kind: the
    wave's first such arrivals, which the seed orders."""
    n_ext = mix["bad_extensions_per_wave"] if msg_type == PRECOMMIT else 0
    n_bad = mix["bad_votes_per_wave"] + n_ext
    deliveries, bursts = vote_script.wave(
        seed, height, msg_type, n_vals,
        dict(mix, bad_vote_share=n_bad / n_vals))
    altered = [pos for kind, pos, _peer in deliveries if kind == MANGLED]
    if len(altered) != n_bad:
        raise RuntimeError("the wave's altered copies are not as asked for")
    return deliveries, bursts, set(altered[:n_ext])


def mangle_extension_signature(seed: int, height: int, pos: int,
                               ext_sig: bytes) -> bytes:
    bit = int.from_bytes(
        rawchain.seed_bytes(seed, "extbit", height, pos)[:2], "big") % 512
    return rawchain.flip_bit(ext_sig, bit)


def take_for_sound(lanes):
    """A control's stand-in verifier: every lane passes."""
    return [True] * len(lanes)
