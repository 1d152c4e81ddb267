"""The arrival script of a consensus round, as plain data: who delivers
which vote when, the seeded duplicates and mangled copies, and the pool
job that signs a height's votes and writes them as wire messages.

Nothing here imports the program or jax, so spawned workers can import it.
Everything is a pure function of ``(seed, ...)``: every seed has the same
counts; the seed moves order, peers, bursts, gaps, lanes and bits.
"""

from __future__ import annotations

import random

from ..harness import chain as rawchain
from ..reference import ed25519_oracle as oracle
from ..reference import vote_round_ref as ref

PREVOTE, PRECOMMIT = ref.PREVOTE, ref.PRECOMMIT
TYPES = (PREVOTE, PRECOMMIT)
SOUND, DUPLICATE, MANGLED = 0, 1, 2


def block_time_ns(height: int) -> int:
    return rawchain.BASE_TIME_NS + height * rawchain.SECOND_NS


def vote_timestamps(height: int, msg_type: int, n: int) -> list[int]:
    """A vote's own timestamp: the block's time, 0.1 s a step of the
    round later, 1 us a validator apart."""
    base = block_time_ns(height) + msg_type * 100_000_000
    return [base + 1_000 * i for i in range(n)]


# --- signing, in a spawn pool ----------------------------------------------

_KEYS: dict = {}


def _secret(seed: int, tag: str, i: int):
    key = _KEYS.get((seed, tag, i))
    if key is None:
        key = _KEYS[(seed, tag, i)] = oracle.keypair(
            rawchain.seed_bytes(seed, tag, i))[0]
    return key


def sign_job(job):
    """Worker: the votes of both types of the validators at ``positions``
    for one block, signed over the reference's own canonical sign-bytes
    and written into the wire template of their type. Returns
    ``[(msg_type, position, timestamp_ns, signature, wire bytes)]``."""
    (seed, tag, positions, key_index, addresses, chain_id, height, block,
     wires) = job
    n = len(key_index)
    out = []
    for t in TYPES:
        tpl = ref.vote_template(t, chain_id, height, 0, *block)
        stamps = vote_timestamps(height, t, n)
        for pos in positions:
            sig = _secret(seed, tag, key_index[pos]).sign(
                ref.sign_bytes(tpl, stamps[pos]))
            out.append((t, pos, stamps[pos], sig,
                        fill_wire(wires[t], stamps[pos], addresses[pos],
                                  pos, sig)))
    return out


def fill_wire(parts, timestamp_ns: int, address: bytes, index: int,
              signature: bytes) -> bytes:
    """A vote message's wire bytes from the template the driver cut out of
    the program's own encoding of one (``parts``: the text around the
    timestamp, the address, the index and the signature, in that order)."""
    return "".join((
        parts[0], str(timestamp_ns), parts[1], address.hex(), parts[2],
        str(index), parts[3], signature.hex(), parts[4],
    )).encode()


# --- the arrival script ----------------------------------------------------


def wave(seed: int, height: int, msg_type: int, n_vals: int, mix: dict):
    """One wave: ``deliveries`` = [(kind, validator position, peer)] in
    arrival order, and ``bursts`` = [(first delivery, offset_s)]: burst k
    is handed over no earlier than offset_s after the wave's start.

    Every validator's sound vote is delivered once by the peer the seed
    assigns it; ``duplicate_share`` of them a second time by another peer
    later in the wave; for ``bad_vote_share`` of them another peer first
    delivers a copy with one signature bit flipped."""
    rng = random.Random(rawchain.seed_bytes(seed, "wave", height, msg_type))
    peers = mix["peers"]
    order = list(range(n_vals))
    rng.shuffle(order)
    n_dup = round(mix["duplicate_share"] * n_vals)
    n_bad = round(mix["bad_vote_share"] * n_vals)
    # the first arrival cannot have a copy before it
    picked = rng.sample(range(1, n_vals), n_dup + n_bad)
    keyed = []
    for k, pos in enumerate(order):
        keyed.append((float(k), SOUND, pos, rng.randrange(peers)))
    for k in picked[:n_dup]:
        other = (keyed[k][3] + 1 + rng.randrange(peers - 1)) % peers
        keyed.append((rng.uniform(k, n_vals), DUPLICATE, order[k], other))
    for k in picked[n_dup:]:
        other = (keyed[k][3] + 1 + rng.randrange(peers - 1)) % peers
        keyed.append((rng.uniform(-1.0, k), MANGLED, order[k], other))
    keyed.sort(key=lambda row: row[0])
    deliveries = [(kind, pos, peer) for _k, kind, pos, peer in keyed]
    # bursts: geometric sizes, exponential gaps scaled to the wave's span
    p = 1.0 / mix["burst_mean"]
    starts, at = [], 0
    while at < len(deliveries):
        starts.append(at)
        size = 1
        while rng.random() > p and size < mix["burst_cap"]:
            size += 1
        at += size
    gaps = [rng.expovariate(1.0) for _ in starts[1:]]
    scale = mix["wave_span_ms"] / 1e3 / sum(gaps) if gaps else 0.0
    offsets, t = [0.0], 0.0
    for g in gaps:
        t += g * scale
        offsets.append(t)
    return deliveries, list(zip(starts, offsets))


def mangle(seed: int, height: int, msg_type: int, pos: int,
           signature: bytes) -> bytes:
    bit = int.from_bytes(
        rawchain.seed_bytes(seed, "votebit", height, msg_type, pos)[:2],
        "big") % 512
    return rawchain.flip_bit(signature, bit)
