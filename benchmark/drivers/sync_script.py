"""The scripted chain of a block-sync cell, as plain data: key seeds, vote
timestamps, where the altered blocks fall, and the pool job that signs a
height's ed25519 lanes.

Nothing here imports the program or jax, so spawned workers can import it.
Everything is a pure function of ``(seed, ...)``; every seed has the same
fault heights and schemes, the seed moves the keys, the altered lanes and
bits.
"""

from __future__ import annotations

import hashlib

from ..harness import chain as rawchain
from ..reference import blocksync_ref as ref
from ..reference import ed25519_oracle as oracle

ED, SR = ref.ED, ref.SR
NONCES_PER_KEY = 4


def block_time_ns(height: int) -> int:
    return rawchain.BASE_TIME_NS + height * rawchain.SECOND_NS


def commit_timestamps(height: int, n: int) -> list[int]:
    """A precommit's own timestamp: the block's time + 0.2 s, 1 us a
    validator apart."""
    base = block_time_ns(height) + 200_000_000
    return [base + 1_000 * i for i in range(n)]


def sr_scalar(seed: int, key: int) -> int:
    """An sr25519 validator's secret scalar x (public key = [x]B)."""
    return int.from_bytes(hashlib.sha512(
        rawchain.seed_bytes(seed, "sr", key)).digest(), "little") % ref.L


def sr_nonce(seed: int, key: int, j: int) -> int:
    """One of the key's ``NONCES_PER_KEY`` signing nonces r (R = [r]B);
    height h signs with nonce h mod NONCES_PER_KEY."""
    return int.from_bytes(hashlib.sha512(
        rawchain.seed_bytes(seed, "srnonce", key, j)).digest(),
        "little") % ref.L


def ed_pubkey(seed: int, key: int) -> bytes:
    return oracle.keypair(rawchain.seed_bytes(seed, "ed", key))[1]


_ED_KEYS: dict = {}


def sign_ed_job(job):
    """Worker: ``(seed, [(position, key)], template, stamps)`` ->
    ``[(position, signature)]`` over the reference's own canonical
    sign-bytes."""
    seed, lanes, template, stamps = job
    out = []
    for pos, key in lanes:
        sk = _ED_KEYS.get((seed, key))
        if sk is None:
            sk = _ED_KEYS[(seed, key)] = oracle.keypair(
                rawchain.seed_bytes(seed, "ed", key))[0]
        out.append((pos, sk.sign(ref.sign_bytes(template, stamps[pos]))))
    return out


def fault_plan(seed: int, n_warm: int, schemes: list[str],
               light_lanes: int, offsets: list[int]) -> list[dict]:
    """The altered blocks, the same heights on every seed: the first
    answer to a request for height ``x`` carries block x with one lane of
    its LastCommit (the commit for x - 1) altered by one signature bit,
    the first time an ed25519 lane, the second time an sr25519 lane. The
    lane lies inside the light check's +2/3 (index < ``light_lanes``) and
    off every 8th lane, where a check that skips lanes would miss it."""
    plan = []
    for k, offset in enumerate(offsets):
        scheme = (ED, SR)[k % 2]
        x = n_warm + offset
        cands = [i for i in range(light_lanes)
                 if schemes[i] == scheme and i % 8]
        pick = rawchain.seed_bytes(seed, "fault", x)
        lane = cands[int.from_bytes(pick[:4], "big") % len(cands)]
        bit = int.from_bytes(pick[4:6], "big") % 512
        plan.append({"height": x, "scheme": scheme, "lane": lane,
                     "bit": bit})
    return plan
