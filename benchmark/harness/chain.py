"""Seeded validators and signed commits, as plain data.

Nothing here imports the program or jax, so spawned workers can import it.
Keys, timestamps and signatures are a pure function of ``(seed, ...)``: the
same seed gives the same chain in every process. The adapters that turn this
data into the program's types live with the drivers.
"""

from __future__ import annotations

import hashlib
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass, field
import multiprocessing
import os

from ..reference import canonical
from ..reference import ed25519_oracle as oracle

BASE_TIME_NS = 1_700_000_000_000_000_000
SECOND_NS = 1_000_000_000
VOTING_POWER = 10  # equal powers (``assumed`` in the configuration files)


def seed_bytes(seed: int, *parts) -> bytes:
    return hashlib.sha256(
        b"|".join(str(p).encode() for p in (seed, *parts))
    ).digest()


@dataclass
class RawValidators:
    """``n`` ed25519 validators of equal power in validator-set order:
    ascending address, the order every CometBFT set of equal powers has."""

    seed: int
    tag: str
    pubkeys: list[bytes]
    addresses: list[bytes]
    key_index: list[int]  # position -> index of the seeded key

    def __len__(self) -> int:
        return len(self.pubkeys)


def make_validators(seed: int, tag: str, n: int) -> RawValidators:
    pubs = [oracle.keypair(seed_bytes(seed, tag, i))[1] for i in range(n)]
    addrs = [hashlib.sha256(pk).digest()[:20] for pk in pubs]
    order = sorted(range(n), key=lambda i: addrs[i])
    return RawValidators(
        seed, tag, [pubs[i] for i in order], [addrs[i] for i in order], order
    )


@dataclass
class RawCommit:
    """Every validator's precommit for one block, in validator-set order."""

    chain_id: str
    height: int
    round: int
    block_hash: bytes
    psh_total: int
    psh_hash: bytes
    timestamps: list[int]
    signatures: list[bytes]
    # lanes whose signature was altered after signing (benchmark's record
    # of what it did; the reference does not read it)
    tampered: list[int] = field(default_factory=list)

    def template(self):
        return canonical.vote_template(
            self.chain_id, self.height, self.round, self.block_hash,
            self.psh_total, self.psh_hash,
        )

    def sign_bytes(self, lane: int, template=None) -> bytes:
        return canonical.vote_sign_bytes(
            template or self.template(), self.timestamps[lane]
        )


def _sign_job(job) -> list[tuple[int, list[bytes]]]:
    """Worker: sign the given heights' commits. ``job`` carries only seeds
    and block ids; keys are rebuilt from the seed in the worker."""
    seed, tag, key_index, chain_id, items = job
    sks = [oracle.keypair(seed_bytes(seed, tag, i))[0] for i in key_index]
    out = []
    for height, round_, block_hash, psh_total, psh_hash, stamps in items:
        tpl = canonical.vote_template(
            chain_id, height, round_, block_hash, psh_total, psh_hash
        )
        out.append((height, [
            sk.sign(canonical.vote_sign_bytes(tpl, ts))
            for sk, ts in zip(sks, stamps)
        ]))
    return out


def commit_timestamps(height: int, n: int) -> list[int]:
    base = BASE_TIME_NS + height * SECOND_NS
    return [base + 1_000 * i for i in range(n)]


def spawn_pool() -> ProcessPoolExecutor:
    """One worker per CPU of this process but one (at most 12). Workers
    import this module and the reference only (never jax: the chip belongs
    to the parent)."""
    workers = max(1, min(12, len(os.sched_getaffinity(0)) - 1))
    return ProcessPoolExecutor(
        max_workers=workers, mp_context=multiprocessing.get_context("spawn")
    )


def sign_commits(
    vals: RawValidators, chain_id: str,
    blocks: list[tuple[int, bytes, int, bytes]], pool=None,
) -> dict[int, RawCommit]:
    """Sign round-0 commits for ``blocks`` = [(height, block_hash,
    psh_total, psh_hash)], every validator signing. With a pool the
    heights are spread over its workers."""
    n = len(vals)
    items = [
        (h, 0, bh, pt, ph, commit_timestamps(h, n)) for h, bh, pt, ph in blocks
    ]
    if pool is None or len(items) < 2:
        chunks = [items]
    else:
        k = min(pool._max_workers, len(items))
        chunks = [items[i::k] for i in range(k)]
    jobs = [
        (vals.seed, vals.tag, vals.key_index, chain_id, c) for c in chunks
    ]
    results = map(_sign_job, jobs) if pool is None else pool.map(_sign_job, jobs)
    sigs = {h: s for part in results for h, s in part}
    return {
        h: RawCommit(chain_id, h, 0, bh, pt, ph, stamps, sigs[h])
        for h, _r, bh, pt, ph, stamps in items
    }


def flip_bit(sig: bytes, bit: int) -> bytes:
    out = bytearray(sig)
    out[bit // 8] ^= 1 << (bit % 8)
    return bytes(out)


def tamper(commit: RawCommit, lanes: list[int], seed: int) -> RawCommit:
    """A copy of ``commit`` with one seeded bit flipped in each of
    ``lanes``' signatures."""
    sigs = list(commit.signatures)
    for lane in lanes:
        bit = int.from_bytes(
            seed_bytes(seed, "bit", commit.height, lane)[:2], "big"
        ) % 512
        sigs[lane] = flip_bit(sigs[lane], bit)
    return RawCommit(
        commit.chain_id, commit.height, commit.round, commit.block_hash,
        commit.psh_total, commit.psh_hash, commit.timestamps, sigs,
        tampered=sorted(lanes),
    )
