"""A validator set that rotates, as plain data.

Keys are numbered: key ``k`` is the ed25519 key of ``seed_bytes(seed, tag,
k)``. The set at height ``h`` is the ``n`` keys ``rotate * (h - 1)`` ...
``rotate * (h - 1) + n - 1``, all of equal power, in validator-set order
(ascending address): at each height the ``rotate`` oldest keys leave and
``rotate`` new ones join. Only the heights asked for are made.

Nothing here imports the program or jax, so spawned workers can import it.
"""

from __future__ import annotations

import hashlib

from ..reference import canonical, rfc6962
from ..reference import ed25519_oracle as oracle
from . import chain as rawchain


def _pubkeys_job(job) -> list[bytes]:
    """Worker: the public keys of key numbers ``lo`` ... ``hi - 1``."""
    seed, tag, lo, hi = job
    return [oracle.keypair(rawchain.seed_bytes(seed, tag, k))[1]
            for k in range(lo, hi)]


def simple_validator_leaf(pubkey: bytes, power: int) -> bytes:
    """SimpleValidator{pub_key: PublicKey{ed25519}, voting_power}, the
    leaf of a validator set's hash (types/validator.go Bytes)."""
    body = b"\x0a\x20" + pubkey
    return (b"\x0a" + bytes([len(body)]) + body + b"\x10"
            + canonical._uvarint(power))


class RotatingSets:
    """The sets of the heights asked for, with their keys made once."""

    def __init__(self, seed: int, tag: str, n: int, rotate: int,
                 heights, pool=None, chunk: int = 2048):
        self.seed, self.tag, self.n, self.rotate = seed, tag, n, rotate
        wanted = sorted({k for h in heights for k in self.key_range(h)})
        runs = _runs(wanted, chunk)
        jobs = [(seed, tag, lo, hi) for lo, hi in runs]
        parts = (map(_pubkeys_job, jobs) if pool is None
                 else pool.map(_pubkeys_job, jobs))
        self.pubkey: dict[int, bytes] = {}
        for (lo, _hi), pks in zip(runs, parts):
            self.pubkey.update(zip(range(lo, lo + len(pks)), pks))
        self.address = {k: hashlib.sha256(pk).digest()[:20]
                        for k, pk in self.pubkey.items()}
        self.sets = {h: self._set(h) for h in sorted(set(heights))}

    def key_range(self, height: int) -> range:
        lo = self.rotate * (height - 1)
        return range(lo, lo + self.n)

    def _set(self, height: int) -> rawchain.RawValidators:
        keys = sorted(self.key_range(height), key=self.address.__getitem__)
        return rawchain.RawValidators(
            self.seed, self.tag, [self.pubkey[k] for k in keys],
            [self.address[k] for k in keys], keys)

    def root(self, height: int) -> bytes:
        """The set's hash, from the leaves alone (rfc6962)."""
        return rfc6962.root([
            simple_validator_leaf(pk, rawchain.VOTING_POWER)
            for pk in self.sets[height].pubkeys])


def _runs(keys: list[int], chunk: int) -> list[tuple[int, int]]:
    """``keys`` (sorted) as [lo, hi) runs of at most ``chunk``."""
    out: list[tuple[int, int]] = []
    for k in keys:
        if out and out[-1][1] == k and k - out[-1][0] < chunk:
            out[-1] = (out[-1][0], k + 1)
        else:
            out.append((k, k + 1))
    return out


def sign_commits(sets: RotatingSets, chain_id: str, blocks, stamps,
                 pool=None, slices: int = 4) -> dict[int, rawchain.RawCommit]:
    """Round-0 commits of ``blocks`` = [(height, block_hash, psh_total,
    psh_hash)], every validator of the height's set signing at
    ``stamps[height]``; each commit is cut into ``slices`` jobs of lanes
    so that a pool's workers share the heights."""
    jobs, where = [], []
    for h, bh, pt, ph in blocks:
        vals = sets.sets[h]
        step = -(-len(vals) // slices)
        for lo in range(0, len(vals), step):
            hi = min(lo + step, len(vals))
            jobs.append((sets.seed, sets.tag, vals.key_index[lo:hi], chain_id,
                         [(h, 0, bh, pt, ph, stamps[h][lo:hi])]))
            where.append(h)
    results = (map(rawchain._sign_job, jobs) if pool is None
               else pool.map(rawchain._sign_job, jobs))
    sigs: dict[int, list[bytes]] = {h: [] for h, *_ in blocks}
    for h, part in zip(where, results):
        sigs[h].extend(part[0][1])
    return {
        h: rawchain.RawCommit(chain_id, h, 0, bh, pt, ph, stamps[h], sigs[h])
        for h, bh, pt, ph in blocks
    }
