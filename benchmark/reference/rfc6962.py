"""Plain recursive RFC-6962 Merkle tree over ``hashlib``: what CometBFT's
``merkle.HashFromByteSlices`` computes. Nothing of the program."""

from __future__ import annotations

import hashlib


def root(items: list[bytes]) -> bytes:
    n = len(items)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + items[0]).digest()
    k = 1
    while k * 2 < n:
        k *= 2
    return hashlib.sha256(b"\x01" + root(items[:k]) + root(items[k:])).digest()


def data_hash(txs: list[bytes]) -> bytes:
    """Block data hash: the tree over each tx's SHA-256."""
    return root([hashlib.sha256(t).digest() for t in txs])
