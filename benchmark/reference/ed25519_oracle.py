"""Signature oracle: the ``cryptography`` wheel (OpenSSL), nothing of the
program. Used only on lanes that are honestly signed or bit-flipped, where
RFC 8032 and ZIP-215 agree."""

from __future__ import annotations

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import (
    Ed25519PrivateKey,
    Ed25519PublicKey,
)
from cryptography.hazmat.primitives.serialization import Encoding, PublicFormat


def verify(pubkey: bytes, msg: bytes, sig: bytes) -> bool:
    try:
        Ed25519PublicKey.from_public_bytes(pubkey).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


def verify_lanes(lanes: list[tuple[bytes, bytes, bytes]]) -> list[bool]:
    """One verdict per (pubkey, msg, sig). Top-level so that a spawned
    worker can be handed a slice of lanes."""
    return [verify(pk, m, s) for pk, m, s in lanes]


def keypair(seed32: bytes) -> tuple[Ed25519PrivateKey, bytes]:
    sk = Ed25519PrivateKey.from_private_bytes(seed32)
    return sk, sk.public_key().public_bytes(Encoding.Raw, PublicFormat.Raw)
