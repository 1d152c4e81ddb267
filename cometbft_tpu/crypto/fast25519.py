"""Fast host-side ed25519 verification with exact ZIP-215 semantics.

Role (TPU-first design): the TPU kernel (ops/verify.py) owns large batches,
but a device launch has a fixed cost, so latency-critical small verifies — proposal signatures, p2p
handshake challenges, evidence double-sign checks, sub-threshold commit
batches — run on host. This module is the host path the reference gets
from curve25519-voi (crypto/ed25519/ed25519.go:168): OpenSSL via the
``cryptography`` wheel, ~9k verifies/s/core, ~100x the pure-Python oracle.

Correctness: consensus requires ZIP-215 acceptance (cofactored equation,
liberal point decoding — crypto/ed25519/ed25519.go:26-29). OpenSSL
implements strict-ish RFC 8032 cofactorless verification, which accepts a
SUBSET of ZIP-215: every OpenSSL-valid signature is ZIP-215-valid
(multiply the cofactorless equation by 8), but OpenSSL rejects some
ZIP-215-valid edge encodings (non-canonical y, mixed-order points). So:

  OpenSSL says valid   -> accept (sound, no divergence)
  OpenSSL says invalid -> re-check with the exact pure-Python ZIP-215
                          oracle (ed25519_ref). Honest signatures never
                          take this branch; adversarial edge cases pay
                          ~10 ms — bounded by peer banning upstream.

This two-tier scheme is byte-for-byte equivalent to the ZIP-215 oracle
while being OpenSSL-fast on every honest input.
"""

from __future__ import annotations

from functools import lru_cache

from . import ed25519_ref as ref

try:  # the cryptography wheel is baked in; guard anyway for portability
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey as _OpenSSLKey,
    )

    _HAVE_OPENSSL = True
except Exception:  # pragma: no cover
    _HAVE_OPENSSL = False


@lru_cache(maxsize=4096)
def _loaded_key(pubkey: bytes):
    """Parsed OpenSSL key handle, LRU-cached.

    Validator pubkeys repeat every round; the cache plays the role of the
    reference's 4096-entry expanded-pubkey cache
    (crypto/ed25519/ed25519.go:31,56). Returns None for keys OpenSSL
    refuses to parse (e.g. non-canonical encodings ZIP-215 still admits).
    """
    try:
        return _OpenSSLKey.from_public_bytes(pubkey)
    except Exception:
        return None


def verify_one(pubkey: bytes, msg: bytes, sig: bytes) -> bool:
    """ZIP-215 single verification, OpenSSL fast path."""
    if len(pubkey) == 32 and len(sig) == 64:
        if _HAVE_OPENSSL:
            key = _loaded_key(bytes(pubkey))
            if key is not None:
                try:
                    key.verify(bytes(sig), bytes(msg))
                    return True  # RFC8032-valid implies ZIP-215-valid
                except InvalidSignature:
                    pass  # may still be ZIP-215-valid: recheck below
        # Middle tier: the native batch engine (edbatch.cpp) at n=1 —
        # cofactored RLC with voi/ZIP-215 semantics, ~50x the pure
        # oracle. Primary verify on wheel-less containers; on wheel
        # nodes it also absorbs the ZIP-215 edge encodings OpenSSL
        # refuses to parse or rejects, so only a native REJECT (invalid
        # w.h.p.) pays the exact-oracle recheck.
        from . import host_batch

        if host_batch.available():
            if host_batch.verify_many(
                [bytes(pubkey)], [bytes(msg)], [bytes(sig)]
            )[0]:
                return True
    return ref.verify(bytes(pubkey), bytes(msg), bytes(sig))


def sign_one(seed: bytes, msg: bytes) -> bytes:
    """Deterministic RFC 8032 signing, OpenSSL fast path.

    ed25519 signing is fully deterministic in (seed, msg), so OpenSSL and
    the pure-Python oracle produce identical bytes — this is a pure
    speedup (~100x), not a semantic fork. Equality is pinned in
    tests/test_crypto_host.py."""
    if _HAVE_OPENSSL and len(seed) == 32:
        try:
            from cryptography.hazmat.primitives.asymmetric.ed25519 import (
                Ed25519PrivateKey,
            )

            return Ed25519PrivateKey.from_private_bytes(bytes(seed)).sign(
                bytes(msg)
            )
        except Exception:
            pass
    return ref.sign(bytes(seed), bytes(msg))


def pubkey_from_seed(seed: bytes) -> bytes:
    """Public-key derivation, OpenSSL fast path (deterministic, exact)."""
    if _HAVE_OPENSSL and len(seed) == 32:
        try:
            from cryptography.hazmat.primitives import serialization
            from cryptography.hazmat.primitives.asymmetric.ed25519 import (
                Ed25519PrivateKey,
            )

            return (
                Ed25519PrivateKey.from_private_bytes(bytes(seed))
                .public_key()
                .public_bytes(
                    serialization.Encoding.Raw,
                    serialization.PublicFormat.Raw,
                )
            )
        except Exception:
            pass
    return ref.pubkey_from_seed(bytes(seed))


def verify_many(pubkeys, msgs, sigs) -> list[bool]:
    """Sequential host verification of a small batch.

    Used below the TPU dispatch threshold (crypto/batch). One CPU core at
    ~9k sigs/s beats the device round-trip latency floor for batches up to
    several hundred signatures.
    """
    return [
        verify_one(p, m, s) for p, m, s in zip(pubkeys, msgs, sigs)
    ]
