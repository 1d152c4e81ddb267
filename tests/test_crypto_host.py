"""Host crypto layer: keys, merkle, batch dispatch."""

import hashlib

import pytest

from cometbft_tpu.crypto import (
    Ed25519PrivKey,
    Ed25519PubKey,
    batch,
    create_batch_verifier,
    merkle,
    supports_batch_verifier,
    tmhash,
)

from helpers import HAVE_CRYPTOGRAPHY


class TestKeys:
    def test_sign_verify_roundtrip(self):
        priv = Ed25519PrivKey.from_seed(b"\x01" * 32)
        msg = b"vote sign bytes"
        sig = priv.sign(msg)
        assert priv.pub_key().verify_signature(msg, sig)
        assert not priv.pub_key().verify_signature(msg + b"x", sig)

    def test_address_is_truncated_sha256(self):
        priv = Ed25519PrivKey.from_seed(b"\x02" * 32)
        pk = priv.pub_key()
        assert pk.address() == hashlib.sha256(pk.data).digest()[:20]
        assert len(pk.address()) == 20

    @pytest.mark.skipif(
        not HAVE_CRYPTOGRAPHY,
        reason="secp256k1/OpenSSL key types need the cryptography wheel",
    )
    def test_matches_openssl(self):
        # Cross-check sign path against OpenSSL (same role curve25519-voi
        # plays as oracle for the reference).
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
        from cryptography.hazmat.primitives import serialization

        seed = b"\x07" * 32
        ours = Ed25519PrivKey.from_seed(seed)
        theirs = Ed25519PrivateKey.from_private_bytes(seed)
        raw = serialization.Encoding.Raw
        pub = theirs.public_key().public_bytes(
            raw, serialization.PublicFormat.Raw
        )
        assert ours.pub_key().data == pub
        msg = b"cross-check"
        assert ours.sign(msg) == theirs.sign(msg)

    def test_fast_sign_matches_pure_oracle(self):
        # sign_one/pubkey_from_seed route through OpenSSL; ed25519 is
        # deterministic so the bytes must equal the pure-Python oracle's.
        from cometbft_tpu.crypto import ed25519_ref as ref
        from cometbft_tpu.crypto import fast25519

        for i in range(3):
            seed = bytes([i + 9]) * 32
            msg = b"oracle-pin-%d" % i
            assert fast25519.pubkey_from_seed(seed) == ref.pubkey_from_seed(
                seed
            )
            assert fast25519.sign_one(seed, msg) == ref.sign(seed, msg)


class TestMerkle:
    def test_empty_tree(self):
        assert merkle.hash_from_byte_slices([]) == hashlib.sha256(b"").digest()

    def test_rfc6962_vectors(self):
        # Single leaf = SHA256(0x00 || leaf).
        assert (
            merkle.hash_from_byte_slices([b"L123456"])
            == hashlib.sha256(b"\x00L123456").digest()
        )
        # Two leaves = inner(leaf(a), leaf(b)).
        la = hashlib.sha256(b"\x00" + b"a").digest()
        lb = hashlib.sha256(b"\x00" + b"b").digest()
        assert (
            merkle.hash_from_byte_slices([b"a", b"b"])
            == hashlib.sha256(b"\x01" + la + lb).digest()
        )

    @pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 13])
    def test_proofs_verify(self, n):
        items = [bytes([i]) * (i + 1) for i in range(n)]
        root, proofs = merkle.proofs_from_byte_slices(items)
        assert root == merkle.hash_from_byte_slices(items)
        for i, proof in enumerate(proofs):
            proof.verify(root, items[i])
            with pytest.raises(ValueError):
                proof.verify(root, items[i] + b"!")

    def test_proof_rejects_wrong_index(self):
        items = [b"a", b"b", b"c", b"d"]
        root, proofs = merkle.proofs_from_byte_slices(items)
        with pytest.raises(ValueError):
            proofs[0].verify(root, items[1])


class TestBatchDispatch:
    def test_supports(self):
        pk = Ed25519PrivKey.from_seed(b"\x03" * 32).pub_key()
        assert supports_batch_verifier(pk)
        assert not supports_batch_verifier(object())

    def test_batch_verify_mixed_validity(self):
        privs = [Ed25519PrivKey.from_seed(bytes([i]) * 32) for i in range(6)]
        bv = create_batch_verifier(privs[0].pub_key())
        for i, priv in enumerate(privs):
            msg = b"msg%d" % i
            sig = priv.sign(msg)
            if i == 4:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])
            bv.add(priv.pub_key(), msg, sig)
        assert len(bv) == 6
        ok, bits = bv.verify()
        assert not ok
        assert bits == [True, True, True, True, False, True]

    def test_empty_batch_ok(self):
        bv = batch.Ed25519BatchVerifier()
        ok, bits = bv.verify()
        assert ok and bits == []


class TestHostThresholdDerivation:
    """HOST_BATCH_THRESHOLD is the env pin or the static 768 seed — no
    benchmark file steers it and nothing refits it (an attached
    accelerator has a static seed of its own: host_batch_threshold)."""

    def test_env_override_wins(self, monkeypatch):
        from cometbft_tpu.crypto import batch

        monkeypatch.setenv("COMETBFT_TPU_HOST_THRESHOLD", "96")
        assert batch._derive_host_threshold() == 96
        monkeypatch.setenv("COMETBFT_TPU_HOST_THRESHOLD", "garbage")
        assert batch._derive_host_threshold() == (
            batch._DEFAULT_HOST_BATCH_THRESHOLD
        )

    def test_no_file_steers_the_seed(self, monkeypatch, tmp_path):
        """A chip table lying in the working directory (or named by the
        retired COMETBFT_TPU_CHIP_TABLE knob) changes nothing."""
        import json

        from cometbft_tpu.crypto import batch

        monkeypatch.delenv("COMETBFT_TPU_HOST_THRESHOLD", raising=False)
        path = tmp_path / "BENCH_CHIP_TABLE.json"
        path.write_text(
            json.dumps(
                {
                    "measured_on_accelerator": True,
                    "table": [
                        {
                            "config": "9_device_floor",
                            "measured_crossover_lanes": 256,
                        }
                    ],
                }
            )
        )
        monkeypatch.setenv("COMETBFT_TPU_CHIP_TABLE", str(path))
        monkeypatch.chdir(tmp_path)
        assert batch._derive_host_threshold() == (
            batch._DEFAULT_HOST_BATCH_THRESHOLD
        )
        assert batch._DEFAULT_HOST_BATCH_THRESHOLD == 768


class TestPureHandshakeCrypto:
    """Known-answer vectors for the wheel-less secret-connection crypto
    (crypto/x25519.py, p2p/conn/secret_connection.hkdf_sha256): a bug
    that is self-consistent passes every loopback test, then every
    handshake against a wheel-backed peer fails — only RFC vectors catch
    it before cross-build deployment."""

    def test_x25519_rfc7748_scalar_mult_vector(self):
        # RFC 7748 §5.2 vector 1
        from cometbft_tpu.crypto import x25519

        k = bytes.fromhex(
            "a546e36bf0527c9d3b16154b82465edd"
            "62144c0ac1fc5a18506a2244ba449ac4"
        )
        u = bytes.fromhex(
            "e6db6867583030db3594c1a424b15f7c"
            "726624ec26b3353b10a903a6d0ab1c4c"
        )
        assert x25519.x25519(k, u).hex() == (
            "c3da55379de9c6908e94ea4df28d084f"
            "32eccf03491c71f754b4075577a28552"
        )

    def test_x25519_rfc7748_dh_vectors(self):
        # RFC 7748 §6.1: Alice/Bob keypairs + shared secret
        from cometbft_tpu.crypto import x25519

        a = bytes.fromhex(
            "77076d0a7318a57d3c16c17251b26645"
            "df4c2f87ebc0992ab177fba51db92c2a"
        )
        b = bytes.fromhex(
            "5dab087e624a8a4b79e17f8b83800ee6"
            "6f3bb1292618b6fd1c2f8b27ff88e0eb"
        )
        a_pub, b_pub = x25519.x25519_base(a), x25519.x25519_base(b)
        assert a_pub.hex() == (
            "8520f0098930a754748b7ddcb43ef75a"
            "0dbf3a0d26381af4eba4a98eaa9b4e6a"
        )
        assert b_pub.hex() == (
            "de9edb7d7b7dc1b4d35b61c2ece43537"
            "3f8343c85b78674dadfc7e146f882b4f"
        )
        shared = x25519.x25519(a, b_pub)
        assert shared == x25519.x25519(b, a_pub)
        assert shared.hex() == (
            "4a5d9d5ba4ce2de1728e3bf480350f25"
            "e07e21c947d19e3376f09b3c1e161742"
        )

    def test_hkdf_sha256_rfc5869_vectors(self):
        from cometbft_tpu.p2p.conn.secret_connection import hkdf_sha256

        # RFC 5869 A.1 (basic, explicit salt)
        okm = hkdf_sha256(
            ikm=b"\x0b" * 22,
            info=bytes.fromhex("f0f1f2f3f4f5f6f7f8f9"),
            length=42,
            salt=bytes.fromhex("000102030405060708090a0b0c"),
        )
        assert okm.hex() == (
            "3cb25f25faacd57a90434f64d0362f2a"
            "2d2d0a90cf1a5a4c5db02d56ecc4c5bf"
            "34007208d5b887185865"
        )
        # RFC 5869 A.3 (zero-length salt/info). HMAC zero-pads the key,
        # so the empty salt equals our salt=None default of 32 zeros —
        # this pins exactly the branch the handshake uses.
        okm = hkdf_sha256(ikm=b"\x0b" * 22, info=b"", length=42)
        assert okm.hex() == (
            "8da4e775a563c18f715f802a063c5a31"
            "b8a11f5c5ee1879ec3454e5f3c738d2d"
            "9d201395faa4b61a96c8"
        )
