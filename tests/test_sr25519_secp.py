"""sr25519 + secp256k1 tests (reference analog: crypto/sr25519/*_test.go,
crypto/secp256k1/secp256k1_test.go).

The merlin transcript layer is pinned to merlin's published protocol test
vector and ristretto255 to RFC 9496's generator-multiple vectors, so the
transcript/group machinery matches the upstream ecosystems bit-for-bit.
"""

import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import sr25519 as sr
from cometbft_tpu.crypto.secp256k1 import Secp256k1PrivKey
from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey

from helpers import HAVE_CRYPTOGRAPHY


class TestMerlin:
    def test_published_protocol_vector(self):
        t = sr.Transcript(b"test protocol")
        t.append_message(b"some label", b"some data")
        assert t.challenge_bytes(b"challenge", 32).hex() == (
            "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615"
        )

    def test_transcript_order_matters(self):
        t1 = sr.Transcript(b"p")
        t1.append_message(b"a", b"1")
        t1.append_message(b"b", b"2")
        t2 = sr.Transcript(b"p")
        t2.append_message(b"b", b"2")
        t2.append_message(b"a", b"1")
        assert t1.challenge_bytes(b"c", 32) != t2.challenge_bytes(b"c", 32)


class TestRistretto:
    def test_rfc9496_generator_multiples(self):
        vectors = [
            "e2f2ae0a6abc4e71a884a961c500515f58e30b6aa582dd8db6a65945e08d2d76",
            "6a493210f7499cd17fecb510ae0cea23a110e8d5b901f8acadd3095c73a3b919",
            "94741f5d5d52755ece4f23f044ee27d5d1ea1e2bd196b462166b16152a9d0259",
        ]
        for i, want in enumerate(vectors, start=1):
            assert sr.ristretto_encode(
                ref.scalar_mult(i, ref.BASE)
            ).hex() == want

    def test_decode_encode_roundtrip_and_eq(self):
        for k in (1, 2, 7, 12345):
            pt = ref.scalar_mult(k, ref.BASE)
            enc = sr.ristretto_encode(pt)
            dec = sr.ristretto_decode(enc)
            assert dec is not None
            assert sr.ristretto_eq(dec, pt)
            assert sr.ristretto_encode(dec) == enc

    def test_decode_rejects_noncanonical(self):
        # odd s (negative) must be rejected
        assert sr.ristretto_decode(b"\x01" + b"\x00" * 31) is None
        # s >= p
        assert sr.ristretto_decode(b"\xff" * 32) is None


class TestSchnorrkel:
    def test_sign_verify_roundtrip(self):
        pv = Sr25519PrivKey.from_seed(bytes(range(32)))
        pub = pv.pub_key()
        sig = pv.sign(b"vote data")
        assert len(sig) == 64 and sig[63] & 0x80
        assert pub.verify_signature(b"vote data", sig)
        assert not pub.verify_signature(b"vote atad", sig)
        assert not pub.verify_signature(b"vote data", sig[:32] + bytes(32))
        # wrong signer
        other = Sr25519PrivKey.from_seed(b"\x42" * 32).pub_key()
        assert not other.verify_signature(b"vote data", sig)

    def test_marker_bit_required(self):
        pv = Sr25519PrivKey.from_seed(b"\x07" * 32)
        sig = bytearray(pv.sign(b"m"))
        sig[63] &= 0x7F  # strip schnorrkel v1 marker
        assert not pv.pub_key().verify_signature(b"m", bytes(sig))

    def test_batch_verifier_device_matches_host(self):
        pvs = [Sr25519PrivKey.from_seed(bytes([i]) * 32) for i in range(8)]
        msgs = [b"msg-%d" % i for i in range(8)]
        sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
        sigs[3] = sigs[3][:40] + bytes([sigs[3][40] ^ 1]) + sigs[3][41:]
        msgs[5] = b"tampered"
        bv = crypto_batch.create_batch_verifier(pvs[0].pub_key())
        for pv, m, s in zip(pvs, msgs, sigs):
            bv.add(pv.pub_key(), m, s)
        ok, bits = bv.verify()
        expect = [sr.verify(pv.pub_key().data, m, s)
                  for pv, m, s in zip(pvs, msgs, sigs)]
        assert bits == expect
        assert expect == [True, True, True, False, True, False, True, True]
        assert not ok

    def test_mixed_curve_batches(self):
        """BASELINE config 5 shape: ed25519 + sr25519 verified side by
        side through the per-type dispatch."""
        from cometbft_tpu.crypto.keys import Ed25519PrivKey

        ed = [Ed25519PrivKey.from_seed(bytes([i + 50]) * 32) for i in range(6)]
        srk = [Sr25519PrivKey.from_seed(bytes([i + 90]) * 32) for i in range(6)]
        bv_ed = crypto_batch.create_batch_verifier(ed[0].pub_key())
        bv_sr = crypto_batch.create_batch_verifier(srk[0].pub_key())
        for i, (e, s) in enumerate(zip(ed, srk)):
            m = b"mixed-%d" % i
            bv_ed.add(e.pub_key(), m, e.sign(m))
            bv_sr.add(s.pub_key(), m, s.sign(m))
        ok_e, bits_e = bv_ed.verify()
        ok_s, bits_s = bv_sr.verify()
        assert ok_e and all(bits_e)
        assert ok_s and all(bits_s)


@pytest.mark.skipif(
    not HAVE_CRYPTOGRAPHY,
    reason="secp256k1/OpenSSL key types need the cryptography wheel",
)
class TestSecp256k1:
    def test_sign_verify_roundtrip(self):
        pv = Secp256k1PrivKey.from_seed(b"\x01" * 32)
        pub = pv.pub_key()
        assert len(pub.data) == 33 and pub.data[0] in (2, 3)
        sig = pv.sign(b"payload")
        assert len(sig) == 64
        assert pub.verify_signature(b"payload", sig)
        assert not pub.verify_signature(b"payloae", sig)
        assert not pub.verify_signature(b"payload", bytes(64))

    def test_low_s_normalization(self):
        from cometbft_tpu.crypto.secp256k1 import _N

        pv = Secp256k1PrivKey.from_seed(b"\x02" * 32)
        for i in range(8):
            sig = pv.sign(b"m%d" % i)
            s = int.from_bytes(sig[32:], "big")
            assert s <= _N // 2

    def test_bitcoin_style_address(self):
        pv = Secp256k1PrivKey.from_seed(b"\x03" * 32)
        addr = pv.pub_key().address()
        assert len(addr) == 20  # RIPEMD160(SHA256(pubkey))
        # distinct from the sha256-truncated ed25519 address scheme
        import hashlib

        expect = hashlib.new(
            "ripemd160", hashlib.sha256(pv.pub_key().data).digest()
        ).digest()
        assert bytes(addr) == expect

    def test_no_batch_support(self):
        pv = Secp256k1PrivKey.from_seed(b"\x04" * 32)
        assert not crypto_batch.supports_batch_verifier(pv.pub_key())
        with pytest.raises(ValueError):
            crypto_batch.create_batch_verifier(pv.pub_key())

    def test_registry_roundtrip(self):
        from cometbft_tpu.crypto import keys

        keys.register_extra_key_types()
        pv = Secp256k1PrivKey.from_seed(b"\x05" * 32)
        pk = keys.pubkey_from_type_and_bytes("secp256k1", pv.pub_key().data)
        assert pk == pv.pub_key()
        sv = Sr25519PrivKey.from_seed(b"\x06" * 32)
        pk2 = keys.pubkey_from_type_and_bytes("sr25519", sv.pub_key().data)
        assert pk2 == sv.pub_key()


def test_native_base_mult_matches_oracle():
    """The constant-time native [s]B (signing primitive) is bit-equal to
    the Python oracle across edge and random scalars."""
    import random

    from cometbft_tpu.crypto import ed25519_ref as ref
    from cometbft_tpu.crypto import host_batch

    if not host_batch.available():
        import pytest

        pytest.skip("native engine unavailable")
    rng = random.Random(99)
    scalars = [0, 1, 2, ref.L - 1] + [
        rng.randrange(ref.L) for _ in range(16)
    ]
    for s in scalars:
        pt = host_batch.scalar_base_mult(s)
        assert ref.point_equal(pt, ref.scalar_mult(s, ref.BASE)), s


def test_native_keccak_matches_python():
    """Native keccak-f[1600] produces the exact pure-Python permutation."""
    import os as _os

    from cometbft_tpu.crypto import host_batch

    if not host_batch.available():
        import pytest

        pytest.skip("native engine unavailable")
    rng_state = bytes(range(200))
    a = bytearray(rng_state)
    assert host_batch.keccak_f1600_inplace(a)
    # pure-python reference on the same input (bypass the native route)
    from cometbft_tpu.crypto import sr25519 as sr

    b = bytearray(rng_state)
    lib, host_batch._lib = host_batch._lib, None
    failed = host_batch._lib_failed
    host_batch._lib_failed = True
    try:
        sr.keccak_f1600(b)
    finally:
        host_batch._lib = lib
        host_batch._lib_failed = failed
    assert bytes(a) == bytes(b)


def test_native_challenge_matches_python_transcript():
    """The native STROBE/merlin engine (edb_sr_challenge_batch) equals the
    pure-Python transcript challenge across message lengths that cross the
    STROBE rate boundary (166) and across signing contexts."""
    import secrets

    from cometbft_tpu.crypto import host_batch

    if not host_batch.available():
        pytest.skip("native engine unavailable")
    for ctx in (sr.SIGNING_CTX, b"", b"another-context"):
        lanes = []
        for mlen in (0, 1, 37, 150, 165, 166, 167, 331, 332, 333, 1000):
            mini = secrets.token_bytes(32)
            msg = secrets.token_bytes(mlen)
            sig = sr.sign(mini, msg, context=ctx)
            lanes.append((sr.public_from_mini(mini), msg, sig))
        pks, msgs, sigs = map(list, zip(*lanes))
        ks = sr.challenge_scalars_batch(pks, msgs, sigs, context=ctx)
        expect = [
            sr._challenge_py(ctx, m, p, s[:32]) for p, m, s in lanes
        ]
        assert ks == expect


def test_native_ristretto_to_edwards_matches_python():
    """Native RFC 9496 decode + edwards compression agrees with the
    Python ristretto_decode + compress, including rejects."""
    import secrets

    from cometbft_tpu.crypto import host_batch

    if not host_batch.available():
        pytest.skip("native engine unavailable")
    encs = []
    # valid points: generator multiples + random public keys
    acc = ref.BASE
    for _ in range(8):
        encs.append(sr.ristretto_encode(acc))
        acc = ref.point_add(acc, ref.BASE)
    for _ in range(8):
        encs.append(sr.public_from_mini(secrets.token_bytes(32)))
    # rejects: negative s, s >= p, random junk, the torsion-y edge 1 || 0*31
    encs.append(bytes([0x01]) + bytes(31))
    encs.append(b"\xff" * 32)
    encs.append(bytes([0xed]) + bytes(30) + bytes([0x7f]))  # s == p
    encs.append(secrets.token_bytes(31) + b"\x40")
    blob = b"".join(encs)
    out = host_batch.ristretto_to_edwards_batch(blob, len(encs))
    assert out is not None
    rows, ok = out
    for i, e in enumerate(encs):
        pt = sr.ristretto_decode(e)
        if pt is None:
            assert not ok[i], i
        else:
            assert ok[i], i
            assert rows[32 * i : 32 * i + 32] == ref.compress(pt), i


def test_verify_quads_matches_per_lane_verify():
    """host_batch.verify_quads (one RLC MSM over precomputed quads) gives
    the same verdicts as per-lane sr25519 verification."""
    import secrets

    from cometbft_tpu.crypto import host_batch

    if not host_batch.available():
        pytest.skip("native engine unavailable")
    lanes = []
    for i in range(10):
        mini = secrets.token_bytes(32)
        msg = b"lane-%d" % i
        lanes.append((sr.public_from_mini(mini), msg, sr.sign(mini, msg)))
    # corrupt lanes 3 (scalar bits) and 6 (message binding)
    pks, msgs, sigs = map(list, zip(*lanes))
    sigs[3] = sigs[3][:40] + bytes([sigs[3][40] ^ 4]) + sigs[3][41:]
    msgs[6] = msgs[6] + b"!"
    quads = sr.verification_encs_batch(pks, msgs, sigs)
    bitmap = host_batch.verify_quads(quads)
    assert bitmap == [True, True, True, False, True, True, False,
                      True, True, True]


def test_verification_encs_batch_flags_malformed_lanes():
    """Structurally invalid lanes surface as None quads: wrong lengths,
    missing schnorrkel marker bit, non-canonical scalar, bad ristretto."""
    import secrets

    mini = secrets.token_bytes(32)
    msg = b"ok"
    good = sr.sign(mini, msg)
    pk = sr.public_from_mini(mini)
    no_marker = good[:63] + bytes([good[63] & 0x7F])
    big_s = good[:32] + (ref.L).to_bytes(32, "little")
    big_s = big_s[:63] + bytes([big_s[63] | 0x80])
    bad_r = bytes([0x01]) + bytes(31) + good[32:]
    quads = sr.verification_encs_batch(
        [pk, pk, pk, pk, pk, b"\x00"],
        [msg] * 6,
        [good, no_marker, big_s, bad_r, good[:40], good],
    )
    assert quads[0] is not None
    assert quads[1] is None  # marker bit
    assert quads[2] is None  # s >= L
    assert quads[3] is None  # undecodable R
    assert quads[4] is None  # truncated signature
    assert quads[5] is None  # short pubkey


class TestMixedBatchVerifier:
    """One launch / one MSM across heterogeneous key types — the path
    types/validation.py routes mixed validator sets through (the
    reference falls back to per-signature verifies there,
    types/validation.go:170-176)."""

    def _lanes(self):
        import secrets

        from cometbft_tpu.crypto.keys import Ed25519PrivKey

        lanes = []
        for i in range(4):
            k = Ed25519PrivKey.generate()
            m = b"ed-%d" % i
            lanes.append((k.pub_key(), m, k.sign(m)))
        for i in range(4):
            k = Sr25519PrivKey(secrets.token_bytes(32))
            m = b"sr-%d" % i
            lanes.append((k.pub_key(), m, k.sign(m)))
        return lanes

    def test_interleaved_types_one_verifier(self):
        bv = crypto_batch.MixedBatchVerifier()
        lanes = self._lanes()
        # interleave so per-scheme grouping must preserve lane order
        order = [0, 4, 1, 5, 2, 6, 3, 7]
        for i in order:
            p, m, s = lanes[i]
            bv.add(p, m, s)
        ok, bm = bv.verify()
        assert ok and all(bm) and len(bm) == 8

    def test_mixed_failure_attribution(self):
        bv = crypto_batch.MixedBatchVerifier()
        lanes = self._lanes()
        for j, (p, m, s) in enumerate(lanes):
            if j == 1:  # corrupt an ed25519 lane
                s = s[:6] + bytes([s[6] ^ 1]) + s[7:]
            if j == 6:  # corrupt an sr25519 lane
                m = m + b"!"
            bv.add(p, m, s)
        ok, bm = bv.verify()
        assert not ok
        assert [int(b) for b in bm] == [1, 0, 1, 1, 1, 1, 0, 1]

    @pytest.mark.skipif(
        not HAVE_CRYPTOGRAPHY,
        reason="secp256k1/OpenSSL key types need the cryptography wheel",
    )
    def test_rejects_unbatchable_type(self):
        bv = crypto_batch.MixedBatchVerifier()
        k = Secp256k1PrivKey.generate()
        with pytest.raises(TypeError):
            bv.add(k.pub_key(), b"m", k.sign(b"m"))

    def test_commit_factory_picks_backend(self):
        import secrets

        from cometbft_tpu.crypto.keys import Ed25519PrivKey
        from cometbft_tpu.types.validator_set import (
            Validator,
            ValidatorSet,
        )

        ed = [Ed25519PrivKey.generate().pub_key() for _ in range(2)]
        srk = [
            Sr25519PrivKey(secrets.token_bytes(32)).pub_key()
            for _ in range(2)
        ]
        homo = ValidatorSet([Validator(p, voting_power=1) for p in ed])
        assert isinstance(
            crypto_batch.create_commit_batch_verifier(homo),
            crypto_batch.Ed25519BatchVerifier,
        )
        mixed = ValidatorSet(
            [Validator(p, voting_power=1) for p in ed + srk]
        )
        assert isinstance(
            crypto_batch.create_commit_batch_verifier(mixed),
            crypto_batch.MixedBatchVerifier,
        )
        assert crypto_batch.supports_commit_batch(mixed)


def test_mixed_row_assembly_matches_pack_part_row():
    """The mixed verifier's fused ed25519 row (raw pk|sig|native-kneg)
    is byte-identical to pack_part_row on the same quad — the two
    assemblies of the device wire layout must never diverge."""
    from cometbft_tpu.crypto import host_batch
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.ops import verify as ov

    if not host_batch.available():
        pytest.skip("native engine unavailable")
    k = Ed25519PrivKey.from_seed(b"\x33" * 32)
    msg = b"row-equality"
    sig = k.sign(msg)
    pk = k.pub_key().data
    bv = crypto_batch.MixedBatchVerifier()
    bv.add(k.pub_key(), msg, sig)
    buf, host_ok, a_keys = bv._pack_rows()
    assert host_ok[0] and a_keys[0] == pk
    fused_row = buf[:, 0].tobytes()
    k_int = ref.challenge_scalar(sig[:32], pk, msg)
    s_int = int.from_bytes(sig[32:], "little")
    assert fused_row == ov.pack_part_row(pk, sig[:32], s_int, k_int)


def test_bucket_midpoints_match_pallas_block():
    """bucket_size's midpoint admission hard-codes the Pallas block
    width; if _BLOCK is ever retuned, a mid-bucket launch would raise
    inside _launch and permanently pin the process to the XLA
    kernel (_PALLAS_BROKEN) — this pins the two constants together."""
    from cometbft_tpu.ops import pallas_verify
    from cometbft_tpu.ops import verify as ov

    assert pallas_verify._BLOCK == 512
    assert ov._PALLAS_MIN_LANES == pallas_verify._BLOCK
    for mid in (1536, 3072, 6144, 12288):
        assert ov.bucket_size(mid) == mid
        assert mid % pallas_verify._BLOCK == 0
