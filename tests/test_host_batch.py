"""Native RLC batch verifier tests (crypto/host_batch.py + edbatch.cpp).

Reference analog: curve25519-voi batch verification behind
crypto/ed25519/ed25519.go:196-228 — RLC over the cofactored equation,
one multiscalar multiplication, binary-split attribution on failure.
Must agree lane-for-lane with the pure-Python ZIP-215 oracle.
"""

import random

import pytest

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import fast25519, host_batch

pytestmark = pytest.mark.skipif(
    not host_batch.available(), reason="native toolchain unavailable"
)

rng = random.Random(42)


def _make(n, base=1):
    seeds = [bytes([base + i % 40]) + bytes(31) for i in range(n)]
    pks = [fast25519.pubkey_from_seed(s) for s in seeds]
    msgs = [b"hb-%d" % i for i in range(n)]
    sigs = [fast25519.sign_one(seeds[i], msgs[i]) for i in range(n)]
    return pks, msgs, sigs


def test_all_valid_batch():
    pks, msgs, sigs = _make(40)
    assert host_batch.verify_many(pks, msgs, sigs) == [True] * 40


def test_attribution_matches_oracle():
    pks, msgs, sigs = _make(32)
    bad = {0, 7, 19, 31}
    for b in bad:
        sigs[b] = sigs[b][:-1] + bytes([sigs[b][-1] ^ 1])
    msgs[3] = b"tampered"
    pks[5] = b"short"  # malformed length
    pks[6] = (2).to_bytes(32, "little")  # not on the curve
    sigs[9] = sigs[9][:32] + ref.L.to_bytes(32, "little")  # S >= L
    out = host_batch.verify_many(pks, msgs, sigs)
    expect = [
        len(pks[i]) == 32 and ref.verify(pks[i], msgs[i], sigs[i])
        for i in range(32)
    ]
    assert out == expect


def test_zip215_exceptional_lanes():
    """Non-canonical identity encoding (y = 1 + p) and an order-8 pubkey
    accepted only by the cofactored equation — the consensus-critical
    acceptance set (crypto/ed25519/ed25519.go:26-29)."""
    import sys

    sys.path.insert(0, "tests")
    from test_curve import _order8_point

    nc_ident = (1 + ref.P).to_bytes(32, "little")
    s = 5
    r_enc = ref.compress(ref.scalar_mult(s, ref.BASE))
    sig_ident = r_enc + s.to_bytes(32, "little")

    a_enc = ref.compress(_order8_point())
    zmsg = next(
        b"z%d" % i
        for i in range(64)
        if ref.challenge_scalar(r_enc, a_enc, b"z%d" % i) % 8 != 0
    )
    sig8 = r_enc + s.to_bytes(32, "little")
    assert ref.verify(nc_ident, b"anything", sig_ident)
    assert ref.verify(a_enc, zmsg, sig8)

    pks, msgs, sigs = _make(3, base=60)
    sigs[1] = sigs[2]  # corrupt middle lane
    out = host_batch.verify_many(
        [pks[0], nc_ident, pks[1], a_enc, pks[2]],
        [msgs[0], b"anything", msgs[1], zmsg, msgs[2]],
        [sigs[0], sig_ident, sigs[1], sig8, sigs[2]],
    )
    assert out == [True, True, False, True, True]


def test_random_fuzz_vs_oracle():
    pks, msgs, sigs = _make(24, base=100)
    for i in range(24):
        mode = rng.randrange(4)
        if mode == 1:
            b = bytearray(sigs[i])
            b[rng.randrange(64)] ^= 1 << rng.randrange(8)
            sigs[i] = bytes(b)
        elif mode == 2:
            b = bytearray(pks[i])
            b[rng.randrange(32)] ^= 1 << rng.randrange(8)
            pks[i] = bytes(b)
        elif mode == 3:
            msgs[i] = msgs[i] + b"x"
    out = host_batch.verify_many(pks, msgs, sigs)
    expect = [ref.verify(pks[i], msgs[i], sigs[i]) for i in range(24)]
    assert out == expect


def test_single_lane_and_empty():
    pks, msgs, sigs = _make(1)
    assert host_batch.verify_many(pks, msgs, sigs) == [True]
    assert host_batch.verify_many([], [], []) == []
    sigs[0] = bytes(64)
    assert host_batch.verify_many(pks, msgs, sigs) == [False]


class TestNativePackChallenges:
    """The native packing engine (edb_pack_challenges: C SHA-512 with
    definition-computed constants + 4-limb mod-L reduction) must be
    byte-identical to the Python pack path."""

    def _batch(self, n):
        from cometbft_tpu.crypto import ed25519_ref as ref

        pks, msgs, sigs = [], [], []
        for i in range(n):
            seed = (3000 + i).to_bytes(32, "big")
            pks.append(ref.pubkey_from_seed(seed))
            msgs.append(b"np %d " % i + b"x" * (i % 190))
            sigs.append(ref.sign(seed, msgs[-1]))
        return pks, msgs, sigs

    def test_sha512_constants_match_hashlib(self):
        """One C-SHA512 digest equals hashlib's, across block boundaries
        (the constants are derived, not vendored — this pins them)."""
        from cometbft_tpu.crypto import host_batch
        from cometbft_tpu.ops import verify as ov

        if not host_batch.available():
            import pytest

            pytest.skip("native engine unavailable")
        # messages of many lengths exercise padding edges (112/128)
        pks, msgs, sigs = [], [], []
        from cometbft_tpu.crypto import ed25519_ref as ref

        for ln in list(range(0, 6)) + [47, 48, 49, 63, 64, 65, 111,
                                       112, 113, 127, 128, 129, 255]:
            seed = (5000 + ln).to_bytes(32, "big")
            m = bytes(range(256))[:ln]
            pks.append(ref.pubkey_from_seed(seed))
            msgs.append(m)
            sigs.append(ref.sign(seed, m))
        native = ov._pack_bytes_native(
            pks, msgs, sigs, len(pks), len(pks))
        assert native is not None
        buf_n, ok_n = native
        # Python path, forced
        lib, host_batch._lib = host_batch._lib, None
        failed = host_batch._lib_failed
        host_batch._lib_failed = True
        try:
            buf_p, ok_p = ov.pack_bytes(pks, msgs, sigs)
        finally:
            host_batch._lib = lib
            host_batch._lib_failed = failed
        import numpy as np

        assert np.array_equal(ok_n, ok_p)
        assert np.array_equal(buf_n, buf_p)

    def test_native_pack_matches_python_with_malformed_lanes(self):
        import numpy as np

        from cometbft_tpu.crypto import host_batch
        from cometbft_tpu.ops import verify as ov

        if not host_batch.available():
            import pytest

            pytest.skip("native engine unavailable")
        pks, msgs, sigs = self._batch(24)
        pks[3] = b"\x01" * 31  # short pubkey
        sigs[5] = b"\x02" * 63  # short sig
        # non-canonical S >= L
        s_big = (ov.L + 5).to_bytes(32, "little")
        sigs[7] = sigs[7][:32] + s_big
        native = ov._pack_bytes_native(pks, msgs, sigs, 24, 24)
        assert native is not None
        buf_n, ok_n = native
        lib, host_batch._lib = host_batch._lib, None
        failed = host_batch._lib_failed
        host_batch._lib_failed = True
        try:
            buf_p, ok_p = ov.pack_bytes(pks, msgs, sigs)
        finally:
            host_batch._lib = lib
            host_batch._lib_failed = failed
        assert np.array_equal(ok_n, ok_p)
        assert not ok_n[3] and not ok_n[5] and not ok_n[7]
        assert np.array_equal(buf_n, buf_p)

    def test_sc_reduce_random_hashes(self):
        """sc_reduce512 vs Python bigints on random 64-byte values,
        via the pack entry (kneg rows)."""
        import random

        import numpy as np

        from cometbft_tpu.crypto import ed25519_ref as ref
        from cometbft_tpu.crypto import host_batch

        if not host_batch.available():
            import pytest

            pytest.skip("native engine unavailable")
        rng = random.Random(31337)
        n = 64
        # craft lanes whose digests we recompute in python
        pks, msgs, sigs = self._batch(n)
        recs = b"".join(
            bytes(p) + bytes(s) for p, s in zip(pks, sigs)
        )
        blob = b"".join(msgs)
        offs = [0]
        for m in msgs:
            offs.append(offs[-1] + len(m))
        out = host_batch.pack_challenges(recs, blob, offs, n)
        assert out is not None
        kneg_blob, s_ok = out
        assert s_ok.all()
        for i in range(n):
            k = ref.challenge_scalar(sigs[i][:32], pks[i], msgs[i])
            expect = ((ref.L - k) % ref.L).to_bytes(32, "little")
            got = kneg_blob[32 * i : 32 * i + 32]
            assert got == expect, i
        del rng
