"""Curve/verify kernel tests vs the pure-Python ZIP-215 oracle."""

import random

import numpy as np

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import curve, field, verify

rng = random.Random(1234)


def to_dev_point(pt):
    """Oracle extended point -> (4, 20) limb array."""
    return np.stack([field.to_limbs(c % ref.P) for c in pt])


def from_dev_point(arr):
    return tuple(field.from_limbs(row) % ref.P for row in np.asarray(arr))


def batch_points(pts):
    """List of oracle points -> (4, 20, N) device batch."""
    return np.stack([to_dev_point(p) for p in pts], axis=-1)


def rand_point():
    k = rng.randrange(ref.L)
    return ref.scalar_mult(k, ref.BASE)


def test_point_add_double_vs_ref():
    pts = [rand_point() for _ in range(8)] + [ref.IDENTITY, ref.BASE]
    a = batch_points(pts)
    b = batch_points(list(reversed(pts)))
    got_add = curve.point_add(a, b)
    got_dbl = curve.point_double(a)
    for i, (p, q) in enumerate(zip(pts, list(reversed(pts)))):
        assert _proj_eq(ref.point_add(p, q), from_dev_point(got_add[..., i]))
        assert _proj_eq(ref.point_double(p), from_dev_point(got_dbl[..., i]))


def _proj_eq(p_ref, p_dev):
    X1, Y1, Z1, _ = p_ref
    X2, Y2, Z2, _ = p_dev
    return (X1 * Z2 - X2 * Z1) % ref.P == 0 and (Y1 * Z2 - Y2 * Z1) % ref.P == 0


def test_decompress_vs_ref():
    cases = []
    for _ in range(8):
        cases.append(ref.compress(rand_point()))
    # identity, negative zero (ZIP-215 accept), non-canonical y (>= p)
    cases.append(ref.compress(ref.IDENTITY))
    cases.append((1).to_bytes(32, "little"))  # y=1 (identity encoding)
    cases.append(bytes(31) + b"\x80")  # y=0, sign=1: "negative zero"
    cases.append((ref.P + 3).to_bytes(32, "little"))  # non-canonical y
    cases.append((2).to_bytes(32, "little"))  # y=2: not on curve
    y_limbs, signs = [], []
    for enc in cases:
        v = int.from_bytes(enc, "little")
        y_limbs.append(field.to_limbs(v & ((1 << 255) - 1)))
        signs.append(v >> 255)
    pts, ok = curve.decompress(
        np.stack(y_limbs, axis=-1), np.array(signs, np.int32)
    )
    ok = np.asarray(ok)
    for i, enc in enumerate(cases):
        expect = ref.decompress(enc)
        assert bool(ok[i]) == (expect is not None), f"case {i}"
        if expect is not None:
            assert _proj_eq(expect, from_dev_point(pts[..., i])), f"case {i}"


def make_batch(n):
    pks, msgs, sigs = [], [], []
    for i in range(n):
        seed = rng.randrange(2**256).to_bytes(32, "big")
        pk = ref.pubkey_from_seed(seed)
        msg = b"vote %d" % i + rng.randrange(2**64).to_bytes(8, "big")
        pks.append(pk)
        msgs.append(msg)
        sigs.append(ref.sign(seed, msg))
    return pks, msgs, sigs


def test_verify_batch_valid():
    pks, msgs, sigs = make_batch(6)
    ok, mask = verify.verify_batch(pks, msgs, sigs)
    assert ok and mask.all()


def test_verify_batch_mixed_invalid():
    pks, msgs, sigs = make_batch(8)
    # lane 1: flipped sig bit; lane 3: wrong message; lane 5: wrong pubkey;
    # lane 6: non-canonical S (host reject); lane 7: truncated sig
    sigs[1] = bytes([sigs[1][0] ^ 1]) + sigs[1][1:]
    msgs[3] = b"tampered"
    pks[5], _, _ = (lambda t: (t[0][0], None, None))(make_batch(1))
    s_big = (int.from_bytes(sigs[6][32:], "little") + ref.L).to_bytes(
        32, "little"
    )
    sigs[6] = sigs[6][:32] + s_big
    sigs[7] = sigs[7][:40]
    ok, mask = verify.verify_batch(pks, msgs, sigs)
    expect = [True, False, True, False, True, False, False, False]
    assert not ok
    assert list(mask) == expect
    # oracle agrees lane by lane
    for pk, msg, sig, e in zip(pks, msgs, sigs, expect):
        assert ref.verify(pk, msg, sig) == e


def _order8_point():
    """Generator of the 8-torsion: [L]P for a random curve point P."""
    y = 2
    while True:
        enc = int.to_bytes(y, 32, "little")
        pt = ref.decompress(enc)
        y += 1
        if pt is None:
            continue
        t = ref.scalar_mult(ref.L, pt)
        # order exactly 8 <=> [4]T != O
        if not ref.is_identity(
            ref.point_double(ref.point_double(t))
        ) and not ref.is_identity(t):
            return t


def test_verify_zip215_small_order():
    """Mixed-order A accepted by the cofactored equation only.

    A = order-8 torsion point, R = [S]B: then [S]B - [k]A - R = [-k]A lies
    in the 8-torsion, so the cofactored check [8](...) == O accepts for ANY
    k — while the strict cofactorless equation [S]B == R + [k]A demands
    [k]A == O, i.e. k ≡ 0 (mod 8). Picking a message where k mod 8 != 0
    pins the kernel to voi-style ZIP-215 (consensus-critical): a silent
    switch to RFC 8032 cofactorless semantics fails this test.
    """
    a_pt = _order8_point()
    a_enc = ref.compress(a_pt)
    s = 5
    r_pt = ref.scalar_mult(s, ref.BASE)
    r_enc = ref.compress(r_pt)
    sig = r_enc + s.to_bytes(32, "little")
    msg = None
    for i in range(64):  # find a challenge with k % 8 != 0 (7/8 per try)
        cand = b"zip215-%d" % i
        if ref.challenge_scalar(r_enc, a_enc, cand) % 8 != 0:
            msg = cand
            break
    assert msg is not None
    k = ref.challenge_scalar(r_enc, a_enc, msg)
    # cofactorless check rejects:
    lhs = ref.scalar_mult(s, ref.BASE)
    rhs = ref.point_add(r_pt, ref.scalar_mult(k, a_pt))
    assert not ref.point_equal(lhs, rhs)
    # cofactored (ZIP-215) accepts — oracle and device agree:
    assert ref.verify(a_enc, msg, sig)
    ok, mask = verify.verify_batch([a_enc], [msg], [sig])
    assert ok and mask.all()


def test_verify_batch_pipelined_chunks():
    """verify_batch's pipelined pack->dispatch path (n > _CHUNK)
    maps lanes to the right outputs across chunk boundaries."""
    from cometbft_tpu.ops import verify as ov

    old = ov._CHUNK
    ov._CHUNK = 8
    try:
        pks, msgs, sigs = make_batch(20)  # 3 chunks: 8 + 8 + 4
        bad = {3, 9, 17}  # one per chunk
        for i in bad:
            sigs[i] = bytes([sigs[i][0] ^ 1]) + sigs[i][1:]
        ok, mask = ov.verify_batch(pks, msgs, sigs)
        assert not ok
        assert [bool(m) for m in mask] == [i not in bad for i in range(20)]
    finally:
        ov._CHUNK = old


def test_verify_agrees_with_oracle_fuzz():
    """Randomized cross-check device vs oracle on mutated signatures."""
    pks, msgs, sigs = make_batch(10)
    for i in range(10):
        mode = i % 3
        if mode == 1:
            b = bytearray(sigs[i])
            b[rng.randrange(64)] ^= 1 << rng.randrange(8)
            sigs[i] = bytes(b)
        elif mode == 2:
            b = bytearray(pks[i])
            b[rng.randrange(32)] ^= 1 << rng.randrange(8)
            pks[i] = bytes(b)
    _, mask = verify.verify_batch(pks, msgs, sigs)
    for i in range(10):
        assert bool(mask[i]) == ref.verify(pks[i], msgs[i], sigs[i]), i
