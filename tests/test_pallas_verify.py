"""Pallas verify kernel vs the XLA kernel and the pure-Python oracle.

The Pallas kernel (ops/pallas_verify.py) is the single-chip TPU fast path;
under the CPU test platform it runs in interpreter mode, which executes
the same jaxpr the Mosaic compiler lowers on hardware. Interpret mode is
slow (minutes per trace), so all edge cases share ONE kernel invocation:
lane-for-lane agreement with ops.curve.verify_kernel (the XLA program)
and the ZIP-215 oracle, including the consensus-critical acceptance
edge cases.
"""

import pytest

import random

import numpy as np

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import curve, pallas_verify, verify

from test_curve import _order8_point, make_batch

# Interpret-mode execution of the full ladder is tens of minutes per
# invocation on small CPU hosts — slow tier (the XLA-lowering parity
# tests in test_curve/test_verify_routes stay tier-1).
pytestmark = pytest.mark.slow

rng = random.Random(77)


def _run_both(pks, msgs, sigs):
    arrays, host_ok = verify.pack_inputs(pks, msgs, sigs)
    import jax.numpy as jnp

    xla = np.asarray(
        curve.verify_kernel(**{k: jnp.asarray(v) for k, v in arrays.items()})
    )
    pal = np.asarray(pallas_verify.verify_kernel(**arrays, interpret=True))
    return xla & host_ok, pal & host_ok


def test_pallas_matches_xla_and_oracle():
    """One 16-lane batch covering valid, corrupted, and ZIP-215 edges.

    Lanes: 0 valid / 1 flipped sig / 2 valid / 3 wrong msg / 4 valid /
    5 wrong pubkey / 6 pubkey y >= p (ZIP-215 accept of non-canonical) /
    7 pubkey not on curve / 8 R not on curve / 9 small-order pubkey
    accepted by the cofactored equation only / 10.. random mutations.
    """
    pks, msgs, sigs = make_batch(16)
    sigs[1] = bytes([sigs[1][0] ^ 1]) + sigs[1][1:]
    msgs[3] = b"tampered"
    pks[5] = make_batch(1)[0][0]

    # lane 6: NON-CANONICAL pubkey encoding, which ZIP-215 accepts.
    # Honest keys essentially never have y < 19 (the only values where
    # y + p still fits 255 bits), so use the exceptional encoding of the
    # IDENTITY, y = 1 + p: the equation becomes [S]B == R exactly.
    pks[6] = (1 + ref.P).to_bytes(32, "little")
    _s6 = 7
    sigs[6] = ref.compress(
        ref.scalar_mult(_s6, ref.BASE)
    ) + _s6.to_bytes(32, "little")
    # lane 7: pubkey y=2 is not on the curve; lane 8: R not on the curve
    pks[7] = (2).to_bytes(32, "little")
    sigs[8] = (2).to_bytes(32, "little") + sigs[8][32:]

    # lane 9: cofactored-only acceptance (mixed-order pubkey). A is an
    # order-8 torsion point and R = [S]B, so [S]B - [k]A - R = [-k]A is
    # 8-torsion: the cofactored check accepts for any k while the strict
    # equation would demand k % 8 == 0 (see test_curve for the full
    # derivation).
    a_pt = _order8_point()
    a_enc = ref.compress(a_pt)
    s = 5
    r_enc = ref.compress(ref.scalar_mult(s, ref.BASE))
    zmsg = next(
        b"zip215-%d" % i
        for i in range(64)
        if ref.challenge_scalar(r_enc, a_enc, b"zip215-%d" % i) % 8 != 0
    )
    pks[9], msgs[9], sigs[9] = a_enc, zmsg, r_enc + s.to_bytes(32, "little")

    for i in range(10, 16):
        mode = i % 3
        if mode == 1:
            b = bytearray(sigs[i])
            b[rng.randrange(64)] ^= 1 << rng.randrange(8)
            sigs[i] = bytes(b)
        elif mode == 2:
            b = bytearray(pks[i])
            b[rng.randrange(32)] ^= 1 << rng.randrange(8)
            pks[i] = bytes(b)

    xla, pal = _run_both(pks, msgs, sigs)
    assert np.array_equal(xla, pal)
    for i in range(16):
        assert bool(pal[i]) == ref.verify(pks[i], msgs[i], sigs[i]), i
    assert pal[6] and pal[9]  # the ZIP-215 acceptance lanes really accept
    assert not pal[7] and not pal[8]


def test_pallas_multi_block_grid():
    """A batch spanning several grid blocks still maps lanes to outputs."""
    old = pallas_verify._BLOCK
    pallas_verify._BLOCK = 8
    try:
        pks, msgs, sigs = make_batch(16)
        sigs[3] = bytes(64)  # invalid in block 0
        sigs[12] = bytes([sigs[12][0] ^ 1]) + sigs[12][1:]  # block 1
        arrays, host_ok = verify.pack_inputs(pks, msgs, sigs)
        pal = (
            np.asarray(pallas_verify.verify_kernel(**arrays, interpret=True))
            & host_ok
        )
        expect = [ref.verify(pks[i], msgs[i], sigs[i]) for i in range(16)]
        assert list(pal) == expect
    finally:
        pallas_verify._BLOCK = old
        pallas_verify._compiled.cache_clear()
