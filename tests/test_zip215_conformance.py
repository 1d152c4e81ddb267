"""ZIP-215 conformance corpus: the speccheck equivalence classes, 4-way.

The published ed25519-speccheck hex corpus ("Taming the Many EdDSAs",
SSR 2020; github.com/novifinancial/ed25519-speccheck) cannot be vendored
into this zero-egress image, so this corpus reproduces the paper's
equivalence classes BY CONSTRUCTION: torsion points are computed as
[L]P from scratch, non-canonical encodings enumerated as y+p for y < 19,
mixed-order keys as [a]B + T8, and every vector carries its expected
verdict derived ANALYTICALLY in its comment from the ZIP-215 rules — the
consensus semantics of the reference engine
(/root/reference/crypto/ed25519/ed25519.go:26-29, curve25519-voi):

  (a) cofactored equation [8][S]B = [8]R + [8][k]A;
  (b) non-canonical point encodings (y >= p, negative zero) ACCEPTED;
  (c) S must be canonical: 0 <= S < L;
  (d) small-order / mixed-order A and R ACCEPTED.

Expected verdicts are NOT read from any backend, so the test is not
circular. All four verify tiers must then agree bit-identically on every
vector (SURVEY §7(b): any divergence here is consensus-forking):

  1. ed25519_ref.verify            — pure-Python oracle
  2. crypto/host_batch.verify_many — native C++ RLC/Pippenger MSM
  3. ops/curve.verify_kernel       — XLA lowering
  4. ops/pallas_verify (interpret) — Pallas lowering (slow tier)
"""

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.crypto import host_batch
from cometbft_tpu.ops import curve, verify


def _torsion_points():
    """All 8 torsion points as multiples of an order-8 generator.

    The curve group is Z_L x Z_8; for any point P, [L]P lies in the
    8-torsion. Scan small y until [L]P has order exactly 8.
    """
    y = 2
    while True:
        pt = ref.decompress(int.to_bytes(y, 32, "little"))
        y += 1
        if pt is None:
            continue
        t = ref.scalar_mult(ref.L, pt)
        if not ref.is_identity(t) and not ref.is_identity(
            ref.point_double(ref.point_double(t))
        ):
            return [ref.scalar_mult(i, t) for i in range(8)]


def build_corpus():
    """Returns list of (name, pubkey, msg, sig, expected_verdict)."""
    V = []
    msg = b"zip215 conformance msg"
    seed = b"\x2a" * 32
    a, _prefix, A_enc = ref._expand_seed(seed)
    honest_sig = ref.sign(seed, msg)

    # --- baseline sanity ---------------------------------------------
    # honest RFC 8032 signature: accepted by every scheme variant.
    V.append(("honest", A_enc, msg, honest_sig, True))
    # honest signature over a different message: k changes, reject.
    V.append(("wrong_msg", A_enc, b"other msg", honest_sig, False))
    # honest signature under an unrelated key: reject.
    A2 = ref.pubkey_from_seed(b"\x2b" * 32)
    V.append(("wrong_key", A2, msg, honest_sig, False))

    torsion = _torsion_points()
    r = 123457
    R_enc = ref.compress(ref.scalar_mult(r, ref.BASE))
    r_sig_tail = int.to_bytes(r % ref.L, 32, "little")

    # --- small-order A (paper cases 0-1) -----------------------------
    # A in the 8-torsion, R = [r]B, S = r. Then
    #   [8]([S]B - [k]A - R) = [8r]B - [k]([8]A=O) - [8r]B = O
    # for EVERY challenge k: cofactored accepts; cofactorless rejects
    # unless k = 0 mod ord(A). ZIP-215 verdict: ACCEPT, all 8 points.
    for i, T in enumerate(torsion):
        V.append(
            (f"small_order_A_{i}", ref.compress(T), msg,
             R_enc + r_sig_tail, True)
        )

    # --- small-order R (paper case 2) --------------------------------
    # R in the torsion, honest A = [a]B, S = k*a mod L. Then
    #   [8]([ka]B - [k][a]B - R) = [8](-R) = O.  ZIP-215: ACCEPT.
    for i, T in enumerate(torsion[:4]):
        Re = ref.compress(T)
        k = ref.challenge_scalar(Re, A_enc, msg)
        s = (k * a) % ref.L
        V.append(
            (f"small_order_R_{i}", A_enc, msg,
             Re + int.to_bytes(s, 32, "little"), True)
        )

    # --- S = 0 with identity A and R (paper case 0 corner) -----------
    #   [8][0]B = O = [8]O + [8][k]O.  ZIP-215: ACCEPT.
    ident = ref.compress(ref.IDENTITY)
    V.append(("s0_identity_AR", ident, msg, ident + bytes(32), True))

    # --- mixed-order A (paper cases 3-4: the key differentiator) -----
    # A' = [a]B + T8, R = [r]B, S = r + k*a where k is hashed over the
    # MIXED encoding. Then [S]B - [k]A' - R = -[k]T8, an 8-torsion
    # element: cofactored accepts for every k, cofactorless only when
    # k = 0 mod 8. Pick a msg whose k != 0 mod 8 so the vector separates
    # the two. ZIP-215: ACCEPT.
    Am_enc = ref.compress(
        ref.point_add(ref.scalar_mult(a, ref.BASE), torsion[1])
    )
    m_mixed = next(
        b"zip215-mixedA-%d" % i
        for i in range(64)
        if ref.challenge_scalar(R_enc, Am_enc, b"zip215-mixedA-%d" % i) % 8
        != 0
    )
    k = ref.challenge_scalar(R_enc, Am_enc, m_mixed)
    s = (r + k * a) % ref.L
    V.append(
        ("mixed_order_A", Am_enc, m_mixed,
         R_enc + int.to_bytes(s, 32, "little"), True)
    )

    # --- mixed-order R (paper case 5) --------------------------------
    # R' = [r]B + T8, honest A, S = r + k*a with k over R'. Then
    # [S]B - [k]A - R' = -T8: cofactored ACCEPTS.
    Rm_enc = ref.compress(
        ref.point_add(ref.scalar_mult(r, ref.BASE), torsion[1])
    )
    m_mr = next(
        b"zip215-mixedR-%d" % i
        for i in range(64)
        if ref.challenge_scalar(Rm_enc, A_enc, b"zip215-mixedR-%d" % i) % 8
        != 0
    )
    k = ref.challenge_scalar(Rm_enc, A_enc, m_mr)
    s = (r + k * a) % ref.L
    V.append(
        ("mixed_order_R", A_enc, m_mr,
         Rm_enc + int.to_bytes(s, 32, "little"), True)
    )

    # --- non-canonical encodings (paper cases 6-9) -------------------
    # Encodings with y' = y + p < 2^255 exist only for y < 19; the
    # on-curve ones are all small-order (y=0: order 4; y=1: identity).
    # ZIP-215 rule (b) ACCEPTS them; the small-order constructions above
    # then make the equation hold. RFC 8032 strict would reject the
    # encoding outright — these vectors pin the ZIP-215 choice.
    noncanon_small, noncanon_full = [], []
    for y in range(19):
        for sign in (0, 1):
            e = int.to_bytes((y + ref.P) | (sign << 255), 32, "little")
            pt = ref.decompress(e)
            if pt is None:
                continue
            # small order <=> [8]P = O; only those admit the S=r /
            # S=k*a acceptance constructions below (y=0: order 4,
            # y=1: identity). Larger on-curve y decode to full-order
            # points whose discrete log is unknown.
            p8 = ref.point_double(
                ref.point_double(ref.point_double(pt))
            )
            (noncanon_small if ref.is_identity(p8) else noncanon_full
             ).append((y, sign, e))
    assert noncanon_small, "no small-order non-canonical points found"
    for y, sign, e in noncanon_small:
        # as A (small order): R = [r]B, S = r accepts as above
        V.append(
            (f"noncanon_A_y{y}s{sign}", e, msg, R_enc + r_sig_tail, True)
        )
        # as R (small order): S = k*a accepts as above
        k = ref.challenge_scalar(e, A_enc, msg)
        s = (k * a) % ref.L
        V.append(
            (f"noncanon_R_y{y}s{sign}", A_enc, msg,
             e + int.to_bytes(s, 32, "little"), True)
        )

    # negative zero: canonical y=1 with sign bit 1 decodes to x=0 under
    # ZIP-215 (RFC 8032 rejects). With A = identity, R = [r]B, S = r the
    # equation holds. ZIP-215: ACCEPT.
    negzero = int.to_bytes(1 | (1 << 255), 32, "little")
    V.append(("negative_zero_A", negzero, msg, R_enc + r_sig_tail, True))

    # --- non-canonical S (paper cases 10-11): rule (c) rejects -------
    s_int = int.from_bytes(honest_sig[32:], "little")
    V.append(
        ("s_plus_L", A_enc, msg,
         honest_sig[:32] + int.to_bytes(s_int + ref.L, 32, "little"),
         False)
    )
    V.append(
        ("s_eq_L", A_enc, msg,
         honest_sig[:32] + int.to_bytes(ref.L, 32, "little"), False)
    )
    V.append(
        ("s_max", A_enc, msg,
         honest_sig[:32] + b"\xff" * 32, False)
    )

    # --- off-curve encodings: decompression fails, reject ------------
    off = int.to_bytes(2, 32, "little")  # y=2 is not on the curve
    V.append(("A_off_curve", off, msg, honest_sig, False))
    V.append(
        ("R_off_curve", A_enc, msg, off + honest_sig[32:], False)
    )

    # non-canonical A of full order with an unrelated signature: the
    # encoding is admitted (rule b) but the equation fails. Reject —
    # for the equation, not the encoding.
    if noncanon_full:
        V.append(("noncanon_full_order_A", noncanon_full[0][2], msg,
                  honest_sig, False))

    return V


CORPUS = build_corpus()
_IDS = [v[0] for v in CORPUS]


def _split(corpus):
    pks = [v[1] for v in corpus]
    msgs = [v[2] for v in corpus]
    sigs = [v[3] for v in corpus]
    expect = [v[4] for v in corpus]
    return pks, msgs, sigs, expect


def test_oracle_matches_analytic_verdicts():
    """Tier 1: the pure-Python oracle agrees with every derived verdict."""
    pks, msgs, sigs, expect = _split(CORPUS)
    got = [ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    bad = [
        (n, e, g)
        for (n, *_), e, g in zip(CORPUS, expect, got)
        if e != g
    ]
    assert not bad, f"oracle diverges from ZIP-215 analysis: {bad}"


def test_host_batch_matches_corpus():
    """Tier 2: the native MSM batch verifier, lane for lane."""
    pks, msgs, sigs, expect = _split(CORPUS)
    got = host_batch.verify_many(pks, msgs, sigs)
    bad = [
        (n, e, bool(g))
        for (n, *_), e, g in zip(CORPUS, expect, got)
        if e != bool(g)
    ]
    assert not bad, f"host_batch diverges: {bad}"


def test_xla_kernel_matches_corpus():
    """Tier 3: the XLA lowering, one batched launch over the corpus."""
    import jax.numpy as jnp

    pks, msgs, sigs, expect = _split(CORPUS)
    arrays, host_ok = verify.pack_inputs(pks, msgs, sigs)
    got = (
        np.asarray(
            curve.verify_kernel(
                **{k: jnp.asarray(v) for k, v in arrays.items()}
            )
        )
        & host_ok
    )
    bad = [
        (n, e, bool(g))
        for (n, *_), e, g in zip(CORPUS, expect, got)
        if e != bool(g)
    ]
    assert not bad, f"XLA kernel diverges: {bad}"


@pytest.mark.slow
def test_pallas_kernel_matches_corpus():
    """Tier 4: the Pallas lowering in interpret mode (the same jaxpr
    Mosaic compiles on hardware), one invocation over all vectors."""
    from cometbft_tpu.ops import pallas_verify

    pks, msgs, sigs, expect = _split(CORPUS)
    arrays, host_ok = verify.pack_inputs(pks, msgs, sigs)
    got = (
        np.asarray(pallas_verify.verify_kernel(**arrays, interpret=True))
        & host_ok
    )
    bad = [
        (n, e, bool(g))
        for (n, *_), e, g in zip(CORPUS, expect, got)
        if e != bool(g)
    ]
    assert not bad, f"Pallas kernel diverges: {bad}"


@pytest.mark.parametrize("pubkey_cache", ["1", "0"])
def test_verify_batch_production_path_matches_corpus(
    monkeypatch, pubkey_cache
):
    """The production dispatch (ops.verify.verify_batch — what VoteSet
    and commit verification actually call) returns the same per-lane
    bitmap as the analytic verdicts, on the cached-arena route and on
    the uncached one."""
    monkeypatch.setenv("COMETBFT_TPU_PUBKEY_CACHE", pubkey_cache)
    pks, msgs, sigs, expect = _split(CORPUS)
    ok, bitmap = verify.verify_batch(pks, msgs, sigs)
    assert ok == all(expect) or not all(expect)
    bad = [
        (n, e, bool(g))
        for (n, *_), e, g in zip(CORPUS, expect, bitmap)
        if e != bool(g)
    ]
    assert not bad, f"verify_batch diverges: {bad}"
