"""Batched edwards25519 point arithmetic + the ed25519 verify kernel (JAX).

TPU-first design notes:

* Points are extended twisted-Edwards coordinates stacked as ``(4, 20, *B)``
  int32 arrays ([X, Y, Z, T] of 20-limb field elements, see ops.field).
  Batch dims TRAIL (minor-most = signature axis) so vector lanes are full.
* All formulas are the *complete* a=-1 addition laws — branchless, valid for
  every input including identity and small-order points. Completeness is a
  correctness requirement under ZIP-215 (reference semantics:
  crypto/ed25519/ed25519.go:26-29 in the Go engine), not just a convenience:
  mixed-order points are admissible and the cofactored equation
  [8]([S]B - [k]A - R) == O must be evaluated exactly.
* Point decompression (sqrt candidate x = u*v^3*(u*v^7)^((p-5)/8)) runs on
  device, batched, with the ~265-mul addition-chain power; non-points
  surface as a False lane in the validity mask instead of an exception.
* The double-scalar multiplication [S]B + [k']A (k' = -k mod L, legal under
  the cofactored check because [8][L]A = O) is a 4-bit windowed joint
  ladder: 64 windows of (4 shared doublings + one add from a per-lane
  16-entry table of A-multiples + one add from a constant 16-entry table of
  B-multiples). Table entries are kept in precomputed "Niels" form
  (Y+X, Y-X, 2Z, 2dT) so a table add costs 8 field muls (7 when the entry
  is affine, Z == 1) versus 9 for the generic complete add. Selection is a
  branchless one-hot multiply-reduce — no gathers, no data-dependent
  control flow; 64 fixed trips under lax.fori_loop.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import field
from .field import add, canonical, dbl2, eq, is_zero, mul, neg, sq, sub

P = field.P
L = 2**252 + 27742317777372353535851937790883648493
D_INT = (-121665 * pow(121666, P - 2, P)) % P
D2_INT = (2 * D_INT) % P
SQRT_M1_INT = pow(2, (P - 1) // 4, P)
_BY = (4 * pow(5, P - 2, P)) % P

WINDOWS = 64  # 4-bit windows over 256-bit scalars
WBITS = 4
TSIZE = 1 << WBITS


def _recover_x_int(y: int, sign: int) -> int:
    u = (y * y - 1) % P
    v = (D_INT * y * y + 1) % P
    x = (u * pow(v, 3, P) * pow(u * pow(v, 7, P) % P, (P - 5) // 8, P)) % P
    if (v * x * x - u) % P != 0:
        x = x * SQRT_M1_INT % P
    assert (v * x * x - u) % P == 0
    if x & 1 != sign:
        x = (P - x) % P
    return x


_BX = _recover_x_int(_BY, 0)

# Constant points as Python int tuples; materialized inside jit as constants.
IDENTITY_INT = (0, 1, 1, 0)
BASE_INT = (_BX, _BY, 1, _BX * _BY % P)


def _base_table_host() -> np.ndarray:
    """(16, 3, 20) int32: v*B for v in [0,16) in affine-Niels form
    (y+x, y-x, 2d*x*y), computed exactly on host with Python ints."""

    def ext_add(p, q):
        x1, y1, z1, t1 = p
        x2, y2, z2, t2 = q
        a = (y1 - x1) * (y2 - x2) % P
        b = (y1 + x1) * (y2 + x2) % P
        c = t1 * D2_INT % P * t2 % P
        d = 2 * z1 * z2 % P
        e, f, g, h = b - a, d - c, d + c, b + a
        return (e * f % P, g * h % P, f * g % P, e * h % P)

    rows = []
    pt = IDENTITY_INT
    for v in range(TSIZE):
        x, y, z, _ = pt
        zinv = pow(z, P - 2, P)
        xa, ya = x * zinv % P, y * zinv % P
        rows.append(
            [
                field.to_limbs((ya + xa) % P),
                field.to_limbs((ya - xa) % P),
                field.to_limbs(2 * D_INT * xa % P * ya % P),
            ]
        )
        pt = ext_add(pt, BASE_INT)
    return np.stack([np.stack(r) for r in rows])


_BASE_TABLE = _base_table_host()


def const_point(coords, batch_ndim: int = 0) -> jnp.ndarray:
    """(x, y, z, t) Python ints -> (4, 20, 1 x batch_ndim) device constant."""
    return jnp.stack([field.const(c, batch_ndim) for c in coords])


def broadcast_point(point: jnp.ndarray, batch_shape) -> jnp.ndarray:
    return jnp.broadcast_to(
        point.reshape(point.shape[:2] + (1,) * len(batch_shape)),
        point.shape[:2] + tuple(batch_shape),
    )


def point_add(p: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Complete addition, a=-1 extended coordinates (9 field muls)."""
    x1, y1, z1, t1 = p[0], p[1], p[2], p[3]
    x2, y2, z2, t2 = q[0], q[1], q[2], q[3]
    a = mul(sub(y1, x1), sub(y2, x2))
    b = mul(add(y1, x1), add(y2, x2))
    c = mul(mul(t1, field.bconst(D2_INT, t1)), t2)
    d = dbl2(mul(z1, z2))
    e = sub(b, a)
    f = sub(d, c)
    g = add(d, c)
    h = add(b, a)
    return jnp.stack([mul(e, f), mul(g, h), mul(f, g), mul(e, h)])


def point_double(p: jnp.ndarray) -> jnp.ndarray:
    """Complete doubling (4 squarings + 4 muls)."""
    x1, y1, z1 = p[0], p[1], p[2]
    a = sq(x1)
    b = sq(y1)
    c = dbl2(sq(z1))
    h = add(a, b)
    e = sub(h, sq(add(x1, y1)))
    g = sub(a, b)
    f = add(c, g)
    return jnp.stack([mul(e, f), mul(g, h), mul(f, g), mul(e, h)])


def point_double_n(p: jnp.ndarray, n: int) -> jnp.ndarray:
    """n consecutive doublings, skipping T on all but the last.

    Doubling reads only (X, Y, Z); T (the E*H product) is needed only by
    the *add* that follows a doubling chain. Dropping it from the first
    n-1 doublings saves one field mul each — doubling chains are ~2/3 of
    the ladder's muls, so this is a free ~5% (64 windows x 3 muls)."""
    x1, y1, z1 = p[0], p[1], p[2]
    for i in range(n):
        a = sq(x1)
        b = sq(y1)
        c = dbl2(sq(z1))
        h = add(a, b)
        e = sub(h, sq(add(x1, y1)))
        g = sub(a, b)
        f = add(c, g)
        x1, y1, z1 = mul(e, f), mul(g, h), mul(f, g)
    return jnp.stack([x1, y1, z1, mul(e, h)])


def point_neg(p: jnp.ndarray) -> jnp.ndarray:
    return jnp.stack([neg(p[0]), p[1], p[2], neg(p[3])])


def to_niels(p: jnp.ndarray) -> jnp.ndarray:
    """Extended point -> projective-Niels (Y+X, Y-X, 2Z, 2dT): one mul."""
    x, y, z, t = p[0], p[1], p[2], p[3]
    return jnp.stack(
        [add(y, x), sub(y, x), dbl2(z), mul(t, field.bconst(D2_INT, t))]
    )


def to_affine_niels(p: jnp.ndarray) -> jnp.ndarray:
    """Affine (Z==1) extended point -> (Y+X, Y-X, 2dT): one mul."""
    x, y, t = p[0], p[1], p[3]
    return jnp.stack(
        [add(y, x), sub(y, x), mul(t, field.bconst(D2_INT, t))]
    )


def niels_add(p: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """p + Q where Q is in projective-Niels form (8 field muls)."""
    x1, y1, z1, t1 = p[0], p[1], p[2], p[3]
    u2, v2, w2, t2d = n[0], n[1], n[2], n[3]
    a = mul(sub(y1, x1), v2)
    b = mul(add(y1, x1), u2)
    c = mul(t1, t2d)
    d = mul(z1, w2)
    e = sub(b, a)
    f = sub(d, c)
    g = add(d, c)
    h = add(b, a)
    return jnp.stack([mul(e, f), mul(g, h), mul(f, g), mul(e, h)])


def affine_niels_add(p: jnp.ndarray, n3: jnp.ndarray) -> jnp.ndarray:
    """p + Q where Q is affine-Niels (y+x, y-x, 2dxy), Z == 1: 7 muls."""
    x1, y1, z1, t1 = p[0], p[1], p[2], p[3]
    u2, v2, t2d = n3[0], n3[1], n3[2]
    a = mul(sub(y1, x1), v2)
    b = mul(add(y1, x1), u2)
    c = mul(t1, t2d)
    d = dbl2(z1)
    e = sub(b, a)
    f = sub(d, c)
    g = add(d, c)
    h = add(b, a)
    return jnp.stack([mul(e, f), mul(g, h), mul(f, g), mul(e, h)])


def is_identity(p: jnp.ndarray) -> jnp.ndarray:
    """True where p == O, i.e. X == 0 and Y == Z (projective). Shape (*B,)."""
    return is_zero(p[0]) & is_zero(sub(p[1], p[2]))


def decompress(y_limbs: jnp.ndarray, sign: jnp.ndarray):
    """Batched ZIP-215 point decompression on device.

    ``y_limbs``: (20, *B) limbs of the 255-bit y encoding — may be
    non-canonical (y >= p), which ZIP-215 *accepts*; lazy reduction makes
    that free here. ``sign``: (*B,) 0/1 x-parity bit.

    Returns (point (4, 20, *B), ok (*B,) bool). "Negative zero"
    (x == 0, sign == 1) is accepted per ZIP-215 (the parity flip on x = 0 is
    a no-op, exactly the voi semantics the Go engine relies on).
    """
    one = jnp.broadcast_to(field.const(1, y_limbs.ndim - 1), y_limbs.shape)
    yy = sq(y_limbs)
    u = sub(yy, one)
    v = add(mul(field.bconst(D_INT, yy), yy), one)
    v3 = mul(sq(v), v)
    v7 = mul(sq(v3), v)
    x = mul(mul(u, v3), field.pow_2_252_m3(mul(u, v7)))
    vxx = mul(v, sq(x))
    root_ok = eq(vxx, u)
    flip_ok = eq(vxx, neg(u))
    x = jnp.where(flip_ok[None], mul(x, field.bconst(SQRT_M1_INT, x)), x)
    ok = root_ok | flip_ok
    xc = canonical(x)
    parity = xc[0] & 1
    x = jnp.where((parity != sign)[None], neg(xc), xc)
    point = jnp.stack([x, y_limbs, one, mul(x, y_limbs)])
    return point, ok


def _build_a_table(a_pt: jnp.ndarray) -> jnp.ndarray:
    """Per-lane table [O, A, 2A, ..., 15A] in projective-Niels form.

    a_pt: (4, 20, *B) decompressed pubkey (affine, Z=1). Returns
    (16, 4, 20, *B). One double + 13 Niels adds + one batched conversion.
    """
    batch = a_pt.shape[2:]
    a_niels3 = to_affine_niels(a_pt)
    entries = [a_pt, point_double(a_pt)]
    for _ in range(2, TSIZE - 1):
        entries.append(affine_niels_add(entries[-1], a_niels3))
    # (15, 4, 20, *B) -> (4, 20, 15, *B): limbs back on axis 0 per coord so
    # the Niels conversion runs as ONE batched field op over all 15 entries.
    stacked = jnp.moveaxis(jnp.stack(entries), 0, 2)
    niels = jnp.moveaxis(to_niels(stacked), 2, 0)  # (15, 4, 20, *B)
    ident = jnp.broadcast_to(
        const_point((1, 1, 2, 0), len(batch))[None],
        (1,) + niels.shape[1:],
    )
    return jnp.concatenate([ident, niels], axis=0)


def _select(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Branchless one-hot row select: table (16, *rest, *B), idx (*B,)."""
    iota = jnp.arange(TSIZE, dtype=jnp.int32).reshape(
        (TSIZE,) + (1,) * idx.ndim
    )
    onehot = (idx[None] == iota).astype(jnp.int32)
    oh = onehot.reshape(
        (TSIZE,) + (1,) * (table.ndim - 1 - idx.ndim) + idx.shape
    )
    return jnp.sum(oh * table, axis=0)


def build_pubkey_tables(y_a: jnp.ndarray, sign_a: jnp.ndarray):
    """Decompress pubkeys and expand their 16-entry Niels tables.

    The device-side half of the expanded-pubkey cache (the reference keeps
    a 4096-entry LRU of expanded keys, crypto/ed25519/ed25519.go:31,56;
    SURVEY §7(c) calls for HBM-resident tables keyed by validator set).
    Validators recur every round — paying the ~254-squaring sqrt chain and
    the 14-point-op table build once per KEY instead of once per LAUNCH
    removes ~11% of the per-signature muls in steady state.

    Returns (table (16, 4, 20, *B) int32, ok (*B,) bool).
    """
    a_pt, ok = decompress(y_a, sign_a)
    return _build_a_table(a_pt), ok


def verify_kernel_cached(
    table_a: jnp.ndarray,
    y_r: jnp.ndarray,
    sign_r: jnp.ndarray,
    s_nibs: jnp.ndarray,
    kneg_nibs: jnp.ndarray,
) -> jnp.ndarray:
    """Cofactored verification with a PRE-EXPANDED pubkey table.

    Same math as :func:`verify_kernel` minus A's decompression and table
    build — callers gather per-lane tables from the HBM-resident cache
    (ops/verify.PubkeyTableCache) and pass them in. Only R decompresses
    here. Returns (*B,) bool; the caller must AND in the cached per-key
    decompress-ok bits.
    """
    batch = y_r.shape[1:]
    r_pt, ok_r = decompress(y_r, sign_r)
    table_b = jnp.asarray(
        _BASE_TABLE.reshape((TSIZE, 3, field.NLIMB) + (1,) * len(batch))
    )
    ident = broadcast_point(const_point(IDENTITY_INT), batch)

    def body(j, acc):
        acc = point_double_n(acc, WBITS)
        acc = niels_add(acc, _select(table_a, kneg_nibs[j]))
        acc = affine_niels_add(acc, _select(table_b, s_nibs[j]))
        return acc

    acc = jax.lax.fori_loop(0, WINDOWS, body, ident)
    acc = affine_niels_add(acc, to_affine_niels(point_neg(r_pt)))
    acc = point_double(point_double(point_double(acc)))
    return is_identity(acc) & ok_r


def verify_kernel(
    y_a: jnp.ndarray,
    sign_a: jnp.ndarray,
    y_r: jnp.ndarray,
    sign_r: jnp.ndarray,
    s_nibs: jnp.ndarray,
    kneg_nibs: jnp.ndarray,
) -> jnp.ndarray:
    """Batched cofactored ed25519 verification.

    Inputs (B = batch shape, limb/window axes lead):
      y_a, y_r:        (20, *B) y-limbs of pubkey A and signature point R
      sign_a, sign_r:  (*B,)    x-parity bits
      s_nibs:          (64, *B) 4-bit windows of S, MSB first (host checks S < L)
      kneg_nibs:       (64, *B) 4-bit windows of (-k mod L), k = SHA512(R||A||M) mod L

    Returns (*B,) bool: [8]([S]B + [-k]A - R) == O and both points decoded.
    The SHA-512 challenge is computed on host: hashing is byte-serial work
    with no TPU affinity, while the ~3k field muls per signature here are
    the >99.9% compute share and batch perfectly.
    """
    batch = y_a.shape[1:]

    # Decompress A and R in one stacked launch: (20, 2, *B).
    y2 = jnp.stack([y_a, y_r], axis=1)
    s2 = jnp.stack([sign_a, sign_r], axis=0)
    pts, oks = decompress(y2, s2)
    a_pt = pts[:, :, 0]
    r_pt = pts[:, :, 1]
    ok_a = oks[0]
    ok_r = oks[1]

    table_a = _build_a_table(a_pt)  # (16, 4, 20, *B)
    table_b = jnp.asarray(
        _BASE_TABLE.reshape((TSIZE, 3, field.NLIMB) + (1,) * len(batch))
    )

    ident = broadcast_point(const_point(IDENTITY_INT), batch)

    def body(j, acc):
        acc = point_double_n(acc, WBITS)
        acc = niels_add(acc, _select(table_a, kneg_nibs[j]))
        acc = affine_niels_add(acc, _select(table_b, s_nibs[j]))
        return acc

    acc = jax.lax.fori_loop(0, WINDOWS, body, ident)

    # Subtract R: add affine-Niels of -R = (-x, y, -t).
    acc = affine_niels_add(acc, to_affine_niels(point_neg(r_pt)))
    acc = point_double(point_double(point_double(acc)))
    return is_identity(acc) & ok_a & ok_r
