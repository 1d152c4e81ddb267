"""Pallas TPU kernel for batched ed25519 verification.

Why a hand kernel when ops/curve.py already runs under jit: the XLA
lowering materializes every field-mul intermediate — a (20, 20, N) outer
product plus carry chains per multiply, ~3.6k multiplies per signature —
so the verify is HBM-bandwidth-bound at a few percent VPU utilization.
This kernel keeps the accumulator point, the per-lane 16-entry table and
every temporary in VMEM for the whole 64-window ladder; HBM traffic is
one read of the packed inputs and one write of the validity bitmap.

Layout: a field element is (20, B) int32 limbs of 13 bits, limb axis on
sublanes, the B-lane signature axis minor (vector lanes) — same
representation and lazy-carry discipline as ops/field.py (limbs <= 10015,
single-pass carries; see the interval proof in tests/test_field.py). The
math is the same complete a=-1 Edwards formulas and ZIP-215 acceptance as
ops/curve.py (reference semantics: crypto/ed25519/ed25519.go:26-29 and
curve25519-voi's cofactored batch equation in the Go engine); results are
asserted bit-identical to the XLA kernel in tests/test_curve.py.

Differences from the XLA path, all for Mosaic friendliness:
* mul accumulates the 39 product columns with 20 static slice-adds
  instead of the pad/flatten/reshape "shear" (leading-axis reshapes force
  relayouts in Mosaic).
* table selects are explicit 16-step one-hot multiply-accumulates.
* A and R decompress together as one (20, 2B) batch so the ~254-squaring
  sqrt chain runs at double vector width.
"""

from __future__ import annotations

import threading
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import curve, field

BITS = field.BITS
NLIMB = field.NLIMB
MASK = field.MASK
FOLD = field.FOLD
TSIZE = curve.TSIZE
WINDOWS = curve.WINDOWS
WBITS = curve.WBITS

_P_LIMBS = tuple(int(v) for v in field._P_LIMBS)

# Array-shaped constants can't be captured by a Pallas kernel body, and
# (20, 1) values trip Mosaic's both-axes broadcast limitation. Instead
# every constant is rebuilt at kernel entry from Python ints as a stack
# of scalar splat rows — 52 concats of 20 (1, B) splats, executed once
# per block and dwarfed by the ~3.6k field muls that follow.


def _rows(limbs, batch) -> jnp.ndarray:
    """Static limb list -> (20, B) via scalar splats (Mosaic-friendly)."""
    return jnp.concatenate(
        [jnp.full((1, batch), int(v), jnp.int32) for v in limbs], axis=0
    )


class _TraceConsts:
    """Trace-time constants, built lazily per (name, lane width).

    The cache is THREAD-LOCAL and reset at each kernel trace entry so
    tracers never leak between traces — two threads tracing concurrently
    (e.g. blocksync and consensus both compiling on first use) must not
    share or wipe each other's tracer-backed constants. Widths: B for
    the ladder, 2B for the fused A+R decompression.
    """

    _tls = threading.local()

    @classmethod
    def reset(cls):
        cls._tls.cache = {}

    @classmethod
    def _get(cls, key, limbs, batch):
        cache = getattr(cls._tls, "cache", None)
        if cache is None:
            cache = cls._tls.cache = {}
        k = (key, batch)
        if k not in cache:
            cache[k] = _rows(limbs, batch)
        return cache[k]

    @classmethod
    def sub_bias(cls, batch):
        return cls._get("bias", field._SUB_BIAS, batch)

    @classmethod
    def d(cls, batch):
        return cls._get("d", field.to_limbs(curve.D_INT), batch)

    @classmethod
    def d2(cls, batch):
        return cls._get("d2", field.to_limbs(curve.D2_INT), batch)

    @classmethod
    def sqrt_m1(cls, batch):
        return cls._get("sqrt_m1", field.to_limbs(curve.SQRT_M1_INT), batch)

    @classmethod
    def base_entry(cls, k, batch):
        return tuple(
            cls._get(("bt", k, c), curve._BASE_TABLE[k, c], batch)
            for c in range(3)
        )


_TC = _TraceConsts


# ---------------------------------------------------------------- field ops
# Same semantics as ops/field.py, restricted to Mosaic-friendly shapes:
# every value is (..., 20, B) int32 with static leading axes.


def _carry(x, passes):
    for _ in range(passes):
        lo = x & MASK
        hi = x >> BITS
        rolled = jnp.concatenate([hi[..., -1:, :] * FOLD, hi[..., :-1, :]], axis=-2)
        x = lo + rolled
    return x


def _add(a, b):
    return _carry(a + b, 1)


def _sub(a, b):
    return _carry(a + _TC.sub_bias(max(a.shape[-1], b.shape[-1])) - b, 1)


def _neg(a):
    return _carry(_TC.sub_bias(a.shape[-1]) - a, 1)


def _dbl2(a):
    return _carry(a + a, 1)


def _mul(a, b):
    """(20, B) x (20, B) -> (20, B): schoolbook columns via slice-adds.

    Either operand may be a (20, 1) broadcast constant."""
    batch = max(a.shape[-1], b.shape[-1])
    # Pre-broadcast (20, 1) constants along lanes only: a row slice of a
    # (20, 1) operand would otherwise need a (1,1)->(20,B) splat, which
    # Mosaic refuses (both sublanes and lanes at once).
    if a.shape[-1] != batch:
        a = jnp.broadcast_to(a, (a.shape[0], batch))
    if b.shape[-1] != batch:
        b = jnp.broadcast_to(b, (b.shape[0], batch))
    rows = 2 * NLIMB - 1
    cols = None
    for i in range(NLIMB):
        t = a[i : i + 1] * b  # (20, B), lands at rows [i, i+20)
        parts = []
        if i:
            parts.append(jnp.zeros((i, batch), jnp.int32))
        parts.append(t)
        if rows - NLIMB - i:
            parts.append(jnp.zeros((rows - NLIMB - i, batch), jnp.int32))
        term = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        cols = term if cols is None else cols + term
    return _fold_cols(cols)


def _fold_cols(cols):
    lo_cols = cols[:NLIMB]
    hi_cols = cols[NLIMB:]  # 19 columns at weight 2^(260 + 13i)
    hi_lo = (hi_cols & MASK) * FOLD
    hi_hi = (hi_cols >> BITS) * FOLD
    batch = cols.shape[-1]
    zero = jnp.zeros((1, batch), jnp.int32)
    r = (
        lo_cols
        + jnp.concatenate([hi_lo, zero], axis=0)
        + jnp.concatenate([zero, hi_hi], axis=0)
    )
    return _carry(r, 3)


def _sq(a):
    """Squaring at ~half the multiplies of _mul (210 vs 400).

    cols[c] = 2 * sum_{i<j, i+j=c} a_i*a_j + (c even ? a_{c/2}^2 : 0).
    Overflow check under the lazy bound (limbs <= 10015, products
    <= 1.0030e8): worst cross column has 10 pairs -> doubled sum
    <= 2.006e9; worst mixed column 9 pairs + diagonal
    <= 2 * 9 * 1.0030e8 + 1.0030e8 = 1.906e9 — both < 2^31 - 1.
    """
    batch = a.shape[-1]
    rows = 2 * NLIMB - 1
    cross = None
    for i in range(NLIMB - 1):
        t = a[i : i + 1] * a[i + 1 :]  # a_i * a_j, j > i: (19-i, B)
        top = 2 * i + 1  # lands at rows [2i+1, i+20)
        bottom = rows - top - (NLIMB - 1 - i)
        parts = [jnp.zeros((top, batch), jnp.int32), t]
        if bottom:
            parts.append(jnp.zeros((bottom, batch), jnp.int32))
        term = jnp.concatenate(parts, axis=0)
        cross = term if cross is None else cross + term
    d = a * a  # diagonals: a_i^2 at row 2i
    zero1 = jnp.zeros((1, batch), jnp.int32)
    diag_parts = []
    for i in range(NLIMB):
        diag_parts.append(d[i : i + 1])
        if i != NLIMB - 1:
            diag_parts.append(zero1)
    diag = jnp.concatenate(diag_parts, axis=0)  # (39, B)
    return _fold_cols(cross + cross + diag)


def _canonical(x):
    """Unique representative in [0, p); mirrors field.canonical."""
    batch = x.shape[-1]
    for _ in range(2):
        limbs = []
        c = jnp.zeros((1, batch), jnp.int32)
        for i in range(NLIMB - 1):
            v = x[i : i + 1] + c
            limbs.append(v & MASK)
            c = v >> BITS
        v = x[NLIMB - 1 :] + c
        limbs.append(v & 0xFF)
        top = v >> 8
        limbs[0] = limbs[0] + top * 19
        x = jnp.concatenate(limbs, axis=0)
    borrow = jnp.zeros((1, batch), jnp.int32)
    diff = []
    for i in range(NLIMB):
        v = x[i : i + 1] - _P_LIMBS[i] + borrow
        diff.append(v & (MASK if i < NLIMB - 1 else 0xFF))
        borrow = v >> (BITS if i < NLIMB - 1 else 8)
    ge_p = borrow == 0
    y = jnp.concatenate(diff, axis=0)
    return jnp.where(ge_p, y, x)


def _is_zero(x):
    return jnp.all(_canonical(x) == 0, axis=-2, keepdims=True)


def _eq(a, b):
    return jnp.all(_canonical(a) == _canonical(b), axis=-2, keepdims=True)


def _sq_n(x, n):
    return jax.lax.fori_loop(0, n, lambda i, v: _sq(v), x)


def _pow_2_252_m3(z):
    """z ** (2^252 - 3): the curve25519 addition chain (field.pow_2_252_m3)."""
    z2 = _sq(z)
    z8 = _sq_n(z2, 2)
    z9 = _mul(z, z8)
    z11 = _mul(z2, z9)
    z22 = _sq(z11)
    z_5_0 = _mul(z9, z22)
    z_10_0 = _mul(_sq_n(z_5_0, 5), z_5_0)
    z_20_0 = _mul(_sq_n(z_10_0, 10), z_10_0)
    z_40_0 = _mul(_sq_n(z_20_0, 20), z_20_0)
    z_50_0 = _mul(_sq_n(z_40_0, 10), z_10_0)
    z_100_0 = _mul(_sq_n(z_50_0, 50), z_50_0)
    z_200_0 = _mul(_sq_n(z_100_0, 100), z_100_0)
    z_250_0 = _mul(_sq_n(z_200_0, 50), z_50_0)
    return _mul(_sq_n(z_250_0, 2), z)


# ---------------------------------------------------------------- point ops
# Points are 4-tuples (x, y, z, t) of (20, B) arrays — kept as Python
# tuples (not stacked) so Mosaic never sees >3-d values.


def _point_double(p):
    x1, y1, z1, _ = p
    a = _sq(x1)
    b = _sq(y1)
    c = _dbl2(_sq(z1))
    h = _add(a, b)
    e = _sub(h, _sq(_add(x1, y1)))
    g = _sub(a, b)
    f = _add(c, g)
    return (_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h))


def _niels_add(p, n):
    """p + Q, Q in projective-Niels (Y+X, Y-X, 2Z, 2dT): 8 muls."""
    x1, y1, z1, t1 = p
    u2, v2, w2, t2d = n
    a = _mul(_sub(y1, x1), v2)
    b = _mul(_add(y1, x1), u2)
    c = _mul(t1, t2d)
    d = _mul(z1, w2)
    e = _sub(b, a)
    f = _sub(d, c)
    g = _add(d, c)
    h = _add(b, a)
    return (_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h))


def _affine_niels_add(p, n3):
    """p + Q, Q affine-Niels (y+x, y-x, 2dxy): 7 muls."""
    x1, y1, z1, t1 = p
    u2, v2, t2d = n3
    a = _mul(_sub(y1, x1), v2)
    b = _mul(_add(y1, x1), u2)
    c = _mul(t1, t2d)
    d = _dbl2(z1)
    e = _sub(b, a)
    f = _sub(d, c)
    g = _add(d, c)
    h = _add(b, a)
    return (_mul(e, f), _mul(g, h), _mul(f, g), _mul(e, h))


def _decompress(y, sign):
    """(20, B) y-limbs + (1, B) sign -> ((x,y,z,t) point, (1, B) ok)."""
    batch = y.shape[-1]
    one = jnp.concatenate(
        [jnp.ones((1, batch), jnp.int32), jnp.zeros((NLIMB - 1, batch), jnp.int32)],
        axis=0,
    )
    yy = _sq(y)
    u = _sub(yy, one)
    v = _add(_mul(_TC.d(yy.shape[-1]), yy), one)
    v3 = _mul(_sq(v), v)
    v7 = _mul(_sq(v3), v)
    x = _mul(_mul(u, v3), _pow_2_252_m3(_mul(u, v7)))
    vxx = _mul(v, _sq(x))
    root_ok = _eq(vxx, u)
    flip_ok = _eq(vxx, _neg(u))
    x = jnp.where(flip_ok, _mul(x, _TC.sqrt_m1(x.shape[-1])), x)
    ok = root_ok | flip_ok
    xc = _canonical(x)
    parity = xc[0:1] & 1
    x = jnp.where(parity != sign, _neg(xc), xc)
    return (x, y, one, _mul(x, y)), ok


def _onehot(idx, batch):
    """(1, B) window value -> (16, B) one-hot int32."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (TSIZE, batch), 0)
    return (iota == idx).astype(jnp.int32)


# ------------------------------------------------------------------ kernel


def _verify_block_kernel(
    y_a_ref, sign_a_ref, y_r_ref, sign_r_ref, s_ref, kneg_ref, out_ref
):
    _TC.reset()
    batch = y_a_ref.shape[-1]

    # Decompress A and R as one double-width batch: the sqrt addition
    # chain (~254 squarings) dominates decompression and vectorizes
    # across both points.
    y2 = jnp.concatenate([y_a_ref[:], y_r_ref[:]], axis=-1)
    s2 = jnp.concatenate([sign_a_ref[:], sign_r_ref[:]], axis=-1)
    pt2, ok2 = _decompress(y2, s2)
    a_pt = tuple(c[:, :batch] for c in pt2)
    r_pt = tuple(c[:, batch:] for c in pt2)
    ok = ok2[:, :batch] & ok2[:, batch:]

    # Per-lane table [O, A, .., 15A] in projective-Niels form, stored as
    # 4 coordinate stacks of shape (16*20, B) so selects stay 2-d.
    entries = [a_pt, _point_double(a_pt)]
    a_niels3 = (
        _add(a_pt[1], a_pt[0]),
        _sub(a_pt[1], a_pt[0]),
        _mul(a_pt[3], _TC.d2(batch)),
    )
    for _ in range(2, TSIZE - 1):
        entries.append(_affine_niels_add(entries[-1], a_niels3))
    ident_niels = (  # O in Niels form: (1, 1, 2, 0)
        jnp.concatenate(
            [jnp.ones((1, batch), jnp.int32), jnp.zeros((NLIMB - 1, batch), jnp.int32)],
            axis=0,
        ),
    )
    one_l = ident_niels[0]
    two_l = jnp.concatenate(
        [jnp.full((1, batch), 2, jnp.int32), jnp.zeros((NLIMB - 1, batch), jnp.int32)],
        axis=0,
    )
    zero_l = jnp.zeros((NLIMB, batch), jnp.int32)
    niels_entries = [(one_l, one_l, two_l, zero_l)]
    for e in entries:
        x, yv, z, t = e
        niels_entries.append(
            (_add(yv, x), _sub(yv, x), _dbl2(z), _mul(t, _TC.d2(batch)))
        )
    # (16*20, B) per coordinate.
    tab = [
        jnp.concatenate([niels_entries[k][c] for k in range(TSIZE)], axis=0)
        for c in range(4)
    ]

    def select_a(oh):
        """One-hot (16, B) -> projective-Niels 4-tuple of (20, B)."""
        out = []
        for c in range(4):
            acc = tab[c][0:NLIMB] * oh[0:1]
            for k in range(1, TSIZE):
                acc = acc + tab[c][k * NLIMB : (k + 1) * NLIMB] * oh[k : k + 1]
            out.append(acc)
        return tuple(out)

    def select_b(oh):
        """One-hot (16, B) -> affine-Niels 3-tuple from the constant table."""
        out = []
        for c in range(3):
            acc = _TC.base_entry(0, batch)[c] * oh[0:1]
            for k in range(1, TSIZE):
                acc = acc + _TC.base_entry(k, batch)[c] * oh[k : k + 1]
            out.append(acc)
        return tuple(out)

    ident = (zero_l, one_l, one_l, zero_l)

    def body(j, acc):
        for _ in range(WBITS):
            acc = _point_double(acc)
        kn = kneg_ref[pl.ds(j, 1), :]
        sn = s_ref[pl.ds(j, 1), :]
        acc = _niels_add(acc, select_a(_onehot(kn, batch)))
        acc = _affine_niels_add(acc, select_b(_onehot(sn, batch)))
        return acc

    acc = jax.lax.fori_loop(0, WINDOWS, body, ident)

    # Subtract R (affine, Z == 1): add (-x, y, -t) in affine-Niels form.
    rx, ry, _, rt = r_pt
    nrx = _neg(rx)
    r_niels = (_add(ry, nrx), _sub(ry, nrx), _mul(_neg(rt), _TC.d2(batch)))
    acc = _affine_niels_add(acc, r_niels)
    for _ in range(3):
        acc = _point_double(acc)

    is_id = _is_zero(acc[0]) & _eq(acc[1], acc[2])
    out_ref[:] = (is_id & ok).astype(jnp.int32)


def _verify_block_kernel_cached(
    tab0_ref, tab1_ref, tab2_ref, tab3_ref, ok_a_ref,
    y_r_ref, sign_r_ref, s_ref, kneg_ref, out_ref,
):
    """Ladder with a PRE-GATHERED pubkey table (expanded-pubkey cache).

    ``tabN_ref``: (16*20, B) Niels coordinate stacks gathered from the
    HBM arena by the surrounding jit (ops/verify.PubkeyTableCache);
    ``ok_a_ref``: (1, B) cached decompress-ok bits. Only R decompresses
    here — the sqrt chain and per-launch table build of
    :func:`_verify_block_kernel` are gone (~11% fewer muls, and the
    decompression batch is half as wide).
    """
    _TC.reset()
    batch = y_r_ref.shape[-1]

    r_pt, ok = _decompress(y_r_ref[:], sign_r_ref[:])
    ok = ok & (ok_a_ref[:] != 0)

    tab = [tab0_ref[:], tab1_ref[:], tab2_ref[:], tab3_ref[:]]

    def select_a(oh):
        out = []
        for c in range(4):
            acc = tab[c][0:NLIMB] * oh[0:1]
            for k in range(1, TSIZE):
                acc = acc + tab[c][k * NLIMB : (k + 1) * NLIMB] * oh[k : k + 1]
            out.append(acc)
        return tuple(out)

    def select_b(oh):
        out = []
        for c in range(3):
            acc = _TC.base_entry(0, batch)[c] * oh[0:1]
            for k in range(1, TSIZE):
                acc = acc + _TC.base_entry(k, batch)[c] * oh[k : k + 1]
            out.append(acc)
        return tuple(out)

    one_l = jnp.concatenate(
        [jnp.ones((1, batch), jnp.int32),
         jnp.zeros((NLIMB - 1, batch), jnp.int32)],
        axis=0,
    )
    zero_l = jnp.zeros((NLIMB, batch), jnp.int32)
    ident = (zero_l, one_l, one_l, zero_l)

    def body(j, acc):
        for _ in range(WBITS):
            acc = _point_double(acc)
        kn = kneg_ref[pl.ds(j, 1), :]
        sn = s_ref[pl.ds(j, 1), :]
        acc = _niels_add(acc, select_a(_onehot(kn, batch)))
        acc = _affine_niels_add(acc, select_b(_onehot(sn, batch)))
        return acc

    acc = jax.lax.fori_loop(0, WINDOWS, body, ident)

    rx, ry, _, rt = r_pt
    nrx = _neg(rx)
    r_niels = (_add(ry, nrx), _sub(ry, nrx), _mul(_neg(rt), _TC.d2(batch)))
    acc = _affine_niels_add(acc, r_niels)
    for _ in range(3):
        acc = _point_double(acc)

    is_id = _is_zero(acc[0]) & _eq(acc[1], acc[2])
    out_ref[:] = (is_id & ok).astype(jnp.int32)


@lru_cache(maxsize=None)
def _compiled_cached(n: int, block: int, interpret: bool):
    grid = n // block
    spec2 = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, block), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    call = pl.pallas_call(
        _verify_block_kernel_cached,
        grid=(grid,),
        in_specs=[
            spec2(TSIZE * NLIMB),
            spec2(TSIZE * NLIMB),
            spec2(TSIZE * NLIMB),
            spec2(TSIZE * NLIMB),
            spec2(1),
            spec2(NLIMB),
            spec2(1),
            spec2(WINDOWS),
            spec2(WINDOWS),
        ],
        out_specs=spec2(1),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )

    def fn(table, ok_a, y_r, sign_r, s_nibs, kneg_nibs):
        # table: (16, 4, 20, n) gathered from the arena by the caller's
        # jit -> 4 coordinate stacks (16*20, n) for 2-d VMEM blocks.
        planes = [
            table[:, c].reshape(TSIZE * NLIMB, n) for c in range(4)
        ]
        return call(
            *planes,
            ok_a.astype(jnp.int32).reshape(1, n),
            y_r,
            sign_r.reshape(1, n),
            s_nibs,
            kneg_nibs,
        )[0].astype(bool)

    return fn


def verify_kernel_cached(table, ok_a, y_r, sign_r, s_nibs, kneg_nibs, *,
                         interpret=False):
    """Cached-table drop-in for ops.curve.verify_kernel_cached (+ ok AND)."""
    n = y_r.shape[-1]
    block = _block_for(n)
    if n % block:
        raise ValueError(f"batch {n} not a multiple of block {block}")
    return _compiled_cached(n, block, interpret)(
        table, ok_a, y_r, sign_r, s_nibs, kneg_nibs
    )


_BLOCK = 512


def _block_for(n: int) -> int:
    return min(n, _BLOCK)


@lru_cache(maxsize=None)
def _compiled(n: int, block: int, interpret: bool):
    grid = n // block
    spec2 = lambda rows: pl.BlockSpec(  # noqa: E731
        (rows, block), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    call = pl.pallas_call(
        _verify_block_kernel,
        grid=(grid,),
        in_specs=[
            spec2(NLIMB),
            spec2(1),
            spec2(NLIMB),
            spec2(1),
            spec2(WINDOWS),
            spec2(WINDOWS),
        ],
        out_specs=spec2(1),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )

    def fn(y_a, sign_a, y_r, sign_r, s_nibs, kneg_nibs):
        return call(
            y_a,
            sign_a.reshape(1, n),
            y_r,
            sign_r.reshape(1, n),
            s_nibs,
            kneg_nibs,
        )[0].astype(bool)

    return fn


def verify_kernel(y_a, sign_a, y_r, sign_r, s_nibs, kneg_nibs, *,
                  interpret=False):
    """Drop-in for ops.curve.verify_kernel with the same array contract.

    ``interpret=False`` compiles for the chip through Mosaic, which
    only targets TPU; callers that want the interpreter (CPU tests,
    chip_smoke.py's dry run) ask for it. Nothing here reads the backend.
    """
    n = y_a.shape[-1]
    block = _block_for(n)
    if n % block:
        raise ValueError(f"batch {n} not a multiple of block {block}")
    return _compiled(n, block, interpret)(
        y_a, sign_a, y_r, sign_r, s_nibs, kneg_nibs
    )
