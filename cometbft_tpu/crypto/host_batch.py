"""Host ed25519 batch verification over the native MSM engine.

The reference's host hot path is curve25519-voi BATCH verification
(crypto/ed25519/ed25519.go:196-228): draw random 128-bit coefficients
z_i and check the single random-linear-combination equation

    [8]( [sum z_i S_i]B - sum [z_i k_i]A_i - sum [z_i]R_i ) == O

with one multiscalar multiplication. This module is that algorithm for
this framework: CPython does the byte-level work (SHA-512 challenges,
canonicality checks, bigint coefficient reduction mod L — microseconds
per batch) and native/edbatch.cpp does the Pippenger MSM and ZIP-215
decompression via ctypes.

Roles:
  * the MEASURED baseline for bench.py's vs_baseline (replacing the
    former "OpenSSL single-verify x 2.0" guess), and
  * the production host path for sub-device-threshold batches
    (crypto/batch.Ed25519BatchVerifier): a 150-validator commit verifies
    in ~1 MSM instead of 150 sequential OpenSSL calls.

Soundness: an invalid signature passes the RLC check with probability
~2^-128 over the coefficient draw (z_i from ``secrets``). On batch
failure, lanes are attributed by binary splitting (reusing the drawn
coefficients — they were never revealed), bottoming out in single
cofactored verifies through the same MSM core, so every per-lane verdict
has exact ZIP-215 semantics (crypto/ed25519/ed25519.go:26-29).
"""

from __future__ import annotations

import ctypes
import os
from array import array
from collections.abc import Sequence
from itertools import accumulate
from operator import eq as _eq
from ..libs import sync as libsync
import secrets

import numpy as np

from ..libs.native_build import NativeBuildError, build_and_load
from . import ed25519_ref as ref

L = ref.L
_B_ENC = bytes([0x58]) + bytes([0x66]) * 31  # compressed base point

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "native")
_SRC = os.path.abspath(os.path.join(_NATIVE_DIR, "edbatch.cpp"))
_SO = os.path.abspath(os.path.join(_NATIVE_DIR, "_edbatch.so"))

_build_lock = libsync.Mutex("crypto.host_batch._build_lock")
_lib = None
_lib_failed = False


def _load():
    """Compile + load the native engine once; None if the toolchain is
    unavailable (callers fall back to sequential OpenSSL verification)."""
    global _lib, _lib_failed
    if _lib is not None or _lib_failed:
        return _lib
    with _build_lock:
        if _lib is not None or _lib_failed:
            return _lib
        try:
            # build_and_load only reuses a .so stamped with this exact
            # source, so every symbol _bind names is there
            lib = build_and_load(_SRC, _SO)
            _bind(lib)
            _install_sha512_constants(lib)
            _lib = lib
        except NativeBuildError:
            _lib_failed = True
            import logging

            logging.getLogger(__name__).warning(
                "native batch engine unavailable; sequential host "
                "verification serves every host batch",
                exc_info=True,
            )
    return _lib


def _bind(lib) -> None:
    """ctypes signatures for every engine symbol; raises AttributeError
    when the loaded .so predates one (callers force a rebuild)."""
    lib.edb_msm_is_identity_x8.restype = ctypes.c_long
    lib.edb_msm_is_identity_x8.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t
    ]
    lib.edb_decompress_ok.restype = None
    lib.edb_decompress_ok.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p
    ]
    lib.edb_scalar_base_mult_xy.restype = None
    lib.edb_scalar_base_mult_xy.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p
    ]
    lib.edb_keccak_f1600.restype = None
    lib.edb_keccak_f1600.argtypes = [ctypes.c_void_p]
    lib.edb_sha512_set_constants.restype = None
    lib.edb_sha512_set_constants.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p
    ]
    lib.edb_pack_challenges.restype = ctypes.c_long
    lib.edb_pack_challenges.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_void_p,
        ctypes.c_size_t, ctypes.c_char_p, ctypes.c_char_p,
    ]
    lib.edb_pack_wire.restype = ctypes.c_long
    lib.edb_pack_wire.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
        ctypes.c_size_t, ctypes.c_void_p,
    ]
    lib.edb_verify_batch.restype = ctypes.c_long
    lib.edb_verify_batch.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_void_p,
        ctypes.c_char_p, ctypes.c_size_t,
    ]
    lib.edb_sr_challenge_batch.restype = ctypes.c_long
    lib.edb_sr_challenge_batch.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, ctypes.c_char_p,
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_char_p,
    ]
    lib.edb_ristretto_to_edwards.restype = None
    lib.edb_ristretto_to_edwards.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ctypes.c_char_p,
    ]
    # called with the interpreter lock HELD (PYFUNCTYPE): the call takes
    # microseconds, and a thread that lets the lock go waits whole time
    # slices behind every other runnable thread to get it back
    lib.edb_vote_sign_bytes = ctypes.PYFUNCTYPE(
        None, ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p,
        ctypes.c_size_t, ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_void_p, ctypes.c_void_p,
    )(("edb_vote_sign_bytes", lib))


def _install_sha512_constants(lib) -> None:
    """Compute the FIPS 180-4 SHA-512 constants from their definition
    (first 64 fractional bits of the cube/square roots of the first
    primes, exact integer arithmetic — no hardcoded magic tables) and
    install them in the native engine. hashlib parity is pinned by
    tests/test_host_batch tests."""
    primes = []
    cand = 2
    while len(primes) < 80:
        if all(cand % p for p in primes):
            primes.append(cand)
        cand += 1

    def iroot(x: int, k: int) -> int:
        """Exact integer k-th root via Newton on Python ints."""
        if x == 0:
            return 0
        r = 1 << ((x.bit_length() + k - 1) // k)
        while True:
            nr = ((k - 1) * r + x // r ** (k - 1)) // k
            if nr >= r:
                break
            r = nr
        return r

    def frac_bits(p: int, k: int) -> int:
        # floor(frac(p^(1/k)) * 2^64)
        r = iroot(p << (64 * k), k)
        return r - ((iroot(p, k)) << 64)

    k80 = (ctypes.c_uint64 * 80)(*[frac_bits(p, 3) for p in primes])
    h8 = (ctypes.c_uint64 * 8)(*[frac_bits(p, 2) for p in primes[:8]])
    lib.edb_sha512_set_constants(k80, h8)


def available() -> bool:
    return _load() is not None


class MsgColumn(Sequence):
    """A batch's messages as one column: ``blob`` (bytes) and ``offs``
    (``array('Q')``, one entry more than lanes), lane i being
    ``blob[offs[i]:offs[i + 1]]`` — what the native encoder writes
    (:func:`vote_sign_bytes`) and what the native packers read in place
    (:func:`pack_wire`, :func:`pack_challenges`).

    Immutable, and a ``list[bytes]`` to whoever indexes, iterates,
    slices or compares it: lanes are cut on demand. A contiguous slice
    is again a column over the same blob (``offs`` is absolute, its
    first entry need not be 0); a strided one is a list."""

    __slots__ = ("_blob", "_offs")

    def __init__(self, blob: bytes, offs: array):
        self._blob = blob
        self._offs = offs

    @classmethod
    def joined(cls, msgs) -> "MsgColumn":
        """The column of a plain sequence of bytes-likes."""
        return cls(
            b"".join(msgs),
            array("Q", accumulate(map(len, msgs), initial=0)),
        )

    @property
    def blob(self) -> bytes:
        return self._blob

    @property
    def offs(self) -> array:
        return self._offs

    def __len__(self) -> int:
        return len(self._offs) - 1

    def __getitem__(self, i):
        offs = self._offs
        if isinstance(i, slice):
            start, stop, step = i.indices(len(offs) - 1)
            if step != 1:
                return [self[k] for k in range(start, stop, step)]
            stop = max(start, stop)
            return MsgColumn(self._blob, offs[start : stop + 1])
        if i < 0:
            i += len(offs) - 1
        if not 0 <= i < len(offs) - 1:
            raise IndexError("MsgColumn index out of range")
        return self._blob[offs[i] : offs[i + 1]]

    def __iter__(self):
        offs = self._offs.tolist()
        return map(self._blob.__getitem__, map(slice, offs, offs[1:]))

    def __eq__(self, other):
        if not isinstance(other, (MsgColumn, list, tuple)):
            return NotImplemented
        return len(self) == len(other) and all(map(_eq, self, other))

    __hash__ = None

    def __repr__(self) -> str:
        return f"MsgColumn({len(self)} lanes, {len(self._blob)} bytes)"


def _offs_pointer(offs, n: int):
    """(address, keep-alive) of n + 1 ``uint64`` offsets: an
    ``array('Q')`` is read where it lies, anything else is copied into
    one first."""
    if not (isinstance(offs, array) and offs.typecode == "Q"):
        offs = array("Q", offs)
    if len(offs) != n + 1:
        raise ValueError(f"{n} lanes need {n + 1} offsets, not {len(offs)}")
    return offs.buffer_info()[0], offs


def pack_challenges(recs: bytes, msgs_blob: bytes, offs, n: int):
    """Native per-lane challenges for callers that assemble their own
    rows (the mixed verifier's ed25519 lanes).

    ``recs``: n x 96 bytes (A|R|S); ``msgs_blob`` + ``offs`` (n+1 u64):
    concatenated sign bytes. Returns (kneg_rows 32n bytes, s_ok (n,)
    bool) or None when the native engine is unavailable.
    """
    lib = _load()
    if lib is None:
        return None
    out_kneg = ctypes.create_string_buffer(32 * n)
    out_ok = ctypes.create_string_buffer(n)
    offs_at, offs = _offs_pointer(offs, n)
    rc = lib.edb_pack_challenges(
        recs, msgs_blob, offs_at, n, out_kneg, out_ok
    )
    if rc != 0:
        return None
    return out_kneg.raw, np.frombuffer(out_ok.raw, np.uint8).astype(bool)


def pack_wire(
    keys: bytes, sigs: bytes, msgs: MsgColumn, out: np.ndarray,
    out_ok: np.ndarray,
) -> bool:
    """The device wire buffer of ops/verify.pack_bytes in one native
    call, from columns read in place: ``keys`` n x 32 bytes, ``sigs``
    n x 64, ``msgs`` the sign bytes. Lane i becomes column i of ``out``
    (C-contiguous (128, width) uint8, width >= n; columns past n are
    left as they are), ``out_ok[i]`` (bool, (n,)) is S < L, and a lane
    with S >= L gets a zero column. The interpreter lock is released
    for the call. False when the native engine is unavailable."""
    lib = _load()
    if lib is None:
        return False
    n = len(msgs)
    if len(keys) != 32 * n or len(sigs) != 64 * n or len(out_ok) != n:
        raise ValueError("pack_wire: columns of unequal lane counts")
    if (
        out.shape[0] != 128 or out.shape[1] < n
        or not out.flags.c_contiguous
    ):
        raise ValueError(
            "pack_wire: out must be C-contiguous (128, width >= lanes)"
        )
    rc = lib.edb_pack_wire(
        keys, sigs, msgs.blob, msgs.offs.buffer_info()[0], n,
        out.ctypes.data, out.shape[1], out_ok.ctypes.data,
    )
    return rc == 0


def vote_sign_bytes(prefix: bytes, suffix: bytes, timestamps_ns):
    """CanonicalVote sign bytes of votes that differ in the timestamp
    alone, for types/canonical.vote_sign_bytes_many: ``timestamps_ns`` an
    ``array('q')``. Returns the lanes as a :class:`MsgColumn`, the
    layout ``pack_wire`` takes; None when the native engine is
    unavailable. The call keeps the interpreter lock (it takes ~30 ns a
    lane)."""
    lib = _load()
    if lib is None:
        return None
    n = len(timestamps_ns)
    out = bytearray(n * (len(prefix) + len(suffix) + 32))
    offs = array("Q", bytes(8 * (n + 1)))
    lib.edb_vote_sign_bytes(
        prefix, len(prefix), suffix, len(suffix),
        timestamps_ns.buffer_info()[0], n,
        (ctypes.c_char * len(out)).from_buffer(out), offs.buffer_info()[0],
    )
    return MsgColumn(bytes(memoryview(out)[: offs[n]]), offs)


def sr_challenge_batch(
    ctx_state: bytes, recs: bytes, msgs_blob: bytes, offs, n: int
):
    """Batched sr25519 (schnorrkel) verification challenges.

    ``ctx_state``: 203-byte serialized STROBE state of the merlin
    transcript prefix Transcript("SigningContext") + append("", ctx)
    (crypto/sr25519._context_prefix — pure function of the signing
    context, cached). ``recs``: n x 64 bytes (pk | R); ``msgs_blob`` +
    ``offs`` (n+1 u64): concatenated sign bytes. Returns n x 32 bytes of
    little-endian challenges k_i mod L, or None when the native engine
    is unavailable. Reference surface: crypto/sr25519/batch.go:14-46.
    """
    lib = _load()
    if lib is None:
        return None
    out_k = ctypes.create_string_buffer(32 * n)
    offs_at, offs = _offs_pointer(offs, n)
    rc = lib.edb_sr_challenge_batch(
        ctx_state, recs, msgs_blob, offs_at, n, out_k
    )
    if rc != 0:
        return None
    return out_k.raw


def ristretto_to_edwards_batch(encs: bytes, m: int):
    """Decode m ristretto255 encodings (RFC 9496) to compressed edwards.

    Returns (enc_rows: 32*m bytes, ok: (m,) bool) or None when the
    native engine is unavailable. Both sr25519 batch consumers — the
    host MSM and the TPU kernel — take compressed edwards points, so
    the decode and re-compression never touch Python bigints.
    """
    lib = _load()
    if lib is None:
        return None
    out_enc = ctypes.create_string_buffer(32 * m)
    out_ok = ctypes.create_string_buffer(m)
    lib.edb_ristretto_to_edwards(encs, m, out_enc, out_ok)
    return out_enc.raw, np.frombuffer(out_ok.raw, np.uint8).astype(bool)


def _msm_identity(points: bytes, coeffs: bytes, m: int) -> int:
    return _load().edb_msm_is_identity_x8(points, coeffs, m)


def _decompress_ok(encs: bytes, m: int) -> np.ndarray:
    out = ctypes.create_string_buffer(m)
    _load().edb_decompress_ok(encs, m, out)
    return np.frombuffer(out.raw, np.uint8).astype(bool)


def keccak_f1600_inplace(state: bytearray) -> bool:
    """Native keccak-f[1600] on a 200-byte state; False if unavailable
    (the merlin/STROBE layer falls back to its pure-Python permutation)."""
    lib = _load()
    if lib is None:
        return False
    buf = (ctypes.c_ubyte * 200).from_buffer(state)
    lib.edb_keccak_f1600(ctypes.addressof(buf))
    return True


def scalar_base_mult(scalar: int):
    """[s]B as an extended-coordinate point tuple, or None if the native
    engine is unavailable.

    The SIGNING primitive: the C side uses a constant-time window select
    (no secret-indexed loads/branches), unlike the variable-time Python
    oracle — sr25519 signing routes here (crypto/sr25519.py). ~50 us vs
    ~5 ms pure Python.
    """
    lib = _load()
    if lib is None:
        return None
    out = ctypes.create_string_buffer(64)
    lib.edb_scalar_base_mult_xy(
        (scalar % L).to_bytes(32, "little"), out
    )
    x = int.from_bytes(out.raw[:32], "little")
    y = int.from_bytes(out.raw[32:], "little")
    return (x, y, 1, x * y % ref.P)


class _Lane:
    __slots__ = ("a", "r", "s", "k", "z")

    def __init__(self, a, r, s, k, z):
        self.a, self.r, self.s, self.k, self.z = a, r, s, k, z


def _check_lanes_res(lanes) -> int:
    """One RLC MSM over the given lanes.

    Returns the raw engine verdict: 1 all-valid, 0 equation fails,
    -(2+i) when MSM input point i fails ZIP-215 decoding (the engine
    decompresses before any bucket work, so a decode failure costs
    only the decompression prefix, not an MSM)."""
    m = 2 * len(lanes) + 1
    points = bytearray()
    coeffs = bytearray()
    b = 0
    for ln in lanes:
        b = (b + ln.z * ln.s) % L
        points += ln.a
        coeffs += ((-(ln.z * ln.k)) % L).to_bytes(32, "little")
        # -R with coefficient +z (128-bit) instead of R with L - z
        # (252-bit): point negation is a sign-bit flip on the encoding
        # (exact under ZIP-215 incl. the x == 0 fixed point), and short
        # coefficients skip half the Pippenger windows.
        points += ln.r[:31] + bytes([ln.r[31] ^ 0x80])
        coeffs += ln.z.to_bytes(32, "little")
    points += _B_ENC
    coeffs += b.to_bytes(32, "little")
    return _msm_identity(bytes(points), bytes(coeffs), m)


def _check_lanes(lanes) -> bool:
    """True iff all lanes valid; callers guarantee decodable points."""
    res = _check_lanes_res(lanes)
    # decompress failures were filtered upstream; a residual -n is a
    # bug, not an invalid signature — surface it
    if res < 0:
        raise RuntimeError(f"unexpected decompress failure at {-res - 2}")
    return res == 1


def _verdict_lanes(lanes, out, idx_map, res=None) -> None:
    """Full RLC verdict over built lanes: one MSM; on an undecodable
    point, filter it and re-check; on equation failure, binary-split
    attribution. Shared by verify_many's sad path and verify_quads so
    the ed25519 and sr25519 host paths can't diverge.

    ``res``: a verdict already obtained for exactly these lanes and
    coefficients (verify_many's fused edb_verify_batch call) — skips
    the redundant opening MSM."""
    if not lanes:
        return
    if res is None:
        res = _check_lanes_res(lanes)
    if res == 1:
        for i in idx_map:
            out[i] = True
        return
    if res < 0:
        enc = b"".join(ln.a + ln.r for ln in lanes)
        ok = _decompress_ok(enc, 2 * len(lanes))
        good, gmap = [], []
        for j, (ln, i) in enumerate(zip(lanes, idx_map)):
            if ok[2 * j] and ok[2 * j + 1]:
                good.append(ln)
                gmap.append(i)
        lanes, idx_map = good, gmap
        if not lanes:
            return
        if _check_lanes(lanes):
            for i in idx_map:
                out[i] = True
            return
    _attribute(lanes, out, idx_map)


def _attribute(lanes, out, idx_map) -> None:
    """Binary-split attribution of a failing batch (voi-style)."""
    if len(lanes) == 1:
        out[idx_map[0]] = _check_lanes(lanes)
        return
    if _check_lanes(lanes):
        for i in idx_map:
            out[i] = True
        return
    mid = len(lanes) // 2
    _attribute(lanes[:mid], out, idx_map[:mid])
    _attribute(lanes[mid:], out, idx_map[mid:])


def verify_quads(quads) -> list[bool] | None:
    """RLC batch verdict over precomputed (A_enc, R_enc, s, k) quads.

    The sr25519 HOST path: challenges come from the native merlin engine
    (sr_challenge_batch) and the points are ristretto decodes
    re-compressed as edwards encodings — the curve equation, one
    Pippenger MSM, and the binary-split attribution are exactly the
    ed25519 machinery (reference: crypto/sr25519/batch.go:48-61 feeds
    the same curve25519-voi verifier core its ed25519 batch uses).
    Entries may be None (malformed lane -> False). Returns None when the
    native engine is unavailable.
    """
    if _load() is None:
        return None
    n = len(quads)
    out = [False] * n
    lanes, idx_map = [], []
    for i, q in enumerate(quads):
        if q is None:
            continue
        a_enc, r_enc, s, k = q
        z = 0
        while z == 0:  # z == 0 voids the RLC: redraw (p = 2^-128)
            z = int.from_bytes(secrets.token_bytes(16), "little")
        lanes.append(_Lane(bytes(a_enc), bytes(r_enc), s, k, z))
        idx_map.append(i)
    _verdict_lanes(lanes, out, idx_map)
    return out


def verify_many(pubkeys, msgs, sigs) -> list[bool]:
    """Batch ZIP-215 verification; one MSM for an all-valid batch.

    Falls back to fast25519 (sequential OpenSSL + oracle recheck) when
    the native engine is unavailable.
    """
    if _load() is None:
        from . import fast25519

        return fast25519.verify_many(pubkeys, msgs, sigs)
    n = len(pubkeys)
    out = [False] * n
    # Happy path: ONE fused native call — SHA-512 challenges, mod-L
    # coefficient math, the basepoint scalar, and the MSM all in C. The
    # only per-lane Python left is the length/S<L admission filter.
    well = []  # (index, pubkey, sig, msg) of well-formed lanes
    for i in range(n):
        p, m, s = bytes(pubkeys[i]), bytes(msgs[i]), bytes(sigs[i])
        if len(p) != 32 or len(s) != 64:
            continue
        if int.from_bytes(s[32:], "little") >= L:
            continue  # S must be canonical even under ZIP-215
        well.append((i, p, s, m))
    if not well:
        return out
    zs = bytearray(secrets.token_bytes(16 * len(well)))
    zero16 = bytes(16)
    for j in range(len(well)):  # z == 0 voids the RLC: redraw (p=2^-128)
        while zs[16 * j : 16 * j + 16] == zero16:
            zs[16 * j : 16 * j + 16] = secrets.token_bytes(16)
    recs = b"".join(p + s for _i, p, s, _m in well)
    msgs_blob = b"".join(m for *_x, m in well)
    offs = [0]
    for *_x, m in well:
        offs.append(offs[-1] + len(m))
    offs_arr = (ctypes.c_uint64 * len(offs))(*offs)
    res = _load().edb_verify_batch(
        recs, msgs_blob, offs_arr, bytes(zs), len(well)
    )
    if res == 1:
        for i, *_x in well:
            out[i] = True
        return out
    # Sad path (invalid signature or undecodable point in the batch):
    # rebuild Python lanes for attribution, REUSING the drawn
    # coefficients (they were never revealed, so they stay sound — and
    # the splits then re-check exactly the committed linear
    # combination). Paying the challenge twice here is fine — this path
    # only runs under attack/corruption.
    lanes, idx_map = [], []
    for j, (i, p, s, m) in enumerate(well):
        k = ref.challenge_scalar(s[:32], p, m)
        z = int.from_bytes(zs[16 * j : 16 * j + 16], "little")
        lanes.append(
            _Lane(p, s[:32], int.from_bytes(s[32:], "little"), k, z)
        )
        idx_map.append(i)
    _verdict_lanes(lanes, out, idx_map, res=res)
    return out
