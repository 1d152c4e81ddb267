"""sr25519 (schnorrkel v1) verification, written plainly from the published
specifications and nothing of the program:

  Keccak-f[1600]   FIPS 202, section 3
  STROBE-128       strobe.sourceforge.io, v1.0.2 (the operations merlin uses)
  merlin           merlin.cool, transcript protocol v1.0
  ristretto255     RFC 9496, sections 4.3.1 (decode) and 4.3.2 (encode)
  schnorrkel       w3f/schnorrkel sign.rs: Schnorr over ristretto255 with a
                   merlin transcript; the signing context of substrate chains

A signature is R (32 bytes, a ristretto255 encoding) || s (32 bytes, little
endian, the top bit set as the v1 marker). It verifies when the marker is
set, s < L, the public key decodes, and the encoding of [s]B - [k]A equals R,
with k the transcript's 64-byte challenge reduced mod L. Variable time, one
lane at a time: this is an oracle, not a verifier to deploy.
"""

from __future__ import annotations

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)
BASE = (
    15112221349535400772501151409588531511454012693041857206046113283949847762202,
    46316835694926478169428394003475163141307993866256225615783033603165251855960,
    1,
    15112221349535400772501151409588531511454012693041857206046113283949847762202
    * 46316835694926478169428394003475163141307993866256225615783033603165251855960
    % P,
)
IDENTITY = (0, 1, 1, 0)
SIGNING_CONTEXT = b"substrate"

# --- Keccak-f[1600] ---------------------------------------------------------

_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
# rotation offsets r[x][y]
_ROT = [
    [0, 36, 3, 41, 18],
    [1, 44, 10, 45, 2],
    [62, 6, 43, 15, 61],
    [28, 55, 25, 21, 56],
    [27, 20, 39, 8, 14],
]
_M64 = (1 << 64) - 1


def _rol(v: int, n: int) -> int:
    return ((v << n) | (v >> (64 - n))) & _M64 if n else v


def keccak_f1600(state: bytearray) -> None:
    """The permutation, in place, on 200 bytes: lane (x, y) is the 8 bytes
    at 8 * (x + 5y), little endian."""
    a = [[int.from_bytes(state[8 * (x + 5 * y):8 * (x + 5 * y) + 8], "little")
          for y in range(5)] for x in range(5)]
    for rc in _RC:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4] for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        a = [[a[x][y] ^ d[x] for y in range(5)] for x in range(5)]
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rol(a[x][y], _ROT[x][y])
        a = [[b[x][y] ^ ((~b[(x + 1) % 5][y]) & b[(x + 2) % 5][y])
              for y in range(5)] for x in range(5)]
        a[0][0] ^= rc
    for x in range(5):
        for y in range(5):
            state[8 * (x + 5 * y):8 * (x + 5 * y) + 8] = a[x][y].to_bytes(
                8, "little")


# --- STROBE-128, the operations merlin uses --------------------------------

_FLAG_I, _FLAG_A, _FLAG_C, _FLAG_T, _FLAG_M, _FLAG_K = 1, 2, 4, 8, 16, 32
_R = 166  # 200 - 128 / 4: the rate of STROBE-128


class Strobe128:
    def __init__(self, protocol_label: bytes):
        st = bytearray(200)
        st[0:6] = bytes([1, _R + 2, 1, 0, 1, 96])
        st[6:18] = b"STROBEv1.0.2"
        keccak_f1600(st)
        self.st = st
        self.pos = 0
        self.pos_begin = 0
        self.cur_flags = 0
        self.meta_ad(protocol_label, False)

    def _run_f(self) -> None:
        self.st[self.pos] ^= self.pos_begin
        self.st[self.pos + 1] ^= 0x04
        self.st[_R + 1] ^= 0x80
        keccak_f1600(self.st)
        self.pos = 0
        self.pos_begin = 0

    def _absorb(self, data: bytes) -> None:
        for byte in data:
            self.st[self.pos] ^= byte
            self.pos += 1
            if self.pos == _R:
                self._run_f()

    def _squeeze(self, n: int) -> bytes:
        out = bytearray()
        for _ in range(n):
            out.append(self.st[self.pos])
            self.st[self.pos] = 0
            self.pos += 1
            if self.pos == _R:
                self._run_f()
        return bytes(out)

    def _begin_op(self, flags: int, more: bool) -> None:
        if more:
            if flags != self.cur_flags:
                raise ValueError("a continued operation changed its flags")
            return
        old_begin = self.pos_begin
        self.pos_begin = self.pos + 1
        self.cur_flags = flags
        self._absorb(bytes([old_begin, flags]))
        if flags & (_FLAG_C | _FLAG_K) and self.pos != 0:
            self._run_f()

    def meta_ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_M | _FLAG_A, more)
        self._absorb(data)

    def ad(self, data: bytes, more: bool) -> None:
        self._begin_op(_FLAG_A, more)
        self._absorb(data)

    def prf(self, n: int, more: bool) -> bytes:
        self._begin_op(_FLAG_I | _FLAG_A | _FLAG_C, more)
        return self._squeeze(n)


# --- merlin -----------------------------------------------------------------


class Transcript:
    def __init__(self, label: bytes):
        self.strobe = Strobe128(b"Merlin v1.0")
        self.append_message(b"dom-sep", label)

    def append_message(self, label: bytes, message: bytes) -> None:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(len(message).to_bytes(4, "little"), True)
        self.strobe.ad(message, False)

    def challenge_bytes(self, label: bytes, n: int) -> bytes:
        self.strobe.meta_ad(label, False)
        self.strobe.meta_ad(n.to_bytes(4, "little"), True)
        return self.strobe.prf(n, False)


# --- edwards25519 in extended coordinates (a = -1) ---------------------------


def point_add(p1, p2):
    x1, y1, z1, t1 = p1
    x2, y2, z2, t2 = p2
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * D * t1 * t2 % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def point_neg(p):
    x, y, z, t = p
    return ((-x) % P, y, z, (-t) % P)


def double_scalar_mult(a: int, pa, b: int, pb):
    """[a]pa + [b]pb, one double-and-add over the bits of both."""
    both = point_add(pa, pb)
    acc = IDENTITY
    for i in range(max(a.bit_length(), b.bit_length()) - 1, -1, -1):
        acc = point_add(acc, acc)
        bit_a, bit_b = (a >> i) & 1, (b >> i) & 1
        if bit_a and bit_b:
            acc = point_add(acc, both)
        elif bit_a:
            acc = point_add(acc, pa)
        elif bit_b:
            acc = point_add(acc, pb)
    return acc


# --- ristretto255 (RFC 9496) ------------------------------------------------


def _is_negative(x: int) -> bool:
    return x % P & 1 == 1


def _abs(x: int) -> int:
    x %= P
    return P - x if _is_negative(x) else x


def _sqrt_ratio_m1(u: int, v: int) -> tuple[bool, int]:
    """RFC 9496 section 4.2: (was_square, r) with r = +sqrt(u/v),
    +sqrt(i*u/v) or 0."""
    v3 = v * v % P * v % P
    v7 = v3 * v3 % P * v % P
    r = u * v3 % P * pow(u * v7 % P, (P - 5) // 8, P) % P
    check = v * r % P * r % P
    correct = check == u % P
    flipped = check == (-u) % P
    flipped_i = check == (-u) * SQRT_M1 % P
    if flipped or flipped_i:
        r = r * SQRT_M1 % P
    return correct or flipped, _abs(r)


INVSQRT_A_MINUS_D = _sqrt_ratio_m1(1, (-1 - D) % P)[1]


def ristretto_decode(data: bytes):
    """The point, or None for an encoding that is not canonical or not a
    point of the group."""
    if len(data) != 32:
        return None
    s = int.from_bytes(data, "little")
    if s >= P or _is_negative(s):
        return None
    ss = s * s % P
    u1 = (1 - ss) % P
    u2 = (1 + ss) % P
    u2_sqr = u2 * u2 % P
    v = (-(D * u1 % P * u1) - u2_sqr) % P
    was_square, invsqrt = _sqrt_ratio_m1(1, v * u2_sqr % P)
    den_x = invsqrt * u2 % P
    den_y = invsqrt * den_x % P * v % P
    x = _abs(2 * s * den_x)
    y = u1 * den_y % P
    t = x * y % P
    if not was_square or _is_negative(t) or y == 0:
        return None
    return (x, y, 1, t)


def ristretto_encode(point) -> bytes:
    x0, y0, z0, t0 = point
    u1 = (z0 + y0) * (z0 - y0) % P
    u2 = x0 * y0 % P
    _, invsqrt = _sqrt_ratio_m1(1, u1 * u2 % P * u2 % P)
    den1 = invsqrt * u1 % P
    den2 = invsqrt * u2 % P
    z_inv = den1 * den2 % P * t0 % P
    if _is_negative(t0 * z_inv):
        x, y = y0 * SQRT_M1 % P, x0 * SQRT_M1 % P
        den_inv = den1 * INVSQRT_A_MINUS_D % P
    else:
        x, y, den_inv = x0, y0, den2
    if _is_negative(x * z_inv):
        y = (-y) % P
    return _abs(den_inv * (z0 - y)).to_bytes(32, "little")


# --- schnorrkel v1 ----------------------------------------------------------


def challenge(pubkey: bytes, msg: bytes, r_enc: bytes,
              context: bytes = SIGNING_CONTEXT) -> int:
    """k: the signing transcript's ``sign:c`` challenge, 64 bytes mod L."""
    t = Transcript(b"SigningContext")
    t.append_message(b"", context)
    t.append_message(b"sign-bytes", msg)
    t.append_message(b"proto-name", b"Schnorr-sig")
    t.append_message(b"sign:pk", pubkey)
    t.append_message(b"sign:R", r_enc)
    return int.from_bytes(t.challenge_bytes(b"sign:c", 64), "little") % L


def verify(pubkey: bytes, msg: bytes, sig: bytes,
           context: bytes = SIGNING_CONTEXT) -> bool:
    if len(pubkey) != 32 or len(sig) != 64 or not sig[63] & 0x80:
        return False
    s = int.from_bytes(sig[32:63] + bytes([sig[63] & 0x7F]), "little")
    if s >= L:
        return False
    a = ristretto_decode(pubkey)
    if a is None:
        return False
    k = challenge(pubkey, msg, sig[:32], context)
    return ristretto_encode(
        double_scalar_mult(s, BASE, k, point_neg(a))) == sig[:32]


def verify_lanes(lanes: list[tuple[bytes, bytes, bytes]]) -> list[bool]:
    """One verdict per (pubkey, msg, sig). Top level so that a spawned
    worker can be handed a slice of lanes."""
    return [verify(pk, m, s) for pk, m, s in lanes]
