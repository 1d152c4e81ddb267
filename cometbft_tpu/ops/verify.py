"""Host <-> device glue for batched ed25519 verification.

Split of labor (TPU-first):

* Host (numpy, vectorized): byte unpacking, limb packing, the SHA-512
  challenge k = SHA512(R || A || M) mod L (byte-serial, C-speed, irrelevant
  cost next to the curve math), canonicality check S < L, batch padding.
  The only per-lane Python work is the hash + two bigint ops; all byte ->
  bit -> limb/nibble conversion is bulk numpy.
* Device (jax, ops.curve.verify_kernel): point decompression, the
  ~3k-field-mul windowed double-scalar ladder per signature, validity bitmap.

Batches are padded to shape buckets (powers of two) so each bucket compiles
once and stays cached — ragged per-round batch sizes (validator sets churn)
must not retrigger XLA compilation in the consensus hot loop (reference
behavior this replaces: per-round crypto/batch.BatchVerifier construction in
types/validation.go:153-257).

Array layout: batch axis LAST everywhere (y limbs (20, N), scalars (64, N)
nibbles) — see ops/field.py for why batch-minor wins on TPU.
"""

from __future__ import annotations

import os
import time

from ..libs import accel as libaccel
from ..libs import devstats as libdevstats
from ..libs.accel import ACCELERATOR_BACKENDS
from ..libs import metrics as libmetrics
from ..libs import sync as libsync
from collections import OrderedDict, deque
from functools import lru_cache

import numpy as np

import jax

from ..crypto import ed25519_ref
from . import curve, field
from . import warm as libwarm

L = curve.L
_MIN_BUCKET = 8
# the phase label of this module's own batches (verify_batch); callers
# that bring their own packing (coalescer, sr25519, mixed) pass theirs
_BACKEND = "ed25519-tpu"

_LIMB_WEIGHTS = (1 << np.arange(field.BITS, dtype=np.int32))  # (13,)
_NIB_WEIGHTS = np.array([1, 2, 4, 8], np.int32)


def bucket_size(n: int) -> int:
    """Smallest compile-shape bucket holding n (8 <= bucket <= _CHUNK):
    powers of two plus the 3*2^k midpoints that are multiples of the
    512-lane Pallas block (1536, 3072, 6144, 12288).

    Mid buckets cut worst-case padding from 2x toward 1.33x where the
    kernel time is lane-proportional — a 10k-lane light-client commit
    pads to 12288, not 16384 (measured 77 ms vs 120 ms on a v5e).
    Smaller midpoints are skipped: they are not block-multiples (the
    Pallas wrappers require n % 512 == 0 at or above one block), and
    sub-1024 batches route host anyway. Batches past _CHUNK never reach
    here — verify_bytes_async splits them into pipelined _CHUNK-lane
    launches first.
    """
    assert n <= _CHUNK, n
    b = _MIN_BUCKET
    while b < n:
        mid = b + b // 2
        if mid >= n and mid % 512 == 0:
            return mid
        b *= 2
    return b


def _le_bits(arr: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 -> (N, 256) bits, little-endian bit order."""
    return np.unpackbits(arr, axis=1, bitorder="little")


def _msb_nibbles(arr: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 little-endian scalars -> (64, N) 4-bit windows MSB-first."""
    bits = _le_bits(arr).reshape(arr.shape[0], 64, 4)
    nibs = (bits.astype(np.int32) * _NIB_WEIGHTS).sum(axis=2)  # LSB-first
    return np.ascontiguousarray(nibs[:, ::-1].T)


def _y_limbs(bits: np.ndarray) -> np.ndarray:
    """(N, 256) little-endian bits -> (20, N) 13-bit y limbs.

    Reshape + tiny reduce instead of a (255, 20) matmul: numpy integer
    matmul has no BLAS path and was the dominant packing cost.
    """
    n = bits.shape[0]
    padded = np.zeros((n, field.NLIMB * field.BITS), np.int32)
    padded[:, :255] = bits[:, :255]
    limbs = (padded.reshape(n, field.NLIMB, field.BITS) * _LIMB_WEIGHTS).sum(
        axis=2, dtype=np.int32
    )
    return np.ascontiguousarray(limbs.T)


def pack_part_row(a_enc, r_enc, s_int: int, k_int: int) -> bytes:
    """One 128-byte wire row A | R | S | (-k mod L), little-endian.

    The layout's home for quad-shaped inputs: :func:`pack_parts` and
    the sr25519 lanes of the mixed verifier's fused packer build
    through it. The mixed verifier's ed25519 lanes assemble the SAME
    layout from raw wire bytes + the native packer's kneg (no int
    round-trip); byte equality of the two assemblies is pinned by
    tests/test_sr25519_secp.py::
    test_mixed_row_assembly_matches_pack_part_row.
    """
    return (
        bytes(a_enc)
        + bytes(r_enc)
        + s_int.to_bytes(32, "little")
        + ((L - k_int) % L).to_bytes(32, "little")
    )


def pack_parts(parts) -> tuple[np.ndarray, np.ndarray]:
    """Pack pre-decomposed verification quadruples into the wire format.

    ``parts[i]`` is (a_edwards32, r_edwards32, s_int, k_int) or None for a
    host-rejected lane. Used by signature schemes whose challenge is NOT
    SHA512(R||A||M) — sr25519 computes k from a merlin transcript on host
    and rides the same cofactored kernel (crypto/sr25519.py).
    """
    n = len(parts)
    host_ok = np.ones(n, bool)
    buf = np.zeros((128, n), np.uint8)
    for i, part in enumerate(parts):
        if part is None:
            host_ok[i] = False
            continue
        buf[:, i] = np.frombuffer(pack_part_row(*part), np.uint8)
    return buf, host_ok


def pack_bytes(
    pubkeys, msgs, sigs, width: int | None = None
) -> tuple[np.ndarray, np.ndarray]:
    """Host-side packing to the compact device wire format.

    Returns (buf (128, width) uint8, host_ok (n,) bool); ``width``
    defaults to n and may be the launch bucket, the columns past n then
    being zero as the launch's padding would have made them. Rows 0-31
    pubkey, 32-63 R, 64-95 S, 96-127 (-k mod L), all little-endian
    bytes; the device unpacks bits/limbs/nibbles itself
    (:func:`unpack_on_device`). Shipping 128 B/sig instead of ~680 B of
    pre-unpacked int32 limbs cuts the host->HBM transfer ~5x. Malformed
    inputs (wrong lengths, non-canonical S >= L) get host_ok=False and
    zeroed lanes.

    ``msgs`` is a ``host_batch.MsgColumn`` or any sequence of
    bytes-likes, as are the lanes of ``pubkeys`` and ``sigs``. With the
    native engine the lanes travel as columns (:func:`_pack_bytes_native`)
    and no Python statement runs once per lane; without it, the loop
    below.
    """
    n = len(pubkeys)
    if not n == len(msgs) == len(sigs):
        raise ValueError("pack_bytes needs a message and a signature per key")
    if width is None:
        width = n
    native = _pack_bytes_native(pubkeys, msgs, sigs, n, width)
    if native is not None:
        return native
    libmetrics.observe_pack_lanes("per_lane", n)
    host_ok = np.ones(n, bool)
    pk_buf = bytearray(32 * n)
    rr_buf = bytearray(32 * n)
    ss_buf = bytearray(32 * n)
    kneg_buf = bytearray(32 * n)
    # One tight Python loop for the parts numpy can't do: variable-length
    # guards, the SHA-512 challenge, and 256-bit canonicality/modular ops.
    challenge = ed25519_ref.challenge_scalar
    for i in range(n):
        p_i, s_i = pubkeys[i], sigs[i]
        if len(p_i) != 32 or len(s_i) != 64:
            host_ok[i] = False
            continue
        s_int = int.from_bytes(s_i[32:], "little")
        if s_int >= L:  # S must be canonical even under ZIP-215
            host_ok[i] = False
            continue
        k = challenge(s_i[:32], p_i, msgs[i])
        o = 32 * i
        pk_buf[o : o + 32] = p_i
        rr_buf[o : o + 32] = s_i[:32]
        ss_buf[o : o + 32] = s_i[32:]
        kneg_buf[o : o + 32] = ((L - k) % L).to_bytes(32, "little")

    rows = [
        np.frombuffer(bytes(b), np.uint8).reshape(n, 32).T
        for b in (pk_buf, rr_buf, ss_buf, kneg_buf)
    ]
    buf = np.zeros((128, width), np.uint8)
    buf[:, :n] = np.concatenate(rows, axis=0)
    return buf, host_ok


_Z32 = bytes(32)
_Z64 = bytes(64)


def _pack_bytes_native(pubkeys, msgs, sigs, n: int, width: int):
    """pack_bytes through the native engine; None to fall back.

    The lanes travel as columns: the keys joined to n x 32 bytes, the
    signatures to n x 64, the messages one blob with its offsets (a
    ``MsgColumn`` is taken as it is, a plain sequence joined), and ONE
    native call (native/edbatch.cpp edb_pack_wire, interpreter lock
    released) hashes every lane's challenge and writes the rows already
    transposed into the (128, width) buffer. Nothing here runs once per
    lane in Python, and no numpy call walks the lanes, while every key
    is 32 bytes and every signature 64. A lane that is not gets
    host_ok False and a zero column as ever: zero records stand in for
    it in the columns (the one per-lane pass, counted as
    ``crypto_verify_pack_lanes_total{path="per_lane"}``).
    """
    from ..crypto import host_batch

    if not host_batch.available():
        return None
    path, bad = "columnar", ()
    if n and (set(map(len, pubkeys)) != {32} or set(map(len, sigs)) != {64}):
        path = "per_lane"
        pubkeys, sigs = list(pubkeys), list(sigs)
        bad = [
            i for i in range(n)
            if len(pubkeys[i]) != 32 or len(sigs[i]) != 64
        ]
        for i in bad:
            pubkeys[i], sigs[i] = _Z32, _Z64
    if not isinstance(msgs, host_batch.MsgColumn):
        msgs = host_batch.MsgColumn.joined(msgs)
    buf = np.zeros((128, width), np.uint8)
    host_ok = np.empty(n, bool)
    if not host_batch.pack_wire(
        b"".join(pubkeys), b"".join(sigs), msgs, buf, host_ok
    ):
        return None
    if bad:
        host_ok[bad] = False
        buf[:, bad] = 0
    libmetrics.observe_pack_lanes(path, n)
    return buf, host_ok


def pack_inputs(pubkeys, msgs, sigs):
    """Host-side packing of (pubkey, msg, sig) triples, batch axis last.

    Returns (arrays dict for verify_kernel, host_ok mask). Used by callers
    that need the unpacked limb arrays on host (e.g. the sharded multi-chip
    path); the single-chip fast path ships :func:`pack_bytes` instead.
    """
    buf, host_ok = pack_bytes(pubkeys, msgs, sigs)
    n = buf.shape[1]
    pk_bits = _le_bits(np.ascontiguousarray(buf[0:32].T))
    rr_bits = _le_bits(np.ascontiguousarray(buf[32:64].T))
    arrays = {
        "y_a": _y_limbs(pk_bits),
        "sign_a": pk_bits[:, 255].astype(np.int32),
        "y_r": _y_limbs(rr_bits),
        "sign_r": rr_bits[:, 255].astype(np.int32),
        "s_nibs": _msb_nibbles(np.ascontiguousarray(buf[64:96].T)),
        "kneg_nibs": _msb_nibbles(np.ascontiguousarray(buf[96:128].T)),
    }
    return arrays, host_ok


# -- device-side byte unpacking helpers (shared by the uncached, cached
# and builder unpackers; a fork here would silently diverge the paths) --


def _dev_le_bits(rows):  # (32, N) int32 -> (256, N)
    import jax.numpy as jnp

    shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
    bits = (rows[:, None, :] >> shifts) & 1
    return bits.reshape(256, rows.shape[-1])


def _dev_y_limbs(bits):  # (256, N) -> (20, N)
    import jax.numpy as jnp

    n = bits.shape[-1]
    padded = jnp.concatenate(
        [bits[:255], jnp.zeros((5, n), jnp.int32)], axis=0
    )
    w = (1 << jnp.arange(field.BITS, dtype=jnp.int32)).reshape(1, -1, 1)
    return jnp.sum(padded.reshape(field.NLIMB, field.BITS, n) * w, axis=1)


def _dev_msb_nibbles(rows):  # (32, N) -> (64, N), MSB-first windows
    import jax.numpy as jnp

    lo = rows & 15
    hi = rows >> 4
    nibs = jnp.stack([lo, hi], axis=1).reshape(64, rows.shape[-1])
    return nibs[::-1]


def unpack_on_device(buf):
    """(128, N) uint8 wire buffer -> verify_kernel arrays, on device.

    Bit/limb/nibble unpacking is a handful of shifts and tiny reduces —
    negligible VPU work that saves ~5x on the host->HBM transfer.
    """
    import jax.numpy as jnp

    b = buf.astype(jnp.int32)
    pk_bits = _dev_le_bits(b[0:32])
    rr_bits = _dev_le_bits(b[32:64])
    return {
        "y_a": _dev_y_limbs(pk_bits),
        "sign_a": pk_bits[255],
        "y_r": _dev_y_limbs(rr_bits),
        "sign_r": rr_bits[255],
        "s_nibs": _dev_msb_nibbles(b[64:96]),
        "kneg_nibs": _dev_msb_nibbles(b[96:128]),
    }


# -- verdict bit-packing ---------------------------------------------------
# The ok-mask is the ONLY payload the host consumes from a verify
# launch, and it used to ride back as one bool byte per lane. Packing
# it into uint8 mask words ON DEVICE (a reshape + tiny weighted reduce,
# fused into the kernel's jit program) shrinks the d2h readback 8x;
# device_transfer_bytes_total{d2h} reconciles at bucket/8 bytes per
# launch (tests/test_observability.py::TestNoRecompileGuard). Every
# lane count here is a shape bucket, so N % 8 == 0 always holds.

_OK_BIT_WEIGHTS = np.array([1, 2, 4, 8, 16, 32, 64, 128], np.int32)


def _pack_ok_bits(ok):
    """(N,) device bool -> (N//8,) uint8, little-endian bit order."""
    import jax.numpy as jnp

    bits = ok.astype(jnp.int32).reshape(-1, 8)
    w = jnp.asarray(_OK_BIT_WEIGHTS)
    return jnp.sum(bits * w, axis=1).astype(jnp.uint8)


def unpack_ok_bits(packed: np.ndarray, n: int) -> np.ndarray:
    """Host inverse of :func:`_pack_ok_bits`: (n,) bool validity."""
    return np.unpackbits(
        np.ascontiguousarray(packed, np.uint8), bitorder="little"
    )[:n].astype(bool)


def _kernel_from_bytes(buf):
    return _pack_ok_bits(curve.verify_kernel(**unpack_on_device(buf)))


# ------------------------------------------------------------------ cache
# HBM-resident expanded-pubkey cache. The reference keeps a 4096-entry
# LRU of expanded pubkeys because validators recur every round
# (crypto/ed25519/ed25519.go:31,56); the TPU analog caches each key's
# DECOMPRESSED point + 16-entry Niels table in a device arena, so a
# steady-state commit verify ships only (R, S, -k) plus uint16 slot
# indices and skips the ~254-squaring sqrt chain and the 14-point-op
# table build entirely (~11% of per-signature muls, SURVEY §7(c)).


def _unpack_rsk_on_device(buf):
    """(96, N) uint8 rows R|S|kneg -> cached-kernel arrays, on device."""
    import jax.numpy as jnp

    b = buf.astype(jnp.int32)
    rr_bits = _dev_le_bits(b[0:32])
    return {
        "y_r": _dev_y_limbs(rr_bits),
        "sign_r": rr_bits[255],
        "s_nibs": _dev_msb_nibbles(b[32:64]),
        "kneg_nibs": _dev_msb_nibbles(b[64:96]),
    }


def _cached_kernel(arena, arena_ok, idxs, buf):
    arrays = _unpack_rsk_on_device(buf)
    table = arena[:, :, :, idxs]
    ok = curve.verify_kernel_cached(table, **arrays)
    return _pack_ok_bits(ok & arena_ok[idxs])


# The routed Pallas launches compile for the chip (Mosaic). Interpret
# mode exists for tests and chip_smoke.py's CPU dry run, which set this
# before the first trace; nothing infers it from the backend.
_PALLAS_INTERPRET = False


def _cached_kernel_pallas(arena, arena_ok, idxs, buf):
    from . import pallas_verify

    arrays = _unpack_rsk_on_device(buf)
    table = arena[:, :, :, idxs]
    return _pack_ok_bits(pallas_verify.verify_kernel_cached(
        table, arena_ok[idxs], **arrays, interpret=_PALLAS_INTERPRET
    ))


def _builder_kernel(buf):
    """(32, M) uint8 pubkey bytes -> (table, ok) for the arena."""
    import jax.numpy as jnp

    bits = _dev_le_bits(buf.astype(jnp.int32))
    return curve.build_pubkey_tables(_dev_y_limbs(bits), bits[255])


def _scatter_kernel(arena, arena_ok, slots, tables, oks):
    arena = arena.at[:, :, :, slots].set(tables)
    arena_ok = arena_ok.at[slots].set(oks)
    return arena, arena_ok


def _donatable(argnums: tuple[int, ...]) -> tuple[int, ...]:
    """Donate per-launch input buffers on accelerator backends only.

    Donation lets XLA reuse the wire buffer's HBM for ladder temporaries
    (the buffer is dead after unpacking); on the CPU test backend
    donation is unsupported and every call would warn, so gate it.
    """
    return argnums if jax.default_backend() in ACCELERATOR_BACKENDS else ()


# Every degradation the dispatch layer absorbs is counted here and
# logged at its site, so whoever needs the device (chip_smoke.py, an
# operator reading /debug/devstats) can tell a served launch from a
# covered fault. Plain ints bumped without a lock: a lost update under
# a race costs one count, never a verdict.
_FAULTS = {"pallas": 0, "prestage": 0}
_LAUNCHES: dict[str, int] = {}  # devstats kernel name -> launches served


def _note_fault(kind: str, e: Exception, **fields) -> None:
    _FAULTS[kind] += 1
    from ..libs import log as _log

    _log.default_logger().with_module("ops.verify").error(
        f"{kind} fault absorbed; degraded path serves the launch",
        err=repr(e)[:200],
        **fields,
    )


def _served(kernel: str) -> None:
    _LAUNCHES[kernel] = _LAUNCHES.get(kernel, 0) + 1


def dispatch_counters() -> dict:
    """Launches served per kernel, absorbed faults per kind, and
    whether Pallas has been retired in this process (``["pallas"]``
    after a fault, else empty)."""
    return {
        "launches": dict(_LAUNCHES),
        "faults": dict(_FAULTS),
        "pallas_broken": ["pallas"] if _PALLAS_BROKEN else [],
    }


# Buckets at or below this get a DEDICATED jit per (flavor, bucket):
# their own executable cache, their own devstats kernel identity
# (``verify.xla.g64``), and a compile traced with exactly that grid —
# so a 64-lane coalescer window never shares (or walks) the big-bucket
# kernel's signature cache, and the per-window fixed cost of small
# grids is attributable per bucket in the 9_device_floor breakdown.
_SMALL_GRID_MAX = 256


def _small_grid(bucket: int):
    return bucket if bucket <= _SMALL_GRID_MAX else None


@lru_cache(maxsize=None)
def _cached_jits():
    _enable_compilation_cache()
    # NOTE: the scatter deliberately does NOT donate the arena — a verify
    # thread may hold the previous arena reference (handed out by lookup)
    # and dispatch against it after the update; donation would invalidate
    # that buffer under it. Updates are rare (new validator keys), the
    # ~21 MB copy is cheap. (The verify-side jits live in
    # _jitted_kernel.)
    # devstats.track wraps each jit for compile accounting (axis = the
    # positional arg whose last dim is the lane bucket): every XLA
    # compile lands in xla_compile_total{kernel,bucket} and the
    # no-recompile tier-1 guard.
    return (
        libdevstats.track("arena.build", jax.jit(_builder_kernel), axis=0),
        libdevstats.track(
            "arena.scatter", jax.jit(_scatter_kernel), axis=3
        ),
    )


def _builder_bucket(m: int) -> int:
    """Power-of-two compile bucket of a builder launch for ``m`` keys."""
    size = _MIN_BUCKET
    while size < m:
        size *= 2
    return size


# Kept slot vectors of a PubkeyTableCache: the validator sets a process
# checks commits of at one time are a handful (two light clients on one
# chain share one), and a coalescer window's key sequence rarely repeats.
_MEMO_ENTRIES = 4


class PubkeyTableCache:
    """LRU arena of expanded pubkey tables resident on device.

    ``lookup`` maps pubkey byte strings to slot indices, building missing
    entries in one bucketed launch and scattering them into the arena.
    Thread-safe: verify paths run from consensus, blocksync and RPC
    threads concurrently; a scatter produces a NEW arena value (no
    donation), so a verify dispatched against the previous arena keeps a
    live buffer and gathers never race an eviction.
    """

    # One full _CHUNK of distinct signers stays cacheable (a 10k-lane
    # light-client batch must not bail to the uncached path just because
    # it exceeds the arena). 4x the reference's 4096-entry LRU
    # (crypto/ed25519/ed25519.go:31) — theirs sizes a CPU heap, this
    # sizes HBM: ~84 MB of a v5e's 16 GB.
    CAPACITY = 16384

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        # Slot indices ship host->device on EVERY cached-path window;
        # use the narrowest dtype that can address capacity+1 slots
        # (the +1 scratch slot included): uint16 halves the per-lane
        # index wire cost vs int32 for every arena up to 65535 slots.
        # The no-recompile transfer reconciliation pins the reduction.
        self.idx_dtype = (
            np.uint16 if capacity + 1 <= 1 << 16 else np.int32
        )
        self._lock = libsync.Mutex("ops.verify._lock")
        self._slots: OrderedDict[bytes, int] = OrderedDict()
        self._arena = None
        self._arena_ok = None
        # the slot vectors of the last few key columns answered, newest
        # first: (column, _gen it was computed under, width, idxs). _gen
        # counts every slot assigned or evicted, so an entry is served
        # only while the slots are what its vector was read from
        self._memo: list[tuple[bytes, int, int, np.ndarray]] = []
        self._gen = 0
        self.hits = 0
        self.misses = 0
        self.builds = 0  # builder launches (device round trips)
        self.evictions = 0  # LRU slot reclaims (devstats exports these)

    def _ensure_arena(self):
        import jax.numpy as jnp

        if self._arena is None:
            # +1 scratch slot: bucket-padding lanes of a build scatter
            # there (duplicate scatter indices have an unspecified
            # winner, so pads must never alias a real slot)
            self._arena = jnp.zeros(
                (curve.TSIZE, 4, field.NLIMB, self.capacity + 1), jnp.int32
            )
            self._arena_ok = jnp.zeros((self.capacity + 1,), bool)

    def missing(self, pubkeys) -> int:
        """How many distinct keys of ``pubkeys`` a lookup would have to
        build (a pure query: no LRU touch, no launch)."""
        keys = {bytes(pk) for pk in pubkeys}
        with self._lock:
            return sum(1 for pk in keys if pk not in self._slots)

    def _key_column(self, pubkeys) -> bytes | None:
        """The keys joined into one n x 32 column, the memo's key; None
        when a lane is not a 32-byte key (such a batch is walked)."""
        try:
            if set(map(len, pubkeys)) == {32}:
                return b"".join(pubkeys)
        except TypeError:
            pass
        return None

    def _retire_memo(self) -> None:
        """The slots are about to change (caller holds the lock): every
        kept slot vector dies with them. Their keys were served without
        a touch of the LRU, so they are touched now, least lately served
        first, before an eviction chooses its victim: a set answered
        from the memo is never the oldest by accident."""
        slots = self._slots
        for column, _gen, _width, _idxs in reversed(self._memo):
            for at in range(0, len(column), 32):
                pk = column[at : at + 32]
                if pk in slots:
                    slots.move_to_end(pk)
        self._memo.clear()

    def lookup(self, pubkeys, width: int | None = None):
        """Per-pubkey slot indices into the arena, building misses.

        Returns (idxs (width,) ``idx_dtype``, arena, arena_ok), or None
        when the call's UNIQUE keys exceed the arena (every lane of one
        gather needs a live slot — callers fall back to the uncached
        kernel). ``width`` defaults to the number of keys and may be the
        launch bucket: the entries past the keys then read slot 0, as
        the launch's padding would. Keys used by the current call are
        pinned: eviction never frees a slot this call's gather will
        read.

        A batch whose keys are all resident and whose key column is one
        this cache has answered before, with no slot assigned or
        evicted since (``_gen``), gets the kept vector back (read-only):
        one join, one comparison of bytes, no statement per lane. A
        validator set's commit checks repeat their column header after
        header.

        Locking: the builder launch (a full device round trip for new
        keys) runs OUTSIDE the lock, so a cache miss on one path
        (a new validator key seen by RPC) never stalls concurrent
        hit-only lookups from consensus/blocksync. Slot assignment,
        the scatter, and the final (idxs, arena, arena_ok) capture all
        happen under one lock hold, so a concurrent update can't tear
        the pairing; tables are a pure function of the key, so two
        threads racing to build the same key scatter identical values.
        """
        n = len(pubkeys)
        if width is None:
            width = n
        column = self._key_column(pubkeys)
        if column is not None:
            with self._lock:
                idxs = self._memo_get(column, width)
                if idxs is not None:
                    self.hits += n
                    arena, arena_ok = self._arena, self._arena_ok
            if idxs is not None:
                libmetrics.observe_pubkey_lookup("memo", n)
                return idxs, arena, arena_ok
        hit = self._walk(pubkeys, column, width)
        libmetrics.observe_pubkey_lookup(
            "walked" if hit is not None else "uncached", n)
        return hit

    def _memo_get(self, column: bytes, width: int):
        """The kept slot vector of ``column`` (caller holds the lock);
        an entry from before the last change of the slots is dropped."""
        memo = self._memo
        for at, (kept, gen, kept_width, idxs) in enumerate(memo):
            if kept_width == width and kept == column:
                if gen != self._gen:
                    del memo[at]
                    return None
                if at:
                    memo.insert(0, memo.pop(at))
                return idxs
        return None

    def _walk(self, pubkeys, column, width: int):
        """:meth:`lookup` key by key through the LRU, building misses;
        an answer for a well-formed ``column`` is kept for the next."""
        builder, scatter = _cached_jits()
        keys = list(map(bytes, pubkeys))
        in_use = set(keys)
        if len(in_use) > self.capacity:
            return None
        built: list[tuple[list[bytes], object, object]] = []
        built_keys: set[bytes] = set()
        # Bounded retries: under sustained eviction churn (concurrent
        # callers with disjoint key sets larger than capacity) a thread
        # could otherwise rebuild evicted keys forever. Three builder
        # launches is already pathological; give up to the uncached
        # kernel path rather than spin.
        for _attempt in range(4):
            with self._lock:
                self._ensure_arena()
                to_build = [
                    pk
                    for pk in dict.fromkeys(keys)
                    if pk not in self._slots and pk not in built_keys
                ]
                if not to_build:
                    for batch_keys, tables, oks in built:
                        size = int(tables.shape[-1])
                        slots = np.full(
                            size, self.capacity, self.idx_dtype
                        )  # pads -> scratch slot
                        for j, pk in enumerate(batch_keys):
                            slot = self._slots.get(pk)
                            if slot is None:
                                if self._memo:
                                    self._retire_memo()
                                if len(self._slots) >= self.capacity:
                                    # evict the oldest key NOT referenced
                                    # by this call (an in-use eviction
                                    # would redirect an already-assigned
                                    # idx to a foreign table)
                                    slot = None
                                    for old in self._slots:
                                        if old not in in_use:
                                            slot = self._slots.pop(old)
                                            self.evictions += 1
                                            break
                                    # unreachable: len(in_use) <=
                                    # capacity guarantees an evictable
                                    # slot exists
                                    assert slot is not None
                                else:
                                    slot = len(self._slots)
                                self._slots[pk] = slot
                                self._gen += 1
                            slots[j] = slot
                        self._arena, self._arena_ok = scatter(
                            self._arena, self._arena_ok, slots, tables, oks
                        )
                    idxs = np.zeros(width, self.idx_dtype)
                    idxs[:len(keys)] = list(
                        map(self._slots.__getitem__, keys)
                    )
                    # the LRU touch and the tallies, lane order kept,
                    # without a statement per lane
                    deque(map(self._slots.move_to_end, keys), maxlen=0)
                    missed = sum(map(built_keys.__contains__, keys))
                    self.misses += missed
                    self.hits += len(keys) - missed
                    if column is not None:
                        idxs.flags.writeable = False
                        self._memo.insert(
                            0, (column, self._gen, width, idxs)
                        )
                        del self._memo[_MEMO_ENTRIES:]
                    return idxs, self._arena, self._arena_ok
            if _attempt == 3:
                break  # 3 builds done and keys STILL missing: stop
            # Outside the lock: one bucketed builder launch for the keys
            # still missing. A key evicted between iterations (another
            # thread filling the arena mid-build) sends us around again;
            # with in_use pinned per call that is vanishingly rare.
            tables, oks = self._build(builder, to_build)
            built.append((to_build, tables, oks))
            built_keys.update(to_build)
        return None  # churn won the race 3x: uncached kernel fallback

    def _build(self, builder, to_build: list[bytes]):
        """One builder launch for ``to_build``, padded to its bucket:
        (tables, oks) on the device, a malformed key's ``ok`` cleared.
        The ``table_build`` phase (span ``verify.table_build``, field
        ``keys``) times the host buffer and the launch's dispatch; its
        backend is ``arena`` whichever verify path asked, since it lies
        inside that path's ``pack`` phase and is not one of its tiles."""
        import jax.numpy as jnp

        m = len(to_build)
        with libmetrics.verify_phase("table_build", "arena", keys=m):
            size = _builder_bucket(m)
            buf = np.zeros((32, size), np.uint8)
            for j, pk in enumerate(to_build):
                if len(pk) == 32:
                    buf[:, j] = np.frombuffer(pk, np.uint8)
            self.builds += 1
            tables, oks = builder(buf)
            libdevstats.record_h2d(buf.nbytes)
            host_wellformed = np.array(
                [len(pk) == 32 for pk in to_build] + [True] * (size - m),
                bool,
            )
            oks = jnp.logical_and(oks, jnp.asarray(host_wellformed))
        libmetrics.observe_tables_built(m)
        return tables, oks


_PUBKEY_CACHE = PubkeyTableCache()


def prestage_pubkeys(pubkeys) -> int:
    """Warm the expanded-pubkey arena ahead of verification.

    Called from the consensus FSM at enter-new-round (round-3 verdict
    task 3): with the validator set's tables already HBM-resident, a
    commit verify ships only R|S|k per lane and the steady-state path
    performs ZERO builder launches. Returns the number of builder
    launches this warm-up performed (0 = already staged).

    COMETBFT_TPU_PRESTAGE: "auto" (default) warms only on accelerator
    backends — on the CPU test mesh the production sub-threshold path is
    the host verifier and an eager device build would only slow tests;
    "1" forces (tests), "0" disables.
    """
    mode = os.environ.get("COMETBFT_TPU_PRESTAGE", "auto")
    if mode == "0" or not _cache_enabled():
        return 0
    if mode != "1" and jax.default_backend() not in ACCELERATOR_BACKENDS:
        return 0
    keys = [bytes(pk) for pk in pubkeys][: _PUBKEY_CACHE.capacity]
    if not keys:
        return 0
    before = _PUBKEY_CACHE.builds
    try:
        _PUBKEY_CACHE.lookup(keys)
    except Exception as e:
        # warm-up must never take down the FSM; counted, not hidden
        _note_fault("prestage", e)
        return 0
    return _PUBKEY_CACHE.builds - before


def _kernel_from_bytes_pallas(buf):
    from . import pallas_verify

    return _pack_ok_bits(pallas_verify.verify_kernel(
        **unpack_on_device(buf), interpret=_PALLAS_INTERPRET
    ))


_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


@lru_cache(maxsize=None)
def _enable_compilation_cache() -> str:
    """Persistent XLA compilation cache: each kernel compiles once per
    (backend, bucket) across ALL processes — node restarts, tests, CLI
    runs — instead of paying the XLA/Mosaic compile each boot.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and
    this sets no directory in code; otherwise the cache lives at a
    fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored)
    — the path is part of the cache key, so it never depends on $HOME,
    a temp name, a pid or the clock. Returns the directory in use.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = _CHECKOUT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache_dir


# The two routes of a verify launch, each with its two programs. A
# route's wire arguments (host arrays, the rows last) follow whatever
# stays resident on the device; ``rows_arg`` is the rows' position: the
# argument whose last dimension is the lane bucket, and the one
# donated — NEVER the arena.
_ROUTES = {
    # (128, N) wire rows A|R|S|kneg
    "verify": (0, {
        "xla": _kernel_from_bytes,
        "pallas": _kernel_from_bytes_pallas,
    }),
    # arena, arena_ok resident; (N,) slot indices and (96, N) R|S|kneg
    "verify_cached": (3, {
        "xla": _cached_kernel,
        "pallas": _cached_kernel_pallas,
    }),
}


@lru_cache(maxsize=None)
def _jitted_kernel(route: str, which: str, grid=None):
    """The jit of one (route, program, grid) triple, tracked by
    devstats as ``<route>.<program>[.g<grid>]``.

    ``grid`` pins a dedicated small-bucket jit (see _SMALL_GRID_MAX):
    its own executable cache and its own devstats kernel name, so
    small-window compiles and launches are attributable per bucket.
    """
    _enable_compilation_cache()
    rows_arg, programs = _ROUTES[route]
    label = which if grid is None else f"{which}.g{grid}"
    return libdevstats.track(
        f"{route}.{label}",
        jax.jit(programs[which], donate_argnums=_donatable((rows_arg,))),
        axis=rows_arg,
    )


# Which program a launch runs is decided here and nowhere else: a bucket
# of at least _PALLAS_MIN_LANES on an accelerator backend launches the
# Pallas program (VMEM-resident ladder) unless Pallas has faulted in
# this process; everything else launches the XLA program, which tier-1
# holds to the oracle on every run (CPU tests, virtual-device meshes —
# interpret-mode Pallas is far slower than the XLA program there — and
# small buckets, on their dedicated small-grid jits).
#
# Below _PALLAS_MIN_LANES the XLA program serves even on the chip:
# small-lane Mosaic layouts compile pathologically slowly and the launch
# is latency-bound there anyway. (Whether a batch reaches the device at
# all is crypto/batch's question: its static cut is 96 lanes with an
# accelerator, 768 on a CPU backend.) The floor is one Pallas block,
# pinned to pallas_verify._BLOCK by tests/test_sr25519_secp.py.
_PALLAS_MIN_LANES = 512
_PALLAS_BROKEN = False  # a Pallas launch has faulted in this process


def _pallas_wanted(lanes: int) -> bool:
    return (
        lanes >= _PALLAS_MIN_LANES
        and not _PALLAS_BROKEN
        and libaccel.accelerator_backend()
    )


def _note_pallas_fault(e: Exception) -> None:
    global _PALLAS_BROKEN
    _PALLAS_BROKEN = True
    _note_fault("pallas", e)


def _start_readback(out) -> None:
    """Queue the ok mask's d2h copy behind its kernel at launch, so that
    materializing blocks once (:func:`_kernel_wait`) and ``np.asarray``
    finds the bytes on the host. Fetching only after the wait is a
    second blocking call, one more hand-over of the GIL per launch:
    with two light clients under one GIL that read 3% of sigs_per_s
    (PERF.md, PR 24)."""
    out.copy_to_host_async()


def _launch(route: str, resident: tuple, wire: tuple):
    """Launch one bucket of ``route`` without blocking: the Pallas
    program where :func:`_pallas_wanted`, falling to the XLA program if
    Mosaic balks at trace or compile time. An XLA fault propagates.

    ``resident`` are the device-resident arguments (the arena), ``wire``
    the host arrays this launch ships, rows last. They are launched from
    host memory, the rows donated: every launch owns its inputs, so any
    number of threads may launch one shape at once, with one jit
    dispatch each (a staging step before the launch costs the
    coalescer's executor two more waits for the GIL and the chip showed
    no gain from it: PERF.md section 6, PR 26). The caller keeps
    ``wire``: a donated device copy is gone, the host arrays are what a
    retry launches again.

    Returns (device ok-mask words, program). jit dispatch is
    asynchronous, so a Mosaic *runtime* fault only surfaces when the
    result materializes: resolve through :func:`_materialize`.
    """
    lanes = wire[-1].shape[1]
    grid = _small_grid(lanes)
    which = "pallas" if _pallas_wanted(lanes) else "xla"
    while True:
        kernel = _jitted_kernel(route, which, grid)
        try:
            out = kernel(*resident, *wire)
        except Exception as e:
            if which == "xla":
                raise
            _note_pallas_fault(e)
            which = "xla"
        else:
            # only the wire arguments cross the host->device edge
            libdevstats.record_h2d(sum(a.nbytes for a in wire))
            _served(kernel.kernel)
            _start_readback(out)
            return out, which


def _kernel_wait(out, backend: str, lanes: int) -> None:
    """Block until a launch's output is ready: the wait for the kernel
    (and the copy :func:`_start_readback` queued behind it), apart from
    the ``np.asarray`` and the unpack that follow
    (``crypto_verify_phase_seconds{phase="kernel_wait"}`` and the
    ``verify.kernel_wait`` span, inside the caller's readback)."""
    with libmetrics.TimedPhase(
        libmetrics.node_metrics().verify_phase_seconds.labels(
            "kernel_wait", backend
        ),
        "verify.kernel_wait", backend=backend, lanes=lanes,
    ):
        # cometlint: disable=CLNT002 -- first half of the sanctioned
        # per-launch readback: the wait, timed apart from the copy
        out.block_until_ready()


def _launch_async(route: str, resident: tuple, wire: tuple, n: int,
                  backend: str):
    """:func:`_launch` now; the returned closure is
    :func:`_materialize` on that launch, the (n,) validity bitmap."""
    out, which = _launch(route, resident, wire)
    return lambda: _materialize(
        out, which, route, resident, wire, n, backend
    )


def _materialize(out, which: str, route: str, resident: tuple,
                 wire: tuple, n: int, backend: str) -> np.ndarray:
    """The launch's first ``n`` verdicts on the host, with device-side
    Pallas faults rerouted: Pallas is retired and the launch repeated
    through :func:`_launch`, which then serves it by XLA, from the host
    arrays the caller kept. Bounded: an XLA fault raises.

    The wire value is the bit-packed ok mask (:func:`_pack_ok_bits` —
    bucket/8 uint8 words, what record_d2h counts), unpacked here to
    per-lane bools."""
    while True:
        try:
            _kernel_wait(out, backend, n)
            # cometlint: disable=CLNT002 -- THE sanctioned per-launch
            # readback: every async dispatch materializes exactly once,
            # here
            arr = np.asarray(out)
        except Exception as e:
            if which == "xla":
                raise
            _note_pallas_fault(e)
            out, which = _launch(route, resident, wire)
        else:
            libdevstats.record_d2h(arr.nbytes)
            return unpack_ok_bits(arr, 8 * arr.shape[0])[:n]


# One launch per batch up to _CHUNK lanes: on this chip a window of
# buckets 64-1024 reads 4.5-7.8 ms and bucket 8192 reads 17.2 ms
# (PERF.md sections 5-6, ledger), so the launch's fixed cost is paid
# once and splitting finer only pays it again. Batches past _CHUNK
# still split, so a single dispatch stays bounded (compile shape, VMEM
# head-room); verify_batch packs and dispatches at the same grain, so
# the host packing of chunk i+1 overlaps the kernel of chunk i.
_CHUNK = 16384


def verify_bytes_async(buf: np.ndarray, n: int, backend: str = _BACKEND):
    """Dispatch a packed wire buffer to the device without blocking.

    Returns a zero-arg closure that materializes the (n,) validity bitmap;
    callers can overlap host work (packing the next batch, consensus
    bookkeeping) with device execution and pay the readback sync once.
    Batches beyond the per-launch sweet spot are auto-chunked and
    pipelined. ``backend`` labels the closure's kernel-wait phase.
    """
    finals = []
    for lo in range(0, max(n, 1), _CHUNK):
        m = min(_CHUNK, n - lo)
        # each chunk pads to its own bucket: a 64-lane remainder
        # launches 64 lanes, not a full _CHUNK
        size = bucket_size(m)
        if buf.shape[1] == size:
            piece = buf  # one chunk, packed at the bucket's width
        else:
            piece = buf[:, lo:lo + m]
            if m < size:
                piece = np.pad(piece, [(0, 0), (0, size - m)])
        finals.append(_launch_async("verify", (), (piece,), m, backend))
    if len(finals) == 1:
        return finals[0]
    return lambda: np.concatenate([f() for f in finals])


def _cache_enabled() -> bool:
    return os.environ.get("COMETBFT_TPU_PUBKEY_CACHE", "1") != "0"


def _shard_devices():
    """Devices to shard verify_batch over, or None for single-device.

    Sharding is opt-in: COMETBFT_TPU_SHARD=1 shards whenever more than
    one device exists; anything else (the default) is single-device, so
    the same path serves a 1-chip and a 4-chip host. SURVEY §2.9 wants
    production batches sharded over the signature axis on multi-chip
    hosts; no benchmark cell has measured that it pays, and until one
    does seeing four chips must not reroute a commit.
    """
    if os.environ.get("COMETBFT_TPU_SHARD") != "1":
        return None
    devs = jax.devices()
    return devs if len(devs) >= 2 else None


def _verify_batch_sharded(pubkeys, msgs, sigs, n_dev: int):
    """Shard one flat batch over the signature axis of the device mesh.

    Lanes are padded to n_dev x pow2 so each (device-count, bucket)
    shape compiles once; the one cross-device collective is the 1-byte
    per-commit verdict all-reduce (parallel/mesh.py).
    """
    from ..parallel import mesh as pmesh

    n = len(pubkeys)
    t0 = time.perf_counter()
    arrays, host_ok = pack_inputs(pubkeys, msgs, sigs)
    per_dev = _MIN_BUCKET
    while per_dev * n_dev < n:
        per_dev *= 2
    nb = per_dev * n_dev
    if nb != n:
        arrays = {
            k: np.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, nb - n)])
            for k, v in arrays.items()
        }
        host_ok = np.pad(host_ok, (0, nb - n))
    t1 = time.perf_counter()
    libmetrics.observe_verify_phase(
        "pack", "ed25519-tpu", t1 - t0, n, arena="sharded"
    )
    if libdevstats.enabled():
        # the sharded path ships pre-unpacked limb arrays (pack_inputs),
        # not the compact 128 B/lane wire rows — record what actually
        # crosses the edge
        libdevstats.record_h2d(
            sum(v.nbytes for v in arrays.values()) + host_ok.nbytes
        )
    ok = pmesh.verify_sharded(
        arrays, host_ok, pmesh.default_mesh(), 1, nb
    )[0][:n]
    libdevstats.record_d2h(ok.nbytes)
    # pjit materializes inside verify_sharded — dispatch and readback
    # are one phase on the multi-chip path
    libmetrics.observe_verify_phase(
        "dispatch", "ed25519-tpu", time.perf_counter() - t1, n,
        arena="sharded",
    )
    return bool(ok.all()), ok


def verify_rsk_async(buf: np.ndarray, idxs: np.ndarray, arena, arena_ok,
                     n: int, backend: str = _BACKEND):
    """Dispatch a cached-table launch: (96, n) R|S|kneg rows + arena slots.

    Same async contract as :func:`verify_bytes_async`. ``n`` must be
    <= _CHUNK (callers chunk above that). Rows and slots that already
    have the bucket's width (``pack_bytes`` and ``lookup`` given it)
    launch as they are."""
    size = bucket_size(n)
    if buf.shape[1] != size:
        buf = np.pad(buf, [(0, 0), (0, size - n)])
    if idxs.shape[0] != size:
        idxs = np.pad(idxs, (0, size - n))  # slot 0 gather: harmless
    return _launch_async(
        "verify_cached", (arena, arena_ok), (idxs, buf), n, backend
    )


def verify_prepacked(buf: np.ndarray, keys, n: int, backend: str):
    """Async verify of a pre-packed (128, n) wire buffer with cache routing.

    ``keys``: per-lane 32-byte edwards A encodings (b"" / short for
    host-rejected lanes — they verify False via the arena ok bit). Used
    by schemes that pack their own challenge (sr25519: merlin transcript
    k, crypto/sr25519.py) but share the cofactored kernel — and the
    expanded-point cache, since the arena is keyed by the edwards
    encoding itself.
    """
    if not _cache_enabled():
        return verify_bytes_async(buf, n, backend)
    finals = []
    for lo in range(0, n, _CHUNK):
        hi = min(lo + _CHUNK, n)
        hit = _PUBKEY_CACHE.lookup(keys[lo:hi])
        if hit is not None:
            idxs, arena, arena_ok = hit
            finals.append(
                verify_rsk_async(
                    buf[32:, lo:hi], idxs, arena, arena_ok, hi - lo,
                    backend,
                )
            )
        else:
            finals.append(
                verify_bytes_async(buf[:, lo:hi], hi - lo, backend)
            )
    if len(finals) == 1:
        return finals[0]
    return lambda: np.concatenate([f() for f in finals])


# ------------------------------------------------ cold shapes stay off
# the ticket path. Measured on a v5e with libtpu 0.0.34 (PERF.md,
# Bring-up): a cold verify_cached compile takes 12-16 s and a builder
# compile up to 28 s, against the coalescer's 5 s ticket bound.


def _warm_shape(key) -> None:
    """Compile everything a coalescer window touches for one shape, by
    launching that shape on dummy lanes (ops/warm.WarmSet's contract).

    ``("window", bucket)``: the verify launch itself.
    ``("build", size)``: the arena builder + scatter for ``size`` new
    keys; the scatter targets the scratch slot and its result is
    dropped (no donation), so the live arena is untouched.
    """
    kind, size = key
    buf = np.zeros((128, size), np.uint8)
    if kind == "window" and not _cache_enabled():
        verify_bytes_async(buf, size)()
        return
    cache = _PUBKEY_CACHE
    with cache._lock:
        cache._ensure_arena()
        arena, arena_ok = cache._arena, cache._arena_ok
    if kind == "window":
        idxs = np.zeros(size, cache.idx_dtype)
        verify_rsk_async(buf[32:], idxs, arena, arena_ok, size)()
        return
    builder, scatter = _cached_jits()
    tables, oks = builder(buf[:32])
    scratch = np.full(size, cache.capacity, cache.idx_dtype)
    jax.block_until_ready(scatter(arena, arena_ok, scratch, tables, oks))


WARM = libwarm.WarmSet("verify", _warm_shape)


def window_ready(pubkeys) -> bool:
    """Whether a coalescer window over these lanes can launch without
    compiling anything: its bucket's kernel is warm and, where it
    carries keys the arena has not seen, so is the builder for that
    many. A False answer queues the missing compiles in the background
    and the caller runs the window on host."""
    ready = WARM.ready(("window", bucket_size(len(pubkeys))))
    if _cache_enabled():
        new_keys = _PUBKEY_CACHE.missing(pubkeys)
        if new_keys and not WARM.ready(
            ("build", _builder_bucket(new_keys))
        ):
            ready = False
    return ready


def _chunk_phase(phase: str, lanes: int, **fields):
    """One verify_batch phase of one pipelined chunk: the span alone;
    verify_batch observes the histogram once per batch."""
    return libmetrics.TimedPhase(
        None, "verify." + phase, backend=_BACKEND, lanes=lanes, **fields
    )


def verify_batch(pubkeys, msgs, sigs) -> tuple[bool, np.ndarray]:
    """Verify a batch of ed25519 signatures on device.

    Returns (all_valid, per_signature_validity) — the contract of the Go
    engine's crypto.BatchVerifier.Verify (crypto/crypto.go:45-54), including
    per-lane results so callers can attribute failures without a second pass
    (types/validation.go:243-250's find-first-invalid fallback).

    Steady state routes through the expanded-pubkey cache: per lane the
    device receives 96 bytes (R, S, -k) plus a 2-byte arena slot, and the
    kernel skips pubkey decompression + table build entirely.
    """
    n = len(pubkeys)
    if n == 0:
        return True, np.zeros(0, bool)
    devs = _shard_devices()
    if devs is not None:
        return _verify_batch_sharded(pubkeys, msgs, sigs, len(devs))
    use_cache = _cache_enabled()
    chunks = []  # (materialize, host_ok, lanes, arena disposition)
    # Phase attribution: pack = host staging incl. the arena lookup (a
    # miss's builder launch is part of staging cost), dispatch = the
    # async jit launches, readback = the one sanctioned materialization
    # (the wait for the kernel, then copy and unpack). Each is a
    # verify.<phase> span per pipelined chunk, so the lanes of a batch's
    # spans of one name add up to the batch; their durations are summed
    # into ONE crypto_verify_phase_seconds observation per batch, so the
    # three phases tile the crypto_verify_batch_seconds interval.
    pack_ns = disp_ns = read_ns = 0
    for lo in range(0, n, _CHUNK):
        hi = min(lo + _CHUNK, n)
        lanes = pubkeys, msgs, sigs
        if hi - lo < n:
            lanes = pubkeys[lo:hi], msgs[lo:hi], sigs[lo:hi]
        # rows and slots are made at the launch's width, so the launch
        # has nothing to pad
        width = bucket_size(hi - lo)
        # Pipeline host packing with device execution: each chunk is
        # dispatched as soon as it is packed, so the per-lane SHA-512 /
        # packing cost of chunk i+1 overlaps chunk i's kernel time.
        builds_before = _PUBKEY_CACHE.builds
        with _chunk_phase("pack", hi - lo) as ph:
            buf, hok = pack_bytes(*lanes, width=width)
            hit = (
                _PUBKEY_CACHE.lookup(lanes[0], width) if use_cache else None
            )
            if not use_cache:
                arena_state = "off"
            elif hit is None:
                arena_state = "bypass"  # churn exhausted the arena
            elif _PUBKEY_CACHE.builds > builds_before:
                arena_state = "miss"  # lookup had to build tables
            else:
                arena_state = "hit"
            ph.set(arena=arena_state)
        pack_ns += ph.dur_ns
        with _chunk_phase("dispatch", hi - lo, arena=arena_state) as ph:
            if hit is not None:
                idxs, arena, arena_ok = hit
                finish = verify_rsk_async(
                    buf[32:], idxs, arena, arena_ok, hi - lo
                )
            else:
                finish = verify_bytes_async(buf, hi - lo)
        disp_ns += ph.dur_ns
        chunks.append((finish, hok, hi - lo, arena_state))
    device_oks = []
    for finish, _, lanes, arena_state in chunks:
        with _chunk_phase("readback", lanes, arena=arena_state) as ph:
            device_oks.append(finish())
        read_ns += ph.dur_ns
    if len(chunks) == 1:
        device_ok, host_ok = device_oks[0], chunks[0][1]
    else:
        device_ok = np.concatenate(device_oks)
        host_ok = np.concatenate([c[1] for c in chunks])
    hist = libmetrics.node_metrics().verify_phase_seconds
    hist.labels("pack", _BACKEND).observe(pack_ns / 1e9)
    hist.labels("dispatch", _BACKEND).observe(disp_ns / 1e9)
    hist.labels("readback", _BACKEND).observe(read_ns / 1e9)
    valid = device_ok & host_ok
    return bool(valid.all()), valid
