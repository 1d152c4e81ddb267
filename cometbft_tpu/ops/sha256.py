"""Batched SHA-256 on device: the hash half of the accelerator plane.

Every SHA-256 in the node — mempool tx keys, PartSet leaf/proof
construction, merkle app-hash/header roots — used to be serial host
``hashlib`` work sitting next to an idle accelerator. Hashing, not just
signatures, dominates blockchain data paths (arXiv:2407.03511), and
MSM + hashing are the two primitives hardware proof pipelines share
(arXiv:2504.06211) — so this kernel is both the data-path win and the
on-ramp to proof generation.

Split of labor (same TPU-first discipline as ops/verify.py):

* Host: SHA-256 padding (append 0x80, zero fill, 64-bit bit length) and
  big-endian word extraction into fixed-shape buckets — the pack step,
  analogous to the ed25519 ``pack_bytes`` path. Per-lane cost is one
  ``np.frombuffer`` view; no per-byte Python.
* Device (jax): the message schedule + 64-round compression function,
  vectorized across lanes. Lanes are independent, so the whole window
  is one embarrassingly-parallel VPU program; multi-block messages run
  the compression sequentially over the block axis via ``lax.scan``
  with per-lane active masks (shorter lanes stop updating state).

Shapes are bucketed on BOTH axes so each (block-bucket, lane-bucket)
pair compiles once and stays cached: the block bucket is the smallest
power of two holding the longest message's padded block count, the lane
bucket the smallest power of two >= the lane count (min 8). Ragged
windows in the consensus hot loop must never retrigger XLA compilation
— the no-recompile guard covers these kernels too.

Array layout: batch axis LAST everywhere (blocks ``(B, 16, L)`` uint32,
state ``(8, L)``) — see ops/field.py for why batch-minor wins on TPU.
All arithmetic is uint32 with natural mod-2^32 wraparound; digests are
bit-identical to ``hashlib.sha256`` (fuzz-pinned across every padding
boundary by tests/test_hashplane.py).
"""

from __future__ import annotations

import hashlib
from functools import lru_cache

import numpy as np

import jax

from ..libs import devstats as libdevstats
from . import warm as libwarm

_MIN_LANES = 8
# Lanes per launch cap, like ops/verify._CHUNK: one dispatch stays a
# bounded compile shape; the hash plane's windows are capped well below
# this anyway (COMETBFT_TPU_HASH_MAX_LANES).
MAX_LANES = 8192

# Round constants / initial state (FIPS 180-4).
_K = np.array([
    0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5,
    0x3956c25b, 0x59f111f1, 0x923f82a4, 0xab1c5ed5,
    0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
    0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174,
    0xe49b69c1, 0xefbe4786, 0x0fc19dc6, 0x240ca1cc,
    0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
    0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7,
    0xc6e00bf3, 0xd5a79147, 0x06ca6351, 0x14292967,
    0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
    0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85,
    0xa2bfe8a1, 0xa81a664b, 0xc24b8b70, 0xc76c51a3,
    0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
    0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5,
    0x391c0cb3, 0x4ed8aa4a, 0x5b9cca4f, 0x682e6ff3,
    0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
    0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2,
], dtype=np.uint32)

_H0 = np.array([
    0x6a09e667, 0xbb67ae85, 0x3c6ef372, 0xa54ff53a,
    0x510e527f, 0x9b05688c, 0x1f83d9ab, 0x5be0cd19,
], dtype=np.uint32)


# -- host-side pack ---------------------------------------------------------


def n_blocks(msg_len: int) -> int:
    """Padded 64-byte block count of an ``msg_len``-byte message."""
    return (msg_len + 8) // 64 + 1


def block_bucket(blocks: int) -> int:
    """Smallest power-of-two compile bucket holding ``blocks`` (>= 1)."""
    b = 1
    while b < blocks:
        b *= 2
    return b


def lane_bucket(n: int) -> int:
    """Smallest power-of-two lane bucket holding n (8 <= bucket)."""
    b = _MIN_LANES
    while b < n:
        b *= 2
    return b


def _pad(msg: bytes) -> bytes:
    """FIPS 180-4 padding: 0x80, zeros, 64-bit big-endian bit length."""
    ln = len(msg)
    rem = (ln + 1 + 8) % 64
    zeros = (64 - rem) % 64
    return msg + b"\x80" + b"\x00" * zeros + (8 * ln).to_bytes(8, "big")


def pack_messages(msgs, blocks_cap: int | None = None):
    """Pack a message list into one bucketed device wire buffer.

    Returns ``(blocks (B, 16, L) uint32, nblocks (L,) int32)`` where B
    is the block bucket of the LONGEST message and L the lane bucket of
    ``len(msgs)``. Callers group messages by block bucket first (the
    hash plane's window split) so a window of 55-byte tx keys never
    pads to a 64 KiB part's block count. ``blocks_cap`` asserts the
    caller's bucketing (None recomputes it here).
    """
    n = len(msgs)
    nb = [n_blocks(len(m)) for m in msgs]
    bb = blocks_cap if blocks_cap is not None else block_bucket(max(nb, default=1))
    lb = lane_bucket(n)
    blocks = np.zeros((bb, 16, lb), np.uint32)
    # per-lane block counts ship h2d every launch: the narrowest dtype
    # that can hold the bucket's block count (uint16 up to 4 MiB
    # messages) halves-to-quarters the mask-lane wire cost vs int32
    nblocks = np.zeros(lb, np.uint16 if bb <= 0xFFFF else np.int32)
    for i, m in enumerate(msgs):
        padded = _pad(bytes(m))
        k = nb[i]
        if k > bb:
            raise ValueError(f"message of {k} blocks exceeds bucket {bb}")
        blocks[:k, :, i] = np.frombuffer(padded, ">u4").reshape(k, 16)
        nblocks[i] = k
    return blocks, nblocks


# -- the device kernel ------------------------------------------------------


def _rotr(x, r: int):
    return (x >> r) | (x << (32 - r))


def _compress(state, words):
    """One SHA-256 compression: state (8, L) + block words (16, L).

    The 64 rounds are ONE rolled ``lax.fori_loop`` carrying the working
    variables a..h (8, L) and a 16-word ring of the message schedule
    (16, L): round t consumes ring slot t % 16 and overwrites it with
    w[t + 16], so no round's expression ever contains another's. (The
    Python-unrolled form handed XLA one fused elementwise expression
    that re-evaluated the w[t] recurrence exponentially: it compiled,
    then never finished executing.) uint32 adds wrap mod 2^32 natively.
    """
    import jax.numpy as jnp
    from jax import lax

    k = jnp.asarray(_K)

    def w_at(ring, i):
        return lax.dynamic_index_in_dim(ring, i % 16, 0, keepdims=False)

    def round_(t, carry):
        v, ring = carry
        a, b, c, d, e, f, g, h = (v[i] for i in range(8))
        wt = w_at(ring, t)
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k[t] + wt
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        v = jnp.stack([t1 + s0 + maj, a, b, c, d + t1, e, f, g])
        # w[t+16] = w[t] + s0(w[t+1]) + w[t+9] + s1(w[t+14]); written
        # past round 47 too — those slots are never read again
        w1, w14 = w_at(ring, t + 1), w_at(ring, t + 14)
        nxt = (
            wt
            + (_rotr(w1, 7) ^ _rotr(w1, 18) ^ (w1 >> 3))
            + w_at(ring, t + 9)
            + (_rotr(w14, 17) ^ _rotr(w14, 19) ^ (w14 >> 10))
        )
        ring = lax.dynamic_update_index_in_dim(ring, nxt, t % 16, 0)
        return v, ring

    v, _ = lax.fori_loop(0, 64, round_, (state, words))
    return state + v


def _sha256_kernel(blocks, nblocks):
    """(B, 16, L) uint32 blocks + per-lane block counts -> (8, L) state.

    The scan walks the block axis; a lane whose message ended keeps its
    state (masked where), so one launch serves every length inside the
    bucket bit-identically.
    """
    import jax.numpy as jnp
    from jax import lax

    lanes = blocks.shape[2]
    state = jnp.tile(jnp.asarray(_H0)[:, None], (1, lanes))

    def step(st, inp):
        words, idx = inp
        new = _compress(st, words)
        active = (idx < nblocks)[None, :]
        return jnp.where(active, new, st), None

    idxs = jnp.arange(blocks.shape[0], dtype=jnp.int32)
    state, _ = lax.scan(step, state, (blocks, idxs))
    return state


@lru_cache(maxsize=None)
def _jitted_kernel(blocks_bucket: int):
    """The tracked jit for ONE block bucket, built lazily (importing
    this module must not touch jax.jit). The kernel compiles per
    (block-bucket, lane-bucket) shape pair, but devstats keys its
    recompile detector on (kernel-name, lane-bucket) — so each block
    bucket gets its OWN jit + kernel name (``sha256.xla.b<B>``), or a
    fresh block bucket at an already-seen lane bucket would read as a
    phantom steady-state recompile and feed the recompile-storm
    watchdog. Compiles land in
    ``xla_compile_total{kernel="sha256.xla.b<B>",bucket=<lanes>}`` and
    the tier-1 no-recompile guard covers the hash plane too."""
    from .verify import _enable_compilation_cache

    _enable_compilation_cache()
    return libdevstats.track(
        f"sha256.xla.b{blocks_bucket}",
        # no donation: the (8, L) state cannot alias the (B, 16, L)
        # blocks, so XLA only answered "donated buffers not usable"
        jax.jit(_sha256_kernel),
        axis=0,
    )


def _digests_from_state(arr: np.ndarray, n: int) -> list[bytes]:
    """(8, L) uint32 host state -> n 32-byte big-endian digests."""
    raw = np.ascontiguousarray(arr.T[:n]).astype(">u4").tobytes()
    return [raw[32 * i : 32 * i + 32] for i in range(n)]


def sha256_many_async(msgs, blocks_cap: int | None = None):
    """Dispatch one bucketed batch; returns a zero-arg materializer.

    Same async contract as ops/verify.verify_bytes_async: the closure
    blocks on the device once and returns the per-lane 32-byte digests
    (bit-identical to ``hashlib.sha256``). Callers keep lanes within
    one block bucket (``blocks_cap``) and under :data:`MAX_LANES` — the
    hash plane's window split guarantees both.
    """
    n = len(msgs)
    if n == 0:
        return lambda: []
    if n > MAX_LANES:
        raise ValueError(f"{n} lanes exceed the {MAX_LANES}-lane launch cap")
    blocks, nblocks = pack_messages(msgs, blocks_cap)
    out = _jitted_kernel(blocks.shape[0])(blocks, nblocks)
    libdevstats.record_h2d(blocks.nbytes + nblocks.nbytes)

    def materialize() -> list[bytes]:
        # cometlint: disable=CLNT002 -- THE sanctioned readback of a hash
        # launch: every async dispatch materializes exactly once, here
        arr = np.asarray(out)
        libdevstats.record_d2h(arr.nbytes)
        return _digests_from_state(arr, n)

    return materialize


def _warm_shape(key) -> None:
    """Compile one (block bucket, lane bucket) by hashing empty lanes
    (ops/warm.WarmSet's contract)."""
    blocks_bucket, lanes = key
    sha256_many_async([b""] * lanes, blocks_bucket)()


# Hash-plane windows launch only shapes that are warm here; a cold one
# runs on hashlib while this compiles it (measured 0.2-2.0 s per shape
# on a v5e, PERF.md Bring-up — under the 5 s ticket bound, but a bound
# a loaded host should not have to race).
WARM = libwarm.WarmSet("sha256", _warm_shape)


def shape_ready(blocks_bucket: int, n: int) -> bool:
    """Whether an ``n``-lane launch in ``blocks_bucket`` would compile
    nothing; False queues the compile in the background."""
    return WARM.ready((blocks_bucket, lane_bucket(n)))


def sha256_many_host(msgs) -> list[bytes]:
    """The host oracle: one ``hashlib`` digest per message."""
    return [hashlib.sha256(m).digest() for m in msgs]
