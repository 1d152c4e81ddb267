"""Batch-verification dispatch: key type -> batch verifier backend.

Reference surface: crypto/crypto.go:45-54 (BatchVerifier interface) and
crypto/batch/batch.go:11-32 (CreateBatchVerifier / SupportsBatchVerifier).

The ed25519 backend accumulates (pubkey, msg, sig) triples on host and
verifies them in ONE TPU kernel launch (ops/verify.py) — the engine-wide
hot path: commit verification (types/validation.go:153-257), light-client
replay, blocksync catch-up, and the vote-ingest micro-batching window all
come through this interface.
"""

from __future__ import annotations

import os

import numpy as np

from ..libs import metrics as libmetrics
from ..libs import sync as libsync
from . import keys
from .host_batch import MsgColumn
from .keys import Ed25519PubKey


class BatchVerifier:
    """Add/Verify contract of crypto.BatchVerifier (crypto/crypto.go:45-54).

    ``verify`` returns (all_valid, per_signature_validity); per-lane results
    let callers attribute failures without the second single-verify pass the
    reference falls back to (types/validation.go:243-250).
    """

    # where the last ``verify`` ran, for a backend that can tell
    # ("device" / "host"); None for one that cannot
    route: str | None = None

    def add(self, pub_key, msg: bytes, signature: bytes) -> None:
        raise NotImplementedError

    def add_many(self, pub_keys, msgs, signatures) -> None:
        """``add`` for each triple in order. The backends check the key
        types first and then extend their lists, so a foreign key
        raises ``add``'s TypeError before any lane is taken."""
        for pub_key, msg, signature in zip(pub_keys, msgs, signatures):
            self.add(pub_key, msg, signature)

    def verify(self) -> tuple[bool, list[bool]]:
        raise NotImplementedError

    def __len__(self) -> int:
        raise NotImplementedError


# Below this size the host verifier finishes before one device launch's
# fixed cost. The reference has the inverse constant
# (batchVerifyThreshold, types/validation.go:13-17: below it batching
# isn't worth setup).
#
# Two static seeds, no live refit (see host_batch_threshold): 768 where
# jax runs on the CPU (tests, a host-only node: the "device" is XLA-CPU
# and never wins), and _ACCEL_HOST_BATCH_THRESHOLD where an accelerator
# is attached. COMETBFT_TPU_HOST_THRESHOLD overrides both (operator / a
# bench probe).
_DEFAULT_HOST_BATCH_THRESHOLD = 768
# Measured on a v5e in a quiet process (PERF.md section 6, PR 26): the
# host RLC batch costs 1.7 ms + 26.6 us a lane, a device window (pack,
# dispatch, readback) 4.5 ms in buckets 64 and 128, 6.0 in 256, 5.0 in
# 512, 7.8 in 1024: the lines cross at 107 lanes, within 7% of each
# other from 90 to 130. Every real validator set's commit checks (a
# 59-lane trusting check, a 117-lane light check) stand on either side
# of it, so a cut that is refitted from timings taken under load flips
# between them from process to process (eleven runs read 64 to 16,384).
# The cut stands a little under the crossing because a host window also
# holds the coalescer's executor for the whole verify, a device window
# only for pack and dispatch.
_ACCEL_HOST_BATCH_THRESHOLD = 96


def _derive_host_threshold() -> int:
    env = os.environ.get("COMETBFT_TPU_HOST_THRESHOLD")
    if env:
        try:
            return max(2, int(env))
        except ValueError:
            pass
    return _DEFAULT_HOST_BATCH_THRESHOLD


HOST_BATCH_THRESHOLD = _derive_host_threshold()


class AdaptiveCrossover:
    """Runtime-calibrated host/device batch-size crossover.

    Used by the hash plane alone (crypto/hashplane, one instance per
    SHA block bucket); the verify plane's cut is static, see
    :func:`host_batch_threshold`. Both sides get the same
    model, matching what 9_device_floor measures:
    ``time(n) = floor + slope * n`` — the device floor is the launch
    cost that dominates small batches, and the host floor is the fixed
    per-call cost of ``host_batch.verify_many`` (the dominant host feed
    is tiny sub-cutover coalescer windows, and folding that per-call
    cost into a per-lane rate would drag the crossover below the host
    MSM's true win region). Every end-to-end observation
    (crypto/batch._observe, plus the coalescer's windows — the steady
    state's only source of small-n samples on both sides) feeds decayed
    least-squares accumulators; the crossover solves
    ``h_floor + h_rate * n = d_floor + d_slope * n`` and is clamped to
    [64, 16384].

    Until both sides have ``MIN_SAMPLES`` the caller's seed answers; an
    operator env pin (COMETBFT_TPU_HOST_THRESHOLD) disables adaptation
    entirely.
    """

    DECAY = 0.98  # per-observation decay of the running moments
    MIN_SAMPLES = 5
    LO, HI = 64, 16384

    def __init__(self) -> None:
        self._mtx = libsync.Mutex("crypto.batch._crossover")
        # decayed least-squares moments of (n, seconds) pairs per side
        self._host = [0.0, 0.0, 0.0, 0.0, 0.0]  # sw, sx, sy, sxx, sxy
        self._dev = [0.0, 0.0, 0.0, 0.0, 0.0]
        self._host_n = 0
        self._dev_n = 0

    def _accumulate(self, acc: list[float], n: int, seconds: float) -> None:
        d = self.DECAY
        acc[0] = d * acc[0] + 1.0
        acc[1] = d * acc[1] + n
        acc[2] = d * acc[2] + seconds
        acc[3] = d * acc[3] + float(n) * n
        acc[4] = d * acc[4] + n * seconds

    def observe_host(self, n: int, seconds: float) -> None:
        if n <= 0 or seconds <= 0:
            return
        with self._mtx:
            self._host_n += 1
            self._accumulate(self._host, n, seconds)

    def observe_device(self, n: int, seconds: float) -> None:
        if n <= 0 or seconds <= 0:
            return
        with self._mtx:
            self._dev_n += 1
            self._accumulate(self._dev, n, seconds)

    @staticmethod
    def _fit(acc: list[float]) -> tuple[float, float]:
        """(floor, slope) of time(n) = floor + slope*n from the decayed
        moments. Samples at ~one size give a pure floor (slope 0) —
        conservative, since a flat model overstates that side's cost at
        small n and understates it at large n only where the other
        side's slope decides anyway."""
        sw, sx, sy, sxx, sxy = acc
        mx = sx / sw
        my = sy / sw
        var = sxx / sw - mx * mx
        cov = sxy / sw - mx * my
        if var > 1e-9:
            slope = max(0.0, cov / var)
            floor = max(0.0, my - slope * mx)
        else:
            slope, floor = 0.0, my
        return floor, slope

    def reset(self) -> None:
        """Drop every accumulated sample (a refit from scratch).

        The decayed moments forget slowly (~50-sample half-life); when
        the device cost profile steps — the readback drain lands, a
        kernel swap — stale samples would keep
        answering for the OLD floor for hundreds of windows. Callers
        that change the profile (bench captures, an operator toggling
        staging knobs) reset so the live fit re-converges on the new
        floor immediately."""
        with self._mtx:
            self._host = [0.0, 0.0, 0.0, 0.0, 0.0]
            self._dev = [0.0, 0.0, 0.0, 0.0, 0.0]
            self._host_n = 0
            self._dev_n = 0

    def fit_summary(self) -> dict:
        """The live floor fit, for bench/debug surfaces: per-side
        (floor_s, slope_s_per_lane, samples) plus the solved crossover.
        Floors are None while that side is uncalibrated."""
        with self._mtx:
            host_n, dev_n = self._host_n, self._dev_n
            h = (
                self._fit(self._host)
                if host_n >= self.MIN_SAMPLES and self._host[0] > 0
                else None
            )
            d = (
                self._fit(self._dev)
                if dev_n >= self.MIN_SAMPLES and self._dev[0] > 0
                else None
            )
        return {
            "host_floor_s": h[0] if h else None,
            "host_rate_s_per_lane": h[1] if h else None,
            "host_samples": host_n,
            "device_floor_s": d[0] if d else None,
            "device_slope_s_per_lane": d[1] if d else None,
            "device_samples": dev_n,
            "crossover_lanes": self.threshold(),
        }

    def threshold(self) -> int | None:
        """The calibrated crossover, or None while uncalibrated."""
        with self._mtx:
            if (
                self._host_n < self.MIN_SAMPLES
                or self._dev_n < self.MIN_SAMPLES
                or self._host[0] <= 0
                or self._dev[0] <= 0
            ):
                return None
            h_floor, h_rate = self._fit(self._host)
            d_floor, d_slope = self._fit(self._dev)
        if h_rate <= d_slope:
            # the host's per-lane cost never exceeds the device's: past
            # any floors the host wins at EVERY size, keep everything up
            # to the clamp ceiling on host
            return self.HI
        # h_floor + h_rate*n = d_floor + d_slope*n; a device floor
        # already below the host floor clamps at LO (device wins from
        # the smallest routed sizes)
        n_star = (d_floor - h_floor) / (h_rate - d_slope)
        return int(min(self.HI, max(self.LO, n_star)))


_ENV_PINNED = bool(os.environ.get("COMETBFT_TPU_HOST_THRESHOLD"))


def _adaptive_enabled() -> bool:
    """The hash plane's live refit (crypto/hashplane) applies when not
    env-pinned and either forced (COMETBFT_TPU_ADAPTIVE_THRESHOLD=1) or
    running on an accelerator backend — CPU test runs must stay
    deterministically on the seed."""
    if _ENV_PINNED:
        return False
    mode = os.environ.get("COMETBFT_TPU_ADAPTIVE_THRESHOLD", "auto")
    if mode == "0":
        return False
    if mode == "1":
        return True
    from ..libs.accel import accelerator_backend_live

    return accelerator_backend_live()


def host_batch_threshold() -> int:
    """Lanes from which a batch (a lone one, or a coalescer window) runs
    on the device rather than in the host batch verifier: operator env
    pin > the accelerator's static seed where one is attached > the
    module seed (HOST_BATCH_THRESHOLD — monkeypatchable). Nothing a
    process measures moves it, so a deployment routes the same way in
    every process and all through each.

    Whether a batch SHARES a window is another question, answered by
    :meth:`Ed25519BatchVerifier.verify`."""
    base = HOST_BATCH_THRESHOLD
    if _ENV_PINNED or base != _DEFAULT_HOST_BATCH_THRESHOLD:
        return base
    # live peek only: this sits inside every batch verify, which must
    # never pay (or hang in) jax backend init
    from ..libs.accel import accelerator_backend_live

    return _ACCEL_HOST_BATCH_THRESHOLD if accelerator_backend_live() else base


def _all_instances(pub_keys, key_class) -> bool:
    """One type check over a batch's keys: by class, not by key."""
    return all(issubclass(t, key_class) for t in set(map(type, pub_keys)))


def _check_lane_counts(pub_keys, msgs, signatures) -> None:
    if not len(pub_keys) == len(msgs) == len(signatures):
        raise ValueError("add_many needs a message and a signature per key")


def _extend_lanes(bv, pub_keys, msgs, signatures) -> None:
    """add_many's three extends, after the backend's type check."""
    _check_lane_counts(pub_keys, msgs, signatures)
    bv._pubkeys.extend([pk.data for pk in pub_keys])
    bv._msgs.extend(map(bytes, msgs))
    bv._sigs.extend(map(bytes, signatures))


class Ed25519BatchVerifier(BatchVerifier):
    """TPU-backed ed25519 batch verification with a host small-batch path.

    The lanes are kept as three columns, keys, messages and signatures,
    and handed on as such: to ``ops/verify.verify_batch``, to the
    coalescer and to ``host_batch.verify_many``, each of which takes
    any sequence of bytes-likes in each slot. ``add_many`` on an empty
    verifier (a commit check's one call) keeps the message column it is
    given, a ``host_batch.MsgColumn`` from the sign-bytes encoder above
    all, which the device packer then reads in place: nothing between
    the encoder and the wire buffer cuts it into lanes."""

    def __init__(self) -> None:
        self._pubkeys: list[bytes] = []
        self._msgs = []  # a list, or the MsgColumn add_many was given
        self._sigs: list[bytes] = []

    def _msg_list(self) -> list:
        """The messages as a list that can grow."""
        if not isinstance(self._msgs, list):
            self._msgs = list(self._msgs)
        return self._msgs

    def add(self, pub_key, msg: bytes, signature: bytes) -> None:
        if not isinstance(pub_key, Ed25519PubKey):
            raise TypeError("Ed25519BatchVerifier requires ed25519 keys")
        self._pubkeys.append(pub_key.data)
        self._msg_list().append(bytes(msg))
        self._sigs.append(bytes(signature))

    def add_many(self, pub_keys, msgs, signatures) -> None:
        if not _all_instances(pub_keys, Ed25519PubKey):
            raise TypeError("Ed25519BatchVerifier requires ed25519 keys")
        _check_lane_counts(pub_keys, msgs, signatures)
        keys = [pk.data for pk in pub_keys]
        if self._pubkeys:
            self._pubkeys.extend(keys)
            self._msg_list().extend(map(bytes, msgs))
            self._sigs.extend(signatures)
            return
        self._pubkeys = keys
        self._msgs = (
            msgs if isinstance(msgs, MsgColumn) else list(map(bytes, msgs))
        )
        self._sigs = list(signatures)

    def __len__(self) -> int:
        return len(self._pubkeys)

    def verify(self) -> tuple[bool, list[bool]]:
        import time as _time

        from . import coalesce

        t0 = _time.perf_counter()
        n = len(self._pubkeys)
        # Share a window: a batch smaller than one coalescer window
        # from a process with a routed coalescer ALWAYS rides it —
        # concurrent small callers (per-vote admission, commit checks,
        # a light service's 59- and 117-lane checks) pack into one
        # launch instead of each paying a launch or a host pass alone.
        # Device or host is then the window's question, decided by its
        # lanes (crypto/coalesce._launch_inner), never this batch's.
        co = coalesce.active()
        if co is not None and n < co.max_lanes:
            routes: list = []
            bits = co.try_verify(
                self._pubkeys, self._msgs, self._sigs, routes
            )
            if bits is not None:
                self.route = "device" if "device" in routes else "host"
                _observe("ed25519-coalesce", t0, len(bits))
                return all(bits), list(bits)
            # not served (stopped, tripped, deadline): the lone paths
            # below. Restart the clock: a failed attempt's wait (worst
            # case a stalled-device ticket timeout) must not be charged
            # to the backend that then answers
            t0 = _time.perf_counter()
        on_host = n < host_batch_threshold()
        self.route = "host" if on_host else "device"
        if on_host:
            # the native RLC host batch (one multiscalar mult, the voi
            # algorithm), which itself falls back to sequential OpenSSL
            # when the engine can't build
            from . import host_batch

            bitmap = host_batch.verify_many(
                self._pubkeys, self._msgs, self._sigs
            )
            libmetrics.observe_verify_phase(
                "fallback",
                "ed25519-host",
                _time.perf_counter() - t0,
                len(bitmap),
            )
            _observe("ed25519-host", t0, len(bitmap))
            return all(bitmap), bitmap
        from ..ops import verify as ov

        # pack/dispatch/readback phase attribution happens inside
        # ops.verify.verify_batch (the phases live there)
        ok_all, bitmap = ov.verify_batch(self._pubkeys, self._msgs, self._sigs)
        _observe("ed25519-tpu", t0, n)
        return ok_all, list(np.asarray(bitmap, bool))


class Sr25519BatchVerifier(BatchVerifier):
    """sr25519 batch verification on the SAME TPU kernel as ed25519.

    The merlin challenge k is computed on host per lane
    (crypto/sr25519.verification_parts); the cofactored curve equation
    [8](sB - kA - R) == O then decides ristretto equality exactly
    (ristretto quotients out the torsion the cofactor clears). Reference
    surface: crypto/sr25519/batch.go:14-46.
    """

    # Without the native engine the host fallback is sequential pure
    # Python (~30 ms/sig, 6 scalar mults): the device wins from a
    # handful of lanes. WITH it, the host runs the same one-MSM RLC
    # path as ed25519 (native merlin challenges + verify_quads), so the
    # ed25519 crossover applies.
    HOST_THRESHOLD = 4

    def __init__(self) -> None:
        self._pubkeys: list[bytes] = []
        self._msgs: list[bytes] = []
        self._sigs: list[bytes] = []

    def add(self, pub_key, msg: bytes, signature: bytes) -> None:
        from .sr25519 import Sr25519PubKey

        if not isinstance(pub_key, Sr25519PubKey):
            raise TypeError("Sr25519BatchVerifier requires sr25519 keys")
        self._pubkeys.append(pub_key.data)
        self._msgs.append(bytes(msg))
        self._sigs.append(bytes(signature))

    def add_many(self, pub_keys, msgs, signatures) -> None:
        from .sr25519 import Sr25519PubKey

        if not _all_instances(pub_keys, Sr25519PubKey):
            raise TypeError("Sr25519BatchVerifier requires sr25519 keys")
        _extend_lanes(self, pub_keys, msgs, signatures)

    def __len__(self) -> int:
        return len(self._pubkeys)

    def verify(self) -> tuple[bool, list[bool]]:
        import time as _time

        from . import host_batch
        from . import sr25519 as sr

        t0 = _time.perf_counter()
        n = len(self._pubkeys)
        # Routing: with the native engine, the host path is the same
        # one-MSM RLC pipeline as ed25519 (merlin challenges batched in
        # C, then verify_quads), so the ed25519 host/device crossover
        # applies. Without it the host is sequential pure Python
        # (~30 ms/sig) and the device wins from a handful of lanes.
        native = host_batch.available()
        host_cut = host_batch_threshold() if native else self.HOST_THRESHOLD
        if n < host_cut:
            with libmetrics.verify_phase("fallback", "sr25519-host", lanes=n):
                bitmap = None
                if native:
                    bitmap = host_batch.verify_quads(_sr_prep(
                        "sr25519-host", self._pubkeys, self._msgs,
                        self._sigs))
                if bitmap is None:
                    bitmap = [
                        sr.verify(p, m, s)
                        for p, m, s in zip(
                            self._pubkeys, self._msgs, self._sigs
                        )
                    ]
            _observe("sr25519-host", t0, n)
            return all(bitmap), bitmap
        from ..ops import verify as ov

        backend = "sr25519-tpu"
        with libmetrics.verify_phase("pack", backend, ed_lanes=0, sr_lanes=n):
            parts = _sr_prep(backend, self._pubkeys, self._msgs, self._sigs)
            buf, host_ok = ov.pack_parts(parts)
            # The expanded-point cache is keyed by the edwards A encoding,
            # so sr25519 validators (converted ristretto points) share the
            # same arena as ed25519 pubkeys.
            a_keys = [p[0] if p is not None else b"" for p in parts]
        device_ok = _launch_prepacked(buf, a_keys, n, backend)
        valid = device_ok & host_ok
        _observe(backend, t0, n)
        return bool(valid.all()), list(np.asarray(valid, bool))


def _sr_prep(backend: str, pubkeys, msgs, sigs) -> list:
    """The sr25519 lanes' host share of a verify: ristretto -> edwards
    conversion and merlin challenges, batched through the native engine
    (``verify.sr_prep`` span, ``crypto_verify_phase_seconds{phase=
    "sr_prep"}``)."""
    from . import sr25519 as sr

    with libmetrics.verify_phase("sr_prep", backend, lanes=len(pubkeys)):
        return sr.verification_encs_batch(pubkeys, msgs, sigs)


def _launch_prepacked(buf, a_keys, n: int, backend: str) -> np.ndarray:
    """Dispatch a pre-packed wire buffer (arena lookup and launch: the
    ``verify.dispatch`` span) and read its verdicts back (``verify.
    readback``, the launch's ``verify.kernel_wait`` inside), through
    ops/verify's shared launch and materialise functions."""
    from ..ops import verify as ov

    # a lane the host refused has no key (an sr25519 point that does not
    # decode, a malformed key): it reads a live lane's table, its verdict
    # is host_ok's False whatever the device says, and the arena builds
    # no table for an empty key
    live = next((k for k in a_keys if k), None)
    if live is not None and not all(a_keys):
        a_keys = [k or live for k in a_keys]
    with libmetrics.verify_phase("dispatch", backend, lanes=n):
        done = ov.verify_prepacked(buf, a_keys, n, backend)
    with libmetrics.verify_phase("readback", backend, lanes=n):
        return done()


class MixedBatchVerifier(BatchVerifier):
    """One verifier for a heterogeneous (ed25519 + sr25519) lane set.

    Both schemes decompose to the same quadruple (A_edwards, R_edwards,
    s, k) and differ only in challenge derivation (SHA-512 vs merlin
    STROBE — both computed off-device), so a mixed batch is ONE
    cofactored device launch, or ONE host RLC MSM. The reference cannot
    batch mixed sets at all: CreateBatchVerifier keys off a single type
    and verifyCommitBatch falls back to per-signature verification
    (types/validation.go:170-176); here a mixed commit stays batched.
    """

    def __init__(self) -> None:
        self._types: list[str] = []
        self._pubkeys: list[bytes] = []
        self._msgs: list[bytes] = []
        self._sigs: list[bytes] = []

    def add(self, pub_key, msg: bytes, signature: bytes) -> None:
        t = getattr(pub_key, "type", None)
        if t not in _BATCH_BACKENDS:
            raise TypeError(f"unsupported key type for batching: {t!r}")
        self._types.append(t)
        self._pubkeys.append(pub_key.data)
        self._msgs.append(bytes(msg))
        self._sigs.append(bytes(signature))

    def add_many(self, pub_keys, msgs, signatures) -> None:
        types = [getattr(pk, "type", None) for pk in pub_keys]
        for t in dict.fromkeys(types):  # the first foreign type first
            if t not in _BATCH_BACKENDS:
                raise TypeError(f"unsupported key type for batching: {t!r}")
        _extend_lanes(self, pub_keys, msgs, signatures)
        self._types.extend(types)

    def __len__(self) -> int:
        return len(self._pubkeys)

    def _ed_lane_idxs(self) -> list[int]:
        """ed25519 lanes passing the length admission; S-canonicity and
        A/R decodability are decided downstream (native packer / MSM
        engine / device kernel), exactly like the pure ed25519 paths."""
        return [
            i
            for i, t in enumerate(self._types)
            if t == keys.ED25519_KEY_TYPE
            and len(self._pubkeys[i]) == 32
            and len(self._sigs[i]) == 64
        ]

    def _ed_knegs(self, ed_idx: list[int]):
        """(kneg_rows bytes, s_ok) from the native fused SHA-512 packer,
        or None when the toolchain is absent."""
        from . import host_batch

        recs = b"".join(self._pubkeys[i] + self._sigs[i] for i in ed_idx)
        offs = [0]
        for i in ed_idx:
            offs.append(offs[-1] + len(self._msgs[i]))
        return host_batch.pack_challenges(
            recs, b"".join(self._msgs[i] for i in ed_idx), offs,
            len(ed_idx),
        )

    def _sr_quads(self, out: list, backend: str) -> list[int]:
        """Scatter sr25519 lane quads into ``out``; returns the sr lane
        indices. The ONE home of sr admission + scatter, shared by the
        host (_quads) and device (_pack_rows) paths."""
        sr_idx = [i for i, t in enumerate(self._types) if t == "sr25519"]
        if sr_idx:
            sq = _sr_prep(
                backend,
                [self._pubkeys[i] for i in sr_idx],
                [self._msgs[i] for i in sr_idx],
                [self._sigs[i] for i in sr_idx],
            )
            for j, i in enumerate(sr_idx):
                out[i] = sq[j]
        return sr_idx

    def _quads(self, backend: str) -> list:
        """Per-lane (A_enc, R_enc, s, k), challenges batched per scheme
        through the native engine (merlin STROBE for sr25519, fused
        SHA-512 for ed25519); None marks a structurally invalid lane."""
        from . import ed25519_ref as ref

        n = len(self._pubkeys)
        quads: list = [None] * n
        self._sr_quads(quads, backend)
        ed_idx = self._ed_lane_idxs()
        if not ed_idx:
            return quads
        L = ref.L
        packed = self._ed_knegs(ed_idx)
        if packed is not None:
            kneg_rows, s_ok = packed
            for j, i in enumerate(ed_idx):
                if not s_ok[j]:
                    continue
                sig = self._sigs[i]
                kneg = int.from_bytes(
                    kneg_rows[32 * j : 32 * j + 32], "little"
                )
                quads[i] = (
                    self._pubkeys[i],
                    sig[:32],
                    int.from_bytes(sig[32:], "little"),
                    (L - kneg) % L,
                )
            return quads
        for i in ed_idx:  # toolchain-less: per-lane Python challenge
            pk, sig = self._pubkeys[i], self._sigs[i]
            s = int.from_bytes(sig[32:], "little")
            if s >= L:
                continue  # S must be canonical even under ZIP-215
            k = ref.challenge_scalar(sig[:32], pk, self._msgs[i])
            quads[i] = (pk, sig[:32], s, k)
        return quads

    _ZERO_ROW = bytes(128)

    def _pack_rows(self) -> tuple[np.ndarray, np.ndarray, list]:
        """(buf (128, n), host_ok, a_keys): the device wire rows
        A|R|S|kneg, challenges batched per scheme through the native
        engine (fused SHA-512 packer for ed25519, STROBE for sr25519) —
        no per-lane Python bigints on the happy path. Row layout lives
        in ops/verify.pack_part_row / pack_challenges."""
        from ..ops import verify as ov
        from . import host_batch

        backend = "mixed-tpu"
        if not host_batch.available():
            # toolchain-less: build everything through the shared quad
            # packer (one Python challenge loop lives in _quads) —
            # checked FIRST so the ed record/message blobs aren't joined
            # just to learn pack_challenges must return None
            quads = self._quads(backend)
            buf, host_ok = ov.pack_parts(quads)
            a_keys = [q[0] if q is not None else b"" for q in quads]
            return buf, host_ok, a_keys
        n = len(self._pubkeys)
        rows: list = [None] * n
        a_keys: list = [b""] * n
        sq: list = [None] * n
        for i in self._sr_quads(sq, backend):
            q = sq[i]
            if q is None:
                continue
            rows[i] = ov.pack_part_row(*q)
            a_keys[i] = bytes(q[0])
        ed_idx = self._ed_lane_idxs()
        packed = self._ed_knegs(ed_idx) if ed_idx else None
        if ed_idx and packed is None:  # engine vanished mid-flight
            quads = self._quads(backend)
            buf, host_ok = ov.pack_parts(quads)
            return buf, host_ok, [
                q[0] if q is not None else b"" for q in quads
            ]
        if ed_idx:
            kneg_rows, s_ok = packed
            for j, i in enumerate(ed_idx):
                if not s_ok[j]:
                    continue
                # raw-bytes row pk|R|S|kneg: byte-identical to
                # pack_part_row's layout (sig is R||S on the wire, kneg
                # from the native packer) — pinned by
                # test_mixed_row_assembly_matches_pack_part_row
                rows[i] = (
                    self._pubkeys[i]
                    + self._sigs[i]
                    + kneg_rows[32 * j : 32 * j + 32]
                )
                a_keys[i] = self._pubkeys[i]
        host_ok = np.array([r is not None for r in rows], bool)
        blob = b"".join(
            r if r is not None else self._ZERO_ROW for r in rows
        )
        buf = np.ascontiguousarray(
            np.frombuffer(blob, np.uint8).reshape(n, 128).T
        )
        return buf, host_ok, a_keys

    def verify(self) -> tuple[bool, list[bool]]:
        import time as _time

        from . import host_batch

        t0 = _time.perf_counter()
        n = len(self._pubkeys)
        native = host_batch.available()
        if native:
            host_cut = host_batch_threshold()
        else:
            # Toolchain-less host cost is dominated by pure-Python
            # sr25519 verifies (~30 ms/sig); ed25519 lanes verify via
            # OpenSSL in ~50 us. The tiny sr cutoff applies only when
            # sr lanes actually dominate — an ed-heavy mixed batch
            # keeps the ed crossover.
            n_sr = sum(1 for t in self._types if t == "sr25519")
            host_cut = (
                Sr25519BatchVerifier.HOST_THRESHOLD
                if n_sr >= Sr25519BatchVerifier.HOST_THRESHOLD
                else host_batch_threshold()
            )
        if n < host_cut:
            with libmetrics.verify_phase("fallback", "mixed-host", lanes=n):
                bitmap = (
                    host_batch.verify_quads(self._quads("mixed-host"))
                    if native else None
                )
                if bitmap is None:
                    from .sr25519 import verify as sr_verify

                    bitmap = [
                        (
                            keys.Ed25519PubKey(pk).verify_signature(m, s)
                            if t == keys.ED25519_KEY_TYPE
                            else sr_verify(pk, m, s)
                        )
                        for t, pk, m, s in zip(
                            self._types, self._pubkeys, self._msgs,
                            self._sigs,
                        )
                    ]
            _observe("mixed-host", t0, n)
            return all(bitmap), list(bitmap)
        backend = "mixed-tpu"
        n_sr = self._types.count("sr25519")
        with libmetrics.verify_phase(
            "pack", backend, ed_lanes=n - n_sr, sr_lanes=n_sr
        ):
            buf, host_ok, a_keys = self._pack_rows()
        device_ok = _launch_prepacked(buf, a_keys, n, backend)
        valid = device_ok & host_ok
        _observe(backend, t0, n)
        return bool(valid.all()), list(np.asarray(valid, bool))


_BATCH_BACKENDS: dict[str, type] = {
    keys.ED25519_KEY_TYPE: Ed25519BatchVerifier,
    "sr25519": Sr25519BatchVerifier,
}


def _key_types(validator_set) -> frozenset:
    """The key types of a set's validators: a ValidatorSet answers from
    the profile it keeps behind a witness of its keys
    (``ValidatorSet.key_types``); anything else is scanned."""
    from ..types.validator_set import ValidatorSet

    if isinstance(validator_set, ValidatorSet):
        return validator_set.key_types()
    return frozenset(
        getattr(v.pub_key, "type", None)
        for v in getattr(validator_set, "validators", [])
    )


def supports_commit_batch(validator_set) -> bool:
    """True when every key type in the set has a batch backend (a mixed
    set rides MixedBatchVerifier)."""
    types = _key_types(validator_set)
    return bool(types) and all(t in _BATCH_BACKENDS for t in types)


def create_commit_batch_verifier(validator_set) -> BatchVerifier:
    """Batch verifier for a (possibly heterogeneous) validator set.

    Homogeneous sets get their scheme's dedicated backend (ed25519 keeps
    the fused native happy path); mixed sets get MixedBatchVerifier —
    one launch where the reference falls back to per-signature verifies.
    """
    types = _key_types(validator_set)
    if len(types) == 1:
        backend = _BATCH_BACKENDS.get(next(iter(types)))
        if backend is not None:
            return backend()
    if types and all(t in _BATCH_BACKENDS for t in types):
        return MixedBatchVerifier()
    raise ValueError(
        f"batch verification unsupported for key types {sorted(types)!r}"
    )


def _observe(backend: str, t0: float, n: int) -> None:
    """Record end-to-end batch-verify latency/volume. Routed through
    node_metrics() like every other instrumentation site: the running
    node's registry when one is up, a throwaway sink otherwise."""
    import time as _time

    dt = _time.perf_counter() - t0
    m = libmetrics.node_metrics()
    m.verify_batch_seconds.labels(backend).observe(dt)
    m.verify_batch_sigs.labels(backend).inc(n)


def prestage_validators(validator_set) -> int:
    """Warm the device pubkey arena for a validator set's ed25519 keys.

    The FSM calls this at enter-new-round so steady-state commit/vote
    verification ships only R|S|k (ops/verify.prestage_pubkeys; the
    device analog of the reference's expanded-pubkey LRU being hot,
    crypto/ed25519/ed25519.go:31,56). sr25519 keys are skipped: their
    arena key is the CONVERTED edwards encoding, and the conversion
    itself is the expensive host step — converting eagerly per round
    would cost more than the build it saves.
    """
    keys_bytes = [
        v.pub_key.data
        for v in getattr(validator_set, "validators", [])
        if getattr(v.pub_key, "type", None) == keys.ED25519_KEY_TYPE
    ]
    if not keys_bytes:
        return 0
    from ..ops import verify as ov

    return ov.prestage_pubkeys(keys_bytes)


def supports_batch_verifier(pub_key) -> bool:
    return getattr(pub_key, "type", None) in _BATCH_BACKENDS


def create_batch_verifier(pub_key) -> BatchVerifier:
    """Instantiate the batch backend for ``pub_key``'s type.

    Raises ValueError for unsupported types — callers fall back to
    single-signature verification (types/validation.go:170-176 semantics).
    """
    backend = _BATCH_BACKENDS.get(getattr(pub_key, "type", None))
    if backend is None:
        raise ValueError(
            f"batch verification unsupported for key type "
            f"{getattr(pub_key, 'type', None)!r}"
        )
    return backend()
