"""Cross-caller hash coalescer: the device-resident SHA-256 plane.

The verify coalescer (crypto/coalesce.py) proved the shape: concurrent
single-item callers submit lanes to per-submit tickets, an executor
thread coalesces them into fixed-shape-bucket device micro-batches, and
windows double-buffer so the host pack of window N+1 overlaps the
device execute of window N. This module is the SAME machinery for
SHA-256 — the node's OTHER ubiquitous crypto primitive
(arXiv:2407.03511: hashing dominates blockchain data paths):

* concurrent callers — mempool CheckTx tx-key hashing
  (mempool/clist_mempool.py TxKey), PartSet leaf hashing on both the
  build and the gossip-verify side (types/part_set.py via
  crypto/merkle.py), and block/data/header merkle levels
  (types/block.py) — submit message lanes and block on a ticket;
* the executor flushes windows by size (COMETBFT_TPU_HASH_MAX_LANES)
  or deadline (COMETBFT_TPU_HASH_WINDOW_US), splits each window's
  lanes by SHA block bucket (a 55-byte tx key must not pad to a
  64 KiB part's block count), and launches each bucket through
  ops/sha256's bucketed kernel;
* each block bucket carries its OWN adaptive host/device crossover
  (crypto/batch.AdaptiveCrossover instances fed per-bucket): the lane
  count where the device wins a window of 1-block messages is very
  different from where it wins 1024-block part hashing, and the live
  fit learns both separately;
* host fallback is clean AND cheap: unlike ed25519 (where a host
  window still wins as one RLC MSM), SHA-256 has no host batch trick —
  so the routed helpers fall back to plain ``hashlib`` WITHOUT
  queueing whenever no device could take the window (device-less
  container, sub-floor messages, or a batch below every bucket's
  device cut), and the flush deadline is work-proportional. The one
  deliberately OPTIMISTIC path is single-message routing
  (``hash_bytes`` at >= 1 KiB): a storm of concurrent 1-lane callers
  can only form a winning window if each queues before knowing the
  others exist, so an uncontended large single pays a bounded thread
  handoff (tens of us against an enclosing RPC/gossip operation that
  costs milliseconds) — the same trade the verify coalescer makes for
  lone votes;
* digests are bit-identical to ``hashlib.sha256`` everywhere (the
  kernel is fuzz-pinned across every padding boundary), so routing can
  never change a hash — only where it is computed.

Locking: ``crypto.hashplane._mtx`` guards the pending queue — the
flush path pops a window under it and releases it before pack,
dispatch, the materializing readback, and ticket resolution;
``crypto.hashplane._rb_mtx`` guards only the executor->drain handoff
(dispatched windows materialize on a dedicated readback drain thread,
FIFO, so execute of window N+1 overlaps the d2h of window N). Neither
blocks on the device while held and neither acquires an engine mutex
(asserted by tests/test_lint_graph.py, same contract as the verify
coalescer's locks).
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import deque

from ..libs import devledger as libdevledger
from ..libs import health as libhealth
from ..libs import metrics as libmetrics
from ..libs import sync as libsync
from ..libs import trace as libtrace
from ..libs.service import BaseService, ServiceError
from .coalesce import (
    _DEFAULT_MAX_INFLIGHT,
    _env_int,
    _env_opt_int,
    deadline_remaining,
)

# Deadline window before a sub-size window flushes anyway; same scale
# and rationale as the verify coalescer's window.
_DEFAULT_WINDOW_US = 500
# Lanes that trigger an immediate size flush (and the per-window cap).
# Hash lanes are cheaper to stage than signature lanes, but a window
# splits into per-block-bucket launches, so the cap bounds the SUM.
_DEFAULT_MAX_LANES = 2048
# Ticket wait bound for the routed helpers; like the verify bound it is
# ALSO the worst-case stall a wedged device can inflict on a caller
# that holds an engine mutex (PartSet verify under consensus.state).
_RESULT_TIMEOUT_S = 5.0
# Breaker cooldown once a ticket outlives the full bound (see
# crypto/coalesce._TRIP_COOLDOWN_S — identical semantics).
_TRIP_COOLDOWN_S = 30.0

# Routed-helper floors: below these the host hashlib call is so cheap
# that even a perfectly coalesced device window cannot recover the
# ticket round trip, so the helpers skip the queue entirely.
#   hash_bytes: single messages (mempool tx keys, PartSet leaf verify)
#   route only at >= this many bytes;
_SUM_ROUTE_MIN_BYTES = 1024
#   hash_many / merkle levels: batches route only when the window
#   carries at least this much total padded-block work.
_ROUTE_MIN_BLOCKS = 64

# Seed for the per-bucket device cutover while its adaptive fit is
# uncalibrated: device wins once a window carries ~this many total
# SHA blocks, so the lane cutover for bucket B is ~SEED/B (clamped).
_SEED_DEVICE_BLOCKS = 2048

# The deadline a window waits for more lanes is PROPORTIONAL to the
# host cost of the work already pending (capped by the window knob): a
# lone 2 KiB tx key (~1 us of hashlib) must not sit out a 500 us window
# to discover nobody else was hashing — that would be a 100x+ latency
# regression on uncontended paths (serial blocksync part verifies, a
# single RPC CheckTx) — while a 64-part PartSet build (~15 ms host) can
# afford the full window for siblings to pile in. Under a real storm
# concurrent submits are already queued when the executor collects, so
# a short budget still coalesces everything actually concurrent; the
# budget only bounds how long the plane gambles on FUTURE arrivals.
_HOST_S_PER_BLOCK = 25e-9  # single-core hashlib cost per 64-byte block
_WAIT_COST_FACTOR = 2.0  # wait at most ~2x the pending work's host cost


class HashplaneStoppedError(ServiceError):
    """submit() after the drain began — callers fall back to hashlib."""


class _Ticket:
    """One submit()'s pending digests; resolved exactly once."""

    __slots__ = (
        "n", "blocks", "caller", "t_submit", "_done", "_digests", "_exc"
    )

    def __init__(self, n: int, blocks: int = 0, caller: int = 0):
        self.n = n
        # caller class (libs/devledger enum) captured at submit — the
        # device-time ledger's attribution key
        self.caller = caller
        # total padded SHA blocks across this submit's lanes — the
        # executor's work-proportional deadline budget reads it
        self.blocks = blocks
        self.t_submit = time.perf_counter()
        self._done = threading.Event()
        self._digests: list[bytes] | None = None
        self._exc: BaseException | None = None

    def resolve(self, digests) -> None:
        self._digests = list(digests)
        self._done.set()

    def fail(self, exc: BaseException) -> None:
        self._exc = exc
        self._done.set()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float | None = None) -> list[bytes]:
        """Block for this submit's digests. Callers may hold engine
        mutexes here — the wait is bounded by the flush-window deadline
        plus one launch, and the executor acquires no engine mutex
        (tests/test_lint_graph.py pins crypto.hashplane._mtx edge-free),
        so no lock cycle can form through it."""
        ok = self._done.wait(timeout)  # cometlint: disable=CLNT009 -- bounded coalescer wait: resolved within the flush-window deadline + one launch by the executor thread, which acquires no engine mutex (asserted leaf in test_lint_graph); replaces an equal-or-longer inline host hash under the same caller locks only when routing said the device wins
        if not ok:
            raise TimeoutError(
                f"coalesced hash not resolved within {timeout}s "
                f"({self.n} lanes)"
            )
        if self._exc is not None:
            raise self._exc
        return list(self._digests or [])


class _Inflight:
    """A window with dispatched-but-unmaterialized device buckets."""

    __slots__ = (
        "finishes", "out", "groups", "lanes", "reason", "device",
        "t_launch", "host_s",
    )

    def __init__(self, finishes, out, groups, lanes, reason,
                 t_launch=0.0, host_s=0.0):
        # [(materializer, window_indices, block_bucket, prep_s, lanes)]
        self.finishes = finishes
        self.out = out  # window-ordered digest slots (host buckets filled)
        self.groups = groups  # [(ticket, msgs)] — the hashlib rescue wire
        self.lanes = lanes
        self.reason = reason
        self.device = bool(finishes)
        # window pop time (queue-wait anchor) and the host-bucket
        # fallback seconds already spent at launch — _finish adds the
        # device buckets' prep+readback for the window execute total
        self.t_launch = t_launch
        self.host_s = host_s


class _BucketCrossover:
    """Per-block-bucket adaptive host/device lane cutover.

    One crypto/batch.AdaptiveCrossover per SHA block bucket, fed from
    the plane's own window timings: ``threshold(bucket)`` answers "at
    how many lanes does a window of THIS message size win on device".
    Until a bucket is calibrated the seed curve answers
    (~:data:`_SEED_DEVICE_BLOCKS` total blocks); adaptation follows the
    same gate as the verify crossover (env force / accelerator-only).
    """

    def __init__(self) -> None:
        self._mtx = libsync.Mutex("crypto.hashplane._crossover")
        self._fits: dict[int, object] = {}

    def _fit(self, bucket: int):
        from . import batch as crypto_batch

        with self._mtx:
            xo = self._fits.get(bucket)
            if xo is None:
                xo = crypto_batch.AdaptiveCrossover()
                self._fits[bucket] = xo
            return xo

    def note_host(self, bucket: int, lanes: int, seconds: float) -> None:
        from . import batch as crypto_batch

        if crypto_batch._adaptive_enabled():
            self._fit(bucket).observe_host(lanes, seconds)

    def note_device(self, bucket: int, lanes: int, seconds: float) -> None:
        from . import batch as crypto_batch

        if crypto_batch._adaptive_enabled():
            self._fit(bucket).observe_device(lanes, seconds)

    def threshold(self, bucket: int) -> int:
        seed = max(2, _SEED_DEVICE_BLOCKS // max(1, bucket))
        from . import batch as crypto_batch

        if not crypto_batch._adaptive_enabled():
            return seed
        t = self._fit(bucket).threshold()
        return seed if t is None else t


CROSSOVER = _BucketCrossover()


class HashCoalescer(BaseService):
    """Background hash executor coalescing concurrent digest callers.

    ``submit`` enqueues message lanes and returns a ticket; the
    executor thread flushes windows by size or deadline, splits each
    window by SHA block bucket, and double-buffers device launches
    (the pack of window N+1 overlaps the execute of window N). See the
    module docstring for the full design.
    """

    _JOIN_TIMEOUT_S = 10.0

    def __init__(
        self,
        window_us: int | None = None,
        max_lanes: int | None = None,
        min_device_lanes: int | None = None,
        device: bool | None = None,
        max_inflight: int | None = None,
        logger=None,
    ):
        super().__init__("HashCoalescer", logger)
        self.window_s = (
            window_us
            if window_us is not None
            else _env_int("COMETBFT_TPU_HASH_WINDOW_US", _DEFAULT_WINDOW_US)
        ) / 1e6
        from ..ops.sha256 import MAX_LANES as _kernel_cap

        # clamped to the kernel's per-launch cap: an oversized knob
        # would make every size-flushed window's launch raise and fall
        # back — the device path would silently never engage
        self.max_lanes = min(
            _kernel_cap,
            max(
                1,
                max_lanes
                if max_lanes is not None
                else _env_int(
                    "COMETBFT_TPU_HASH_MAX_LANES", _DEFAULT_MAX_LANES
                ),
            ),
        )
        # None = defer to the per-bucket crossover at flush time
        self.min_device_lanes: int | None = (
            min_device_lanes
            if min_device_lanes is not None
            else _env_opt_int("COMETBFT_TPU_HASH_MIN_DEVICE_LANES")
        )
        # None = defer to the process-wide accelerator probe; True/False
        # pin (tests, bench).
        self._device = device
        self._mtx = libsync.Mutex("crypto.hashplane._mtx")
        self._cv = libsync.Condition(self._mtx, name="crypto.hashplane._mtx")
        self._pending: deque[tuple] = deque()  # (ticket, msgs)
        self._pending_lanes = 0
        self._pending_blocks = 0  # padded-block sum: the wait budget
        # lockfree: drain gate — locked writes, advisory fast-path reads; a stale read routes one submit to the host fallback
        self._draining = False
        # lock-free running flag, same rationale as the verify coalescer
        # lockfree: locked writes, advisory fast-path reads (see crypto/coalesce.py)
        self._accepting = False
        # lockfree: breaker deadline — locked writes, racy reads re-check under the lock before re-arming
        self._tripped_until = 0.0
        self._thread: threading.Thread | None = None
        # executor-owned mirrors so the rescue paths can always reach a
        # popped window's tickets (see crypto/coalesce.py)
        # lockfree: flight ring — executor appends, drain thread removes, rescues snapshot via tuple(); GIL-atomic list ops, single writer per end
        self._inflights: list[_Inflight] = []
        self._staging: list[tuple] | None = None
        # readback drain handoff, mirroring the verify coalescer's:
        # dispatched windows materialize on a dedicated drain thread in
        # submission order while the executor packs + dispatches the
        # next window; the depth bound keeps the pipeline bounded.
        self.max_inflight = max(
            1,
            max_inflight
            if max_inflight is not None
            else _env_int(
                "COMETBFT_TPU_HASH_INFLIGHT", _DEFAULT_MAX_INFLIGHT
            ),
        )
        self._rb_mtx = libsync.Mutex("crypto.hashplane._rb_mtx")
        self._rb_cv = libsync.Condition(
            self._rb_mtx, name="crypto.hashplane._rb_mtx"
        )
        self._readback: deque[_Inflight] = deque()
        self._rb_busy = 0
        self._rb_closed = False
        self._rb_alive = False
        self._rb_thread: threading.Thread | None = None
        self.windows = 0
        self.device_windows = 0
        self.tickets = 0
        # buckets kept on hashlib because their shape was still
        # compiling, and breaker trips — both zero once warmed
        self.cold_buckets = 0
        self.trips = 0

    # -- lifecycle ---------------------------------------------------------

    def on_start(self) -> None:
        with self._mtx:
            self._draining = False
        with self._rb_mtx:
            self._rb_closed = False
            self._rb_alive = True
        rt = threading.Thread(
            target=self._drain_run, name="hash-readback", daemon=True
        )
        rt.start()
        # lockfree: start/stop lifecycle handle, written only by the thread driving the service transition
        self._rb_thread = rt
        t = threading.Thread(target=self._run, name="hash-plane", daemon=True)
        t.start()
        # lockfree: start/stop lifecycle handle, written only by the thread driving the service transition
        self._thread = t
        with self._mtx:
            self._accepting = True

    def on_stop(self) -> None:
        """Drain: every pending ticket is resolved before stop returns."""
        with self._mtx:
            self._draining = True
            self._accepting = False
            self._cv.notify_all()
        with self._rb_mtx:
            # wake an executor blocked at the in-flight depth bound
            self._rb_cv.notify_all()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=self._JOIN_TIMEOUT_S)
        rt = self._rb_thread
        if rt is not None and rt is not threading.current_thread():
            self._close_readback()
            rt.join(timeout=self._JOIN_TIMEOUT_S)
        # Safety net mirroring the verify coalescer's: host-resolve
        # anything a dead or wedged executor left behind; done() gates
        # make overlap with a still-alive executor benign.
        with self._mtx:
            leftovers, self._pending = self._pending, deque()
            self._pending_lanes = 0
            self._pending_blocks = 0
        for group in leftovers:
            self._resolve_group_host(group)
        for group in self._staging or ():
            self._resolve_group_host(group)
        for fl in tuple(self._inflights):
            self._rescue_inflight(fl)
            self._drop_inflight(fl)

    # -- submission --------------------------------------------------------

    def submit(self, msgs) -> _Ticket:
        """Queue message lanes; returns the ticket with their digests.
        Raises :class:`HashplaneStoppedError` once the drain began."""
        return self.submit_many([msgs])[0]

    def submit_many(self, groups) -> list[_Ticket]:
        """Batch-submit several lane groups as ONE queue transaction
        (one mutex hold, one executor wake-up) — a chunked oversized
        batch packs into consecutive windows without interleaving."""
        from ..ops.sha256 import n_blocks

        tickets: list[_Ticket] = []
        staged: list[tuple] = []
        cid = libdevledger.current_caller()
        for msgs in groups:
            blocks = 0
            try:
                blocks = sum(n_blocks(len(m)) for m in msgs)
            except TypeError:
                pass  # unsized lanes fail in _stage, per-ticket
            t = _Ticket(len(msgs), blocks, cid)
            tickets.append(t)
            if t.n == 0:
                t.resolve([])
            else:
                staged.append((t, msgs))
        if not staged:
            return tickets
        with self._mtx:
            if self._draining or not self._accepting:
                raise HashplaneStoppedError(self._name)
            for g in staged:
                self._pending.append(g)
                self._pending_lanes += g[0].n
                self._pending_blocks += g[0].blocks
            self.tickets += len(staged)
            self._cv.notify_all()
        return tickets

    def try_hash_many(self, msgs) -> list[bytes] | None:
        """submit + wait with a clean not-routed signal.

        Returns the per-lane digests, or None when the plane cannot
        serve the request (stopped, breaker cooldown, wait expired) —
        the caller then hashes on host, so routing never changes a
        digest. Oversized groups chunk into ``max_lanes`` tickets
        submitted as one batch. Waits honor the thread's
        crypto/coalesce.request_deadline budget; a deadline-capped
        expiry returns None WITHOUT tripping the breaker.
        """
        rem = deadline_remaining()
        if rem is not None and rem <= 0:
            return None
        if not self._claim_probe():
            return None
        n = len(msgs)
        if n <= self.max_lanes:
            groups = [msgs]
        else:
            groups = [
                msgs[i : i + self.max_lanes]
                for i in range(0, n, self.max_lanes)
            ]
        try:
            tickets = self.submit_many(groups)
        except ServiceError:
            return None
        digests: list[bytes] = []
        for ticket in tickets:
            wait_s = _RESULT_TIMEOUT_S
            capped = False
            rem = deadline_remaining()
            if rem is not None and rem < wait_s:
                wait_s, capped = max(rem, 0.0), True
            try:
                digests.extend(ticket.result(wait_s))
            except TimeoutError:
                # full-bound expiry = wedged executor evidence; trip the
                # cooldown breaker so subsequent callers fall back to
                # hashlib instantly (see crypto/coalesce.try_verify —
                # identical containment contract)
                if not capped:
                    self._trip()
                return None
            except Exception:
                return None
        self._rearm()
        return digests

    def batch_worth_routing(self, msgs) -> bool:
        """True when this batch ALONE can put at least one of its block
        buckets over that bucket's device cut (and carries the minimum
        total work). Single-caller batches (merkle levels, Data.hash)
        don't need cross-caller coalescing to win — one below every
        cut would deterministically host-hash inside the executor,
        paying two thread handoffs for a hashlib call the caller could
        run inline. Singles (:func:`hash_bytes`) stay optimistic: a
        storm of concurrent 1-lane callers can only form a winning
        window if each queues before knowing the others exist."""
        counts: dict[int, int] = {}
        total = 0
        from ..ops.sha256 import block_bucket, n_blocks

        for m in msgs:
            nb = n_blocks(len(m))
            total += nb
            bb = block_bucket(nb)
            counts[bb] = counts.get(bb, 0) + 1
        if total < _ROUTE_MIN_BLOCKS:
            return False
        return any(
            c >= self._device_cut(bb) for bb, c in counts.items()
        )

    def device_capable(self) -> bool:
        """Whether windows COULD take a device path at all. The routed
        helpers consult this before queueing: a coalesced host window
        has no batch win for SHA-256 (hashlib is already optimal), so
        on device-less containers callers must stay on plain hashlib
        with zero ticket round trips."""
        if self._device is not None:
            return self._device
        from ..libs.accel import accelerator_backend_live

        return accelerator_backend_live()

    def routable(self) -> bool:
        """Accepting submits and not inside a breaker cooldown. PURE
        query — never consumes the half-open probe."""
        return self._accepting and (
            self._tripped_until == 0.0
            or time.monotonic() >= self._tripped_until
        )

    def _claim_probe(self) -> bool:
        if self._tripped_until == 0.0:
            return True
        with self._mtx:
            if self._tripped_until == 0.0:
                return True
            if time.monotonic() < self._tripped_until:
                return False
            self._tripped_until = time.monotonic() + _TRIP_COOLDOWN_S
            return True

    def _rearm(self) -> None:
        if self._tripped_until == 0.0:
            return
        with self._mtx:
            self._tripped_until = 0.0
        libhealth.note_breaker_rearm()

    def _trip(self) -> None:
        """Unroute a wedged plane for one cooldown; queued groups hand
        to a hashlib rescue thread so no caller hangs behind a wedged
        executor. Feeds the SAME breaker health channel as the verify
        coalescer (EV_BREAKER ring rows + the wedged-coalescer
        watchdog): either plane wedging means the shared device path
        stalled, and it must page + capture a black-box bundle instead
        of failing over silently."""
        leftovers: deque | None = None
        with self._mtx:
            if self._draining or not self._accepting:
                return
            self._tripped_until = time.monotonic() + _TRIP_COOLDOWN_S
            self.trips += 1
            if self._pending:
                leftovers, self._pending = self._pending, deque()
                self._pending_lanes = 0
                self._pending_blocks = 0
            self._cv.notify_all()
        if leftovers:
            groups = tuple(leftovers)
            threading.Thread(
                target=lambda: [
                    self._resolve_group_host(g) for g in groups
                ],
                name="hash-plane-rescue",
                daemon=True,
            ).start()
        # health hook: the wedged-coalescer watchdog converts this
        # notice into a trip + black-box bundle (no lock held here)
        libhealth.note_breaker_trip()
        if self.logger is not None:
            self.logger.error(
                "hash plane unresponsive; unrouted for cooldown",
                timeout_s=_RESULT_TIMEOUT_S,
                cooldown_s=_TRIP_COOLDOWN_S,
            )

    # -- the executor ------------------------------------------------------

    def _run(self) -> None:
        try:
            while True:
                try:
                    groups, lanes, reason = self._collect(block=True)
                    if groups:
                        self._staging = groups
                        handle = self._launch(groups, lanes, reason)
                        if handle is not None:
                            self._inflights.append(handle)
                            self._hand_to_drain(handle)
                        self._staging = None
                    if reason == "quit":
                        return
                except Exception:
                    # survive anything; rescue every slot a ticket can
                    # live in (staging + every drain-queue slot)
                    try:
                        import traceback

                        traceback.print_exc()
                    except Exception:
                        pass
                    staged, self._staging = self._staging, None
                    for group in staged or ():
                        self._resolve_group_host(group)
                    for fl in tuple(self._inflights):
                        self._rescue_inflight(fl)
                        self._drop_inflight(fl)
        finally:
            self._close_readback()
            rt = self._rb_thread
            if rt is not None and rt is not threading.current_thread():
                rt.join(timeout=self._JOIN_TIMEOUT_S)
            with self._mtx:
                self._accepting = False
                leftovers, self._pending = self._pending, deque()
                self._pending_lanes = 0
                self._pending_blocks = 0
            staged, self._staging = self._staging, None
            for group in staged or ():
                self._resolve_group_host(group)
            for group in leftovers:
                self._resolve_group_host(group)
            for fl in tuple(self._inflights):
                self._rescue_inflight(fl)
                self._drop_inflight(fl)

    # -- the readback drain (see crypto/coalesce.py — same design) ---------

    def _hand_to_drain(self, fl: _Inflight) -> None:
        handed = False
        with self._rb_mtx:
            if self._rb_alive and not self._rb_closed:
                self._readback.append(fl)
                handed = True
                self._rb_cv.notify_all()
                while (
                    self._rb_alive
                    and not self._rb_closed
                    and not self._draining
                    and len(self._readback) + self._rb_busy
                    >= self.max_inflight
                ):
                    self._rb_cv.wait(0.2)
        if not handed:
            self._finish(fl)
            self._drop_inflight(fl)

    def _close_readback(self) -> None:
        with self._rb_mtx:
            self._rb_closed = True
            self._rb_cv.notify_all()

    def _drain_run(self) -> None:
        """Materialize dispatched windows in submission order; a finish
        fault falls back to the hashlib rescue for that window only."""
        try:
            while True:
                with self._rb_mtx:
                    while not self._readback and not self._rb_closed:
                        self._rb_cv.wait(0.2)
                    if not self._readback:
                        return
                    fl = self._readback.popleft()
                    self._rb_busy += 1
                try:
                    self._finish(fl)
                except Exception:
                    try:
                        import traceback

                        traceback.print_exc()
                    except Exception:
                        pass
                    self._rescue_inflight(fl)
                finally:
                    self._drop_inflight(fl)
                    with self._rb_mtx:
                        self._rb_busy -= 1
                        self._rb_cv.notify_all()
        finally:
            with self._rb_mtx:
                self._rb_alive = False
                leftovers = list(self._readback)
                self._readback.clear()
                self._rb_cv.notify_all()
            for fl in leftovers:
                self._rescue_inflight(fl)
                self._drop_inflight(fl)

    def _drop_inflight(self, fl: _Inflight) -> None:
        try:
            self._inflights.remove(fl)
        except ValueError:
            pass

    def _collect(self, block: bool):
        """Pop one flush window; same contract as the verify
        coalescer's _collect (reason: size|deadline|drain|idle|quit;
        deadline anchored at the oldest pending ticket) — except the
        deadline budget is work-proportional: min(window knob,
        ~2x the pending lanes' host hashlib cost), recomputed as more
        lanes arrive. A lone tiny key flushes near-instantly instead
        of gambling a full window on future arrivals; heavy windows
        wait the knob like the verify coalescer."""
        with self._mtx:
            if block:
                while not self._pending and not self._draining:
                    self._cv.wait(0.2)
            if not self._pending:
                return None, 0, ("quit" if self._draining else "idle")
            first_t = self._pending[0][0].t_submit
            while self._pending_lanes < self.max_lanes and not self._draining:
                budget = min(
                    self.window_s,
                    _WAIT_COST_FACTOR
                    * _HOST_S_PER_BLOCK
                    * self._pending_blocks,
                )
                rem = budget - (time.perf_counter() - first_t)
                if rem <= 0:
                    break
                self._cv.wait(rem)
            if self._draining:
                reason = "drain"
            elif self._pending_lanes >= self.max_lanes:
                reason = "size"
            else:
                reason = "deadline"
            groups: list[tuple] = []
            lanes = 0
            while self._pending and (
                not groups or lanes + self._pending[0][0].n <= self.max_lanes
            ):
                g = self._pending.popleft()
                groups.append(g)
                lanes += g[0].n
                self._pending_blocks -= g[0].blocks
            self._pending_lanes -= lanes
            return groups, lanes, reason

    def _device_cut(self, bucket: int) -> int:
        """Lane cutover for a block bucket: ctor/env pin > the bucket's
        adaptive crossover > the seed curve."""
        if self.min_device_lanes is not None:
            return self.min_device_lanes
        return CROSSOVER.threshold(bucket)

    def _stage(self, groups):
        """Flatten groups into one window-ordered message list; a lane
        that cannot coerce to bytes fails ONLY its own submit."""
        msgs: list[bytes] = []
        staged: list[tuple] = []  # (ticket, lo, n)
        wire: list[tuple] = []  # (ticket, msgs) for hashlib rescue
        for ticket, raw in groups:
            try:
                lanes = [bytes(m) for m in raw]
                if len(lanes) != ticket.n:
                    raise ValueError(
                        f"lane count mismatch: {len(lanes)} != {ticket.n}"
                    )
            except Exception as e:
                ticket.fail(e)
                continue
            lo = len(msgs)
            msgs.extend(lanes)
            staged.append((ticket, lo, ticket.n))
            wire.append((ticket, lanes))
        return msgs, staged, wire

    def _launch(self, groups, lanes, reason) -> _Inflight | None:
        """Stage + dispatch one window, split by block bucket. Buckets
        the crossover sends to the device dispatch asynchronously (the
        double buffer materializes them NEXT loop turn); host buckets
        resolve inline with hashlib. Returns an in-flight handle when
        any device bucket launched, else resolves synchronously."""
        t_pop = time.perf_counter()
        libdevledger.exec_begin(libdevledger.PLANE_HASH)
        try:
            return self._launch_inner(groups, lanes, reason, t_pop)
        finally:
            libdevledger.exec_end(libdevledger.PLANE_HASH)

    def _launch_inner(self, groups, lanes, reason, t_pop) -> _Inflight | None:
        from ..ops import sha256 as osha

        msgs, staged, wire = self._stage(groups)
        if not staged:
            return None
        n = len(msgs)
        m = libmetrics.node_metrics()
        m.hash_window_lanes.observe(n)
        m.hash_flushes.labels(reason).inc()
        self.windows += 1
        use_device = self.device_capable()
        # split window lanes by block bucket (window order preserved
        # inside each bucket)
        buckets: dict[int, list[int]] = {}
        for i, msg in enumerate(msgs):
            bb = osha.block_bucket(osha.n_blocks(len(msg)))
            buckets.setdefault(bb, []).append(i)
        out: list[bytes | None] = [None] * n
        finishes = []
        host_s = 0.0
        for bb in sorted(buckets):
            idxs = buckets[bb]
            sub = [msgs[i] for i in idxs]
            to_device = use_device and len(idxs) >= self._device_cut(bb)
            if to_device and self._device is None:
                # no compile inside the routed ticket bound: a cold
                # shape hashes on host while ops/warm compiles it (an
                # explicit device=True pin — tests, bench — compiles
                # inline; see crypto/coalesce._launch_inner)
                if not osha.shape_ready(bb, len(idxs)):
                    to_device = False
                    self.cold_buckets += 1
            if to_device:
                t0 = time.perf_counter()
                try:
                    finish = osha.sha256_many_async(sub, bb)
                except Exception:
                    # device staging/dispatch fault: clean hashlib
                    # fallback for this bucket only
                    import traceback

                    traceback.print_exc()
                else:
                    prep = time.perf_counter() - t0
                    libmetrics.observe_hash_phase(
                        "dispatch", prep, len(idxs)
                    )
                    finishes.append((finish, idxs, bb, prep, len(idxs)))
                    continue
            t0 = time.perf_counter()
            for i in idxs:
                out[i] = hashlib.sha256(msgs[i]).digest()
            dt = time.perf_counter() - t0
            host_s += dt
            libmetrics.observe_hash_phase("fallback", dt, len(idxs))
            CROSSOVER.note_host(bb, len(idxs), dt)
        if finishes:
            self.device_windows += 1
            libdevledger.note_window(libdevledger.PLANE_HASH, n, True)
            return _Inflight(
                finishes, out, wire, n, reason,
                t_launch=t_pop, host_s=host_s,
            )
        libdevledger.note_window(libdevledger.PLANE_HASH, n, False)
        self._resolve_bits(
            staged, out, reason, "host", t_launch=t_pop, host_s=host_s
        )
        return None

    def _finish(self, fl: _Inflight) -> None:
        """Materialize a window's device buckets and resolve tickets."""
        t0_ns = time.monotonic_ns()
        busy0 = libdevledger.exec_busy_ns(libdevledger.PLANE_HASH)
        device_s = 0.0
        for finish, idxs, bb, prep, k in fl.finishes:
            t0 = time.perf_counter()
            try:
                digests = finish()
            except Exception:
                # device fault at materialization: hashlib fallback for
                # the bucket — verdict-identical, never an error. The
                # recovery's hashlib time is NOT folded into device_s:
                # the whole window resolves as backend="device", and
                # charging host fault-recovery time as device execute
                # would skew the ledger exactly during the fault
                # episodes attribution exists to explain.
                import traceback

                traceback.print_exc()
                for i in idxs:
                    fl.out[i] = hashlib.sha256(fl_msg(fl, i)).digest()
                continue
            dt = time.perf_counter() - t0
            device_s += prep + dt
            libmetrics.observe_hash_phase("readback", dt, k)
            CROSSOVER.note_device(bb, k, prep + dt)
            for j, i in enumerate(idxs):
                fl.out[i] = digests[j]
        libdevledger.note_readback(libdevledger.PLANE_HASH, t0_ns, busy0)
        staged = []
        lo = 0
        for ticket, lanes in fl.groups:
            staged.append((ticket, lo, ticket.n))
            lo += ticket.n
        self._resolve_bits(
            staged, fl.out, fl.reason, "device",
            t_launch=fl.t_launch, exec_s=device_s, host_s=fl.host_s,
        )

    def _resolve_bits(
        self, staged, out, reason, backend, t_launch=None,
        exec_s=0.0, host_s=0.0,
    ) -> None:
        """Resolve tickets, then account.  ``exec_s`` is the window's
        DEVICE bucket time, ``host_s`` its inline hashlib bucket time —
        a mixed window charges callers both shares separately, so
        /debug/budget's execute_s/host_s split never reports host work
        as device time."""
        for ticket, lo, n in staged:
            ticket.resolve(out[lo : lo + n])
        total = 0
        for _, _, n in staged:
            total += n
        # ledger kill switch gates the whole accounting block
        # (histogram observes + EV_BUDGET rows), same as the verify
        # plane — a dark ledger costs one flag check here
        if libdevledger.enabled():
            m = libmetrics.node_metrics()
            plane = libdevledger.PLANE_HASH
            exec_ns = int(exec_s * 1e9)
            host_ns = int(host_s * 1e9)
            if exec_ns + host_ns > 0:
                libdevledger.note_window_time(plane, exec_ns + host_ns)
            anchor = (
                t_launch if t_launch is not None else time.perf_counter()
            )
            bw = bx = 0  # FSM-adjacent (merkle/mempool) wait/exec sums
            for ticket, lo, n in staged:
                wait_ns = int((anchor - ticket.t_submit) * 1e9)
                if wait_ns < 0:
                    wait_ns = 0
                dev_share = exec_ns * n // total if total else 0
                host_share = host_ns * n // total if total else 0
                cid = ticket.caller
                libdevledger.note_resolve(
                    plane, cid, n, wait_ns, dev_share, host_share
                )
                m.device_queue_wait.labels(
                    "hash", libdevledger.caller_name(cid)
                ).observe(wait_ns / 1e9)
                if cid in libdevledger.BUDGET_HASH_CALLERS:
                    bw += wait_ns
                    bx += dev_share + host_share
            if bw or bx:
                libhealth.record(libhealth.EV_BUDGET, 0, plane, bw, bx)
        if libhealth.enabled():
            libhealth.record(
                libhealth.EV_HASH,
                a=total,
                b=1 if backend == "device" else 0,
            )
        if libtrace.enabled():
            libtrace.event(
                "hash.flush",
                reason=reason,
                backend=backend,
                lanes=total,
                tickets=len(staged),
            )

    def _rescue_inflight(self, fl: _Inflight) -> None:
        """Hashlib-resolve an in-flight window's still-undone tickets
        (executor fault after dispatch, or shutdown with the executor
        wedged); done() gates make racing a live executor benign."""
        for ticket, lanes in fl.groups:
            if ticket.done():
                continue
            try:
                ticket.resolve(
                    [hashlib.sha256(m).digest() for m in lanes]
                )
            except Exception as e:
                ticket.fail(e)

    def _resolve_group_host(self, group) -> None:
        ticket, msgs = group
        if ticket.done():
            return
        try:
            ticket.resolve(
                [hashlib.sha256(bytes(m)).digest() for m in msgs]
            )
        except Exception as e:
            ticket.fail(e)


def fl_msg(fl: _Inflight, i: int) -> bytes:
    """Window-ordered message i of an in-flight window, recovered from
    the per-ticket wire copies (the fallback hash source)."""
    for _, lanes in fl.groups:
        if i < len(lanes):
            return lanes[i]
        i -= len(lanes)
    raise IndexError(i)


# -- process-wide routing switch ------------------------------------------

_ACTIVE: list[HashCoalescer] = []


def push_active(co: HashCoalescer) -> None:
    """Install ``co`` as the process-wide routed hash plane (node boot)."""
    _ACTIVE.append(co)


def pop_active(co: HashCoalescer) -> None:
    for i in range(len(_ACTIVE) - 1, -1, -1):
        if _ACTIVE[i] is co:
            del _ACTIVE[i]
            return


def active() -> HashCoalescer | None:
    """The routed plane, or None when hashing is unrouted."""
    for co in reversed(tuple(_ACTIVE)):
        if co.routable():
            return co
    return None


def configured_mode() -> str:
    """COMETBFT_TPU_HASH: "auto" (default; the node starts a plane only
    on accelerator backends), "1"/"on" force, "0" off."""
    v = os.environ.get("COMETBFT_TPU_HASH", "auto").lower()
    if v in ("1", "on", "true", "yes"):
        return "on"
    if v in ("0", "off", "false", "no"):
        return "off"
    return "auto"


def node_wants_hashplane() -> bool:
    """Whether a booting node should start a HashCoalescer."""
    from ..libs.accel import plane_wanted

    return plane_wanted(configured_mode())


def _routed_device() -> HashCoalescer | None:
    """The routed plane IF it could serve device windows; None
    otherwise. Every routed helper funnels through this gate so a
    device-less container never pays a ticket round trip for work
    hashlib does optimally."""
    co = active()
    if co is not None and co.device_capable():
        return co
    return None


def hash_bytes(bz: bytes) -> bytes:
    """Single-message SHA-256, coalesced when it can win.

    THE drop-in for ``tmhash.sum`` on the cross-caller hot paths
    (mempool tx keys, PartSet leaf verification): identical digests,
    and any routing failure falls back to the host hash — never to a
    different answer. Messages under :data:`_SUM_ROUTE_MIN_BYTES` skip
    the queue (a one-block hashlib call beats any round trip).
    """
    if len(bz) >= _SUM_ROUTE_MIN_BYTES:
        co = _routed_device()
        if co is not None:
            digests = co.try_hash_many([bz])
            if digests is not None and len(digests) == 1:
                return digests[0]
    return hashlib.sha256(bz).digest()


def hash_many(msgs) -> list[bytes]:
    """Batch SHA-256 over independent messages, device-routed when the
    batch can actually win there (enough total work AND at least one
    block bucket reaching its device cut on this batch's own lanes —
    :meth:`HashCoalescer.batch_worth_routing`); host hashlib otherwise.
    Digest-identical either way."""
    if msgs:
        co = _routed_device()
        if co is not None and co.batch_worth_routing(msgs):
            digests = co.try_hash_many(msgs)
            if digests is not None and len(digests) == len(msgs):
                return digests
    return [hashlib.sha256(bytes(m)).digest() for m in msgs]


