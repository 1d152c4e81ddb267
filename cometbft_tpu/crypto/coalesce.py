"""Cross-caller verify coalescer: micro-batched, double-buffered device
launches for the steady-state vote path.

Without this module the TPU is only reachable from whole-commit
verification: an individually-gossiped vote carries ONE signature, one
signature can never cross the host/device crossover
(crypto/batch.host_batch_threshold), so a realistic 100-200-validator
set verifies every steady-state vote serially on the host. Committee-
based-consensus measurements show per-vote EdDSA verification
dominating vote processing and batch verification recovering most of it
(arXiv:2302.00418); pipelined hardware verification engines get their
throughput from keeping the verifier FED with coalesced work rather
than per-request dispatch (arXiv:2112.02229). This module is that
feeder for the verify kernel:

* concurrent callers — vote admission (types/vote_set.py), the
  proposal-signature check (consensus/state.py), evidence/light single
  verifies (types/vote.py routes them all), and sub-crossover batch
  verifiers (crypto/batch.py) — submit signature lanes and block on a
  per-submit ticket. A batch smaller than a window always shares one
  when a coalescer is routed; whether the WINDOW runs on the device is
  decided by its lanes (crypto/batch.host_batch_threshold, static);
* the executor thread coalesces lanes into fixed-shape-bucket device
  micro-batches (the same bucket discipline as every other launch —
  the no-recompile guard stays green), flushed by a size threshold
  (COMETBFT_TPU_COALESCE_MAX_LANES) or a small deadline window
  (COMETBFT_TPU_COALESCE_WINDOW_US);
* windows pipeline through the ``verify_bytes_async`` /
  ``verify_rsk_async`` split plus a dedicated readback drain thread:
  the host-side pack + arena lookup of window N+1 overlaps the device
  execute of window N, and window N's d2h readback materializes on the
  drain thread while N+1 executes — under sustained load the device
  never idles between launches and the per-window cost approaches
  max(execute, readback) instead of their sum. The drain is strictly
  FIFO (tickets resolve in submission order) and the executor blocks
  at the COMETBFT_TPU_COALESCE_INFLIGHT depth bound (default 2, the
  classic double buffer);
* steady-state lanes are index-only: the consensus FSM prestages the
  validator set (crypto/batch.prestage_validators), so a window whose
  signers are arena-resident ships 96 B of R|S|kneg plus a 2-byte slot
  per lane through ``verify_rsk_async``;
* host fallback is clean: device absent -> windows run the native host
  RLC batch (still one MSM for the whole window — coalescing wins on
  host too); sub-``min_device_lanes`` windows run host; shutdown
  drains every pending ticket before ``stop()`` returns; an absent or
  stopped coalescer leaves callers on their unrouted paths.

Behavioral identity: a lane's verdict is computed by the same kernels /
host verifiers as every other batch path, so admission decisions are
bit-identical to ``pub_key.verify_signature``; an exception raised
while staging one submit's lanes fails only that submit's ticket.

Locking: ``crypto.coalesce._mtx`` guards the pending queue — the flush
path pops a window under it and releases it before pack, dispatch, the
materializing readback, and ticket resolution; ``crypto.coalesce.
_rb_mtx`` guards only the executor->drain handoff (the drain pops
under it and releases it before the readback). Neither blocks on the
device while held and neither acquires an engine mutex (both asserted
edge-free by tests/test_lint_graph.py).
"""

from __future__ import annotations

import contextlib
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
from .keys import ED25519_KEY_TYPE

# Deadline window before a sub-size window flushes anyway. 500 us is
# ~an order of magnitude under the per-window device cost, so the
# deadline adds negligible latency while letting concurrent callers
# pile into one launch.
_DEFAULT_WINDOW_US = 500
# Lanes that trigger an immediate size flush (and the per-window cap).
# 1024 covers a full prevote round of a 1000-validator set in one
# launch; typical 100-200-validator windows land in the 128/256
# buckets.
_DEFAULT_MAX_LANES = 1024
# Windows below the device cutover verify on host — still ONE RLC MSM
# per window, so coalescing wins there too; the cutover is
# crypto/batch.host_batch_threshold (env pin > the accelerator's static
# seed > the module seed), which nothing a process measures moves, so a
# deployment's windows route the same way in every process. The
# knob/ctor arg pins a fixed count (tests, bench device-path probes).

# Ticket wait bound for the routed helpers. Routed callers hold engine
# mutexes while they wait (vote admission under vote_set, the proposal
# check under consensus.state), so this bound is ALSO the worst-case
# consensus stall a wedged device can inflict — it must stay near the
# round-timeout scale. On expiry the helper falls back to an unrouted
# host verify (verdict still correct, the work paid twice) and trips
# the cooldown breaker below; a device stall that outlives this bound
# therefore costs one short cooldown of host routing, never a frozen
# node. A cold compile is NOT allowed to eat this bound: windows whose
# bucket has no executable yet run on host while ops/warm compiles it
# in the background (see _launch_inner).
_RESULT_TIMEOUT_S = 5.0
# Device windows dispatched but not yet materialized, across the
# executor and the readback drain thread. 2 = the classic double
# buffer (window N materializing on the drain thread while the
# executor packs + dispatches N+1); raising it deepens the pipeline at
# the cost of more staged wire memory in flight.
_DEFAULT_MAX_INFLIGHT = 2
# How long a tripped coalescer stays unrouted before routing re-arms.
# While tripped, every caller falls back to host instantly and the
# groups already queued behind the (possibly wedged) executor are
# handed to a host rescue thread; on expiry the FIRST routed verify
# claims the half-open probe (try_verify pushes the deadline forward
# for everyone else) — probe success re-arms routing for all, another
# timeout re-trips. A dead device degrades throughput by at most one
# bounded stall per cooldown and a recovered device is picked back up
# without a node restart.
_TRIP_COOLDOWN_S = 30.0
# the backend label of everything a window does (metrics and spans)
_BACKEND = "ed25519-coalesce"


class CoalescerStoppedError(ServiceError):
    """submit() after the drain began — callers fall back to host."""


# -- per-request deadline propagation ---------------------------------------
#
# Request-scoped callers (the light-client proof service serves thousands
# of concurrent RPC clients, each with its own deadline) wrap their work
# in ``request_deadline``; every coalescer ticket wait on that thread is
# then bounded by the REQUEST's remaining budget, not just the global
# wedge bound. A deadline-capped timeout is the caller running out of
# time, not evidence of a wedged executor — it must never trip the
# breaker (that would unroute a healthy device for every other caller).

_DEADLINE_TLS = threading.local()


@contextlib.contextmanager
def request_deadline(deadline_monotonic: float):
    """Bound every coalescer wait on this thread by a monotonic deadline.

    Nested scopes tighten, never loosen: an inner deadline later than
    the enclosing one is clamped to the outer budget.
    """
    prev = getattr(_DEADLINE_TLS, "deadline", None)
    _DEADLINE_TLS.deadline = (
        deadline_monotonic if prev is None else min(prev, deadline_monotonic)
    )
    try:
        yield
    finally:
        _DEADLINE_TLS.deadline = prev


def deadline_remaining() -> float | None:
    """Seconds left in this thread's request deadline (None = unbounded).

    May be negative once the deadline has passed — callers treat <= 0
    as expired."""
    d = getattr(_DEADLINE_TLS, "deadline", None)
    if d is None:
        return None
    return d - time.monotonic()


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


def _env_opt_int(name: str) -> int | None:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return None


class _Ticket:
    """One submit()'s pending verdict.

    Resolved exactly once by the executor (or the shutdown drain) with
    either the per-lane validity bits or the exception that killed this
    submit's lanes — never the whole window's.
    """

    __slots__ = (
        "n", "caller", "t_submit", "wait_span", "route", "_done", "_bits",
        "_exc",
    )

    def __init__(self, n: int, caller: int = 0):
        self.n = n
        # caller class (libs/devledger enum) captured at submit from
        # the submitting thread's declaration — the device-time
        # ledger's attribution key
        self.caller = caller
        self.t_submit = time.perf_counter()
        # where the window that resolved this ticket ran ("device" /
        # "host"); a rescue's host verdicts leave it "host" too
        self.route = "host"
        # coalesce.queue_wait: begun here, on the submitter's thread and
        # under its innermost span (one request's spans stay one tree),
        # ended by the executor when it pops the ticket's window
        self.wait_span = libtrace.begin(
            "coalesce.queue_wait", parent=libtrace.current(), lanes=n
        )
        self._done = threading.Event()
        self._bits: list[bool] | None = None
        self._exc: BaseException | None = None

    def resolve(self, bits) -> None:
        self._bits = list(bits)
        self._done.set()

    def fail(self, exc: BaseException) -> None:
        self._exc = exc
        self._done.set()

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float | None = None) -> list[bool]:
        """Block for this submit's verdict bits.

        Callers may hold engine mutexes here (vote admission waits
        under ``vote_set``, the proposal check under
        ``consensus.state``) — the wait is sanctioned: it is bounded by
        the coalescer's flush-window deadline plus one device launch,
        it replaces equal-or-longer inline host verification under the
        same locks, and the executor thread that resolves it never
        acquires an engine mutex (tests/test_lint_graph.py pins that),
        so no lock cycle can form through it.
        """
        ok = self._done.wait(timeout)  # cometlint: disable=CLNT009 -- bounded coalescer wait: resolved within the flush-window deadline + one launch by the executor thread, which acquires no engine mutex (asserted leaf in test_lint_graph); replaces equal-or-longer inline host verification under the same caller locks
        if not ok:
            raise TimeoutError(
                "coalesced verify not resolved within "
                f"{timeout}s ({self.n} lanes)"
            )
        if self._exc is not None:
            raise self._exc
        return list(self._bits or [])


class _Inflight:
    """A dispatched-but-unmaterialized window (double-buffer slot)."""

    __slots__ = (
        "finish", "host_ok", "groups", "lanes", "reason", "prep_s",
        "wire", "t_launch", "span",
    )

    def __init__(
        self, finish, host_ok, groups, lanes, reason, prep_s, wire,
        t_launch=0.0, span=libtrace.NOP_SPAN,
    ):
        self.finish = finish  # zero-arg materializer from ops/verify
        self.host_ok = host_ok
        self.groups = groups  # [(ticket, lo, n)]
        self.lanes = lanes
        self.reason = reason
        # pack-start-to-dispatch-end seconds, banked at launch: the
        # ledger's execute time is prep + readback, NOT wall time to
        # _finish — the double buffer interleaves window N+1's collect
        # wait and pack before N materializes, and charging that idle
        # gap to the device would systematically overstate its cost
        self.prep_s = prep_s
        self.wire = wire  # (pubkeys, msgs, sigs) for fault recovery
        # window pop time: the queue-wait anchor the ledger charges
        # tickets against (submit -> launch is queueing; launch ->
        # resolve is execute)
        self.t_launch = t_launch
        self.span = span  # the window's coalesce.window span


def _window_phase(phase: str, win, lanes: int, **fields):
    """One phase of one window: ``crypto_verify_phase_seconds{phase,
    backend="ed25519-coalesce"}`` and a ``verify.<phase>`` span under
    the window's ``coalesce.window`` span, whichever thread runs it."""
    return libmetrics.TimedPhase(
        libmetrics.node_metrics().verify_phase_seconds.labels(
            phase, _BACKEND
        ),
        "verify." + phase, win, backend=_BACKEND, lanes=lanes, **fields,
    )


class VerifyCoalescer(BaseService):
    """Background verify executor coalescing single-signature callers.

    ``submit`` enqueues raw ed25519 (pubkey32, msg, sig64) lanes and
    returns a ticket; the executor thread flushes windows by size or
    deadline, double-buffering device launches. See the module
    docstring for the full design.
    """

    # how long on_stop waits for the executor before the safety net
    # takes over the remaining tickets (tests shrink this)
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
        super().__init__("VerifyCoalescer", logger)
        self.window_s = (
            window_us
            if window_us is not None
            else _env_int("COMETBFT_TPU_COALESCE_WINDOW_US", _DEFAULT_WINDOW_US)
        ) / 1e6
        self.max_lanes = max(
            1,
            max_lanes
            if max_lanes is not None
            else _env_int("COMETBFT_TPU_COALESCE_MAX_LANES", _DEFAULT_MAX_LANES),
        )
        # None = defer to the live crossover at flush time
        self.min_device_lanes: int | None = (
            min_device_lanes
            if min_device_lanes is not None
            else _env_opt_int("COMETBFT_TPU_COALESCE_MIN_DEVICE_LANES")
        )
        # None = defer to the process-wide accelerator probe
        # (libs/accel); True/False pin (tests, bench).
        self._device = device
        self._mtx = libsync.Mutex("crypto.coalesce._mtx")
        self._cv = libsync.Condition(self._mtx, name="crypto.coalesce._mtx")
        # pending groups: (ticket, pubkeys, msgs, sigs). A deque: the
        # flush pops hundreds of 1-lane groups per window while holding
        # _mtx, and list.pop(0) would shuffle the whole backlog under
        # the same lock every submit needs.
        self._pending: deque[tuple] = deque()
        self._pending_lanes = 0
        # lockfree: drain gate — locked writes, advisory fast-path reads; a stale read routes one submit to the host fallback
        self._draining = False
        # Lock-free running flag read by submit()/active(): consulting
        # BaseService.is_running there would acquire libs.service._mtx
        # under crypto.coalesce._mtx (or under caller engine mutexes)
        # and grow the lock graph for a boolean. Benign races resolve
        # to the host fallback.
        # lockfree: locked writes, advisory fast-path reads (see above)
        self._accepting = False
        # monotonic deadline until which the breaker keeps this
        # coalescer unrouted (0.0 = armed); see _TRIP_COOLDOWN_S
        # lockfree: breaker deadline — locked writes, racy reads re-check under the lock before re-arming; a stale read only delays routing one window
        self._tripped_until = 0.0
        self._thread: threading.Thread | None = None
        # -- readback drain: dispatched windows hand off to a dedicated
        # drain thread that materializes them IN SUBMISSION ORDER, so
        # the executor starts packing + dispatching window N+1 while
        # window N's d2h readback is still in flight. The depth bound
        # (max_inflight) counts queued + mid-finish windows; the
        # executor blocks at the bound so device memory in flight stays
        # bounded. _rb_mtx guards ONLY this handoff bookkeeping — the
        # drain pops under it and releases it before the materializing
        # readback and ticket resolution (same leaf contract as _mtx).
        self.max_inflight = max(
            1,
            max_inflight
            if max_inflight is not None
            else _env_int(
                "COMETBFT_TPU_COALESCE_INFLIGHT", _DEFAULT_MAX_INFLIGHT
            ),
        )
        self._rb_mtx = libsync.Mutex("crypto.coalesce._rb_mtx")
        self._rb_cv = libsync.Condition(
            self._rb_mtx, name="crypto.coalesce._rb_mtx"
        )
        self._readback: deque[_Inflight] = deque()
        self._rb_busy = 0  # windows the drain popped but hasn't finished
        self._rb_closed = False
        self._rb_alive = False
        self._rb_thread: threading.Thread | None = None
        # dispatched-but-unmaterialized windows, mirrored here (the
        # executor appends, the drain thread drops) so the rescue
        # paths can reach their tickets — a popped window is in
        # neither _pending nor any caller's hands. At most
        # max_inflight live at once (the drain depth bound).
        # lockfree: flight ring — executor appends, drain thread removes, rescues snapshot via tuple(); GIL-atomic list ops, single writer per end
        self._inflights: list[_Inflight] = []
        # the window currently inside _launch (popped from _pending,
        # not yet host-resolved or published to _inflights): same
        # single-writer mirror, so an executor wedged mid-dispatch
        # cannot take these tickets beyond the rescues' reach
        self._staging: list[tuple] | None = None
        # windows flushed / tickets accepted, for tests and /debug
        # dumps: windows < tickets means at least one window carried
        # lanes from more than one submitter — the sharing the module
        # exists for
        self.windows = 0
        self.device_windows = 0
        self.tickets = 0
        # windows kept on host because their shape was still compiling,
        # and breaker trips — both zero on a healthy warmed node
        self.cold_windows = 0
        self.trips = 0

    # -- lifecycle ---------------------------------------------------------

    def on_start(self) -> None:
        with self._mtx:
            self._draining = False
        with self._rb_mtx:
            self._rb_closed = False
            self._rb_alive = True
        rt = threading.Thread(
            target=self._drain_run, name="verify-readback", daemon=True
        )
        rt.start()
        # lockfree: start/stop lifecycle handle, written only by the thread driving the service transition
        self._rb_thread = rt
        t = threading.Thread(
            target=self._run, name="verify-coalescer", daemon=True
        )
        # accept only once the executor exists: if the spawn throws,
        # submits must keep raising (host fallback) rather than queue
        # lanes nobody will ever flush
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
        # Safety net: if the executor died (or the join timed out with
        # it wedged), resolve leftovers on host so no caller hangs —
        # including a window the executor popped and dispatched but
        # never materialized (wedged in a device stall). Racing the
        # still-alive executor is benign: done() gates both sides and a
        # double resolution carries identical verdicts.
        with self._mtx:
            leftovers, self._pending = self._pending, deque()
            self._pending_lanes = 0
        for group in leftovers:
            self._resolve_group_host(group)
        # a window the wedged executor popped but never dispatched
        # (stuck inside _launch) is visible only through the staging
        # slot; don't clear it — the executor owns the slot, and
        # done() gates make a late double resolution benign
        for group in self._staging or ():
            self._resolve_group_host(group)
        for fl in tuple(self._inflights):
            self._rescue_inflight(fl)
            self._drop_inflight(fl)

    # -- submission --------------------------------------------------------

    def submit(self, pubkeys, msgs, sigs) -> _Ticket:
        """Queue raw ed25519 lanes; returns the ticket with their bits.

        ``pubkeys[i]`` is the 32-byte key encoding (``PubKey.data``),
        not a key object — the wire format the packers consume.
        Raises :class:`CoalescerStoppedError` once the drain has begun
        (callers fall back to their unrouted verify).
        """
        return self.submit_many([(pubkeys, msgs, sigs)])[0]

    def submit_many(self, groups) -> list[_Ticket]:
        """Batch-submit several lane groups as ONE queue transaction.

        ``groups`` is a sequence of ``(pubkeys, msgs, sigs)`` triples;
        returns one ticket per group, in order. All groups land in the
        pending queue under a single mutex acquisition with a single
        executor wake-up, so a multi-window caller (an oversized batch
        chunked by :meth:`try_verify`, or the light service issuing a
        whole commit's lanes) cannot interleave with other submitters
        mid-batch — its chunks pack into consecutive windows. Raises
        :class:`CoalescerStoppedError` once the drain has begun.
        """
        tickets: list[_Ticket] = []
        staged: list[tuple] = []
        cid = libdevledger.current_caller()
        for pks, ms, ss in groups:
            t = _Ticket(len(pks), cid)
            tickets.append(t)
            if t.n == 0:
                t.resolve([])
            else:
                staged.append((t, pks, ms, ss))
        if not staged:
            return tickets
        with self._mtx:
            # the breaker gates ROUTING (active()/_claim_probe), not
            # direct submits: a tripped-but-alive executor still
            # flushes, and a wedged one's queue is drained by the next
            # trip's host rescue, so accepted lanes never leak
            if self._draining or not self._accepting:
                raise CoalescerStoppedError(self._name)
            for g in staged:
                self._pending.append(g)
                self._pending_lanes += g[0].n
            self.tickets += len(staged)
            self._cv.notify_all()
        return tickets

    def try_verify(
        self, pubkeys, msgs, sigs, routes: list | None = None
    ) -> list[bool] | None:
        """submit + wait with a clean not-routed signal.

        ``routes``, where given, takes each served ticket's route
        ("device" / "host": where its window ran).

        Returns the per-lane bits, or None when the coalescer cannot
        serve the request (stopped, ticket failed, wait expired) — the
        caller then runs its unrouted path, so routing through here
        never changes a verdict. Groups larger than one window are
        chunked into ``max_lanes``-sized tickets submitted as one batch
        (:meth:`submit_many`) and reassembled in order. Waits honor the
        thread's :func:`request_deadline` budget when one is set; a
        deadline-capped expiry returns None WITHOUT tripping the
        breaker — the caller ran out of time, the executor is fine.
        """
        rem = deadline_remaining()
        if rem is not None and rem <= 0:
            return None
        if not self._claim_probe():
            # breaker cooldown in force (or another caller holds the
            # half-open probe): fall back without queueing anything
            return None
        n = len(pubkeys)
        if n <= self.max_lanes:
            groups = [(pubkeys, msgs, sigs)]
        else:
            groups = [
                (pubkeys[i : i + self.max_lanes],
                 msgs[i : i + self.max_lanes],
                 sigs[i : i + self.max_lanes])
                for i in range(0, n, self.max_lanes)
            ]
        try:
            tickets = self.submit_many(groups)
        except ServiceError:
            return None
        bits: list[bool] = []
        for ticket in tickets:
            wait_s = _RESULT_TIMEOUT_S
            capped = False
            rem = deadline_remaining()
            if rem is not None and rem < wait_s:
                wait_s, capped = max(rem, 0.0), True
            try:
                bits.extend(ticket.result(wait_s))
                if routes is not None:
                    routes.append(ticket.route)
            except TimeoutError:
                # A ticket outliving the FULL result bound means the
                # executor is wedged (stuck dispatch) or a transient
                # outlasted the bound. Trip the cooldown
                # breaker so subsequent callers fall back to host
                # instantly instead of each paying the full bound under
                # engine mutexes — one wedged device must degrade
                # throughput, not freeze consensus. Already-queued
                # callers wait at most one more bound; stop()'s safety
                # net still drains every ticket; a recovered device
                # re-routes after the cooldown. A deadline-capped wait
                # expiring is NOT executor evidence: no trip.
                if not capped:
                    self._trip()
                return None
            except Exception:
                return None
        self._rearm()
        return bits

    def routable(self) -> bool:
        """Accepting submits and not inside a breaker cooldown (an
        expired cooldown counts as routable). PURE query — active()
        and its is-a-coalescer-routed callers must never consume the
        single-flight probe; only try_verify claims it."""
        return self._accepting and (
            self._tripped_until == 0.0
            or time.monotonic() >= self._tripped_until
        )

    def _claim_probe(self) -> bool:
        """True when a routed verify may proceed: breaker armed, or
        this caller atomically won the post-cooldown half-open probe.
        Called ONLY from try_verify — the one place that can cash the
        probe in. Winning pushes the deadline one more cooldown
        forward, so concurrent callers keep falling back until the
        probe's verdict: a successful try_verify re-arms for everyone
        (:meth:`_rearm`), another timeout re-trips."""
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
        """Unroute a wedged coalescer for one breaker cooldown.

        Groups already queued are handed to a host rescue thread: a
        wedged executor may never collect them, and they must not sit
        unresolved for a whole cooldown (or leak until shutdown).
        Overlap with a merely-slow executor is benign — resolution is
        done()-gated and verdicts are identical."""
        leftovers: deque | None = None
        with self._mtx:
            if self._draining or not self._accepting:
                return
            self._tripped_until = time.monotonic() + _TRIP_COOLDOWN_S
            self.trips += 1
            if self._pending:
                leftovers, self._pending = self._pending, deque()
                self._pending_lanes = 0
            self._cv.notify_all()
        if leftovers:
            groups = tuple(leftovers)
            threading.Thread(
                target=lambda: [
                    self._resolve_group_host(g) for g in groups
                ],
                name="verify-coalescer-rescue",
                daemon=True,
            ).start()
        # health hook: the wedged-coalescer watchdog converts this
        # notice into a trip + black-box bundle (no lock held here)
        libhealth.note_breaker_trip()
        if self.logger is not None:
            self.logger.error(
                "verify coalescer unresponsive; unrouted for cooldown",
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
                            # published BEFORE the drain handoff: if
                            # the finish faults or wedges, this
                            # window's tickets must be reachable by
                            # the rescues
                            self._inflights.append(handle)
                            self._hand_to_drain(handle)
                        self._staging = None
                    if reason == "quit":
                        return
                except Exception:
                    # The loop must survive anything: pending tickets
                    # are resolved by _launch/_finish's own fallbacks;
                    # anything still queued drains on the next
                    # iteration (or the on_stop safety net). A staged
                    # or in-flight window's tickets live NOWHERE else —
                    # rescue the staging slot and every tracked window
                    # (every drain-queue slot) before dropping the
                    # handles, or their submitters stall the full
                    # result timeout.
                    try:
                        import traceback

                        traceback.print_exc()
                    except Exception:
                        pass  # closed stderr must not kill the loop
                    staged, self._staging = self._staging, None
                    for group in staged or ():
                        self._resolve_group_host(group)
                    for fl in tuple(self._inflights):
                        self._rescue_inflight(fl)
                        self._drop_inflight(fl)
        finally:
            # The executor is gone for good — normal drain exit or a
            # death nothing above could catch. Let the readback drain
            # finish the windows already handed to it (submission-order
            # resolution with real device verdicts), then make sure no
            # ticket is left for callers to time out on: stop
            # accepting, then drain every slot a ticket can live in
            # (pending queue, staging window, drain-queue windows).
            # Everything here is done()-gated/idempotent, so overlap
            # with on_stop's safety net is benign.
            self._close_readback()
            rt = self._rb_thread
            if rt is not None and rt is not threading.current_thread():
                rt.join(timeout=self._JOIN_TIMEOUT_S)
            with self._mtx:
                self._accepting = False
                leftovers, self._pending = self._pending, deque()
                self._pending_lanes = 0
            staged, self._staging = self._staging, None
            for group in staged or ():
                self._resolve_group_host(group)
            for group in leftovers:
                self._resolve_group_host(group)
            for fl in tuple(self._inflights):
                self._rescue_inflight(fl)
                self._drop_inflight(fl)

    # -- the readback drain ------------------------------------------------

    def _hand_to_drain(self, fl: _Inflight) -> None:
        """Queue a dispatched window for the readback drain, blocking at
        the in-flight depth bound so execute of window N+1 overlaps the
        d2h of window N without letting the pipeline run unboundedly
        ahead. Falls back to finishing inline if the drain thread is
        gone (it must never strand a dispatched window)."""
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
        """Materialize dispatched windows in submission order.

        FIFO over the handoff queue: window N's tickets resolve before
        window N+1's even when N+1's device result lands first — routed
        callers observe the same ordering the synchronous executor
        gave them. A finish fault falls back to the host rescue for
        that window only; the loop survives anything.
        """
        try:
            while True:
                with self._rb_mtx:
                    while not self._readback and not self._rb_closed:
                        self._rb_cv.wait(0.2)
                    if not self._readback:
                        return  # closed and empty
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
            # drain death (normal close or a fault nothing above
            # caught): no handed-off window may be left unresolved,
            # and a depth-blocked executor must wake and notice
            # _rb_alive is down (it then finishes windows inline)
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
        except ValueError:  # already rescued+dropped by on_stop
            pass

    def _collect(self, block: bool):
        """Pop one flush window from the pending queue.

        Returns ``(groups, lanes, reason)``; groups is None for an
        empty poll. reason: "size" | "deadline" | "drain" when a window
        was popped, "idle" (non-blocking poll found nothing — the
        caller materializes its in-flight window), "quit" (draining and
        empty). The deadline anchors at the OLDEST pending ticket's
        submit time, so a request never waits more than one window.
        """
        with self._mtx:
            if block:
                while not self._pending and not self._draining:
                    self._cv.wait(0.2)
            if not self._pending:
                return None, 0, ("quit" if self._draining else "idle")
            first_t = self._pending[0][0].t_submit
            while self._pending_lanes < self.max_lanes and not self._draining:
                rem = self.window_s - (time.perf_counter() - first_t)
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
            self._pending_lanes -= lanes
            return groups, lanes, reason

    def _device_ok(self) -> bool:
        if self._device is not None:
            return self._device
        # live peek only: the flush path runs every window and must
        # never pay (or hang in) jax backend init — node boot's
        # accelerator_backend() probe brings the backend up
        from ..libs.accel import accelerator_backend_live

        return accelerator_backend_live()

    def _stage(self, groups):
        """Flatten groups into wire lists; a lane that cannot coerce to
        bytes fails ONLY its own submit's ticket."""
        pubkeys: list[bytes] = []
        msgs: list[bytes] = []
        sigs: list[bytes] = []
        staged: list[tuple] = []  # (ticket, lo, n)
        for ticket, pks, ms, ss in groups:
            try:
                lanes = [
                    (bytes(pk), bytes(m), bytes(s))
                    for pk, m, s in zip(pks, ms, ss)
                ]
                if len(lanes) != ticket.n:
                    raise ValueError(
                        f"lane count mismatch: {len(lanes)} != {ticket.n}"
                    )
            except Exception as e:
                ticket.fail(e)
                continue
            lo = len(pubkeys)
            for pk, m, s in lanes:
                pubkeys.append(pk)
                msgs.append(m)
                sigs.append(s)
            staged.append((ticket, lo, ticket.n))
        return pubkeys, msgs, sigs, staged

    def _launch(self, groups, lanes, reason) -> _Inflight | None:
        """Stage + dispatch one window. Device windows return an
        in-flight handle (materialized by the NEXT loop turn — the
        double buffer); host windows resolve synchronously and return
        None."""
        t_pop = time.perf_counter()
        # the window's life: pop on this thread -> its tickets resolved
        # (on the drain thread for a device window); pack, dispatch,
        # readback or fallback are its children
        win = libtrace.begin(
            "coalesce.window", reason=reason, backend=_BACKEND
        )
        hist = libmetrics.node_metrics().coalesce_queue_wait_seconds
        for ticket, *_ in groups:
            sp = ticket.wait_span
            sp.end(window=win.id)
            hist.observe(
                sp.dur_ns / 1e9 if sp is not libtrace.NOP_SPAN
                else t_pop - ticket.t_submit
            )
        libdevledger.exec_begin(libdevledger.PLANE_VERIFY)
        try:
            return self._launch_inner(groups, lanes, reason, t_pop, win)
        finally:
            # the executor-busy marker brackets staging, pack, dispatch
            # AND the inline host resolve — the occupancy view's
            # overlap estimator reads it from the readback drain
            libdevledger.exec_end(libdevledger.PLANE_VERIFY)

    def _launch_inner(
        self, groups, lanes, reason, t_pop, win
    ) -> _Inflight | None:
        pubkeys, msgs, sigs, staged = self._stage(groups)
        if not staged:
            # every group failed staging: nothing flushed, nothing to
            # count — a window of all-malformed lanes must not inflate
            # the flush/lane metrics
            win.end(lanes=0, tickets=0, route="none")
            return None
        n = len(pubkeys)
        m = libmetrics.node_metrics()
        m.coalesce_window_lanes.observe(n)
        m.coalesce_flushes.labels(reason).inc()
        self.windows += 1
        use_device = self._device_ok()
        if use_device:
            # crossover only matters once the device gate passed: a
            # device=False pin must keep the flush path off jax entirely
            cut = self.min_device_lanes
            if cut is None:
                from . import batch as crypto_batch

                cut = crypto_batch.host_batch_threshold()
            use_device = n >= cut
        if use_device and self._device is None:
            # A compile must never sit inside the routed ticket bound
            # (_RESULT_TIMEOUT_S): a window whose shape has no
            # executable yet runs on host while ops/warm compiles it.
            # An explicit device=True pin (tests, bench probes — they
            # wait on tickets without that bound) compiles inline.
            from ..ops import verify as ov

            if not ov.window_ready(pubkeys):
                use_device = False
                self.cold_windows += 1
        if use_device:
            t0 = time.perf_counter()
            try:
                from ..ops import verify as ov

                with _window_phase("pack", win, n, route="device") as ph:
                    # rows and slots at the launch's width: no padding
                    # copy between the pack and the launch
                    width = ov.bucket_size(n)
                    buf, host_ok = ov.pack_bytes(pubkeys, msgs, sigs, width)
                    hit = (
                        ov._PUBKEY_CACHE.lookup(pubkeys, width)
                        if ov._cache_enabled()
                        else None
                    )
                    arena = "hit" if hit is not None else "bypass"
                    ph.set(arena=arena)
                with _window_phase(
                    "dispatch", win, n, route="device", arena=arena
                ):
                    if hit is not None:
                        idxs, arena_buf, arena_ok = hit
                        finish = ov.verify_rsk_async(
                            buf[32:], idxs, arena_buf, arena_ok, n,
                            _BACKEND,
                        )
                    else:
                        finish = ov.verify_bytes_async(buf, n, _BACKEND)
                self.device_windows += 1
                libdevledger.note_window(
                    libdevledger.PLANE_VERIFY, n, True
                )
                return _Inflight(
                    finish, host_ok, staged, n, reason,
                    time.perf_counter() - t0, (pubkeys, msgs, sigs),
                    t_launch=t_pop, span=win,
                )
            except Exception:
                # device staging/dispatch fault: clean host fallback
                # for the whole window
                import traceback

                traceback.print_exc()
        libdevledger.note_window(libdevledger.PLANE_VERIFY, n, False)
        self._resolve_host(pubkeys, msgs, sigs, staged, reason, t_pop, win)
        return None

    def _finish(self, fl: _Inflight) -> None:
        """Materialize a dispatched window and resolve its tickets."""
        t0 = time.perf_counter()
        t0_ns = time.monotonic_ns()
        busy0 = libdevledger.exec_busy_ns(libdevledger.PLANE_VERIFY)
        try:
            with _window_phase(
                "readback", fl.span, fl.lanes, route="device"
            ):
                device_ok = fl.finish()
        except Exception:
            # device-side fault at materialization: clean host fallback
            # for the window (tickets resolve with host verdicts, not
            # errors — routing must never change an answer)
            import traceback

            traceback.print_exc()
            pubkeys, msgs, sigs = fl.wire
            self._resolve_host(
                pubkeys, msgs, sigs, fl.groups, fl.reason, fl.t_launch,
                fl.span,
            )
            return
        now = time.perf_counter()
        libdevledger.note_readback(
            libdevledger.PLANE_VERIFY, t0_ns, busy0
        )
        valid = device_ok & fl.host_ok
        self._resolve_bits(
            fl.groups, valid, fl.reason, "device",
            t_launch=fl.t_launch, exec_s=fl.prep_s + (now - t0),
            win=fl.span,
        )

    def _resolve_host(
        self, pubkeys, msgs, sigs, staged, reason, t_launch=None,
        win=libtrace.NOP_SPAN,
    ) -> None:
        """Host-window verdicts: one native RLC batch for the whole
        window (coalescing still wins on host), sequential per-lane
        verify if the batch engine throws."""
        n = len(pubkeys)
        with _window_phase("fallback", win, n, route="host") as ph:
            try:
                from . import host_batch

                bitmap = host_batch.verify_many(pubkeys, msgs, sigs)
            except Exception:
                from . import fast25519

                bitmap = []
                for pk, m, s in zip(pubkeys, msgs, sigs):
                    try:
                        bitmap.append(bool(fast25519.verify_one(pk, m, s)))
                    except Exception:
                        bitmap.append(False)
        self._resolve_bits(
            staged, bitmap, reason, "host", t_launch=t_launch,
            exec_s=ph.dur_ns / 1e9, win=win,
        )

    def _resolve_bits(
        self, staged, bits, reason, backend, t_launch=None, exec_s=0.0,
        win=libtrace.NOP_SPAN,
    ) -> None:
        m = libmetrics.node_metrics()
        now = time.perf_counter()
        total = 0
        for _, _, n in staged:
            total += n
        m.coalesce_lanes.labels(backend).inc(total)
        exec_ns = int(exec_s * 1e9)
        device = backend == "device"
        plane = libdevledger.PLANE_VERIFY
        # the WHOLE accounting block rides the ledger kill switch:
        # COMETBFT_TPU_LEDGER=0 promises a single flag check, so the
        # per-ticket histogram observes (two mutex hops each) and the
        # EV_BUDGET ring rows go dark with the columns
        ledger_on = libdevledger.enabled()
        if ledger_on and exec_ns > 0:
            libdevledger.note_window_time(plane, exec_ns)
        # queue-wait anchor: the window pop — submit->pop is queueing,
        # pop->resolve is execute (charged pro-rata by lane count so
        # per-caller shares reconcile to the window total within
        # integer floor error, < one ns per ticket)
        anchor = t_launch if t_launch is not None else now
        bw = bx = 0  # consensus-caller wait/exec sums (the budget row)
        for ticket, lo, n in staged:
            ticket.route = backend
            ticket.resolve([bool(b) for b in bits[lo : lo + n]])
            m.coalesce_wait_seconds.observe(now - ticket.t_submit)
            if not ledger_on:
                continue
            wait_ns = int((anchor - ticket.t_submit) * 1e9)
            if wait_ns < 0:
                wait_ns = 0
            share = exec_ns * n // total if total else 0
            cid = ticket.caller
            libdevledger.note_resolve(
                plane, cid, n, wait_ns,
                share if device else 0, 0 if device else share,
            )
            m.device_queue_wait.labels(
                "verify", libdevledger.caller_name(cid)
            ).observe(wait_ns / 1e9)
            if cid in libdevledger.BUDGET_VERIFY_CALLERS:
                bw += wait_ns
                bx += share
        if bw or bx:
            # the per-height budget overlay: consensus-caller verify
            # queue+execute time, window-assigned to a height by the
            # budget decomposition (libs/health.budget)
            libhealth.record(
                libhealth.EV_BUDGET, 0, plane, bw, bx
            )
        win.end(lanes=total, tickets=len(staged), route=backend)

    def _rescue_inflight(self, fl: _Inflight) -> None:
        """Resolve an in-flight window's still-undone tickets on host.

        Called when the window's materialization can no longer be
        trusted to happen (executor fault after dispatch, or shutdown
        with the executor wedged). Verdicts come from the retained wire
        copy, so rescued callers get the same answers a clean
        materialization would have produced; a ticket the executor
        resolved concurrently is skipped (done() gates), and one whose
        host re-verify also fails gets the exception instead of a hang.
        """
        pubkeys, msgs, sigs = fl.wire
        for ticket, lo, n in fl.groups:
            if ticket.done():
                continue
            try:
                from . import host_batch

                ticket.resolve(host_batch.verify_many(
                    pubkeys[lo : lo + n],
                    msgs[lo : lo + n],
                    sigs[lo : lo + n],
                ))
            except Exception as e:
                ticket.fail(e)

    def _resolve_group_host(self, group) -> None:
        """Per-group host resolution for the trip-time rescue, the
        shutdown safety net, and post-fault recovery; done()-gated, so
        overlap with a still-alive executor is benign."""
        ticket, pks, ms, ss = group
        if ticket.done():
            return
        try:
            from . import host_batch

            ticket.resolve(host_batch.verify_many(
                [bytes(p) for p in pks],
                [bytes(x) for x in ms],
                [bytes(s) for s in ss],
            ))
        except Exception as e:
            ticket.fail(e)


# -- process-wide routing switch ------------------------------------------
#
# A stack, like libs/metrics' node-metrics stack: in-process multi-node
# test nets push one coalescer per node; the most recent running one
# receives routed verifies, pops are by identity so out-of-order node
# shutdown cannot evict a live node's coalescer.

_ACTIVE: list[VerifyCoalescer] = []


def push_active(co: VerifyCoalescer) -> None:
    """Install ``co`` as the process-wide routed coalescer (node boot)."""
    _ACTIVE.append(co)


def pop_active(co: VerifyCoalescer) -> None:
    for i in range(len(_ACTIVE) - 1, -1, -1):
        if _ACTIVE[i] is co:
            del _ACTIVE[i]
            return


def active() -> VerifyCoalescer | None:
    """The routed coalescer, or None when verification is unrouted."""
    # snapshot: a concurrent pop_active (another node shutting down)
    # must not shrink the list under this walk
    for co in reversed(tuple(_ACTIVE)):
        if co.routable():
            return co
    return None


def breaker_open() -> bool:
    """True while ANY pushed coalescer sits inside a breaker cooldown —
    the health engine's `health_breaker_open` SLI. Pure query (same
    contract as routable(): never consumes the half-open probe)."""
    now = time.monotonic()
    for co in tuple(_ACTIVE):
        t = co._tripped_until
        if t and now < t:
            return True
    return False


def configured_mode() -> str:
    """COMETBFT_TPU_COALESCE: "auto" (default; the node starts a
    coalescer only on accelerator backends), "1"/"on" force, "0" off."""
    v = os.environ.get("COMETBFT_TPU_COALESCE", "auto").lower()
    if v in ("1", "on", "true", "yes"):
        return "on"
    if v in ("0", "off", "false", "no"):
        return "off"
    return "auto"


def node_wants_coalescer() -> bool:
    """Whether a booting node should start a VerifyCoalescer."""
    from ..libs.accel import plane_wanted

    return plane_wanted(configured_mode())


def eligible(pub_key) -> bool:
    """Keys the coalescer can carry (ed25519 — the device wire format)."""
    return (
        getattr(pub_key, "type", None) == ED25519_KEY_TYPE
        and len(getattr(pub_key, "data", b"") or b"") == 32
    )


def verify_signature(pub_key, msg: bytes, signature: bytes) -> bool:
    """Single-signature verify, coalesced when a coalescer is routed.

    THE drop-in for ``pub_key.verify_signature`` on the steady-state
    paths (vote admission, proposal checks, evidence/light): identical
    verdicts, and any routing failure falls back to the unrouted host
    verify — never to a different answer.
    """
    co = active()
    if co is not None and eligible(pub_key):
        bits = co.try_verify([pub_key.data], [msg], [signature])
        if bits is not None and len(bits) == 1:
            return bool(bits[0])
    return pub_key.verify_signature(msg, signature)


def verify_bytes(pubkeys, msgs, sigs) -> list[bool] | None:
    """Batch helper for crypto/batch.py's sub-crossover cutover: raw
    32-byte ed25519 keys -> per-lane bits, or None when unrouted."""
    co = active()
    if co is None:
        return None
    return co.try_verify(pubkeys, msgs, sigs)
