"""Light-client verification as a service: shared-device proof serving.

The ROADMAP's "millions of users" workload: thousands of concurrent
light clients each want skipping-verification of some commit against
their own trust root, and the dominant cost of every request is
commit-signature verification (arXiv:2410.03347 measures bisection
verification dominating committee-based light clients; arXiv:2302.00418
pins that to EdDSA commit checks). One node already owns the fast path
for exactly that work — the batched verifiers and the cross-caller
VerifyCoalescer — but only for in-process callers. ``LightService``
turns it into a service with three pillars:

* **Shared verification planes** — every request runs the standard
  light ``Client`` bisection, but its commit checks go through a
  :class:`CachedCommitVerifier` plane that delegates to
  types/validation's batched verifiers. Sub-crossover commits ride the
  routed VerifyCoalescer (crypto/coalesce), so N concurrent clients'
  trust-gap proofs pack their signature lanes into the SAME device
  windows instead of racing N separate launches.
* **Commit-verification result cache** — successful checks are cached
  by ``(kind, chain_id, height, valset_hash, commit_digest)`` with TTL
  + LRU bounds, and concurrent verifications of the same key are
  single-flighted (one underlying verify; waiters share its outcome).
  Failures are NEVER cached (negative-result poisoning protection): a
  transient fault or an attacker-fed bad commit can only cost its own
  request, never poison a later honest one — and a failed verification
  can never be replayed as a cached success.
* **Backpressure + deadlines** — at most ``max_inflight`` requests
  verify at once; up to ``max_queue`` more wait for a slot and anything
  beyond that is rejected immediately (queue-depth rejection). Each
  request carries a deadline that propagates through
  ``crypto/coalesce.request_deadline`` into every coalescer ticket wait
  and provider fetch, so a deadline-exceeded request unwinds cleanly —
  no leaked in-flight slot, no post-deadline device work.

Per-request isolation: each request verifies relative to the CLIENT's
trust root in a throwaway :class:`~cometbft_tpu.light.store.MemStore`,
so one client's root never widens another's trust — the shared state is
only the (verdict-identical) commit result cache. Results are therefore
bit-identical to a standalone ``Client`` run with the same options.

The RPC surface is ``light_verify`` / ``light_status`` on
rpc/core/routes.py, served by the existing jsonrpc server; the node
boots the service behind ``COMETBFT_TPU_LIGHT`` (node/node.py).

Locking: ``light.service._mtx`` guards admission (in-flight/queue
counters; its condition wait is the sanctioned own-lock case) and
``light.service._cache_mtx`` guards the result cache. The cache lock is
a LEAF — nothing is acquired and nothing blocks under it (asserted
edge-free in tests/test_lint_graph.py like ``libs.trace._mtx``): the
single-flight leader verifies OUTSIDE it and publishes code-last.
"""

from __future__ import annotations

import os
import struct
import threading
import time
from collections import OrderedDict

from ..crypto import coalesce as crypto_coalesce
from ..crypto import tmhash
from ..libs import devledger as libdevledger
from ..libs import metrics as libmetrics
from ..libs import sync as libsync
from ..libs import trace as libtrace
from ..libs.service import BaseService
from ..types import serialization as ser
from ..types.validation import (
    DEFAULT_TRUST_LEVEL,
    Fraction,
    verify_commit_light,
    verify_commit_light_trusting,
)
from . import verifier as light_verifier
from .client import Client, TrustOptions
from .errors import LightClientError
from .provider import Provider
from .store import MemStore

SECOND_NS = light_verifier.SECOND_NS

_DEFAULT_MAX_INFLIGHT = 64
_DEFAULT_MAX_QUEUE = 256
_DEFAULT_DEADLINE_S = 10.0
_DEFAULT_CACHE_SIZE = 4096
_DEFAULT_CACHE_TTL_S = 600.0
_DEFAULT_TRUSTING_PERIOD_NS = 14 * 24 * 3600 * SECOND_NS
# poll granularity of a single-flight waiter between outcome checks
_FLIGHT_WAIT_S = 0.05


class LightServiceError(LightClientError):
    """Base of the service's request-rejection taxonomy (the RPC layer
    maps each subclass to a distinct JSON-RPC error code)."""


class ServiceBusyError(LightServiceError):
    """Backpressure rejection: in-flight AND queue bounds both full."""


class ServiceStoppedError(LightServiceError):
    """Request arrived after the drain began (or before start)."""


class DeadlineExceededError(LightServiceError):
    """The request's deadline expired before verification finished."""


# what _admit raises -> the outcome a refused request is counted under
_REJECTIONS = {
    ServiceBusyError: "rejected",
    ServiceStoppedError: "stopped",
    DeadlineExceededError: "deadline",
}


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


def configured_mode() -> str:
    """COMETBFT_TPU_LIGHT: "0"/off (default) | "1"/on — serve
    light_verify/light_status from this node."""
    v = os.environ.get("COMETBFT_TPU_LIGHT", "0").lower()
    if v in ("1", "on", "true", "yes"):
        return "on"
    return "off"


def node_wants_light_service() -> bool:
    """Whether a booting node should start a LightService."""
    return configured_mode() == "on"


def _check_deadline(what: str = "") -> None:
    rem = crypto_coalesce.deadline_remaining()
    if rem is not None and rem <= 0:
        raise DeadlineExceededError(
            "request deadline exceeded" + (f" ({what})" if what else "")
        )


def _find_deadline(exc: BaseException) -> DeadlineExceededError | None:
    """Dig a DeadlineExceededError out of the wrapper chain.

    The light client wraps causes (VerificationFailedError.reason,
    BadLightBlockError.reason, __cause__/__context__) — a deadline that
    fired deep inside a commit check must still surface as a clean
    deadline rejection, not a generic verification failure."""
    seen: set[int] = set()
    stack: list = [exc]
    while stack:
        e = stack.pop()
        if not isinstance(e, BaseException) or id(e) in seen:
            continue
        seen.add(id(e))
        if isinstance(e, DeadlineExceededError):
            return e
        stack.extend(
            (getattr(e, "reason", None), e.__cause__, e.__context__)
        )
    return None


class _Flight:
    """One in-progress commit verification being single-flighted."""

    __slots__ = ("event", "ok", "exc")

    def __init__(self):
        self.event = threading.Event()
        self.ok = False
        self.exc: BaseException | None = None


class CommitResultCache:
    """TTL + LRU cache of SUCCESSFUL commit verifications, with
    single-flight dedupe of concurrent identical checks.

    Only success is ever cached: verification failures propagate to the
    requester (and to concurrent single-flight waiters of the same key
    — verification is deterministic) but leave no entry behind, so a
    fault can never be replayed and a failure can never masquerade as a
    cached success. ``now`` is injectable for TTL tests.

    The one lock, ``light.service._cache_mtx``, is a leaf: every body
    below is pure dict bookkeeping — no metric, no other lock, no
    blocking call runs under it (tests/test_lint_graph.py pins it
    edge-free like libs.trace._mtx).
    """

    def __init__(
        self,
        capacity: int = _DEFAULT_CACHE_SIZE,
        ttl_s: float = _DEFAULT_CACHE_TTL_S,
        now=time.monotonic,
    ):
        self.capacity = max(1, int(capacity))
        self.ttl_s = float(ttl_s)
        self._now = now
        self._mtx = libsync.Mutex("light.service._cache_mtx")
        self._entries: OrderedDict[tuple, float] = OrderedDict()
        self._flights: dict[tuple, _Flight] = {}
        self.hits = 0
        self.misses = 0
        self.shared = 0
        self.evictions = 0
        self.expired = 0

    def begin(self, key: tuple, recheck: bool = False):
        """One lookup step: ("hit", None) — cached success;
        ("leader", None) — this caller must verify and call done();
        ("wait", flight) — another caller is verifying this key.

        Stats count ONE outcome per logical lookup: a waiter's re-polls
        pass ``recheck=True`` so the wait state tallies nothing here
        (the resolution — shared success, shared failure, or promotion
        to leader — does the counting), and a post-wait cache hit
        counts as ``shared``, not ``hit``.
        """
        with self._mtx:
            exp = self._entries.get(key)
            if exp is not None:
                if self._now() < exp:
                    self._entries.move_to_end(key)
                    if recheck:
                        self.shared += 1
                    else:
                        self.hits += 1
                    return "hit", None
                del self._entries[key]
                self.expired += 1
            fl = self._flights.get(key)
            if fl is not None:
                return "wait", fl
            self._flights[key] = _Flight()
            self.misses += 1
            return "leader", None

    def note_shared(self) -> None:
        """A waiter resolved through the flight outcome directly."""
        with self._mtx:
            self.shared += 1

    def done(self, key: tuple, success: bool,
             exc: BaseException | None = None) -> None:
        """Publish the leader's outcome and release the flight."""
        with self._mtx:
            fl = self._flights.pop(key, None)
            if success:
                self._entries[key] = self._now() + self.ttl_s
                self._entries.move_to_end(key)
                while len(self._entries) > self.capacity:
                    self._entries.popitem(last=False)
                    self.evictions += 1
        if fl is not None:
            # outcome fields BEFORE the event: a waiter that sees the
            # event set must see a consistent verdict
            fl.ok = success
            fl.exc = exc
            fl.event.set()

    def size(self) -> int:
        with self._mtx:
            return len(self._entries)

    def stats(self) -> dict:
        with self._mtx:
            return {
                "size": len(self._entries),
                "capacity": self.capacity,
                "ttl_s": self.ttl_s,
                "hits": self.hits,
                "misses": self.misses,
                "shared": self.shared,
                "evictions": self.evictions,
                "expired": self.expired,
            }


# the columns' fixed header: tag, height, round, lanes, part-set total,
# block-hash length, part-set-hash length
_COLUMNS_HEAD = struct.Struct(">BqqIqII")
# no JSON text starts with this byte: a digest of the columns and one of
# the fallback encoding never share a preimage
_COLUMNS_TAG = 0


def _commit_digest(commit) -> bytes:
    """SHA-256 of a commit's full content (block id + every commit-sig):
    the cache key component that pins WHAT was verified.

    The preimage is the commit laid out as columns: the fixed header, the
    block hash and the part-set hash, then one column a ``CommitSig``
    field — a flag byte a lane, a length byte a lane before the joined
    addresses, a 12-byte signed timestamp a lane, a length byte a lane
    before the joined signatures. The lane count and every length are
    in it, so the layout is injective: two commits that differ in any
    field ``ser.dumps`` covers differ here. Comprehensions and joins, no
    Python statement per field per lane. A commit the columns cannot
    hold (a flag or a length past a byte, an integer past its width, a
    field of another type) is hashed from its JSON encoding, as before.

    No memo on the commit: ``Commit`` is mutable, and a kept digest
    would pin a stale key to a commit changed after its first check."""
    sigs = commit.signatures
    bid = commit.block_id
    psh = bid.part_set_header
    try:
        addrs = [cs.validator_address for cs in sigs]
        lane_sigs = [cs.signature for cs in sigs]
        columns = b"".join((
            _COLUMNS_HEAD.pack(
                _COLUMNS_TAG, commit.height, commit.round, len(sigs),
                psh.total, len(bid.hash), len(psh.hash),
            ),
            bid.hash,
            psh.hash,
            bytes([cs.block_id_flag for cs in sigs]),
            bytes(map(len, addrs)),
            b"".join(addrs),
            b"".join([cs.timestamp_ns.to_bytes(12, "big", signed=True)
                      for cs in sigs]),
            bytes(map(len, lane_sigs)),
            b"".join(lane_sigs),
        ))
    except (AttributeError, TypeError, ValueError, OverflowError,
            struct.error):
        return tmhash.sum(ser.dumps(commit))
    return tmhash.sum(columns)


class CachedCommitVerifier(light_verifier.CommitVerifier):
    """The service's shared verification plane.

    Misses delegate to the standard types/validation commit checks (the
    batched verifiers; sub-crossover commits ride the routed
    VerifyCoalescer) — so verdicts are bit-identical to the default
    plane — while hits and single-flight waiters skip the signature
    work entirely. Every entry point honors the thread's
    ``crypto/coalesce.request_deadline`` budget.
    """

    def __init__(self, cache: CommitResultCache):
        self.cache = cache

    def verify_commit_light(
        self, chain_id, vals, block_id, height, commit
    ) -> None:
        vals_hash = bytes(vals.hash())
        with libmetrics.light_phase("cache_key", "light.cache_key"):
            key = (
                "light",
                chain_id,
                height,
                vals_hash,
                _commit_digest(commit),
                # the FULL expected block id, not just its hash:
                # verify_commit_light compares part_set_header too, and
                # a cached success must never mask a mismatch there
                block_id.encode(),
            )
        # outermost ledger tenant: a proof-service client's coalescer
        # lanes attribute to "light", not the commit-verify mechanism
        with libdevledger.caller_class("light"):
            self._cached(
                key,
                lambda: verify_commit_light(
                    chain_id, vals, block_id, height, commit
                ),
            )

    def verify_commit_light_trusting(
        self, chain_id, vals, commit, trust_level
    ) -> None:
        vals_hash = bytes(vals.hash())
        with libmetrics.light_phase("cache_key", "light.cache_key"):
            key = (
                "trusting",
                chain_id,
                commit.height,
                vals_hash,
                _commit_digest(commit),
                (trust_level.numerator, trust_level.denominator),
            )
        with libdevledger.caller_class("light"):
            self._cached(
                key,
                lambda: verify_commit_light_trusting(
                    chain_id, vals, commit, trust_level
                ),
            )

    def _cached(self, key: tuple, run) -> None:
        m = libmetrics.node_metrics()
        waited = False
        while True:
            _check_deadline("commit verification")
            state, flight = self.cache.begin(key, recheck=waited)
            if state == "hit":
                # a hit after waiting is the flight's success landing
                # in the cache: one logical lookup, counted shared
                m.light_cache_lookups.labels(
                    "shared" if waited else "hit"
                ).inc()
                return
            if state == "wait":
                waited = True
                rem = crypto_coalesce.deadline_remaining()
                wait_s = _FLIGHT_WAIT_S if rem is None \
                    else max(min(rem, _FLIGHT_WAIT_S), 0.0)
                flight.event.wait(wait_s)
                if flight.event.is_set():
                    if flight.ok:
                        self.cache.note_shared()
                        m.light_cache_lookups.labels("shared").inc()
                        return
                    exc = flight.exc
                    if exc is not None and _find_deadline(exc) is None:
                        # deterministic verification: the leader's
                        # failure IS this caller's failure
                        self.cache.note_shared()
                        m.light_cache_lookups.labels("shared").inc()
                        raise exc
                    # the leader aborted on ITS OWN deadline — that
                    # says nothing about the commit; retry as leader
                    # (this caller's deadline bounds the loop)
                # leader still running: loop — the deadline check
                # bounds this; re-polls count nothing
                continue
            # leader: verify OUTSIDE the cache lock, publish code-last
            # (a waiter promoted to leader really verifies: a miss)
            m.light_cache_lookups.labels("miss").inc()
            exc: BaseException | None = None
            try:
                run()
            except BaseException as e:
                exc = e
                raise
            finally:
                self.cache.done(key, exc is None, exc)
            return


class _DeadlineProvider(Provider):
    """Per-request provider wrapper: the request deadline is checked
    before AND after every fetch, so a stalled provider cannot burn
    post-deadline verification work (the fetch itself is bounded by the
    provider's own timeout — rpc_provider carries retry + per-call
    timeout)."""

    def __init__(self, inner: Provider):
        self._inner = inner

    def chain_id(self) -> str:
        return self._inner.chain_id()

    def light_block(self, height: int):
        _check_deadline(f"fetching light block {height}")
        lb = self._inner.light_block(height)
        _check_deadline(f"fetched light block {height}")
        return lb

    def report_evidence(self, ev) -> None:
        self._inner.report_evidence(ev)


class LightService(BaseService):
    """Skipping-verification proof service over one shared device.

    ``verify_at_height`` is the whole request surface: admit under the
    backpressure bounds, build a per-request ``Client`` rooted at the
    caller's trust height (or the service's own root), run the standard
    bisection with the caching plane, and return the verified block's
    identity. ``stop()`` drains: queued waiters are rejected
    immediately, in-flight requests complete (each bounded by its own
    deadline) before stop returns.
    """

    def __init__(
        self,
        provider: Provider,
        chain_id: str,
        trust_options: TrustOptions | None = None,
        witnesses=(),
        trusting_period_ns: int = _DEFAULT_TRUSTING_PERIOD_NS,
        trust_level: Fraction = DEFAULT_TRUST_LEVEL,
        max_clock_drift_ns: int = light_verifier.DEFAULT_MAX_CLOCK_DRIFT_NS,
        root_height: int = 1,
        max_inflight: int | None = None,
        max_queue: int | None = None,
        default_deadline_s: float | None = None,
        cache_size: int | None = None,
        cache_ttl_s: float | None = None,
        own_coalescer: bool = False,
        coalescer_device: bool | None = None,
        coalescer_window_us: int | None = None,
        logger=None,
    ):
        super().__init__("LightService", logger)
        self.provider = provider
        self.chain_id = chain_id
        self.trust_options = trust_options
        self.witnesses = list(witnesses)
        self.trusting_period_ns = trusting_period_ns
        self.trust_level = trust_level
        self.max_clock_drift_ns = max_clock_drift_ns
        self.root_height = root_height
        self.max_inflight = max(
            1,
            max_inflight
            if max_inflight is not None
            else _env_int(
                "COMETBFT_TPU_LIGHT_MAX_INFLIGHT", _DEFAULT_MAX_INFLIGHT
            ),
        )
        self.max_queue = max(
            0,
            max_queue
            if max_queue is not None
            else _env_int("COMETBFT_TPU_LIGHT_MAX_QUEUE", _DEFAULT_MAX_QUEUE),
        )
        self.default_deadline_s = (
            default_deadline_s
            if default_deadline_s is not None
            else _env_float(
                "COMETBFT_TPU_LIGHT_DEADLINE_S", _DEFAULT_DEADLINE_S
            )
        )
        self.cache = CommitResultCache(
            capacity=(
                cache_size
                if cache_size is not None
                else _env_int(
                    "COMETBFT_TPU_LIGHT_CACHE_SIZE", _DEFAULT_CACHE_SIZE
                )
            ),
            ttl_s=(
                cache_ttl_s
                if cache_ttl_s is not None
                else _env_float(
                    "COMETBFT_TPU_LIGHT_CACHE_TTL_S", _DEFAULT_CACHE_TTL_S
                )
            ),
        )
        self.plane = CachedCommitVerifier(self.cache)
        # admission state under light.service._mtx; the condition's own
        # wait is the sanctioned case (queue waiters under their lock)
        self._mtx = libsync.Mutex("light.service._mtx")
        self._cv = libsync.Condition(self._mtx, name="light.service._mtx")
        self._accepting = False
        self._inflight = 0
        self._queued = 0
        self._counts = {
            "ok": 0, "error": 0, "rejected": 0, "deadline": 0, "stopped": 0,
        }
        self._lazy_root: TrustOptions | None = None
        self._want_own_coalescer = own_coalescer
        self._coalescer_device = coalescer_device
        self._coalescer_window_us = coalescer_window_us
        self._own_coalescer = None

    # -- lifecycle ---------------------------------------------------------

    def on_start(self) -> None:
        if self._want_own_coalescer:
            co = crypto_coalesce.VerifyCoalescer(
                window_us=self._coalescer_window_us,
                device=self._coalescer_device,
                logger=self.logger,
            )
            co.start()
            crypto_coalesce.push_active(co)
            self._own_coalescer = co
        with self._mtx:
            self._accepting = True

    def on_stop(self) -> None:
        """Drain: reject queued waiters, let in-flight requests finish."""
        with self._mtx:
            self._accepting = False
            self._cv.notify_all()
        # every in-flight request is bounded by its own deadline; the
        # slack covers unwind work after the deadline fires
        limit = time.monotonic() + self.default_deadline_s + 5.0
        with self._mtx:
            while self._inflight > 0 and time.monotonic() < limit:
                self._cv.wait(0.1)
        if self._own_coalescer is not None:
            crypto_coalesce.pop_active(self._own_coalescer)
            try:
                if self._own_coalescer.is_running():
                    self._own_coalescer.stop()
            except Exception:
                pass

    # -- admission (backpressure) ------------------------------------------

    def _admit(self, deadline: float) -> None:
        with self._mtx:
            if not self._accepting:
                raise ServiceStoppedError("light service is not running")
            if self._inflight < self.max_inflight:
                self._inflight += 1
                return
            if self._queued >= self.max_queue:
                raise ServiceBusyError(
                    f"light service at capacity ({self.max_inflight} in "
                    f"flight, {self.max_queue} queued)"
                )
            self._queued += 1
            try:
                while self._accepting and self._inflight >= self.max_inflight:
                    rem = deadline - time.monotonic()
                    if rem <= 0:
                        raise DeadlineExceededError(
                            "deadline exceeded waiting for an in-flight slot"
                        )
                    self._cv.wait(min(rem, 0.2))
                if not self._accepting:
                    raise ServiceStoppedError(
                        "light service stopped while queued"
                    )
                self._inflight += 1
            finally:
                self._queued -= 1

    def _release(self, outcome: str) -> int:
        with self._mtx:
            self._inflight -= 1
            self._counts[outcome] = self._counts.get(outcome, 0) + 1
            self._cv.notify_all()
            return self._inflight

    def _count_rejection(self, outcome: str) -> None:
        with self._mtx:
            self._counts[outcome] = self._counts.get(outcome, 0) + 1

    # -- the request surface -----------------------------------------------

    def verify_at_height(
        self,
        height: int,
        trust_height: int | None = None,
        trust_hash: bytes | None = None,
        deadline_s: float | None = None,
        now_ns: int | None = None,
    ) -> dict:
        """Serve one skipping-verification request.

        Verifies the chain's block at ``height`` relative to the
        caller's trust root (``trust_height``/``trust_hash``; the
        service's own root when omitted; the root's hash is fetched
        from the provider when only a height is given — the caller
        trusts this service's view, the usual proxy posture).
        ``deadline_s`` may only tighten the service default. Returns
        the verified block's identity and the bisection trace. Raises
        :class:`ServiceBusyError` (backpressure),
        :class:`DeadlineExceededError`, :class:`ServiceStoppedError`,
        or the standard light-client errors on verification failure.
        """
        if height is None or int(height) <= 0:
            raise LightServiceError("height must be positive")
        height = int(height)
        # a caller's deadline may only TIGHTEN the service default: the
        # default is also the drain bound (on_stop waits it out plus
        # slack) and the slot-hold ceiling — an unclamped client value
        # could pin every in-flight slot and outlive shutdown
        dl = self.default_deadline_s
        if deadline_s is not None:
            dl = min(max(float(deadline_s), 0.0), dl)
        deadline = time.monotonic() + dl
        # the request's root span: admission wait, the per-request
        # client's header phases and its coalescer tickets' queue waits
        # are its descendants
        with libtrace.span("light.service.request", height=height) as sp:
            self._admitted(deadline)
            outcome = "error"
            try:
                with crypto_coalesce.request_deadline(deadline):
                    result = self._serve(
                        height, trust_height, trust_hash, now_ns
                    )
                outcome = "ok"
                return result
            except BaseException as e:
                dexc = _find_deadline(e)
                if dexc is not None:
                    outcome = "deadline"
                    if dexc is e:
                        raise
                    raise DeadlineExceededError(str(dexc)) from e
                raise
            finally:
                m = libmetrics.node_metrics()
                left = self._release(outcome)
                m.light_requests.labels(outcome).inc()
                m.light_inflight.set(left)
                sp.set(outcome=outcome)

    def _admitted(self, deadline: float) -> None:
        """Take an in-flight slot or raise, counting the rejection. The
        wait is ``light_service_queue_wait_seconds`` (admitted requests
        only) and the ``light.service.admit`` span, one clock pair."""
        m = libmetrics.node_metrics()
        with libmetrics.TimedPhase(None, "light.service.admit") as ph:
            try:
                self._admit(deadline)
            except LightServiceError as e:
                rejected = _REJECTIONS[type(e)]
                ph.set(outcome=rejected)
                self._count_rejection(rejected)
                m.light_requests.labels(rejected).inc()
                raise
        m.light_queue_wait.observe(ph.dur_ns / 1e9)
        m.light_inflight.set(self._inflight)

    def _serve(self, height, trust_height, trust_hash, now_ns) -> dict:
        provider = _DeadlineProvider(self.provider)
        opts = self._request_options(provider, trust_height, trust_hash)
        client = Client(
            chain_id=self.chain_id,
            trust_options=opts,
            primary=provider,
            witnesses=list(self.witnesses),
            trusted_store=MemStore(),
            trust_level=self.trust_level,
            max_clock_drift_ns=self.max_clock_drift_ns,
            commit_verifier=self.plane,
        )
        lb = client.verify_light_block_at_height(height, now_ns)
        return {
            "height": str(lb.height),
            "hash": lb.hash().hex().upper(),
            "time_ns": str(lb.signed_header.time_ns),
            "trust_height": str(opts.height),
            "trust_hash": opts.hash.hex().upper(),
            "verified_heights": [b.height for b in client.latest_trace],
        }

    def _request_options(
        self, provider, trust_height, trust_hash
    ) -> TrustOptions:
        if trust_height is None:
            return self._root_options(provider)
        th = int(trust_height)
        if th <= 0:
            raise LightServiceError("trust_height must be positive")
        if trust_hash:
            root = bytes(trust_hash)
        else:
            root = provider.light_block(th).hash()
        return TrustOptions(
            period_ns=self.trusting_period_ns, height=th, hash=root
        )

    def _root_options(self, provider) -> TrustOptions:
        """The service's own root of trust: the ctor's options, or a
        lazily-derived root at ``root_height`` — derived on first use
        because a freshly-booted node may not have any block yet."""
        if self.trust_options is not None:
            return self.trust_options
        opts = self._lazy_root
        if opts is not None:
            return opts
        lb = provider.light_block(self.root_height)
        opts = TrustOptions(
            period_ns=self.trusting_period_ns,
            height=lb.height,
            hash=lb.hash(),
        )
        # benign race: two first requests derive identical roots
        self._lazy_root = opts
        return opts

    # -- introspection (the light_status route) ----------------------------

    def status(self) -> dict:
        with self._mtx:
            counts = dict(self._counts)
            inflight = self._inflight
            queued = self._queued
            running = self._accepting
        out = {
            "running": running,
            "inflight": inflight,
            "queued": queued,
            "max_inflight": self.max_inflight,
            "max_queue": self.max_queue,
            "default_deadline_s": self.default_deadline_s,
            "requests": counts,
            "cache": self.cache.stats(),
        }
        root = self.trust_options or self._lazy_root
        if root is not None:
            out["root"] = {
                "height": str(root.height),
                "hash": root.hash.hex().upper(),
            }
        co = self._own_coalescer or crypto_coalesce.active()
        if co is not None:
            out["coalescer"] = {
                "windows": co.windows,
                "device_windows": co.device_windows,
                "tickets": co.tickets,
            }
        return out
