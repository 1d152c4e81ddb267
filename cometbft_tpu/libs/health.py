"""Always-on consensus flight recorder, SLO/health engine, watchdogs.

The reference CometBFT treats liveness as *observable state* — consensus
metrics per height/round/step — but PR 3's tracer and PR 4's devstats
are opt-in and passive: when a node stalls, wedges its verify executor,
or enters a recompile storm, nothing notices until a human scrapes
``/debug/trace``.  This layer closes that loop with three pieces:

* **Flight recorder** (:class:`FlightRecorder`): a bounded ring of
  structured events — height/round/step transitions, proposal/vote
  admission, per-height commit latency, coalescer breaker trips, XLA
  recompiles, WAL fsyncs, watchdog trips — recorded even when
  ``COMETBFT_TPU_TRACE`` is off.  The black box: when something goes
  wrong, the last few thousand consensus events are already captured.

* **SLO/health engine** (:func:`sample`, :func:`slis`): derives SLIs
  from the ring and the existing metrics families (per-height commit
  latency p50/p99, rounds-per-height, verify-window wait p99, breaker
  state, WAL fsync lag, step-progress age) into ``health_*`` Prometheus
  gauges plus one composite ``health_score`` in [0, 1].

* **Watchdogs** (:class:`HealthMonitor`): a consensus **stall**
  detector (no step progress within a multiple of the commit timeout),
  a **wedged-coalescer** detector (hooked to crypto/coalesce's
  half-open breaker via :func:`note_breaker_trip`), and a
  **recompile-storm** alarm (hooked to the ``xla_recompile_total``
  ledger in libs/devstats).  Any trip raises
  ``health_watchdog_trips_total{watchdog}`` and emits a rate-limited
  **black-box bundle** (flight-recorder ring + devstats snapshot +
  lock-order held stacks + thread dump + trace tail) into the
  debug-dump directory, so forensic state is captured at the moment of
  failure, not minutes later.

Design constraints (stricter than libs/trace — this layer is ON by
default for every node):

* **Allocation-free steady state.**  The record path writes scalars
  into preallocated ``array.array`` columns; slot reservation is one
  GIL-atomic ``itertools.count`` step.  Nothing is retained per record
  — pinned by the tracemalloc guard in tests/test_observability.py,
  which also covers the watchdog's no-trip check.  (Temporaries are
  fine; *retained* allocations are not.)

* **Lock-free record and scrape paths.**  ``record()`` touches no lock
  (concurrent writers reserve distinct slots; a reader may observe a
  torn in-progress row, which the decoder skips — same posture as PR
  4's lock-free compile-record deque).  The one lock here
  (``libs.health._mtx``) serializes only the bundle rate limit and the
  monitor registry, is never held across file I/O or another lock, and
  is asserted edge-free in tests/test_lint_graph.py like
  ``libs.trace._mtx`` / ``libs.devstats._mtx``.

Knobs (registered in config.ENV_KNOBS, enforced by cometlint CLNT007):
``COMETBFT_TPU_HEALTH`` (auto: on while a node runs; 1 force; 0 off),
``COMETBFT_TPU_HEALTH_RING`` (ring capacity),
``COMETBFT_TPU_HEALTH_STALL_MULT`` (stall window as a multiple of the
commit+propose timeout), ``COMETBFT_TPU_HEALTH_BUNDLE_DIR`` (black-box
dump directory override), ``COMETBFT_TPU_HEALTH_BUNDLE_RL_S`` (minimum
seconds between bundles).
"""

from __future__ import annotations

import itertools
import json
import os
import shutil
import threading
import time
from array import array

from . import devledger as libdevledger
from . import lockprof as liblockprof
from . import metrics as libmetrics
from . import netstats as libnetstats
from . import profile as libprofile
from . import sync as libsync
from . import trace as libtrace
from .service import BaseService

_ENV_HEALTH = "COMETBFT_TPU_HEALTH"
_ENV_RING = "COMETBFT_TPU_HEALTH_RING"
_ENV_STALL_MULT = "COMETBFT_TPU_HEALTH_STALL_MULT"
_ENV_BUNDLE_DIR = "COMETBFT_TPU_HEALTH_BUNDLE_DIR"
_ENV_BUNDLE_RL = "COMETBFT_TPU_HEALTH_BUNDLE_RL_S"
_ENV_POSTMORTEM = "COMETBFT_TPU_POSTMORTEM"

DEFAULT_RING_SIZE = 4096
# Stall window = multiplier x (timeout_commit + timeout_propose(0)):
# one full empty-block cycle is the longest a healthy node legitimately
# goes between step transitions, so 25 cycles of silence is a wedge,
# not a slow round (production defaults: ~100 s).
DEFAULT_STALL_MULT = 25.0
DEFAULT_BUNDLE_RL_S = 60.0
# Retention cap: newest bundle directories kept per bundle dir. The
# rate limit floors the write INTERVAL; this bounds the TOTAL — a node
# stalled over a weekend must not fill its data volume with thousands
# of ring dumps.
DEFAULT_BUNDLE_KEEP = 16
# Recompile storm: this many steady-state recompiles inside one rolling
# window is a shape-bucket leak / dtype drift actively destroying
# throughput (each recompile costs seconds of XLA time on the hot path).
STORM_RECOMPILES = 3
STORM_WINDOW_S = 60.0

# -- ring event codes (decoded by _CODE_NAMES / dump()) -----------------
EV_STEP = 1  # height, round, a=RoundStep int
EV_PROPOSAL = 2  # height, round, a=1 accepted / 0 rejected
EV_VOTE = 3  # height, round, a=vote type, b=validator index
EV_COMMIT = 4  # height, round=commit round, a=height latency ns
EV_BREAKER = 5  # a=1 trip / 0 re-arm (crypto/coalesce half-open breaker)
EV_RECOMPILE = 6  # a=shape bucket (libs/devstats steady-state recompile)
EV_FSYNC = 7  # a=WAL fsync ns
EV_WATCHDOG = 8  # a=watchdog bit (see _WATCHDOGS)
EV_GOSSIP = 9  # a=propagation phase code (netstats.PHASE_NAMES), b=lag ns
EV_FAULT = 10  # simnet fault plane: h=src node, r=dst node, a=kind, b=detail
EV_HASH = 11  # hash-plane window flush: a=lanes, b=1 device / 0 host
# plane.budget: FSM-blocking device-plane time per window resolution —
# r=plane (libs/devledger: 0 verify / 1 hash), a=consensus-caller
# queue-wait ns, b=consensus-caller pro-rata execute ns. The per-height
# latency budget (budget_from_events) window-assigns these rows to the
# height they delayed, exactly like EV_FSYNC.
EV_BUDGET = 12
# tx.stage: one sampled transaction crossing a lifecycle stage
# (libs/txtrace): r=stage code (TX_STAGES), a=signed 64-bit key
# fingerprint (first 8 key bytes; decoded as the 16-hex-char ``key``
# prefix), b=stage payload — mempool depth at admit, one-hop lag ns at
# gossip_recv, ns-since-admit at gossip_send/commit. Stamped from the
# ring clock, so virtual-domain (simnet) rows stay merge-consistent.
EV_TX = 13
# sync.lock: a lock wait or hold crossed the lockprof slow threshold
# (libs/lockprof, COMETBFT_TPU_LOCKPROF_SLOW_MS) — r=lockorder.json
# registry slot (decoded to the ``lock`` name), a=duration ns,
# b=site_idx*2+kind (kind 0 wait / 1 hold; site_idx indexes lockprof's
# interned holder-acquire-site table, decoded as ``site``). Bundles
# name the blocker, not just the victim.
EV_LOCK = 14
# prof.window: one sampling-profiler flush window for one subsystem
# (libs/profile, ~1/s per subsystem with samples) — r=subsystem index
# (libs/profile.SUBSYSTEMS, decoded as ``subsystem``), a=the kernel CPU
# ns the subsystem's threads used in the window (their thread CPU
# clocks; not on-CPU samples x the period, which counted a thread
# waiting for the interpreter lock or asleep in C as working), b=total
# samples (on-CPU + blocked). critical_path_from_events window-assigns these to
# name commits gated by GIL-bound Python (``cpu:<subsystem>``), and the
# cpu_saturated postmortem detector scores them.
EV_PROF = 15
# spec.exec: one speculative block execution resolved by the commit
# pipeline (consensus/pipeline) — a=outcome code (_SPEC_OUTCOMES:
# 1 hit / 2 miss / 3 abort), b=speculative FinalizeBlock execute ns
# (0 for miss/abort rows — there is nothing to credit). Recorded at
# consumption/discard time on the FSM thread, so the row sits inside
# the commit window budget_from_events assigns it to.
EV_SPEC = 16

_N_CODES = 17  # size of the per-code last-seen vector

# EV_SPEC outcome vocabulary (recorded by consensus/pipeline)
SPEC_HIT = 1  # precommitted block matched the memoized speculation
SPEC_MISS = 2  # nothing memoized for the committed block — serial path
SPEC_ABORT = 3  # speculation discarded (superseded / failed) unconsumed

_SPEC_OUTCOMES = {SPEC_HIT: "hit", SPEC_MISS: "miss", SPEC_ABORT: "abort"}

# EV_TX stage vocabulary (the decode side of libs/txtrace's stage
# codes — the decoder lives here with the rest of the ring vocabulary,
# txtrace aliases this map so the two cannot diverge)
TX_STAGES = {
    1: "admit",
    2: "gossip_send",
    3: "gossip_recv",
    4: "proposal",
    5: "commit",
}

# EV_FAULT kinds (recorded by cometbft_tpu/simnet): the black-box ring
# explains WHICH fault was live when a scenario failed — a partition
# forming, a link dropping a message class, a node crashing mid-height.
FAULT_PARTITION = 1  # partition formed (detail = group count)
FAULT_HEAL = 2  # partition healed
FAULT_KILL = 3  # node killed (churn)
FAULT_RESTART = 4  # node restarted (churn)
FAULT_DROP = 5  # one message eaten by link faults (detail = channel)
FAULT_LINK = 6  # link fault parameters changed
FAULT_CRASH = 7  # armed COMETBFT_TPU_FAIL crash point fired in-process
# gray-failure vocabulary (PR 13): slow-but-alive and asymmetric faults
FAULT_ONEWAY = 8  # one DIRECTION severed (h=src, r=dst; detail 1=sever 0=restore)
FAULT_SLOW_DISK = 9  # node's disk slowed (h=node; detail = latency ms, 0=cleared)
FAULT_STORM = 10  # sustained mempool storm (detail = tx/s rate, 0=stopped)
FAULT_PEER_EVICT = 11  # a node-side DEFENSE evicted a peer (suspicion /
# statesync chunk-peer rotation); h=node where known, detail=reason code
# FAULT_PEER_EVICT detail namespace (WHICH defense acted): 1-4 are the
# p2p/suspicion reason enum (queue_full/stale/lag/mixed); 5 is a
# statesync chunk-fetch rotation abandoning a timing-out chunk peer
PEER_EVICT_STATESYNC_ROTATE = 5

_FAULT_NAMES = {
    FAULT_PARTITION: "partition",
    FAULT_HEAL: "heal",
    FAULT_KILL: "kill",
    FAULT_RESTART: "restart",
    FAULT_DROP: "drop",
    FAULT_LINK: "link_change",
    FAULT_CRASH: "crash_point",
    FAULT_ONEWAY: "oneway_sever",
    FAULT_SLOW_DISK: "slow_disk",
    FAULT_STORM: "mempool_storm",
    FAULT_PEER_EVICT: "peer_evict",
}


def fault_kind_codes() -> dict[str, int]:
    """Every ``FAULT_*`` kind this module defines, by constant name —
    the registry the EV_FAULT decode-completeness tier-1 test walks, so
    a new fault kind cannot ship without a ``fault_name`` decode entry
    and a docs row."""
    return {
        name: value
        for name, value in globals().items()
        if name.startswith("FAULT_") and isinstance(value, int)
    }

_CODE_NAMES = {
    EV_STEP: "consensus.step",
    EV_PROPOSAL: "consensus.proposal",
    EV_VOTE: "consensus.vote",
    EV_COMMIT: "consensus.commit",
    EV_BREAKER: "coalesce.breaker",
    EV_RECOMPILE: "xla.recompile",
    EV_FSYNC: "wal.fsync",
    EV_WATCHDOG: "health.watchdog",
    EV_GOSSIP: "p2p.gossip",
    EV_FAULT: "simnet.fault",
    EV_HASH: "hash.flush",
    EV_BUDGET: "plane.budget",
    EV_TX: "tx.stage",
    EV_LOCK: "sync.lock",
    EV_PROF: "prof.window",
    EV_SPEC: "spec.exec",
}
# decode the free-form a/b columns per code
_CODE_FIELDS = {
    EV_STEP: ("step", None),
    EV_PROPOSAL: ("accepted", None),
    EV_VOTE: ("type", "index"),
    EV_COMMIT: ("dur_ns", "txs"),
    EV_BREAKER: ("open", None),
    EV_RECOMPILE: ("bucket", None),
    # overlapped=1 marks an fsync that ran OFF the FSM critical section
    # (the pipelined commit-writer, consensus/pipeline): the budget
    # plane excludes it from the serial wal_fsync stage and reports it
    # in the per-height ``overlapped`` credit instead
    EV_FSYNC: ("dur_ns", "overlapped"),
    EV_WATCHDOG: ("watchdog", None),
    EV_GOSSIP: ("phase", "lag_ns"),
    EV_FAULT: ("kind", "detail"),
    EV_HASH: ("lanes", "device"),
    EV_BUDGET: ("wait_ns", "exec_ns"),
    EV_TX: ("key_fp", "val"),
    EV_LOCK: ("dur_ns", "ref"),
    EV_PROF: ("oncpu_ns", "samples"),
    EV_SPEC: ("outcome", "dur_ns"),
}

# codes whose payload is a wall-clock-measured duration: meaningless in
# a virtual-time (simnet) ring, so the cross-node timeline merge drops
# them from virtual-domain sources (cometbft_tpu/postmortem) — EV_PROF
# rides along because its on-CPU estimate is sampled in wall time
WALL_DURATION_CODES = frozenset(
    {EV_FSYNC, EV_BUDGET, EV_LOCK, EV_PROF, EV_SPEC}
)


def ring_event_codes() -> dict[str, int]:
    """Every ``EV_*`` code this module defines, by constant name — the
    registry the decoder-completeness tier-1 test walks, so a new event
    code cannot ship without a decode path and a docs entry."""
    return {
        name: value
        for name, value in globals().items()
        if name.startswith("EV_") and isinstance(value, int)
    }

_STEP_NAMES = {
    1: "NewHeight", 2: "NewRound", 3: "Propose", 4: "Prevote",
    5: "PrevoteWait", 6: "Precommit", 7: "PrecommitWait", 8: "Commit",
}

# watchdog name -> trip bitmask returned by HealthMonitor._check
_WATCHDOGS = (
    ("consensus_stall", 1),
    ("verify_breaker", 2),
    ("recompile_storm", 4),
    ("send_queue_saturated", 8),
    ("slow_disk", 16),
    ("consensus_starved", 32),
    ("tx_starved", 64),
    ("lock_contended", 128),
)
# tx_starved: an ADMITTED tx is older than COMETBFT_TPU_TX_STARVE_COMMITS
# commit intervals WHILE heights keep committing — inclusion is broken
# though the chain is live (a dead chain is the stall watchdog's case,
# and an idle mempool can never starve: the age signal is the oldest
# admitted-uncommitted tx across libs/txtrace's registered mempools).
# consensus_starved: consensus-caller verify queue-wait p99 (windowed,
# from the device_queue_wait_seconds buckets) above the threshold WHILE
# other callers dominate the window's lane share — a light-service /
# mempool storm taxing consensus through the shared device planes. The
# lane-share test keeps an overloaded-but-fairly-shared plane from
# paging as starvation.
STARVE_LANE_SHARE = 0.5  # others' share that counts as "dominating"
STARVE_MIN_LANES = 64  # ledger lanes per check window before judging
# send_queue_saturated: this many CONSECUTIVE checks each observing
# fresh MConnection.send drops on a consensus channel = sustained
# backpressure (a one-off burst drop re-baselines without a trip)
SATURATION_STREAK = 3
# lock_contended: an ENGINE mutex's windowed p99 wait (libs/lockprof
# delta-histogram) at or above the slow threshold in this many
# CONSECUTIVE checks = a serialized resource actively gating the
# engine, not one unlucky acquire
LOCK_CONTENDED_STREAK = 2
_WATCHDOG_NAMES = {bit: name for name, bit in _WATCHDOGS}

_ON_VALUES = ("1", "on", "true", "yes")
_OFF_VALUES = ("0", "off", "false", "no")


def _env_mode() -> str:
    v = os.environ.get(_ENV_HEALTH, "").lower()
    if v in _ON_VALUES:
        return "on"
    if v in _OFF_VALUES:
        return "off"
    return "auto"


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, ""))
    except ValueError:
        return default


def _ring_size_from_env() -> int:
    try:
        n = int(os.environ.get(_ENV_RING, ""))
    except ValueError:
        n = DEFAULT_RING_SIZE
    return max(64, n)


# ----------------------------------------------- ring clock + origins

# Injectable ring timestamp source: the simnet plane swaps in its
# virtual clock (SimClock.time_ns) for the run's lifetime, so every
# ring row of an N-node simulation carries EXACT shared virtual time —
# the property that makes the cross-node timeline merge lossless there.
# Live nodes keep the wall clock and the merge tags cross-node edges
# with a netstamp-derived skew bound instead.
_now_ns = time.time_ns
_clock_domain = "wall"  # "wall" | "virtual" — exported with the ring


def now_ns() -> int:
    """The ring clock (wall on live nodes, the shared virtual clock
    under simnet) — sibling planes (libs/txtrace, the mempool admit
    stamps) read it so their durations stay domain-consistent with the
    ring rows they sit next to."""
    return _now_ns()


def set_clock(fn, domain: str = "wall"):
    """Swap the ring timestamp source; returns the previous
    ``(fn, domain)`` pair so the caller can restore it."""
    global _now_ns, _clock_domain
    prev = (_now_ns, _clock_domain)
    _now_ns = fn
    _clock_domain = domain
    return prev


def clock_domain() -> str:
    return _clock_domain


# Origin attribution: which NODE a ring row belongs to.  One process
# usually hosts one node (origin = its node-id prefix, registered at
# boot), but the simnet plane and the in-process test nets host N — the
# recording THREAD declares its origin (simnet sets it per scheduler
# event; live nodes set it on the cs-receive and mconn-recv threads
# they own), and the decoder emits it as the row's ``node`` field.  The
# record-path read is one thread-local getattr: allocation- and
# lock-free, covered by the tracemalloc guard.
_ORIGIN_NAMES: list[str] = ["local"]  # id 0 = unattributed/this-process
_ORIGIN_IDS: dict[str, int] = {"local": 0}
_origin_tls = threading.local()


def register_origin(name: str) -> int:
    """Intern an origin name -> id (dedupes, so re-registration across
    node restarts and repeated simnet runs is stable).  Registration is
    a setup-path operation (node boot, peer admit) under ``_mtx``."""
    with _mtx:
        oid = _ORIGIN_IDS.get(name)
        if oid is None:
            oid = len(_ORIGIN_NAMES)
            _ORIGIN_NAMES.append(name)
            _ORIGIN_IDS[name] = oid
        return oid


def origin_name(oid: int) -> str:
    names = _ORIGIN_NAMES
    return names[oid] if 0 <= oid < len(names) else "?"


def set_thread_origin(oid: int) -> None:
    """Declare the node whose events this thread records (0 clears)."""
    _origin_tls.oid = oid


def current_thread_origin() -> int:
    return getattr(_origin_tls, "oid", 0)


# ------------------------------------------------------- flight recorder


class FlightRecorder:
    """Bounded lock-free ring of fixed-width consensus events.

    Storage is six parallel ``array.array('q')`` columns plus a
    per-code last-seen ``array('d')`` vector, all preallocated: the
    record path performs only C-level scalar stores, so steady-state
    recording retains zero allocations.  Concurrent writers reserve
    slots through one GIL-atomic ``itertools.count``; a reader racing a
    writer may see one torn row (skipped by the decoder), never a
    corrupt structure.
    """

    __slots__ = (
        "capacity", "_ts", "_code", "_h", "_r", "_a", "_b", "_o",
        "_seq", "_written", "_last", "_commits",
    )

    def __init__(self, capacity: int = DEFAULT_RING_SIZE):
        self.capacity = max(64, int(capacity))
        zeros = [0] * self.capacity
        self._ts = array("q", zeros)
        self._code = array("q", zeros)
        self._h = array("q", zeros)
        self._r = array("q", zeros)
        self._a = array("q", zeros)
        self._b = array("q", zeros)
        self._o = array("q", zeros)  # recording thread's origin id
        self._seq = itertools.count()
        self._written = array("q", [0])
        # monotonic last-seen per event code (watchdog math)
        self._last = array("d", [0.0] * _N_CODES)
        # commit-row tally: the budget memo's invalidation key — the
        # per-height decomposition only changes when a height closes
        self._commits = array("q", [0])

    def record(
        self, code: int, height: int = 0, round_: int = 0,
        a: int = 0, b: int = 0,
    ) -> None:
        seq = next(self._seq)  # GIL-atomic slot reservation
        i = seq % self.capacity
        self._code[i] = 0  # mark in-progress: readers skip torn rows
        self._ts[i] = _now_ns()
        self._h[i] = height
        self._r[i] = round_
        self._a[i] = a
        self._b[i] = b
        self._o[i] = getattr(_origin_tls, "oid", 0)
        self._code[i] = code  # publish last
        if code == EV_STEP:
            # the one last-seen the stall watchdog consumes; the other
            # codes skip the extra clock read on the hot path
            self._last[EV_STEP] = time.monotonic()
        elif code == EV_COMMIT:
            self._commits[0] = self._commits[0] + 1
        if seq >= self._written[0]:
            self._written[0] = seq + 1

    def last_seen(self, code: int) -> float:
        """Monotonic time the code was last recorded (0.0 = never;
        maintained for EV_STEP only — the stall watchdog's signal)."""
        return self._last[code]

    def _iter_slots(self):
        """(slot index) oldest-first over the currently-filled window."""
        w = self._written[0]
        n = min(w, self.capacity)
        for k in range(w - n, w):
            yield k % self.capacity

    def dump(self) -> list[dict]:
        """Decoded ring contents, oldest first (lock-free snapshot; a
        row being written concurrently is skipped)."""
        out = []
        for i in self._iter_slots():
            code = self._code[i]
            name = _CODE_NAMES.get(code)
            if name is None:
                continue  # empty or torn slot
            rec = {
                "ts": self._ts[i],
                "event": name,
                "height": self._h[i],
                "round": self._r[i],
            }
            # .get with a null default, not [code]: a code registered in
            # _CODE_NAMES but missing its field entry must decode (as
            # raw a/b-less row), never KeyError a scrape/bundle path —
            # the completeness test still flags the gap
            fa, fb = _CODE_FIELDS.get(code, (None, None))
            if fa is not None:
                rec[fa] = self._a[i]
            if fb is not None:
                rec[fb] = self._b[i]
            if code == EV_STEP:
                rec["step_name"] = _STEP_NAMES.get(self._a[i], "?")
            elif code == EV_WATCHDOG:
                rec["watchdog_name"] = _WATCHDOG_NAMES.get(self._a[i], "?")
            elif code == EV_GOSSIP:
                rec["phase_name"] = libnetstats.PHASE_NAMES.get(
                    self._a[i], "?"
                )
                if self._r[i] > 0:
                    # simnet delivery rows park the SENDING node's
                    # origin id in the round column (live rows leave 0)
                    rec["src"] = origin_name(self._r[i])
            elif code == EV_FAULT:
                rec["fault_name"] = _FAULT_NAMES.get(self._a[i], "?")
            elif code == EV_BUDGET:
                # the plane rides the round column (libs/devledger
                # plane codes); heightless rows keep round=plane
                rec["plane"] = libdevledger.PLANES[
                    self._r[i] % len(libdevledger.PLANES)
                ]
            elif code == EV_TX:
                # the stage rides the round column; the key exports as
                # its bounded 16-hex-char prefix, never the raw key
                rec["stage_name"] = TX_STAGES.get(self._r[i], "?")
                rec["key"] = format(self._a[i] % (1 << 64), "016x")
            elif code == EV_LOCK:
                # the registry slot rides the round column; b packs
                # kind (low bit) + interned holder-acquire-site index
                rec["lock"] = liblockprof.slot_name(self._r[i])
                rec["kind_name"] = liblockprof.KIND_NAMES.get(
                    self._b[i] & 1, "?"
                )
                rec["site"] = liblockprof.site_name(self._b[i] >> 1)
            elif code == EV_PROF:
                # the subsystem index rides the round column
                rec["subsystem"] = libprofile.subsystem_name(self._r[i])
            elif code == EV_SPEC:
                rec["outcome_name"] = _SPEC_OUTCOMES.get(self._a[i], "?")
            o = self._o[i]
            if o:
                rec["node"] = origin_name(o)
            out.append(rec)
        return out

    def slis(self) -> dict:
        """SLIs derived from the ring: commit-latency quantiles,
        rounds-per-height, WAL fsync lag, step-progress age."""
        commits: list[float] = []
        rounds: list[int] = []
        fsyncs: list[float] = []
        for i in self._iter_slots():
            code = self._code[i]
            if code == EV_COMMIT:
                commits.append(self._a[i] / 1e9)
                rounds.append(self._r[i] + 1)
            elif code == EV_FSYNC:
                fsyncs.append(self._a[i] / 1e9)
        last_step = self._last[EV_STEP]
        return {
            "commits": len(commits),
            "commit_latency_s": {
                "last": round(commits[-1], 6) if commits else None,
                "p50": _quantile(commits, 0.50),
                "p99": _quantile(commits, 0.99),
            },
            "rounds_per_height": (
                round(sum(rounds) / len(rounds), 3) if rounds else None
            ),
            "wal_fsync_p99_s": _quantile(fsyncs, 0.99),
            "step_age_s": (
                round(time.monotonic() - last_step, 3) if last_step else None
            ),
        }

    def status(self) -> dict:
        return {
            "capacity": self.capacity,
            "recorded": self._written[0],
        }


def _quantile(values: list[float], q: float) -> float | None:
    if not values:
        return None
    vs = sorted(values)
    idx = min(len(vs) - 1, int(q * len(vs)))
    return round(vs[idx], 6)


def histogram_quantile(h, q: float) -> float:
    """Upper-bound quantile estimate from a libs/metrics Histogram's
    cumulative buckets (the promql-style read).  Unlocked GIL-consistent
    snapshot: the scrape path must not contend with observers.  The
    math lives in the shared :func:`libmetrics.quantile_from_buckets`
    estimator (one implementation for health, netstats and the
    device-ledger budget plane)."""
    return libmetrics.quantile_from_buckets(h.buckets, list(h._counts), q)


# -------------------------------------------------- module-level recorder

_mode = _env_mode()
_enabled: bool = _mode == "on"
# reference count of node-lifecycle holders (every booting node acquires
# unless the env knob pins health off) — "always-on" means on for every
# running node with zero opt-in, while bare library use stays free
_acquirers = 0

_REC = FlightRecorder(_ring_size_from_env())

# bundle rate limit + monitor registry + origin interning only (all
# setup/trip paths — never the record path)
_mtx = libsync.Mutex("libs.health._mtx")

# breaker-trip notices from crypto/coalesce (module-level so the hook
# needs no monitor handle; a lost increment under a rare write race
# costs one duplicate-free notice, never a missed episode — the ring
# event is recorded regardless)
_BREAKER_NOTICES = array("q", [0])


def enabled() -> bool:
    """The one check hot paths make before recording."""
    return _enabled


def enable(ring: int | None = None) -> None:
    """Force the recorder on (tests, bench).  ``ring`` rebuilds the
    buffer at a new capacity, dropping prior records."""
    global _enabled, _REC
    if ring is not None and ring != _REC.capacity:
        _REC = FlightRecorder(ring)
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop all buffered records (tests, bench bursts)."""
    global _REC
    _REC = FlightRecorder(_REC.capacity)


def set_ring_capacity(n: int) -> None:
    """Rebuild the ring at a new capacity WITHOUT touching the enabled
    flag (simnet scenario runs size the ring to hold a whole run's
    gossip-annotated event stream, then restore the prior capacity)."""
    global _REC
    n = max(64, int(n))
    if n != _REC.capacity:
        _REC = FlightRecorder(n)


def export_ring(node: str | None = None) -> dict:
    """The portable flight-ring export: the ``flight.json`` bundle
    artifact, the ``/debug/flight`` pprof body, and the input shape the
    cross-node timeline merge (cometbft_tpu/postmortem) consumes.

    ``domain`` says which clock stamped the rows ("wall" for live
    nodes, "virtual" for simnet rings — where the shared clock makes a
    cross-node merge exact); ``origins`` is the interned origin-name
    table the per-row ``node``/``src`` fields were decoded from."""
    return {
        "schema": 1,
        "node": node,
        "domain": _clock_domain,
        "origins": list(_ORIGIN_NAMES),
        # measured per-peer clock-skew bounds (netstamp round trips):
        # the merge tags this ring's cross-node edges with them
        "skews": libnetstats.skew_table(),
        "ring": _REC.status(),
        "events": _REC.dump(),
    }


# ------------------------------------------------- per-height budget

# The stage vocabulary of the per-height latency budget — the ``stage``
# label of height_budget_seconds and the keys of every budget row.
BUDGET_STAGES = (
    "proposal_wait",  # enter-height -> Prevote step (proposal receipt)
    "gossip",  # vote-gathering wall time net of plane overlays
    "verify_queue",  # consensus-caller coalescer queue wait
    "verify_execute",  # consensus-caller pro-rata verify execute
    "hash",  # FSM-adjacent hash-plane time (merkle/mempool)
    "spec_exec",  # speculative FinalizeBlock time consumed by a hit
    "wal_fsync",  # FSM-blocking WAL fsync durations in the height window
    "apply",  # Commit step -> applied, net of fsync overlay
    "residual",  # whatever the named stages don't explain
)

_STEP_PREVOTE = 4  # RoundStep.PREVOTE in the EV_STEP step column
_STEP_COMMIT = 8  # RoundStep.COMMIT


def budget_from_events(events) -> dict[int, dict]:
    """Decompose each committed height's latency into BUDGET_STAGES.

    Input is a decoded event stream (``FlightRecorder.dump()`` rows, a
    ``flight.json`` export's ``events``, or a merged multi-node
    stream).  Per height: the EARLIEST commit row anchors the window
    ``[commit_ts - latency, commit_ts]``; that node's first Prevote and
    Commit step rows split it into proposal / vote-gathering / apply
    spans; ``plane.budget`` (EV_BUDGET) and ``wal.fsync`` rows are
    window-assigned by timestamp as overlays, and each span reports its
    remainder — so the stages tile the measured latency and
    ``coverage`` (stage sum / latency) sits at ~1.0 on a healthy burst.
    Pure function: deterministic for a given event list (the timeline
    merge reuses it for its per-height budget rows)."""
    commits: dict[int, tuple] = {}
    steps: dict[tuple, dict] = {}
    planes: list[tuple] = []
    fsyncs: list[tuple] = []
    specs: list[tuple] = []
    for ev in events:
        name = ev.get("event")
        if name == "consensus.commit":
            h = ev.get("height", 0)
            if h:
                cur = commits.get(h)
                if cur is None or ev.get("ts", 0) < cur[0]:
                    commits[h] = (
                        ev.get("ts", 0), ev.get("dur_ns", 0),
                        ev.get("node"),
                    )
        elif name == "consensus.step":
            h = ev.get("height", 0)
            if h:
                d = steps.setdefault((h, ev.get("node")), {})
                s = ev.get("step")
                if s not in d:
                    d[s] = ev.get("ts", 0)
        elif name == "plane.budget":
            planes.append((
                ev.get("ts", 0), ev.get("plane"),
                ev.get("wait_ns", 0), ev.get("exec_ns", 0),
            ))
        elif name == "wal.fsync":
            fsyncs.append((
                ev.get("ts", 0), ev.get("dur_ns", 0),
                ev.get("overlapped", 0),
            ))
        elif name == "spec.exec":
            specs.append((
                ev.get("ts", 0), ev.get("outcome", 0),
                ev.get("dur_ns", 0),
            ))
    out: dict[int, dict] = {}
    for h in sorted(commits):
        cts, dur, node = commits[h]
        if dur <= 0:
            continue
        t0 = cts - dur
        sd = steps.get((h, node), {})
        t_pv = sd.get(_STEP_PREVOTE)
        t_cm = sd.get(_STEP_COMMIT)
        have_steps = t_pv is not None
        e1 = min(max(t_pv, t0), cts) if t_pv else cts
        e2 = min(max(t_cm, e1), cts) if t_cm else cts

        def _span(ts: int) -> int:
            if ts <= e1:
                return 0
            return 1 if ts <= e2 else 2

        # per span: [verify_wait, verify_exec, hash, fsync, spec_exec]
        ov = [[0] * 5, [0] * 5, [0] * 5]
        # overlapped credit: work the pipelined commit moved OFF the
        # serial span (flagged fsyncs; a winning speculation's execute
        # time beyond what the span clamp can absorb). Reported beside
        # the stages — never inside them — so the tiling still covers
        # exactly the FSM-blocking latency without double-counting.
        overlapped_fsync = 0
        for ts, plane, w, x in planes:
            if t0 <= ts <= cts:
                k = _span(ts)
                if plane == "verify":
                    ov[k][0] += w
                    ov[k][1] += x
                else:
                    ov[k][2] += w + x
        for ts, d, lap in fsyncs:
            if t0 <= ts <= cts:
                if lap:
                    overlapped_fsync += d
                else:
                    ov[_span(ts)][3] += d
        for ts, outcome, d in specs:
            if t0 <= ts <= cts and outcome == SPEC_HIT:
                ov[_span(ts)][4] += d
        # Clamp each span's overlay total to the span's wall length:
        # FSM-blocking time inside a span cannot exceed the span, but
        # a shared multi-node ring (in-process nets, simnet) assigns
        # every node's plane rows to the one committing node's window,
        # and concurrent-thread callers (CheckTx hashing, the spec-exec
        # worker) overlap the FSM wall — scaling the components
        # pro-rata keeps the stage tiling honest (coverage ~1.0)
        # instead of double-counting.
        spans = (e1 - t0, e2 - e1, cts - e2)
        overlapped_spec = 0
        for k in range(3):
            tot = sum(ov[k])
            if tot > spans[k] > 0:
                scaled_spec = ov[k][4] * spans[k] // tot
                overlapped_spec += ov[k][4] - scaled_spec
                for j in range(5):
                    ov[k][j] = ov[k][j] * spans[k] // tot
            elif tot > 0 and spans[k] <= 0:
                overlapped_spec += ov[k][4]
                ov[k] = [0] * 5
        vq = ov[0][0] + ov[1][0] + ov[2][0]
        vx = ov[0][1] + ov[1][1] + ov[2][1]
        hs = ov[0][2] + ov[1][2] + ov[2][2]
        fs = ov[0][3] + ov[1][3] + ov[2][3]
        sp = ov[0][4] + ov[1][4] + ov[2][4]
        # a height with NO step rows cannot attribute its wall time to
        # a protocol stage — the unexplained remainder goes to
        # `residual`, not `proposal_wait`, so residual is the honest
        # "no data / decomposition gap" signal rather than a stage
        # that silently absorbs everything
        proposal_wait = (
            max(0, (e1 - t0) - sum(ov[0])) if have_steps else 0
        )
        gossip = max(0, (e2 - e1) - sum(ov[1]))
        apply_ = max(0, (cts - e2) - sum(ov[2]))
        named = proposal_wait + gossip + apply_ + vq + vx + hs + fs + sp
        residual = max(0, dur - named)
        stages_ns = {
            "proposal_wait": proposal_wait,
            "gossip": gossip,
            "verify_queue": vq,
            "verify_execute": vx,
            "hash": hs,
            "spec_exec": sp,
            "wal_fsync": fs,
            "apply": apply_,
            "residual": residual,
        }
        hv = {
            "height": h,
            "node": node,
            "latency_s": round(dur / 1e9, 9),
            "stages": {
                s: round(v / 1e9, 9) for s, v in stages_ns.items()
            },
            "coverage": round((named + residual) / dur, 4),
        }
        if overlapped_fsync or overlapped_spec:
            hv["overlapped"] = {
                "wal_fsync": round(overlapped_fsync / 1e9, 9),
                "spec_exec": round(overlapped_spec / 1e9, 9),
            }
        out[h] = hv
    return out


# budget() memo for the live-ring case: [recorder identity, commit
# tally, result]. sample() runs on every metrics scrape (and health
# tests poll it in tight loops); the per-height decomposition only
# changes when a height CLOSES, so keying the memo on the commit-row
# tally makes every between-commits scrape O(1) instead of a full
# 4096+-slot ring decode. (Overlay rows resolved after a commit carry
# post-commit timestamps, outside every closed window — they cannot
# change a cached view.)
_BUDGET_CACHE: list = [None, -1, None]


def budget(events=None) -> dict:
    """The per-height latency-budget view: ``/debug/budget``'s budget
    body, ``budget.json``'s, and the source of the
    ``height_budget_seconds{stage}`` gauges.  ``events`` defaults to
    the live flight ring (memoized on the ring's commit tally — no new
    commit returns the cached view without re-decoding)."""
    if events is None:
        rec = _REC
        cursor = rec._commits[0]
        if _BUDGET_CACHE[0] is rec and _BUDGET_CACHE[1] == cursor:
            return _BUDGET_CACHE[2]
        evs = rec.dump()
    else:
        rec = None
        evs = events
    per = budget_from_events(evs)
    heights = [per[h] for h in sorted(per)]
    agg = {s: 0.0 for s in BUDGET_STAGES}
    tot = 0.0
    for hv in heights:
        for s in BUDGET_STAGES:
            agg[s] += hv["stages"][s]
        tot += hv["latency_s"]
    out = {
        "commits": len(heights),
        "heights": heights,
        "stages_total_s": {s: round(v, 6) for s, v in agg.items()},
        "stage_fractions": (
            {s: round(v / tot, 4) for s, v in agg.items()}
            if tot > 0
            else None
        ),
        "coverage": (
            round(sum(agg.values()) / tot, 4) if tot > 0 else None
        ),
    }
    if events is None:
        # value slot FIRST: a concurrent reader that matches the key
        # slots below must find the new result, never None/stale
        _BUDGET_CACHE[2] = out
        _BUDGET_CACHE[1] = cursor
        _BUDGET_CACHE[0] = rec
    return out


# ---------------------------------------------------- critical path

# budget stages that are device-plane time — the ``plane`` dimension of
# the critical-path verdict groups them back into their planes
_PLANE_STAGES = {
    "verify": ("verify_queue", "verify_execute"),
    "hash": ("hash",),
}


def critical_path_from_events(events) -> dict[int, dict]:
    """Name, per committed height, the resource that gated the commit.

    Joins three views of the same commit window: the per-height budget
    stage tiles (:func:`budget_from_events` — the coalescer queue waits
    already ride in via the EV_BUDGET overlay rows), the EV_LOCK slow
    lock-wait rows (window-assigned by timestamp, exactly like
    EV_FSYNC), and the device-plane share of the stage tiling.  The
    verdict is ``stage × lock × plane × cpu``: the dominant non-residual
    budget stage, the lock with the largest in-window slow-wait total
    (with the blocking holder's acquire site), the dominant device
    plane, and — when the sampling profiler ran — the subsystem with
    the largest in-window kernel CPU time (EV_PROF window rows: the
    threads' own CPU clocks, so a thread that only waits for the
    interpreter lock or sleeps in C never gates; a commit gated by
    GIL-bound Python in the FSM says ``cpu:consensus``, not
    just ``stage:verify_execute``) — ``gate`` names whichever dimension
    explains the most time.  Pure function of the decoded event stream
    (the postmortem timeline merge reuses it for its per-height
    ``critical_path`` rows)."""
    budgets = budget_from_events(events)
    if not budgets:
        return {}
    # commit window anchors (earliest commit row per height, the same
    # anchor budget_from_events uses) + the EV_LOCK wait rows + the
    # EV_PROF profiler window rows
    anchors: dict[int, tuple] = {}
    lock_rows: list[tuple] = []
    prof_rows: list[tuple] = []
    for ev in events:
        name = ev.get("event")
        if name == "consensus.commit":
            h = ev.get("height", 0)
            if h:
                cur = anchors.get(h)
                if cur is None or ev.get("ts", 0) < cur[0]:
                    anchors[h] = (ev.get("ts", 0), ev.get("dur_ns", 0))
        elif name == "sync.lock":
            if ev.get("kind_name") == "wait":
                lock_rows.append((
                    ev.get("ts", 0), ev.get("lock", "?"),
                    ev.get("dur_ns", 0), ev.get("site", "?"),
                ))
        elif name == "prof.window":
            # the profiler's own thread never gates a commit
            if ev.get("subsystem") != "sampler":
                prof_rows.append((
                    ev.get("ts", 0), ev.get("subsystem", "?"),
                    ev.get("oncpu_ns", 0),
                ))
    out: dict[int, dict] = {}
    for h, bud in budgets.items():
        cts, dur = anchors.get(h, (0, 0))
        if dur <= 0:
            continue
        t0 = cts - dur
        stages = bud["stages"]
        # dominant non-residual stage tile
        stage, stage_s = None, -1.0
        for s, v in stages.items():
            if s != "residual" and v > stage_s:
                stage, stage_s = s, v
        stage_s = max(0.0, stage_s)
        # dominant device plane (its stages' combined tile)
        plane, plane_s = None, 0.0
        for p, names in _PLANE_STAGES.items():
            v = 0.0
            for s in names:
                v += stages.get(s, 0.0)
            if v > plane_s:
                plane, plane_s = p, v
        # hottest lock: largest slow-wait total inside the window
        waits: dict[str, float] = {}
        sites: dict[str, str] = {}
        for ts, lk, d, site in lock_rows:
            if t0 <= ts <= cts:
                waits[lk] = waits.get(lk, 0.0) + d / 1e9
                sites.setdefault(lk, site)
        lock, lock_wait_s = None, 0.0
        for lk, v in waits.items():
            if v > lock_wait_s:
                lock, lock_wait_s = lk, v
        # hottest subsystem by kernel CPU: EV_PROF flush windows are stamped
        # at window END, so a row belongs to the commit window when its
        # flush landed inside it (the per-second granularity matches
        # the ~100 ms-to-seconds commit windows this joins against)
        cpus: dict[str, float] = {}
        for ts, subname, oncpu_ns in prof_rows:
            if t0 <= ts <= cts:
                cpus[subname] = cpus.get(subname, 0.0) + oncpu_ns / 1e9
        cpu, cpu_s = None, 0.0
        for subname, v in cpus.items():
            if v > cpu_s:
                cpu, cpu_s = subname, v
        gate, gate_s = f"stage:{stage}", stage_s
        if lock is not None and lock_wait_s > gate_s:
            gate, gate_s = f"lock:{lock}", lock_wait_s
        if plane is not None and plane_s > gate_s:
            gate, gate_s = f"plane:{plane}", plane_s
        if cpu is not None and cpu_s > gate_s:
            gate, gate_s = f"cpu:{cpu}", cpu_s
        out[h] = {
            "height": h,
            "node": bud.get("node"),
            "latency_s": bud["latency_s"],
            "coverage": bud["coverage"],
            "stage": stage,
            "stage_s": round(stage_s, 6),
            "lock": lock,
            "lock_wait_s": round(lock_wait_s, 6),
            "lock_site": sites.get(lock) if lock else None,
            "plane": plane,
            "plane_s": round(plane_s, 6),
            "cpu": cpu,
            "cpu_s": round(cpu_s, 6),
            "gate": gate,
        }
    return out


def critical_path(events=None) -> dict:
    """The per-height critical-path view: the ``/debug/contention``
    and ``contention.json`` verdict body.  ``events`` defaults to the
    live flight ring."""
    per = critical_path_from_events(
        _REC.dump() if events is None else events
    )
    heights = [per[h] for h in sorted(per)]
    gates: dict[str, int] = {}
    cov = 0.0
    for hv in heights:
        gates[hv["gate"]] = gates.get(hv["gate"], 0) + 1
        cov += hv["coverage"]
    return {
        "commits": len(heights),
        "heights": heights,
        "gates": dict(sorted(gates.items(), key=lambda kv: -kv[1])),
        "coverage": round(cov / len(heights), 4) if heights else None,
    }


def acquire() -> None:
    """Reference-counted enable for node lifecycles (the devstats
    pattern): every booting node acquires, so the recorder is on exactly
    while a node runs — unless ``COMETBFT_TPU_HEALTH=0`` pins it off."""
    global _acquirers, _enabled
    if _env_mode() == "off":
        return
    _acquirers += 1
    _enabled = True


def release() -> None:
    global _acquirers, _enabled
    _acquirers = max(0, _acquirers - 1)
    if _acquirers == 0 and _env_mode() != "on":
        _enabled = False


def monitor_enabled() -> bool:
    """Whether a booting node should start a HealthMonitor (watchdogs
    ride the same kill switch as the recorder)."""
    return _env_mode() != "off"


def record(
    code: int, height: int = 0, round_: int = 0, a: int = 0, b: int = 0
) -> None:
    """Record one flight event.  Allocation-free and lock-free; a
    single flag check when the recorder is off."""
    if not _enabled:
        return
    _REC.record(code, height, round_, a, b)


def recorder() -> FlightRecorder:
    return _REC


def slis() -> dict:
    return _REC.slis()


def note_breaker_trip() -> None:
    """crypto/coalesce hook: the half-open breaker tripped (wedged
    verify executor).  Records the ring event and leaves a notice the
    wedged-coalescer watchdog converts into a trip on its next check.
    Takes no lock — the caller may sit close to engine mutexes."""
    _BREAKER_NOTICES[0] = _BREAKER_NOTICES[0] + 1
    record(EV_BREAKER, a=1)


def note_breaker_rearm() -> None:
    """crypto/coalesce hook: a successful half-open probe re-armed
    routing."""
    record(EV_BREAKER, a=0)


# ------------------------------------------------------------- watchdogs

# HealthMonitor._st slot indices (array('d') state vector: the no-trip
# check path must retain nothing, so every mutable scalar lives in
# preallocated storage)
_ST_PROGRESS_BASE = 0  # stall baseline (monotonic)
_ST_STORM_BASE = 1  # recompile count at the storm window start
_ST_STORM_T0 = 2  # storm window start (monotonic)
_ST_BREAKER_SEEN = 3  # breaker notices already converted to trips
_ST_STORM_TRIP_T = 4  # last storm trip (monotonic; drives storm_active)
_ST_LAST_BUNDLE = 5  # last bundle write (monotonic; rate limit)
_ST_STALLED = 6  # 1.0 while the stall detector considers us stalled
# the saturation watchdog's counters live in a separate int vector
# (``_qfull``: [drops already seen, consecutive-fresh-drop streak]) —
# keeping them out of the float ``_st`` array matters: float temporaries
# land on CPython's float free-list, which tracemalloc counts as LIVE
# blocks attributed to the arithmetic line, tripping the pinned
# allocation-free guard whenever an earlier test perturbed the free-list
_QF_SEEN = 0
_QF_STREAK = 1
_ST_DISK_DEGRADED = 7  # 1.0 while the wired WAL reports disk_degraded
# tx-starvation slots: ring commit tally already seen, monotonic of the
# last observed tally advance, inter-commit interval EWMA (seconds),
# and the edge-trigger episode flag
_ST_TX_SEEN = 8
_ST_TX_LAST_T = 9
_ST_TX_INTERVAL = 10
_ST_TX_STARVED = 11


class HealthMonitor(BaseService):
    """Background watchdog thread over the flight recorder.

    One instance per node (node/node.py starts it alongside the
    Prometheus exporter); ``_check()`` is a pure, allocation-free
    evaluation so tests (and the tracemalloc guard) can drive it
    directly without the thread.
    """

    def __init__(
        self,
        metrics=None,
        stall_base_s: float = 4.0,
        stall_mult: float | None = None,
        bundle_dir: str | None = None,
        bundle_rl_s: float | None = None,
        bundle_keep: int = DEFAULT_BUNDLE_KEEP,
        storm_recompiles: int = STORM_RECOMPILES,
        storm_window_s: float = STORM_WINDOW_S,
        saturation_streak: int = SATURATION_STREAK,
        lock_wait_s: float | None = None,
        starve_s: float | None = None,
        starve_share: float = STARVE_LANE_SHARE,
        starve_min_lanes: int = STARVE_MIN_LANES,
        tx_starve_commits: float | None = None,
        interval_s: float | None = None,
        trace_tail: int = 512,
        idle_ok=None,
        disk_degraded_fn=None,
        logger=None,
    ):
        super().__init__("HealthMonitor", logger)
        self.metrics = metrics
        # disk_degraded_fn: zero-arg bool — the slow-disk watchdog's
        # signal, wired by node/node.py to the consensus WAL's fsync
        # EWMA state (consensus/wal.py disk_degraded()). A trip fires
        # on each False->True transition (per-episode, not per-tick);
        # None (bare harnesses, NopWAL nodes) disables the watchdog.
        self._disk_degraded = disk_degraded_fn
        # idle_ok: zero-arg callable consulted when the stall window
        # expires — True means the silence is LEGITIMATE (the node is
        # still block-syncing, or create_empty_blocks=False with an
        # empty mempool leaves the FSM intentionally parked), so the
        # window re-baselines without a trip. node/node.py wires this
        # to its own sync/mempool state; None = every silence is a
        # stall (bare consensus harnesses, tests).
        self._idle_ok = idle_ok
        self.bundle_keep = bundle_keep
        mult = (
            stall_mult
            if stall_mult is not None
            else _env_float(_ENV_STALL_MULT, DEFAULT_STALL_MULT)
        )
        self.stall_after_s = max(0.05, stall_base_s * mult)
        self.bundle_dir = os.environ.get(_ENV_BUNDLE_DIR) or bundle_dir
        self.bundle_rl_s = (
            bundle_rl_s
            if bundle_rl_s is not None
            else _env_float(_ENV_BUNDLE_RL, DEFAULT_BUNDLE_RL_S)
        )
        self.storm_recompiles = storm_recompiles
        self.storm_window_s = storm_window_s
        self.saturation_streak = max(1, saturation_streak)
        self.interval_s = (
            interval_s
            if interval_s is not None
            else max(0.05, min(1.0, self.stall_after_s / 4.0))
        )
        self.trace_tail = trace_tail
        # trip tallies per watchdog (trip paths may allocate)
        self.trips = {name: 0 for name, _ in _WATCHDOGS}
        self.bundles = 0
        self._thread: threading.Thread | None = None
        # tx-starvation config + the txtrace handle (resolved once at
        # construction — the per-tick check must not run the import
        # machinery; health cannot top-import txtrace, which imports
        # this module for the ring clock and EV_TX recording)
        from . import txtrace as libtxtrace

        self._txtrace = libtxtrace
        self.tx_starve_commits = (
            tx_starve_commits
            if tx_starve_commits is not None
            else libtxtrace.starve_commits()
        )
        # preallocated scalar state — see the _ST_* index comments
        self._st = array("d", [0.0] * 12)
        now = time.monotonic()
        self._st[_ST_PROGRESS_BASE] = now
        self._st[_ST_STORM_T0] = now
        self._st[_ST_STORM_BASE] = float(self._recompile_total())
        self._st[_ST_BREAKER_SEEN] = float(_BREAKER_NOTICES[0])
        # commits that predate this monitor must not feed the
        # inter-commit interval estimate (the lane-watermark posture)
        self._st[_ST_TX_SEEN] = float(_REC._commits[0])
        # drops that predate this monitor must not count toward a streak
        self._qfull = array("q", [0, 0])
        self._qfull[_QF_SEEN] = libnetstats.consensus_queue_full_total()
        # -- consensus-starvation state (preallocated, the _qfull
        # posture): [prev consensus lanes, prev total lanes, starved
        # flag]; the windowed queue-wait bucket watermarks allocate
        # lazily on the first window that reaches starve_min_lanes —
        # never on the steady no-traffic path the tracemalloc guard
        # drives. ``starve_s <= 0`` disables the watchdog.
        self.starve_s = (
            starve_s
            if starve_s is not None
            else libdevledger.starve_threshold_s()
        )
        self.starve_share = starve_share
        self.starve_min_lanes = max(1, starve_min_lanes)
        self._sv = array("q", [0, 0, 0])
        cons0, total0 = libdevledger.verify_lanes_split()
        self._sv[0] = cons0  # lanes that predate this monitor don't count
        self._sv[1] = total0
        # -- lock-contention state (preallocated): the lockprof wait-
        # histogram watermark the windowed p99 deltas run against, plus
        # [consecutive-hot-window streak, last hot slot]. The seeding
        # call advances the watermark so contention that predates this
        # monitor cannot replay as a fresh trip (the lane posture).
        # ``lock_wait_s <= 0`` disables the watchdog.
        self.lock_wait_s = (
            lock_wait_s
            if lock_wait_s is not None
            else liblockprof.slow_threshold_s()
        )
        self._lk_hist = array(
            "q", [0] * (liblockprof.N_SLOTS * liblockprof.N_BUCKETS)
        )
        self._lk = array("q", [0, -1])
        liblockprof.worst_windowed_p99(self._lk_hist)
        self._starve_counts: array | None = None
        if self.starve_s > 0:
            try:
                # same watermark posture as the lanes above: queue-wait
                # observations that predate this monitor must not leak
                # into the first judged window's p99 (the delta would
                # otherwise be computed against a zero baseline and
                # replay an old storm as a fresh trip)
                self._consensus_wait_p99()
            except Exception:
                pass  # no metrics yet: first _check seeds the baseline

    # -- lifecycle ---------------------------------------------------------

    def on_start(self) -> None:
        self._st[_ST_PROGRESS_BASE] = time.monotonic()
        t = threading.Thread(
            target=self._run, name="health-monitor", daemon=True
        )
        # the fallible step FIRST: a failed spawn must leak neither the
        # recorder acquire nor a registry entry
        t.start()
        self._thread = t
        acquire()  # the watchdogs need the recorder's step timeline
        with _mtx:
            _MONITORS.append(self)

    def on_stop(self) -> None:
        with _mtx:
            for i in range(len(_MONITORS) - 1, -1, -1):
                if _MONITORS[i] is self:
                    del _MONITORS[i]
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=2)
        release()

    def _run(self) -> None:
        quit_ev = self.quit_event()
        while not quit_ev.is_set():
            try:
                mask = self._check()
                if mask:
                    self._handle_trips(mask)
            except Exception:
                # a watchdog fault must never take the monitor down
                import traceback

                traceback.print_exc()
            quit_ev.wait(self.interval_s)

    # -- evaluation --------------------------------------------------------

    def _recompile_total(self) -> int:
        """Current ``xla_recompile_total`` from the devstats ledger
        (drains staged compiles — a read path, like every scrape)."""
        from . import devstats as libdevstats

        return libdevstats.counters()["recompiles"]

    def _check(self) -> int:
        """One watchdog evaluation; returns a bitmask of FRESH trips.

        Allocation-free on the no-trip path (pinned by the tracemalloc
        guard): all mutable state lives in the preallocated ``_st``
        vector, and the mask is a small int.
        """
        st = self._st
        now = time.monotonic()
        mask = 0
        # -- consensus stall: no step transition within the window
        last_step = _REC._last[EV_STEP]
        base = st[_ST_PROGRESS_BASE]
        progress = last_step if last_step > base else base
        if now - progress > self.stall_after_s:
            # a legitimately idle node (syncing, or intentionally
            # parked waiting for txs) re-baselines without a trip —
            # only consulted at window expiry, never on the hot path
            if self._idle_ok is not None:
                try:
                    idle = bool(self._idle_ok())
                except Exception:
                    idle = False
            else:
                idle = False
            # re-baseline: one evaluation per window, not per tick
            st[_ST_PROGRESS_BASE] = now
            if idle:
                st[_ST_STALLED] = 0.0
            else:
                mask |= 1
                st[_ST_STALLED] = 1.0
        elif last_step > base:
            st[_ST_STALLED] = 0.0  # progress resumed
        # -- wedged coalescer: breaker notices since the last check
        notices = _BREAKER_NOTICES[0]
        if notices > st[_ST_BREAKER_SEEN]:
            st[_ST_BREAKER_SEEN] = float(notices)
            mask |= 2
        # -- recompile storm: ledger delta inside a rolling window
        cur = self._recompile_total()
        if now - st[_ST_STORM_T0] > self.storm_window_s:
            st[_ST_STORM_T0] = now
            st[_ST_STORM_BASE] = float(cur)
        elif cur - st[_ST_STORM_BASE] >= self.storm_recompiles:
            mask |= 4
            st[_ST_STORM_TRIP_T] = now
            st[_ST_STORM_T0] = now
            st[_ST_STORM_BASE] = float(cur)
        # -- saturated consensus send queue: MConnection.send drops on
        # a consensus channel in SATURATION_STREAK consecutive checks —
        # a full queue that stays full is a peer that stopped draining
        # (or a reactor wedged behind it), not a burst (int-only math:
        # see the _qfull vector comment above)
        qf = self._qfull
        qfull = libnetstats.consensus_queue_full_total()
        if qfull > qf[_QF_SEEN]:
            qf[_QF_STREAK] += 1
            if qf[_QF_STREAK] >= self.saturation_streak:
                mask |= 8
                qf[_QF_STREAK] = 0
        else:
            qf[_QF_STREAK] = 0
        qf[_QF_SEEN] = qfull
        # -- slow disk: the wired WAL's fsync-latency EWMA crossed its
        # degradation threshold (consensus/wal.py hysteresis). Trip on
        # the False->True EDGE only — degradation is an episode, and
        # the widened propose timeouts keep the chain live through it;
        # a raising probe fails toward alerting (degraded=True).
        if self._disk_degraded is not None:
            try:
                degraded = bool(self._disk_degraded())
            except Exception:
                degraded = True
            if degraded and st[_ST_DISK_DEGRADED] == 0.0:
                mask |= 16
            st[_ST_DISK_DEGRADED] = 1.0 if degraded else 0.0
        # -- consensus starvation: consensus-caller verify queue-wait
        # p99 (windowed from the device_queue_wait_seconds buckets)
        # above the threshold WHILE other callers dominate the lane
        # share of the same window. Judged only once the ledger saw
        # starve_min_lanes fresh lanes — an idle or lightly-loaded
        # plane is never starved, and the no-traffic check path stays
        # allocation-free. Edge-triggered per episode like slow_disk.
        if self.starve_s > 0:
            sv = self._sv
            cons, total = libdevledger.verify_lanes_split()
            d_total = total - sv[1]
            if d_total >= self.starve_min_lanes:
                d_cons = cons - sv[0]
                sv[0] = cons
                sv[1] = total
                others = d_total - d_cons
                dominate = others >= d_total * self.starve_share
                p99 = self._consensus_wait_p99()
                if dominate and p99 > self.starve_s:
                    if sv[2] == 0:
                        mask |= 32
                    sv[2] = 1
                else:
                    sv[2] = 0
        # -- tx starvation: the oldest admitted-uncommitted tx is older
        # than N measured commit intervals WHILE heights keep
        # committing. The interval EWMA comes from the ring's commit
        # tally (pre-monitor commits excluded at ctor); "keeps
        # committing" = the tally advanced within the starve window
        # itself, so a dead chain stays the stall watchdog's case.
        # Edge-triggered per episode like slow_disk.
        if self.tx_starve_commits > 0:
            cur_c = _REC._commits[0]
            seen_c = st[_ST_TX_SEEN]
            if cur_c > seen_c:
                t_last = st[_ST_TX_LAST_T]
                if t_last > 0:
                    iv = (now - t_last) / (cur_c - seen_c)
                    ew = st[_ST_TX_INTERVAL]
                    st[_ST_TX_INTERVAL] = (
                        iv if ew == 0.0 else 0.75 * ew + 0.25 * iv
                    )
                st[_ST_TX_LAST_T] = now
                st[_ST_TX_SEEN] = float(cur_c)
            interval = st[_ST_TX_INTERVAL]
            if interval > 0:
                window = self.tx_starve_commits * interval
                committing = (
                    st[_ST_TX_LAST_T] > 0
                    and now - st[_ST_TX_LAST_T] <= window
                )
                if (
                    committing
                    and self._txtrace.oldest_admitted_age_s() > window
                ):
                    if st[_ST_TX_STARVED] == 0.0:
                        mask |= 64
                    st[_ST_TX_STARVED] = 1.0
                else:
                    st[_ST_TX_STARVED] = 0.0
        # -- sustained lock contention: the worst registered engine
        # lock's windowed p99 wait (lockprof delta histogram since the
        # last check) at or above the threshold in
        # LOCK_CONTENDED_STREAK consecutive checks. The streak resets
        # on trip, so a wedged lock re-trips once per streak window,
        # not per tick; int-only state (the _qfull posture).
        if self.lock_wait_s > 0:
            lk = self._lk
            slot, p99 = liblockprof.worst_windowed_p99(self._lk_hist)
            if slot >= 0 and p99 >= self.lock_wait_s:
                lk[1] = slot
                lk[0] += 1
                if lk[0] >= LOCK_CONTENDED_STREAK:
                    mask |= 128
                    lk[0] = 0
            else:
                lk[0] = 0
        return mask

    def _consensus_wait_p99(self) -> float:
        """Windowed p99 of the consensus-caller verify queue wait:
        delta of the device_queue_wait_seconds{plane=verify,caller}
        buckets (summed over the consensus caller classes) since the
        last judged window, through the shared
        libmetrics.quantile_from_buckets estimator."""
        m = self.metrics if self.metrics is not None else (
            libmetrics.node_metrics()
        )
        fam = m.device_queue_wait
        nb = len(fam.buckets) + 1
        prev = self._starve_counts
        if prev is None:
            prev = self._starve_counts = array("q", [0] * nb)
        cur = [0] * nb
        for cid in libdevledger.BUDGET_VERIFY_CALLERS:
            child = fam.labels("verify", libdevledger.caller_name(cid))
            cc = child._counts
            for i in range(nb):
                cur[i] += cc[i]
        delta = [0] * nb
        for i in range(nb):
            delta[i] = cur[i] - prev[i]
            prev[i] = cur[i]
        return libmetrics.quantile_from_buckets(fam.buckets, delta, 0.99)

    def starved(self) -> bool:
        """Last-observed consensus-starvation state."""
        return self._sv[2] != 0

    def tx_starved(self) -> bool:
        """Last-observed tx-starvation state (inclusion broken while
        the chain keeps committing)."""
        return self._st[_ST_TX_STARVED] != 0.0

    def hot_lock(self) -> str | None:
        """The registered lock the contention watchdog most recently
        flagged as over-threshold (None until a window crosses it)."""
        slot = self._lk[1]
        return liblockprof.slot_name(slot) if slot >= 0 else None

    def stalled(self) -> bool:
        return self._st[_ST_STALLED] != 0.0

    def disk_degraded(self) -> bool:
        """Last-observed slow-disk state (updated each check tick)."""
        return self._st[_ST_DISK_DEGRADED] != 0.0

    def storm_active(self) -> bool:
        t = self._st[_ST_STORM_TRIP_T]
        return bool(t) and time.monotonic() - t < self.storm_window_s

    # -- trip handling -----------------------------------------------------

    def _handle_trips(self, mask: int) -> None:
        m = self.metrics if self.metrics is not None else (
            libmetrics.node_metrics()
        )
        names = [name for name, bit in _WATCHDOGS if mask & bit]
        for name, bit in _WATCHDOGS:
            if not mask & bit:
                continue
            self.trips[name] += 1
            m.health_watchdog_trips.labels(name).inc()
            record(EV_WATCHDOG, a=bit)
            if self.logger is not None:
                self.logger.error(
                    "health watchdog tripped",
                    watchdog=name,
                    stall_after_s=round(self.stall_after_s, 3),
                )
        path = self._maybe_bundle("-".join(names), m)
        if path is not None and self.logger is not None:
            self.logger.error("black-box bundle written", path=path)

    def _maybe_bundle(self, reason: str, m) -> str | None:
        """Write one black-box bundle unless the rate limit forbids it.
        The check-and-set runs under ``libs.health._mtx``; all file I/O
        happens after release (the mutex stays a blocking-free leaf)."""
        if not self.bundle_dir:
            return None
        now = time.monotonic()
        with _mtx:
            last = self._st[_ST_LAST_BUNDLE]
            if last and now - last < self.bundle_rl_s:
                return None
            self._st[_ST_LAST_BUNDLE] = now
        try:
            path = write_bundle(
                self.bundle_dir, reason,
                metrics=self.metrics, trace_tail=self.trace_tail,
            )
        except Exception:
            import traceback

            traceback.print_exc()
            return None
        prune_bundles(self.bundle_dir, self.bundle_keep)
        self.bundles += 1
        m.health_bundles.inc()
        return path

    def status(self) -> dict:
        return {
            "running": self.is_running(),
            "stall_after_s": round(self.stall_after_s, 3),
            "interval_s": round(self.interval_s, 3),
            "stalled": self.stalled(),
            "storm_active": self.storm_active(),
            "disk_degraded": self.disk_degraded(),
            "consensus_starved": self.starved(),
            "tx_starved": self.tx_starved(),
            "tx_starve_commits": round(self.tx_starve_commits, 2),
            "starve_threshold_s": round(self.starve_s, 4),
            "lock_wait_s": round(self.lock_wait_s, 4),
            "hot_lock": self.hot_lock(),
            "trips": dict(self.trips),
            "bundles": self.bundles,
            "bundle_dir": self.bundle_dir,
            "bundle_rl_s": self.bundle_rl_s,
            "bundle_keep": self.bundle_keep,
        }


# registry of running monitors (stack semantics like libs/metrics'
# node-metrics stack: the most recent running monitor answers
# process-wide queries; pops are by identity)
_MONITORS: list[HealthMonitor] = []


def active_monitor() -> HealthMonitor | None:
    # lock-free read (tuple snapshot, like crypto/coalesce._ACTIVE):
    # the scrape path consults this and must never touch _mtx — only
    # the start/stop writers serialize on it
    mons = tuple(_MONITORS)
    return mons[-1] if mons else None


# --------------------------------------------------------- black-box dump


def write_bundle(
    dir_: str, reason: str, metrics=None, trace_tail: int = 512
) -> str:
    """Write one black-box bundle directory and return its path.

    Contents: ``manifest.json`` (reason + SLI snapshot), ``flight.json``
    (the decoded flight-recorder ring), ``devstats.json`` (the XLA/device
    telemetry snapshot), ``locks.json`` (deadlock-tier status + every
    thread's held lock-order stack), ``threads.txt`` (all thread
    stacks), ``trace.json`` (tracer status + ring tail).
    """
    safe = "".join(c if (c.isalnum() or c in "-_") else "-" for c in reason)
    path = os.path.join(dir_, f"health-{time.time_ns()}-{safe}")
    os.makedirs(path, exist_ok=True)

    def save(name: str, obj) -> None:
        try:
            with open(os.path.join(path, name), "w") as f:
                if isinstance(obj, str):
                    f.write(obj)
                else:
                    json.dump(obj, f, indent=1, default=str)
        except Exception as e:
            try:
                with open(os.path.join(path, name + ".err"), "w") as f:
                    f.write(repr(e))
            except Exception:
                pass

    save(
        "manifest.json",
        {
            "reason": reason,
            "ts_ns": time.time_ns(),
            "slis": _REC.slis(),
            "ring": _REC.status(),
        },
    )
    save("flight.json", export_ring())
    # the device-time ledger + per-height latency budget: who used the
    # device and where each height's wall time went at the failure edge
    try:
        save(
            "budget.json",
            {"ledger": libdevledger.snapshot(), "budget": budget()},
        )
    except Exception as e:
        save("budget.json.err", repr(e))
    # lock-contention plane + per-height critical path: which mutex the
    # engine waited on and what actually gated each commit, with every
    # thread's blocked-on lock at the failure edge
    try:
        save(
            "contention.json",
            {
                "lockprof": liblockprof.snapshot(),
                "critical_path": critical_path(),
            },
        )
    except Exception as e:
        save("contention.json.err", repr(e))
    # merged cross-node timeline + root-cause attribution: peers' rings
    # are pulled over RPC when COMETBFT_TPU_POSTMORTEM_PEERS names them
    # (reachable or not, the local view is always written) — the knob
    # COMETBFT_TPU_POSTMORTEM=0 skips the pass entirely
    if os.environ.get(_ENV_POSTMORTEM, "").lower() not in _OFF_VALUES:
        try:
            from .. import postmortem as _pm

            save("timeline.json", _pm.bundle_timeline())
        except Exception as e:
            save("timeline.json.err", repr(e))
    # tx-lifecycle plane: in-flight + recently-committed sampled txs
    # and the per-mempool oldest-admitted table — a tx_starved bundle
    # names the starved keys (bounded short prefixes) right here
    try:
        from . import txtrace as libtxtrace

        save("tx.json", libtxtrace.snapshot())
    except Exception as e:
        save("tx.json.err", repr(e))
    try:
        from . import devstats as libdevstats

        save("devstats.json", libdevstats.snapshot())
    except Exception as e:
        save("devstats.json.err", repr(e))
    # sampling-profiler plane: the recent-sample ring covering the
    # seconds BEFORE the trip — what every subsystem was doing (and
    # which lock/queue blocked threads were parked on) at the edge
    try:
        save("profile.json", libprofile.bundle_snapshot())
    except Exception as e:
        save("profile.json.err", repr(e))
    save(
        "locks.json",
        {
            "deadlock_detection": libsync.enabled(),
            "lock_order_mode": libsync.lock_order_mode(),
            "held": {
                str(tid): stack
                for tid, stack in libsync.held_locks_snapshot().items()
            },
        },
    )
    try:
        save("net.json", libnetstats.snapshot())
    except Exception as e:
        save("net.json.err", repr(e))
    try:
        from . import pprof as libpprof

        save("threads.txt", libpprof.thread_dump())
    except Exception as e:
        save("threads.txt.err", repr(e))
    save(
        "trace.json",
        {
            "status": libtrace.status(),
            "events": libtrace.ring_dump()[-trace_tail:],
        },
    )
    return path


def prune_bundles(dir_: str, keep: int) -> None:
    """Bound the ``health-*`` bundle directories in ``dir_`` to ``keep``.

    The rate limit floors the write interval; this bounds the TOTAL on
    disk. Retention favors forensics: the OLDEST bundle (the original
    failure edge) is always kept, and the remaining ``keep - 1`` slots
    hold the newest ones (the still-failing state) — the middle of a
    days-long stall is the least interesting part. ``keep <= 0``
    disables pruning. Names embed ``time.time_ns()``, so the
    lexicographic sort is the chronological one."""
    if keep <= 0:
        return
    try:
        names = sorted(
            n for n in os.listdir(dir_) if n.startswith("health-")
        )
    except OSError:
        return
    if len(names) <= keep:
        return
    doomed = names[1:] if keep == 1 else names[1 : -(keep - 1)]
    for n in doomed:
        shutil.rmtree(os.path.join(dir_, n), ignore_errors=True)


# ------------------------------------------------------ SLO/health engine


def sample(metrics=None) -> dict:
    """Pull-time SLI computation: derive the ``health_*`` gauges and the
    composite score into ``metrics`` (the scraped node's NodeMetrics) or
    the process-wide top.  Touches NO flight-recorder lock (there is
    none) and no engine mutex — safe on every scrape path."""
    m = metrics if metrics is not None else libmetrics.node_metrics()
    s = _REC.slis()
    from ..crypto import coalesce as crypto_coalesce

    breaker_open = crypto_coalesce.breaker_open()
    mon = active_monitor()
    stalled = False
    storm = False
    disk_degraded = False
    tx_starved = False
    if mon is not None:
        storm = mon.storm_active()
        disk_degraded = mon.disk_degraded()
        tx_starved = mon.tx_starved()
        age = s["step_age_s"]
        stalled = mon.stalled() or (
            age is not None and age > mon.stall_after_s
        )
    lat = s["commit_latency_s"]
    if lat["p50"] is not None:
        m.health_commit_latency.labels("p50").set(lat["p50"])
        m.health_commit_latency.labels("p99").set(lat["p99"])
        m.health_commit_latency.labels("last").set(lat["last"])
    if s["rounds_per_height"] is not None:
        m.health_rounds_per_height.set(s["rounds_per_height"])
    if s["wal_fsync_p99_s"] is not None:
        m.health_wal_fsync.set(s["wal_fsync_p99_s"])
    wait_p99 = histogram_quantile(m.coalesce_wait_seconds, 0.99)
    m.health_verify_wait_p99.set(wait_p99)
    m.health_breaker_open.set(1.0 if breaker_open else 0.0)
    if s["step_age_s"] is not None:
        m.health_stall_seconds.set(s["step_age_s"])
    gossip_lag = libnetstats.gossip_lag_s()
    m.health_gossip_lag.set(gossip_lag)
    # tx-lifecycle plane bridge: completed sampled txs observe into
    # the tx histograms from per-registry watermarks, and the
    # mempool_oldest_age_seconds gauge reads the live mempools
    # (libs/txtrace.sample — lazy import: txtrace imports this module
    # for the ring clock and EV_TX recording)
    from . import txtrace as libtxtrace

    libtxtrace.sample(m)
    # device-time ledger bridge + the latest height's latency budget
    # (gauges carry the most recent fully-decomposed height; the full
    # per-height table lives on /debug/budget and in budget.json)
    libdevledger.sample(m)
    # lock-contention bridge: per-lock wait/hold/contended counters
    # from per-registry watermarks (libs/lockprof)
    liblockprof.sample(m)
    # sampling-profiler bridge: per-(subsystem, state) sample counters
    # into profile_samples_total from per-registry watermarks
    libprofile.sample(m)
    bud = budget()
    if bud["heights"]:
        last_stages = bud["heights"][-1]["stages"]
        for stage in BUDGET_STAGES:
            m.height_budget.labels(stage).set(last_stages[stage])
    # composite score: 1.0 healthy; a stall zeroes it (liveness lost);
    # an open breaker or an active recompile storm each cost 0.3, a
    # degraded disk or a starved tx 0.2 each (degraded but live — the
    # chain still commits) — documented in docs/observability.md
    if stalled:
        score = 0.0
    else:
        score = 1.0
        if breaker_open:
            score -= 0.3
        if storm:
            score -= 0.3
        if disk_degraded:
            score -= 0.2
        if tx_starved:
            score -= 0.2
        score = max(0.0, score)
    m.health_score.set(score)
    return {
        "score": round(score, 3),
        "stalled": stalled,
        "breaker_open": breaker_open,
        "recompile_storm": storm,
        "disk_degraded": disk_degraded,
        "tx_starved": tx_starved,
        "verify_wait_p99_s": wait_p99,
        "gossip_lag_p99_s": round(gossip_lag, 6),
        **s,
    }


def debug_budget_json() -> str:
    """Body of the pprof server's ``/debug/budget`` route: the
    device-time ledger (per-caller attribution + occupancy +
    reconciliation) and the per-height latency budget."""
    return json.dumps(
        {
            "ledger": libdevledger.snapshot(),
            "budget": budget(),
        },
        default=str,
    )


def debug_contention_json() -> str:
    """Body of the pprof server's ``/debug/contention`` route: the
    per-lock contention ledger (libs/lockprof), the per-height
    critical-path verdicts, and every thread's held/blocked-on lock
    state."""
    mon = active_monitor()
    return json.dumps(
        {
            "lockprof": liblockprof.snapshot(),
            "critical_path": critical_path(),
            "hot_lock": mon.hot_lock() if mon is not None else None,
            "threads": {
                str(tid): info
                for tid, info in libsync.held_locks_snapshot().items()
            },
        },
        default=str,
    )


def debug_health_json(tail: int = 100) -> str:
    """Body of the pprof server's ``/debug/health`` route."""
    mon = active_monitor()
    out = {
        "enabled": _enabled,
        "ring": _REC.status(),
        "health": sample(),
        "watchdogs": mon.status() if mon is not None else None,
        "events": _REC.dump()[-tail:],
    }
    return json.dumps(out, default=str)
