"""Continuous sampling profiler plane: on-CPU/off-CPU flame data with
subsystem attribution (reference: the net/http/pprof CPU profile the Go
node ships as a first-class operator tool — node/node.go:651-664 — here
rebuilt for a GIL-bound Python engine where *which subsystem holds the
interpreter* and *which lock a thread is parked on* are the questions).

A sampler thread (``prof-sampler``) reads ``sys._current_frames()`` at
``COMETBFT_TPU_PROF_HZ`` (default ~67 Hz, off the round numbers so the
sampler never phase-locks with 10 ms/100 ms engine timers) and folds
every thread's stack into an interned frame table.  Its cost follows the
threads that moved, not the threads that exist: a thread whose leaf
frame is the same object at the same instruction as on the last tick
costs one dict lookup (no walk), and its samples extend one *run* — one
ring row, one aggregate key — until it moves.  Each sample carries:

* a **subsystem** — resolved from the engine's stable thread names
  (``cs-receive`` → consensus, ``mconn-send`` → p2p, ``verify-coalescer``
  → coalescer, ...) with a frame-module fallback for unnamed threads,
  the same resolver ``/debug/pprof/goroutine`` uses for its dump rows;
* a **state** — ``on_cpu`` vs ``blocked``, where blocked is classified
  by (a) libs/sync's per-thread blocked-on registry (a contended
  ``Mutex.acquire`` names the registered lock → ``lock:<name>``), then
  (b) a leaf-frame wait-site registry: ``threading.Condition/Event``
  waits resolve through their caller (coalescer ticket waits, hash-plane
  tickets, executor condition loops), ``selectors``/socket receives,
  ``queue.get``, and the WAL fsync — so off-CPU samples name *which
  lock or queue* a thread was parked on, not just "blocked".

Surfaces (the house plane pattern throughout):

* ``/debug/pprof/profile?seconds=N`` — flamegraph-compatible collapsed
  stacks (``subsystem;state[;wait];root;...;leaf N``) or ``&format=json``;
  without ``seconds`` it serves the bounded recent-sample ring, which is
  how watchdog black-box bundles and ``cometbft-tpu debug dump`` capture
  ``profile.json`` covering the seconds *before* a trip.
* ``profile_samples_total{subsystem,state}`` counters, bridged at scrape
  from lock-free columns by :func:`sample` (libs/health.sample calls it
  next to the txtrace/devledger/lockprof bridges, the consensus receive
  routine once a drain).
* ``thread_cpu_seconds_total{role}``: the kernel's CPU clock of every
  live thread (``pthread_getcpuclockid``), read by the sampler at 4 Hz
  and at stop — never on an engine thread — and summed by role (the
  thread's name without its peer-id suffix, :func:`role_of`), bridged by
  the same :func:`sample`.  Unlike a sample's state it does not count a
  thread waiting for the interpreter lock, or asleep in C, as working.
* EV_PROF flight-ring rows (~1/s per active subsystem: the subsystem's
  kernel CPU over the window and its samples) feeding
  ``health.critical_path()`` — a commit window gated by GIL-bound Python
  says ``cpu:<subsystem>`` — and the ``cpu_saturated`` postmortem
  detector (cometbft_tpu/postmortem/attribute.py).
* :func:`module_shares` — the simnet ``--profile`` report splitting a
  scenario run's wall time into scheduler vs verify vs engine, the
  measurement the parallel-DES ROADMAP item needs.

Like every plane: ``COMETBFT_TPU_PROF`` kill switch (0 pins off, 1 pins
on, default auto — on while an acquirer holds it), devstats-style
``acquire()``/``release()`` refcount with leak-safe node-boot unwind,
an allocation-free *disabled* path (no sampler thread exists, the
record-free module touches nothing — pinned by the tracemalloc guard in
tests/test_observability.py; the *enabled* sampler may allocate while
interning, and attributes that cost to its own ``sampler`` subsystem),
and one mutex (``libs.profile._mtx``) that serializes only setup paths
(enable/disable/refcount), never a sample, registered in lockorder.json
and asserted edge-free in tests/test_lint_graph.py.

Known limitation (documented in docs/observability.md): a thread inside
a C call that leaves no Python frame (``time.sleep``, a builtin socket
recv whose caller is not in the wait-site registry), or waiting for the
interpreter lock, *samples* as on-CPU at its caller's leaf frame — the
registry names the engine's known wait sites, not every stdlib sleep.
The CPU a thread used is the kernel's clock above, not the samples.
"""

from __future__ import annotations

import itertools
import os
import re
import sys
import threading
import time
from array import array

from . import sync as libsync

# NOTE: this module imports NOTHING from the health layer at module
# level — libs/health imports it for EV_PROF decode and the scrape
# bridge, so the one upward call (EV_PROF ring emission) lazily imports
# health on the once-per-second flush path only (the lockprof posture).

_ENV = "COMETBFT_TPU_PROF"
_ENV_HZ = "COMETBFT_TPU_PROF_HZ"
_ENV_RING = "COMETBFT_TPU_PROF_RING"

_ON_VALUES = ("1", "on", "true", "yes")
_OFF_VALUES = ("0", "off", "false", "no")

# ~67 Hz: high enough that a 100 ms commit window holds ~7 samples,
# low enough that a tick (a stack walk for each thread that moved, a
# dict lookup for each that did not; PERF.md gives its price at ~15 and
# ~260 threads) stays a small share of a core; deliberately off
# 50/60/100 Hz so the sampler never aliases against engine timers
# ticking at round rates.
DEFAULT_HZ = 67.0
# recent-sample ring capacity (rows, all threads pooled): a row is one
# thread's run of identical samples, so a parked thread costs one row
# however long it parks and the ring's reach is set by the threads that
# move (docs/observability.md gives it at ~15 and ~260 threads) — the
# "seconds before the trip" a watchdog bundle wants
DEFAULT_RING = 1 << 15
_MAX_DEPTH = 64  # frames walked per stack
_LEAF_PROBE = 6  # leaf frames examined by the wait-site classifier
_MAX_FRAMES = 16384  # interned frame-label cap (overflow -> slot 0)
_MAX_STACKS = 32768  # interned stack cap
_MAX_WAITS = 512  # interned wait-site cap
_FLUSH_NS = 1_000_000_000  # EV_PROF window flush cadence
_CPU_HZ = 4  # thread CPU clocks read at least this often (and at a flush)
# the kernel's per-thread CPU clock (Linux; None: no CPU columns)
_cpuclock = getattr(time, "pthread_getcpuclockid", None)

# -- subsystem vocabulary (indexes are the EV_PROF round-column payload
# and the metric label set; bounded, never caller input) ---------------
SUBSYSTEMS = (
    "unknown",  # 0: no name rule and no engine frame matched
    "consensus",  # FSM + gossip routines + timeout ticker
    "p2p",  # mconn send/recv, switch, pex, suspicion
    "mempool",
    "coalescer",  # verify-coalescer executor + readback
    "hashplane",
    "light",
    "blocksync",
    "rpc",
    "statesync",
    "abci",
    "privval",
    "health",  # health monitor, postmortem peer fetch
    "trace",  # trace file sink
    "load",  # load-generator threads (bench/simnet drivers)
    "simnet",
    "main",  # MainThread (CLI, tests, bench drivers)
    "sampler",  # the profiler's own thread: its overhead is visible
    "other",  # a live thread the engine doesn't own
)
_SUB_IDS = {name: i for i, name in enumerate(SUBSYSTEMS)}
_SUB_SAMPLER = _SUB_IDS["sampler"]
_SUB_UNKNOWN = _SUB_IDS["unknown"]

STATES = ("on_cpu", "blocked")

# thread-name prefix -> subsystem (first match wins; the engine's
# thread names are stable service names, the same seam the lock
# registry and the goroutine dump lean on)
_NAME_PREFIXES = (
    ("prof-sampler", "sampler"),
    ("cs-", "consensus"),
    ("timeout-ticker", "consensus"),
    ("prestage-", "consensus"),
    ("gossip-", "consensus"),
    ("mconn-", "p2p"),
    ("switch-", "p2p"),
    ("pex-", "p2p"),
    ("p2p-", "p2p"),
    ("peer-", "p2p"),
    ("relay-", "p2p"),
    ("mempool", "mempool"),
    ("verify-", "coalescer"),
    ("hash-", "hashplane"),
    ("light-", "light"),
    ("blocksync-", "blocksync"),
    ("rpc-", "rpc"),
    ("statesync", "statesync"),
    ("abci-", "abci"),
    ("privval-", "privval"),
    ("health-", "health"),
    ("pm-fetch-", "health"),
    ("trace-sink", "trace"),
    ("load-", "load"),
    ("sim-", "simnet"),
    ("MainThread", "main"),
)
_NAME_SUFFIXES = (("-http", "rpc"),)  # "{node}-http" RPC listeners

# frame-path fragment -> subsystem, leaf-first fallback for threads the
# name rules don't know (pytest workers, bare threading.Thread targets)
_FRAME_SUBSYSTEMS = (
    ("cometbft_tpu/crypto/coalesce", "coalescer"),
    ("cometbft_tpu/crypto/hashplane", "hashplane"),
    ("cometbft_tpu/consensus/", "consensus"),
    ("cometbft_tpu/p2p/", "p2p"),
    ("cometbft_tpu/mempool", "mempool"),
    ("cometbft_tpu/light/", "light"),
    ("cometbft_tpu/blocksync/", "blocksync"),
    ("cometbft_tpu/rpc/", "rpc"),
    ("cometbft_tpu/statesync/", "statesync"),
    ("cometbft_tpu/abci/", "abci"),
    ("cometbft_tpu/privval/", "privval"),
    ("cometbft_tpu/simnet/", "simnet"),
    ("cometbft_tpu/libs/health", "health"),
)

# (caller-file suffix, caller func or None=any) -> wait-site name, for
# blocked samples whose leaf is a stdlib Condition/Event wait: the
# CALLER names the queue.  Order matters (specific before catch-all).
_WAIT_CALLERS = (
    ("crypto/coalesce.py", "result", "coalesce.ticket"),
    ("crypto/coalesce.py", None, "coalesce.executor"),
    ("crypto/hashplane.py", "result", "hash.ticket"),
    ("crypto/hashplane.py", None, "hash.executor"),
    ("libs/clist.py", None, "clist.wait"),
    ("libs/service.py", None, "service.wait"),
)


def _env_mode() -> str:
    v = os.environ.get(_ENV, "").lower()
    if v in _ON_VALUES:
        return "on"
    if v in _OFF_VALUES:
        return "off"
    return "auto"


def _hz_from_env() -> float:
    try:
        hz = float(os.environ.get(_ENV_HZ, ""))
    except ValueError:
        return DEFAULT_HZ
    return min(1000.0, max(1.0, hz))


def _ring_from_env() -> int:
    try:
        n = int(os.environ.get(_ENV_RING, ""))
    except ValueError:
        return DEFAULT_RING
    return max(256, n)


# ------------------------------------------------------- intern tables
#
# Written ONLY by the sampler thread; readers index append-only lists,
# so a GIL-consistent racy read sees a prefix, never a torn entry.

_frames: list[str] = ["?"]  # idx -> "module.path:func" (0 = overflow)
# keyed by id(code), NOT the code object: code hashing re-hashes the
# bytecode on every lookup (~160ns); an id key is a pointer hash.  The
# id stays valid because _frame_objs pins every interned code object.
_frame_ids: dict = {}  # id(code object) -> idx
_frame_objs: list = [None]  # idx -> code object (strong ref, pins ids)
_frame_meta: list[tuple] = [("", "")]  # idx -> (co_filename, co_name)
_stacks: list[tuple] = [()]  # idx -> frame-idx tuple, LEAF first
_stack_ids: dict = {(): 0}
_waits: list[str] = [""]  # idx -> wait-site name (0 = none / on-CPU)
_wait_ids: dict = {"": 0}
# sid -> (wait site | None, file-fallback subsystem name): both are pure
# functions of the interned stack, so the sampler classifies each
# distinct stack once and the warm tick is a single dict hit per thread
_stack_info: dict = {}
# thread name -> subsystem name | None (the rule scan, memoized)
_name_subs: dict = {}


def _frame_label(code) -> str:
    fn = code.co_filename.replace("\\", "/")
    i = fn.rfind("cometbft_tpu/")
    if i >= 0:
        mod = fn[i:]
    else:
        mod = fn.rsplit("/", 1)[-1]
    if mod.endswith(".py"):
        mod = mod[:-3]
    return f"{mod.replace('/', '.')}:{code.co_name}"


def _intern_frame(code) -> int:
    idx = _frame_ids.get(id(code))
    if idx is None:
        if len(_frames) >= _MAX_FRAMES:
            return 0
        idx = len(_frames)
        _frames.append(_frame_label(code))
        _frame_objs.append(code)
        _frame_meta.append((code.co_filename, code.co_name))
        _frame_ids[id(code)] = idx
    return idx


def _intern_stack(t: tuple) -> int:
    idx = _stack_ids.get(t)
    if idx is None:
        if len(_stacks) >= _MAX_STACKS:
            return 0
        idx = len(_stacks)
        _stacks.append(t)
        _stack_ids[t] = idx
    return idx


def _intern_wait(name: str) -> int:
    idx = _wait_ids.get(name)
    if idx is None:
        if len(_waits) >= _MAX_WAITS:
            return 0
        idx = len(_waits)
        _waits.append(name)
        _wait_ids[name] = idx
    return idx


# ------------------------------------------------- subsystem resolution


def _subsystem_from_name(name: str) -> str | None:
    for prefix, sub in _NAME_PREFIXES:
        if name.startswith(prefix):
            return sub
    for suffix, sub in _NAME_SUFFIXES:
        if name.endswith(suffix):
            return sub
    return None


def _subsystem_from_files(files) -> str | None:
    """Leaf-first scan of frame file paths for an engine module."""
    for fn in files:
        fn = fn.replace("\\", "/")
        for frag, sub in _FRAME_SUBSYSTEMS:
            if frag in fn:
                return sub
        if "cometbft_tpu/" in fn:
            # engine code outside the named packages (libs, types, ...)
            # inherits nothing from the path — keep scanning callers
            continue
    return None


def subsystem_for(tid: int, name: str, frame=None) -> str:
    """The shared thread->subsystem resolver: thread-name rules first,
    then the frame-module fallback when ``frame`` (the thread's current
    frame) is supplied.  ``/debug/pprof/goroutine`` rows and profiler
    samples attribute threads through this one function."""
    sub = _subsystem_from_name(name)
    if sub is not None:
        return sub
    if frame is not None:
        files = []
        f, depth = frame, 0
        while f is not None and depth < _MAX_DEPTH:
            files.append(f.f_code.co_filename)
            f = f.f_back
            depth += 1
        sub = _subsystem_from_files(files)
        if sub is not None:
            return sub
        if files:
            return "other"
    return "unknown"


def subsystem_name(idx: int) -> str:
    """Decode an EV_PROF round-column subsystem index (libs/health)."""
    return SUBSYSTEMS[idx] if 0 <= idx < len(SUBSYSTEMS) else "?"


def wait_name(idx: int) -> str:
    waits = _waits
    return waits[idx] if 0 <= idx < len(waits) else "?"


# ---------------------------------------------------------- thread roles
#
# thread_cpu_seconds_total's label: a thread's name without its peer-id
# or ordinal suffix ("gossip-data-1a2b3c4d" -> "gossip-data", "pm-fetch-3"
# -> "pm-fetch", "Thread-7 (run)" -> "Thread"); at most _MAX_ROLES
# values, "other" (index 0) included, the rest fold into "other".
# Written by the sampler only, append-only like the intern tables.

_MAX_ROLES = 32
_ROLE_SUFFIX = re.compile(r"(-[0-9a-f]{8,}|-\d+(_\d+)*)?( \(.*\))?$")
_ROLE_CHARS = re.compile(r"[^A-Za-z0-9_.\-]")
_roles: list[str] = ["other"]  # role index -> label value
_role_ids: dict = {"other": 0}
_name_roles: dict = {}  # thread name -> role index (memoised, bounded)


def role_of(name: str) -> str:
    """The role a thread of this name is counted under (before the
    cap: :func:`_role_id` folds roles past it into ``other``)."""
    base = name[:_ROLE_SUFFIX.search(name).start()]
    return _ROLE_CHARS.sub("_", base)[:32] or "other"


def _role_id(name: str) -> int:
    idx = _name_roles.get(name)
    if idx is None:
        role = role_of(name)
        idx = _role_ids.get(role)
        if idx is None:
            idx = 0
            if len(_roles) < _MAX_ROLES:
                idx = len(_roles)
                _roles.append(role)
                _role_ids[role] = idx
        if len(_name_roles) < 4096:
            _name_roles[name] = idx
    return idx


# --------------------------------------------------- wait-site registry


def _classify_wait(leaf) -> str | None:
    """Name the wait site from the leaf ``(filename, funcname)`` pairs
    of a blocked-looking stack, or None for on-CPU.  The libs/sync
    blocked-on registry is consulted FIRST by the sampler (it names the
    registered lock exactly); this covers the non-Mutex parks."""
    for i, (fn, func) in enumerate(leaf):
        fn = fn.replace("\\", "/")
        if fn.endswith("threading.py") and func == "wait":
            # a Condition/Event park: the nearest non-threading caller
            # names the queue
            for fn2, func2 in leaf[i + 1:]:
                fn2 = fn2.replace("\\", "/")
                if fn2.endswith("threading.py"):
                    continue
                for suffix, fname, site in _WAIT_CALLERS:
                    if fn2.endswith(suffix) and (
                        fname is None or fname == func2
                    ):
                        return site
                mod = fn2.rsplit("/", 1)[-1]
                return f"cond:{mod[:-3] if mod.endswith('.py') else mod}"
            return "cond:?"
        if fn.endswith("selectors.py") and func == "select":
            return "socket.select"
        if fn.endswith("socketserver.py"):
            return "socket.accept"
        if fn.endswith("queue.py") and func == "get":
            return "queue.get"
        if fn.endswith("consensus/wal.py") and func == "sync":
            return "wal.fsync"
        if "/p2p/" in fn and (
            "recv" in func or "read" in func or func == "accept"
        ):
            return "socket.recv"
    return None


# --------------------------------------------------------- sample store


class _Tables:
    """Preallocated sample columns: the bounded recent-sample ring, the
    per-(subsystem, state) counter vector and the kernel-CPU columns (by
    role and by subsystem) the scrape bridge reads.  Lock-free
    single-writer (the sampler); readers tolerate one torn in-flight
    row via the publish-last stack column (-1 = in progress), the
    flight-recorder discipline.

    A ring row is a *run*: one thread's identical samples at the
    consecutive ticks ``k0, k0 + 1, ...``; ``n`` is the run's sample
    count once it has ended and 0 while it is open (it then reaches the
    last completed tick).  A parked thread costs one row however long it
    parks, and ``tick_ts`` (one stamp a tick) says which of a run's
    samples a reader's ``since`` keeps.  ``busy`` is odd while the
    sampler writes: the seqlock :meth:`_SamplerThread.agg_now` reads
    under."""

    __slots__ = (
        "gen", "capacity", "ts", "tid", "stack", "sub", "state",
        "wait", "k0", "n", "seq", "written", "counts", "ticks",
        "tick_ts", "busy", "cpu_role", "cpu_sub",
    )

    _GEN = itertools.count(1)

    def __init__(self, capacity: int):
        self.gen = next(self._GEN)
        self.capacity = max(256, int(capacity))
        zeros = [0] * self.capacity
        self.ts = array("q", zeros)
        self.tid = array("q", zeros)
        self.stack = array("q", [-1] * self.capacity)
        self.sub = array("q", zeros)
        self.state = array("q", zeros)
        self.wait = array("q", zeros)
        self.k0 = array("q", zeros)
        self.n = array("q", zeros)
        self.seq = itertools.count()
        self.written = array("q", [0])
        self.counts = array("q", [0] * (len(SUBSYSTEMS) * 2))
        self.ticks = array("q", [0])
        self.tick_ts = array("q", zeros)
        self.busy = array("q", [0])
        # kernel CPU ns of the threads, summed by role index and by
        # subsystem index while a sampler ran (monotone: an exited
        # thread keeps its last reading)
        self.cpu_role = array("q", [0] * _MAX_ROLES)
        self.cpu_sub = array("q", [0] * len(SUBSYSTEMS))

    def write(self, ts, tid, sid, sub, state, wid, k0) -> int:
        """Open a run at tick ``k0``; returns its row's sequence number."""
        seq = next(self.seq)
        i = seq % self.capacity
        self.stack[i] = -1  # mark in-progress: readers skip torn rows
        self.ts[i] = ts
        self.tid[i] = tid
        self.sub[i] = sub
        self.state[i] = state
        self.wait[i] = wid
        self.k0[i] = k0
        self.n[i] = 0
        self.stack[i] = sid  # publish last
        if seq >= self.written[0]:
            self.written[0] = seq + 1
        return seq

    def end(self, seq: int, k: int) -> None:
        """End row ``seq``'s run before tick ``k`` (nothing to do once
        the ring has written over the row)."""
        if seq >= self.written[0] - self.capacity:
            i = seq % self.capacity
            self.n[i] = k - self.k0[i]

    def tick_at(self, since_ns: int) -> int:
        """The first retained tick stamped ``since_ns`` or later; 0 when
        every retained tick is (the ring cannot tell what came before)."""
        done = self.ticks[0]
        cap = self.capacity
        lo = max(0, done - cap)
        if since_ns <= 0 or done == 0 or self.tick_ts[lo % cap] >= since_ns:
            return 0
        hi = done
        while lo < hi:
            mid = (lo + hi) // 2
            if self.tick_ts[mid % cap] >= since_ns:
                hi = mid
            else:
                lo = mid + 1
        return lo

    def rows(self, since_ns: int = 0):
        """(ts, tid, stack_id, sub, state, wait_id, samples) oldest-first
        over the filled window, skipping torn rows: ``samples`` counts
        the run's samples at ticks stamped ``since_ns`` or later."""
        w = self.written[0]
        cap = self.capacity
        done = self.ticks[0]
        k_since = self.tick_at(since_ns)
        for s in range(max(0, w - cap), w):
            i = s % cap
            sid = self.stack[i]
            if sid < 0:
                continue
            k0 = self.k0[i]
            n = k0 + (self.n[i] or (done - k0)) - max(k0, k_since)
            if n > 0:
                yield (
                    self.ts[i], self.tid[i], sid,
                    self.sub[i], self.state[i], self.wait[i], n,
                )

    def status(self) -> dict:
        w = self.written[0]
        cap = self.capacity
        done = self.ticks[0]
        # rows are written in tick order: the oldest retained row opened
        # the oldest run the ring still holds
        oldest = self.k0[max(0, w - cap) % cap] if w else done
        return {
            "capacity": cap, "recorded": w, "ticks": done,
            "span_ticks": done - oldest,
        }


_T = _Tables(_ring_from_env())

# cumulative (stack_id, sub, state, wait_id) -> samples of the ENDED part
# of every run (the sampler folds an open run in when it ends and at
# every flush; snapshot_agg adds what is still open); sampler-thread
# writes, snapshot readers copy under the GIL (dict(d) is one C-level
# copy, safe against a concurrent writer)
_agg: dict = {}

_mode = _env_mode()
_acquirers = 0
_hz = _hz_from_env()
_sampler = None  # the running _SamplerThread, None while disabled

# setup paths only (enable/disable/refcount + sampler lifecycle); the
# sample path and every snapshot reader are lock-free — asserted
# edge-free in tests/test_lint_graph.py like the other plane mutexes
_mtx = libsync.Mutex("libs.profile._mtx")


# ------------------------------------------------------------- sampler

# a memo entry (one list per live thread, the sampler's own): the leaf
# frame and its instruction at the last look (the test that the thread
# has not moved), the frames under it, leaf first, the registered lock
# it was parked on, its stack's file-fallback subsystem, its run's
# aggregate key (stack_id, sub, state, wait_id), the tick before which
# the run's samples are folded into the aggregate, and the run's row.
# libs/sync sets and clears a thread's blocked-on cell only inside the
# acquire frame, at other instructions than the blocking call, so an
# unchanged (frame, instruction) pair is an unchanged cell as well.
(
    _M_FRAME, _M_LASTI, _M_CHAIN, _M_LOCK, _M_FSUB, _M_KEY, _M_SETTLED,
    _M_ROW,
) = range(8)
# frames a moved thread's new leaf may stand above a frame the last walk
# saw (a wait re-entered from the same loop: Condition.wait <- a queue's
# wait <- the routine), checked before a walk
_RESUME_PROBE = 4


class _SamplerThread(threading.Thread):
    def __init__(self, hz: float, memo: bool = True):
        super().__init__(name="prof-sampler", daemon=True)
        self.period_ns = int(1e9 / hz)
        # the threads' CPU clocks are read every this many ticks (a
        # reading costs a syscall a thread: 6.3 µs on the chip's host)
        self._cpu_every = max(1, int(hz / _CPU_HZ))
        self._stop_ev = threading.Event()
        # False walks every stack on every tick: the reference the
        # memoised walk is held to (tests)
        self.memo = memo
        self.walks = 0  # stacks walked frame by frame
        # EV_PROF window accumulator: per-(sub, state) samples since
        # the last once-per-second ring flush
        self._win = [0] * (len(SUBSYSTEMS) * 2)
        self._last_flush = time.monotonic_ns()
        # tid -> thread name, refreshed lazily: on a tid we have not
        # seen (new thread) and at every 1 s flush (drops dead tids)
        self._names: dict = {}
        # tid -> memo entry (_M_*). The entry holds the leaf frame
        # object, which keeps a frame that has returned alive until the
        # next tick finds its thread elsewhere
        self._memo: dict = {}
        # Thread -> [CPU clock id, last reading ns, role index, tid]
        self._clocks: dict = {}
        self._cpu_read = False  # the first reading is the baseline
        self._cpu_base: list = []  # cpu_sub at the last flush
        self._t = None  # the tables this sampler's runs live in
        self._me = 0

    def stop(self) -> None:
        self._stop_ev.set()

    def run(self) -> None:
        interval = self.period_ns / 1e9
        while not self._stop_ev.wait(interval):
            try:
                self._tick()
            except Exception:
                # a sampler crash must never take the node with it
                pass
        # a last reading, every run ended and the tail window flushed,
        # so short profiled runs still emit rows
        try:
            self.finish()
        except Exception:
            pass

    def _tick(self, frames: dict | None = None) -> None:
        """One sample of every thread (``frames``: tid -> leaf frame,
        ``sys._current_frames()`` unless a test hands its own)."""
        t = _T
        if t is not self._t:
            # the first tick, or reset() gave fresh tables: the old
            # tables' runs end with them
            self._t = t
            self._memo.clear()
            self._cpu_base = list(t.cpu_sub)
        self._me = threading.get_ident()
        t.busy[0] += 1
        try:
            self._sample(
                t, sys._current_frames() if frames is None else frames
            )
        finally:
            t.busy[0] += 1

    def _sample(self, t: _Tables, frames: dict) -> None:
        k = t.ticks[0]
        now = time.time_ns()
        t.tick_ts[k % t.capacity] = now
        memo = self._memo
        get = memo.get
        memoise = self.memo
        for tid, frame in frames.items():
            m = get(tid)
            if memoise and m is not None:
                if m[0] is frame and m[1] == frame.f_lasti:
                    continue  # where it was: its run goes on
                if self._resumed(tid, frame, m):
                    continue
            self._walk(t, k, now, tid, frame, m)
        if len(memo) != len(frames):
            for tid in [x for x in memo if x not in frames]:
                self._end(t, memo.pop(tid), k)
        t.ticks[0] = k + 1
        mono = time.monotonic_ns()
        flush = mono - self._last_flush >= _FLUSH_NS
        if flush or k % self._cpu_every == 0:
            self._read_cpu(t)
        if flush:
            self._flush(t, mono)

    def _resumed(self, tid: int, frame, m: list) -> bool:
        """Whether a thread that moved stands in the stack it stood in:
        its new frames run the code the old ones ran, down to a frame
        object the last walk saw (it moved inside a function, or
        re-entered a wait from the same loop), and it is parked on the
        lock it was parked on. The run goes on without a walk."""
        chain = m[_M_CHAIN]
        new = []
        f = frame
        for i in range(min(len(chain), _RESUME_PROBE)):
            old = chain[i]
            if f is old:
                cell = libsync._all_blocked.get(tid)
                if (None if cell is None else cell[0]) != m[_M_LOCK]:
                    return False
                chain[:i] = new
                m[_M_FRAME] = frame
                m[_M_LASTI] = frame.f_lasti
                return True
            if f is None or f.f_code is not old.f_code:
                return False
            new.append(f)
            f = f.f_back
        return False

    def _walk(self, t, k, now, tid, frame, m) -> None:
        """Walk the stack of a thread that moved since the last tick (or
        is new), and carry its run on or start another."""
        self.walks += 1
        frame_ids = _frame_ids
        fids = []
        chain = []
        append = fids.append
        keep = chain.append
        f, depth = frame, 0
        while f is not None and depth < _MAX_DEPTH:
            code = f.f_code
            idx = frame_ids.get(id(code))
            if idx is None:
                idx = _intern_frame(code)
            append(idx)
            keep(f)
            f = f.f_back
            depth += 1
        key = tuple(fids)
        sid = _stack_ids.get(key)
        if sid is None:
            sid = _intern_stack(key)
        info = _stack_info.get(sid) if sid else None
        if info is None:
            # first sight of this stack: classify the wait site and the
            # frame-module fallback once, from the interned frame
            # metadata (never re-walk live frame objects)
            meta = _frame_meta
            leaf = [meta[i] for i in fids[:_LEAF_PROBE]]
            files = [meta[i][0] for i in fids]
            info = (
                _classify_wait(leaf),
                _subsystem_from_files(files)
                or ("other" if files else "unknown"),
            )
            if sid:
                _stack_info[sid] = info
        wait_site, files_sub = info
        sub = self._sub_of(tid, files_sub)
        cell = libsync._all_blocked.get(tid)
        lock = None if cell is None else cell[0]
        wait = wait_site if lock is None else "lock:" + lock
        if wait is not None:
            state = 1
            wid = _wait_ids.get(wait)
            if wid is None:
                wid = _intern_wait(wait)
        else:
            state, wid = 0, 0
        akey = (sid, sub, state, wid)
        if m is None:
            self._memo[tid] = [
                frame, frame.f_lasti, chain, lock, files_sub, akey, k,
                t.write(now, tid, sid, sub, state, wid, k),
            ]
            return
        m[_M_FRAME] = frame
        m[_M_LASTI] = frame.f_lasti
        m[_M_CHAIN] = chain
        m[_M_LOCK] = lock
        m[_M_FSUB] = files_sub
        if m[_M_KEY] != akey:
            self._end(t, m, k)
            m[_M_KEY] = akey
            m[_M_SETTLED] = k
            m[_M_ROW] = t.write(now, tid, sid, sub, state, wid, k)
        elif m[_M_ROW] < t.written[0] - t.capacity // 2:
            # the same run, in a row the ring's wrap is coming to: a
            # new row carries it on, so a live thread's recent samples
            # never leave the ring
            t.end(m[_M_ROW], k)
            m[_M_ROW] = t.write(now, tid, sid, sub, state, wid, k)

    def _sub_of(self, tid: int, files_sub: str) -> int:
        if tid == self._me:
            return _SUB_SAMPLER
        names = self._names
        nm = names.get(tid)
        if nm is None:
            names = self._names = {
                th.ident: th.name for th in threading.enumerate()
            }
            nm = names.get(tid, "")
        try:
            subname = _name_subs[nm]
        except KeyError:
            subname = _subsystem_from_name(nm)
            if len(_name_subs) < 4096:
                _name_subs[nm] = subname
        return _SUB_IDS[files_sub if subname is None else subname]

    def _settle(self, t: _Tables, m: list, k: int) -> None:
        """Fold the run's samples before tick ``k`` into the aggregate,
        the counter vector and the EV_PROF window."""
        d = k - m[_M_SETTLED]
        if d > 0:
            key = m[_M_KEY]
            _agg[key] = _agg.get(key, 0) + d
            c = key[1] * 2 + key[2]
            t.counts[c] += d
            self._win[c] += d
            m[_M_SETTLED] = k

    def _end(self, t: _Tables, m: list, k: int) -> None:
        """End the run before tick ``k``."""
        self._settle(t, m, k)
        t.end(m[_M_ROW], k)

    def _read_cpu(self, t: _Tables) -> None:
        """Read every live thread's kernel CPU clock and fold what it
        used since its last reading into the role and subsystem columns.
        The first reading of a sampler is the baseline; a thread first
        seen later counts from its start; one that exits keeps what it
        was last read at."""
        if _cpuclock is None:
            return
        clocks = self._clocks
        memo_get = self._memo.get
        role_ns, sub_ns = t.cpu_role, t.cpu_sub
        count = self._cpu_read
        threads = threading.enumerate()
        for th in threads:
            c = clocks.get(th)
            if c is None:
                try:
                    clk = _cpuclock(th.ident)
                except (OSError, TypeError, ValueError):
                    continue
                c = clocks[th] = [clk, 0, _role_id(th.name), th.ident]
            try:
                ns = time.clock_gettime_ns(c[0])
            except OSError:
                continue  # it exited since enumerate()
            d = ns - c[1]
            if d > 0:
                c[1] = ns
                if count:
                    role_ns[c[2]] += d
                    m = memo_get(c[3])
                    sub_ns[
                        _SUB_UNKNOWN if m is None else m[_M_KEY][1]
                    ] += d
        self._cpu_read = True
        if len(clocks) > len(threads):
            live = set(threads)
            for th in [x for x in clocks if x not in live]:
                del clocks[th]

    def _flush(self, t: _Tables, mono: int) -> None:
        """Fold every open run into the aggregate and emit one EV_PROF
        flight-ring row per subsystem that sampled in the window: r =
        subsystem index, a = the kernel CPU its threads used in the
        window (ns), b = its samples."""
        self._last_flush = mono
        self._names = {th.ident: th.name for th in threading.enumerate()}
        done = t.ticks[0]
        old = t.written[0] - t.capacity // 2
        for tid, m in self._memo.items():
            self._settle(t, m, done)
            if m[_M_ROW] < old or (
                m[_M_KEY][1] != self._sub_of(tid, m[_M_FSUB])
            ):
                # a parked thread's row the ring's wrap is coming to, or
                # a renamed thread: the next tick walks it (a new row
                # for the same run, or a run of its new subsystem)
                m[_M_FRAME] = None
                m[_M_CHAIN] = ()
        win = self._win
        cpu, base = t.cpu_sub, self._cpu_base
        if any(win):
            from . import health  # lazy: health imports this module at top

            if health.enabled():
                for sub in range(len(SUBSYSTEMS)):
                    on, bl = win[sub * 2], win[sub * 2 + 1]
                    if on or bl:
                        health.record(
                            health.EV_PROF, 0, sub,
                            cpu[sub] - base[sub], on + bl,
                        )
            for i in range(len(win)):
                win[i] = 0
        self._cpu_base = list(cpu)

    def finish(self) -> None:
        """A last reading of the threads' clocks, every run ended, the
        tail window flushed: the sampler's last act."""
        t = self._t
        if t is not _T:
            return  # reset() dropped this sampler's tables
        self._me = threading.get_ident()
        t.busy[0] += 1
        try:
            mono = time.monotonic_ns()
            self._read_cpu(t)
            done = t.ticks[0]
            for m in self._memo.values():
                self._end(t, m, done)
            self._memo.clear()
            self._flush(t, mono)
        finally:
            t.busy[0] += 1

    def agg_now(self) -> dict:
        """The cumulative aggregate with the open runs' samples in it,
        read between two ticks (the tables' seqlock), so that a window's
        two snapshots count a parked thread's samples exactly."""
        t = self._t
        for _ in range(200):
            if t is not _T:
                break
            g = t.busy[0]
            if not g & 1:
                agg = dict(_agg)
                done = t.ticks[0]
                pend = [
                    (m[_M_KEY], done - m[_M_SETTLED])
                    for m in list(self._memo.values())
                ]
                if t.busy[0] == g:
                    for key, d in pend:
                        if d > 0:
                            agg[key] = agg.get(key, 0) + d
                    return agg
            time.sleep(0.0002)
        return dict(_agg)


# ------------------------------------------------------ plane lifecycle


def enabled() -> bool:
    """Whether the sampler thread is live."""
    s = _sampler
    return s is not None and s.is_alive()


def _start_locked() -> None:
    global _sampler
    if _sampler is None or not _sampler.is_alive():
        _sampler = _SamplerThread(_hz)
        _sampler.start()


def _stop_locked() -> None:
    global _sampler
    s, _sampler = _sampler, None
    if s is not None:
        s.stop()
        s.join(timeout=2.0)


def enable(hz: float | None = None) -> None:
    """Force the sampler on (tests, bench, the endpoint's live window).
    ``hz`` overrides the sampling rate for the new sampler."""
    global _hz
    if _env_mode() == "off":
        return
    with _mtx:
        if hz is not None and hz != _hz:
            _hz = min(1000.0, max(1.0, float(hz)))
            _stop_locked()
        _start_locked()


def disable() -> None:
    with _mtx:
        _stop_locked()


def acquire() -> None:
    """Reference-counted enable for node lifecycles (the devstats
    pattern): every booting node acquires, so the sampler runs exactly
    while a node does — unless ``COMETBFT_TPU_PROF=0`` pins it off."""
    global _acquirers
    if _env_mode() == "off":
        return
    with _mtx:
        _acquirers += 1
        _start_locked()


def release() -> None:
    global _acquirers
    with _mtx:
        _acquirers = max(0, _acquirers - 1)
        if _acquirers == 0 and _env_mode() != "on":
            _stop_locked()


def reset(capacity: int | None = None) -> None:
    """Drop buffered samples and aggregates (tests, bench windows)."""
    global _T
    with _mtx:
        _T = _Tables(capacity if capacity is not None else _T.capacity)
        _agg.clear()


def status() -> dict:
    s = _sampler
    return {
        "enabled": enabled(),
        "walks": s.walks if s is not None else 0,
        "mode": _env_mode(),
        "hz": _hz,
        "acquirers": _acquirers,
        "ring": _T.status(),
        "frames": len(_frames),
        "stacks": len(_stacks),
        "wait_sites": len(_waits),
    }


# ---------------------------------------------------------- aggregates


def snapshot_agg() -> dict:
    """A point-in-time copy of the cumulative aggregate: (stack_id,
    sub, state, wait_id) -> samples, the runs still open included.  Two
    snapshots subtract into a window (the ``?seconds=N`` endpoint's
    delta)."""
    s = _sampler
    return s.agg_now() if s is not None else dict(_agg)


def delta_agg(before: dict, after: dict) -> dict:
    out = {}
    for k, v in after.items():
        d = v - before.get(k, 0)
        if d > 0:
            out[k] = d
    return out


def collapsed(agg: dict | None = None) -> str:
    """Flamegraph-compatible collapsed stacks, one line per distinct
    (subsystem, state, wait, stack): ``sub;state[;wait];root;..;leaf N``
    — pipe into flamegraph.pl or paste into speedscope as-is."""
    if agg is None:
        agg = snapshot_agg()
    frames, stacks, waits = _frames, _stacks, _waits
    lines = []
    for (sid, sub, state, wid), n in sorted(agg.items()):
        parts = [subsystem_name(sub), STATES[state & 1]]
        if wid:
            parts.append(waits[wid] if wid < len(waits) else "?")
        st = stacks[sid] if sid < len(stacks) else ()
        parts.extend(frames[f] if f < len(frames) else "?" for f in reversed(st))
        lines.append(";".join(parts) + f" {n}")
    return "\n".join(lines) + ("\n" if lines else "")


def profile_dict(agg: dict | None = None) -> dict:
    """The JSON shape of a profile window (the ``&format=json`` body
    and the bundle's ``profile.json`` core): per-(subsystem, state)
    totals plus every distinct stack with its attribution."""
    if agg is None:
        agg = snapshot_agg()
    frames, stacks, waits = _frames, _stacks, _waits
    subs: dict = {}
    out_stacks = []
    for (sid, sub, state, wid), n in sorted(agg.items()):
        sname = subsystem_name(sub)
        st = subs.setdefault(sname, {"on_cpu": 0, "blocked": 0})
        st[STATES[state & 1]] += n
        stk = stacks[sid] if sid < len(stacks) else ()
        out_stacks.append({
            "subsystem": sname,
            "state": STATES[state & 1],
            "wait": (waits[wid] if wid < len(waits) else "?") if wid else None,
            "samples": n,
            "stack": [
                frames[f] if f < len(frames) else "?"
                for f in reversed(stk)
            ],
        })
    return {
        "schema": 1,
        "hz": _hz,
        "samples": sum(agg.values()),
        "subsystems": dict(sorted(subs.items())),
        "stacks": out_stacks,
    }


def recent(last_s: float = 30.0) -> dict:
    """Aggregate the recent-sample ring's last ``last_s`` seconds — the
    pre-trip view watchdog bundles and ``debug dump`` capture."""
    since = time.time_ns() - int(last_s * 1e9)
    agg: dict = {}
    for ts, _tid, sid, sub, state, wid, n in _T.rows(since):
        key = (sid, sub, state, wid)
        agg[key] = agg.get(key, 0) + n
    out = profile_dict(agg)
    out["window_s"] = last_s
    return out


def bundle_snapshot(last_s: float = 30.0) -> dict:
    """The ``profile.json`` black-box artifact: plane status + the
    ring's pre-trip window in both JSON and collapsed form."""
    since = time.time_ns() - int(last_s * 1e9)
    agg: dict = {}
    for ts, _tid, sid, sub, state, wid, n in _T.rows(since):
        key = (sid, sub, state, wid)
        agg[key] = agg.get(key, 0) + n
    out = profile_dict(agg)
    out["window_s"] = last_s
    return {
        "status": status(),
        "recent": out,
        "collapsed": collapsed(agg),
    }


def profile_window(seconds: float, fmt: str = "collapsed") -> str:
    """The ``/debug/pprof/profile`` body.  ``seconds > 0`` holds an
    acquire (so the sampler runs even on a node with the plane idle),
    sleeps, and returns the window's delta; ``seconds <= 0`` serves the
    recent-sample ring without waiting — the pre-trip path bundles and
    ``debug dump`` use."""
    import json as _json

    if seconds > 0:
        if _env_mode() == "off":
            return f"profiler pinned off ({_ENV}=0)\n"
        seconds = min(60.0, seconds)
        acquire()
        try:
            before = snapshot_agg()
            time.sleep(seconds)
            agg = delta_agg(before, snapshot_agg())
        finally:
            release()
        if fmt == "json":
            out = profile_dict(agg)
            out["window_s"] = seconds
            return _json.dumps(out, default=str)
        return collapsed(agg)
    if fmt == "json":
        return _json.dumps(recent(), default=str)
    return collapsed()


# ------------------------------------------------------- scrape bridge


def sample(metrics=None) -> None:
    """Bridge the per-(subsystem, state) sample counters into
    ``profile_samples_total`` and the per-role kernel CPU into
    ``thread_cpu_seconds_total`` from a per-registry watermark —
    pull-time work on the scrape path, zero cost on the sample path (the
    txtrace/lockprof bridge pattern; libs/health.sample calls this at a
    scrape, the consensus receive routine once a drain).  A parked
    thread's samples reach the counter at the sampler's next flush (1 s),
    CPU at its next reading (0.25 s)."""
    if metrics is not None:
        m = metrics
    else:
        from . import metrics as libmetrics

        m = libmetrics.node_metrics()
    fam = getattr(m, "profile_samples", None)
    if fam is None:
        return
    t = _T
    wm = getattr(m, "_profile_wm", None)
    if wm is None or wm["gen"] != t.gen:
        wm = m._profile_wm = {
            "gen": t.gen, "counts": [0] * len(t.counts),
            "cpu": [0] * _MAX_ROLES,
        }
    counts = wm["counts"]
    for i in range(len(t.counts)):
        v = t.counts[i]
        d = v - counts[i]
        if d > 0:
            fam.labels(SUBSYSTEMS[i // 2], STATES[i % 2]).inc(d)
        counts[i] = v
    cpu_fam = m.thread_cpu_seconds
    cpu = wm["cpu"]
    roles = _roles
    for i in range(len(roles)):
        v = t.cpu_role[i]
        d = v - cpu[i]
        if d > 0:
            cpu_fam.labels(roles[i]).inc(d / 1e9)
            cpu[i] = v


# ------------------------------------------------- simnet module shares


def module_shares(agg: dict) -> dict:
    """Split a window's samples into scheduler vs verify vs engine wall
    shares by frame module — the simnet ``--profile`` report.  A simnet
    run executes on ONE scheduler thread, so thread attribution is
    useless there; the leaf-most classifiable frame says whose code the
    interpreter was actually in."""
    frames, stacks = _frames, _stacks
    totals = {"scheduler": 0, "verify": 0, "engine": 0, "other": 0}
    for (sid, _sub, _state, _wid), n in agg.items():
        bucket = "other"
        st = stacks[sid] if sid < len(stacks) else ()
        for f in st:  # leaf first
            label = frames[f] if f < len(frames) else "?"
            if label.startswith((
                "cometbft_tpu.crypto.", "cometbft_tpu.ops.",
            )):
                bucket = "verify"
                break
            if label.startswith("cometbft_tpu.simnet"):
                bucket = "scheduler"
                break
            if label.startswith("cometbft_tpu."):
                bucket = "engine"
                break
        totals[bucket] += n
    total = sum(totals.values())
    return {
        "samples": total,
        "shares": {
            k: round(v / total, 4) if total else 0.0
            for k, v in totals.items()
        },
    }
