"""Low-overhead span/event tracer for the consensus + TPU hot paths.

The CometBFT reference grew ``libs/trace`` (a JSONL event tracer wired
into consensus and p2p) because aggregate metrics cannot answer "where
did THIS slow round spend its time". This is the TPU-native analog: the
batch-verify pipeline's phases (pack / dispatch / readback / fallback),
consensus height/round/step transitions, vote admission, mempool
CheckTx, p2p channel traffic, blocksync applies and WAL fsyncs all emit
timestamped records into a bounded in-memory ring, optionally teed to a
rotating JSONL file (``libs/autofile.Group``).

Design constraints (in priority order):

* **Zero cost when off.** ``COMETBFT_TPU_TRACE`` unset means every
  entry point is one module-flag check and an immediate return: no
  allocation retained, no lock touched, no clock read.  Hot-path call
  sites additionally guard with :func:`enabled` before building their
  field dicts so the disabled path does not even allocate kwargs
  (pinned by tests/test_observability.py's allocation guard).
* **Never block an engine thread.** Record emission appends to a
  ``collections.deque`` (GIL-atomic, lock-free) — the file sink has a
  dedicated writer thread draining a second deque, so no engine mutex
  ever reaches file I/O through the tracer (cometlint CLNT009).  The
  single lock here (``libs.trace._mtx``) only serializes sink
  start/stop and is never held across blocking calls.

* **One clock, the profiler's.** A span's ``start_ns`` and ``dur_ns``
  are two readings of ``time.time_ns()``, the Unix-epoch clock that
  the jax profiler's host plane stamps its own events with (an
  xplane's ``profile_start_time`` + an event's offset is such a
  reading), so a ring record and a device operation of a profiler
  trace stand on one timeline.  While tracing is on, every ``with``-
  span is also entered as a ``jax.profiler.TraceAnnotation`` named
  ``"bft." + name`` on the same thread, but only in a process that has
  imported jax already: tracing never imports it and never initialises
  a backend.  Manual ``begin()/end()`` spans may end on another thread
  and are not mirrored.

Record schema (one JSON object per line in the file sink, same dicts
from :func:`ring_dump`)::

    {"ts": <wall-clock ns>, "kind": "event"|"span", "name": str,
     "thread": str, ...}
    span records add:   "span": id, "parent": id, "start_ns": int,
                        "dur_ns": int ("ts" = start_ns + dur_ns, the
                        end); with-spans also "root": id of the
                        outermost with-span on the thread (one
                        request's spans share it) and "cpu_ns": the
                        thread's CPU time inside the span (the rest of
                        dur_ns is waiting: GIL, device, I/O)
    event records add:  "span": id of the enclosing with-span (if any)
                        plus free-form fields ("dur_ns", "backend",
                        "lanes", "height", ...)

Knobs (registered in config.ENV_KNOBS, enforced by cometlint CLNT007):
``COMETBFT_TPU_TRACE`` (on|1 enables), ``COMETBFT_TPU_TRACE_FILE``
(JSONL sink path), ``COMETBFT_TPU_TRACE_RING`` (ring capacity).
"""

from __future__ import annotations

import itertools
import json
import os
import sys
import threading
import time
from collections import deque

from . import autofile
from . import sync as libsync

_ENV_TRACE = "COMETBFT_TPU_TRACE"
_ENV_TRACE_FILE = "COMETBFT_TPU_TRACE_FILE"
_ENV_TRACE_RING = "COMETBFT_TPU_TRACE_RING"

DEFAULT_RING_SIZE = 8192

_ON_VALUES = ("1", "on", "true", "yes")


def _ring_size_from_env() -> int:
    raw = os.environ.get(_ENV_TRACE_RING, "")
    try:
        n = int(raw) if raw else DEFAULT_RING_SIZE
    except ValueError:
        n = DEFAULT_RING_SIZE
    return max(16, n)


_enabled: bool = os.environ.get(_ENV_TRACE, "").lower() in _ON_VALUES
_ring: deque = deque(maxlen=_ring_size_from_env())
_ids = itertools.count(1)  # span ids; count.__next__ is GIL-atomic
_tls = threading.local()  # .spans: stack of with-entered Span objects
_mtx = libsync.Mutex("libs.trace._mtx")  # sink start/stop only
_sink: "_FileSink | None" = None
# jax.profiler.TraceAnnotation once a process that imported jax traces
_annotation = None
_ANNOTATION_STR_MAX = 64


def _annotation_cls():
    """The profiler's annotation type if this process has jax, found
    without importing anything."""
    global _annotation
    cls = _annotation
    if cls is None:
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        cls = _annotation = getattr(prof, "TraceAnnotation", None)
    return cls


def enabled() -> bool:
    """The one check hot paths make before building trace fields."""
    return _enabled


def enable(ring: int | None = None) -> None:
    """Turn tracing on (tests, /debug/trace/start). ``ring`` resizes the
    buffer, preserving the newest records."""
    global _enabled, _ring
    if ring is not None and ring != _ring.maxlen:
        _ring = deque(_ring, maxlen=max(16, ring))
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def reset() -> None:
    """Drop all buffered records (tests, bench bursts)."""
    _ring.clear()


def ring_dump() -> list[dict]:
    """Snapshot of the ring buffer, oldest first.

    Emitters append concurrently (lock-free by design); a full ring
    mutates on every append, so iteration can observe a mutation and
    raise — retry until a consistent snapshot lands rather than 500ing
    the /debug/trace scrape exactly when the node is busy.
    """
    while True:
        try:
            return list(_ring)
        except RuntimeError:  # deque mutated during iteration
            continue


def status() -> dict:
    s = _sink
    return {
        "enabled": _enabled,
        "ring_capacity": _ring.maxlen,
        "ring_len": len(_ring),
        "sink": s.path if s is not None else None,
    }


# ------------------------------------------------------------- emission


def _emit(rec: dict, fields: dict | None) -> None:
    if fields:
        rec.update(fields)
    _ring.append(rec)
    s = _sink
    if s is not None:
        s.put(rec)


def _emit_event(name: str, fields: dict | None, span_id: int) -> None:
    rec: dict = {
        "ts": time.time_ns(),
        "kind": "event",
        "name": name,
        "thread": threading.current_thread().name,
    }
    if span_id:
        rec["span"] = span_id
    _emit(rec, fields)


def _span_stack() -> list:
    stack = getattr(_tls, "spans", None)
    if stack is None:
        stack = _tls.spans = []
    return stack


def event(name: str, **fields) -> None:
    """Record one point event. Attributed to the innermost with-entered
    span on this thread, if any."""
    if not _enabled:
        return
    stack = getattr(_tls, "spans", None)
    _emit_event(name, fields, stack[-1].id if stack else 0)


class Span:
    """A timed interval.  Two usage modes:

    * ``with span("name", k=v): ...`` — nests on the per-thread stack,
      so events inside attribute to it automatically, and is mirrored
      into the jax profiler's trace as ``bft.name`` (module docstring);
    * ``sp = begin("name", parent=outer); ...; sp.end()`` — manual
      lifetime for state-machine phases (consensus height/round/step)
      that do not nest lexically.  Manual spans never touch the thread
      stack, so they are safe to end from a different callback.

    One record is emitted at ``end()`` carrying ``start_ns`` and the
    measured ``dur_ns``, one clock reading per edge; a span never ends
    twice.  ``dur_ns`` stays on the object for a caller that feeds a
    second sink from the same reading (libs/metrics.TimedPhase).
    """

    __slots__ = (
        "name", "id", "parent", "root", "fields", "start_ns", "dur_ns",
        "_cpu0", "_cpu_ns", "_mirror", "_ended",
    )

    def __init__(self, name: str, parent_id: int, fields: dict | None):
        self.name = name
        self.id = next(_ids)
        self.parent = parent_id
        self.root = 0
        self.fields = fields
        self.dur_ns = 0
        self._cpu_ns = None
        self._mirror = None
        self._ended = False
        self.start_ns = time.time_ns()

    def event(self, name: str, **fields) -> None:
        if not _enabled:
            return
        _emit_event(name, fields, self.id)

    def set(self, **fields) -> None:
        """Fields known only once the work is under way (a result, a
        lane count) join the record that ``end()`` emits."""
        if self.fields is None:
            self.fields = fields
        else:
            self.fields.update(fields)

    def end(self, **fields) -> None:
        if self._ended:
            return
        self._ended = True
        # the epoch clock can be stepped back under a span: never negative
        self.dur_ns = max(0, time.time_ns() - self.start_ns)
        if not _enabled:
            # tracing was turned off mid-span: drop the record — once
            # disabled, nothing reaches the ring or sink
            return
        if fields:
            self.set(**fields)
        rec: dict = {
            "ts": self.start_ns + self.dur_ns,
            "kind": "span",
            "name": self.name,
            "thread": threading.current_thread().name,
            "span": self.id,
        }
        if self.parent:
            rec["parent"] = self.parent
        if self.root:
            rec["root"] = self.root
        rec["start_ns"] = self.start_ns
        rec["dur_ns"] = self.dur_ns
        if self._cpu_ns is not None:
            rec["cpu_ns"] = self._cpu_ns
        _emit(rec, self.fields)

    def __enter__(self) -> "Span":
        stack = _span_stack()
        self.root = stack[0].id if stack else self.id
        stack.append(self)
        cls = _annotation_cls()
        if cls is not None:
            self._mirror = cls("bft." + self.name, **{
                k: v for k, v in (self.fields or {}).items()
                if isinstance(v, int)
                or (isinstance(v, str) and len(v) <= _ANNOTATION_STR_MAX)
            })
            self._mirror.__enter__()
        self._cpu0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._cpu_ns = time.thread_time_ns() - self._cpu0
        if self._mirror is not None:
            self._mirror.__exit__(*exc)
            self._mirror = None
        stack = _span_stack()
        for i in range(len(stack) - 1, -1, -1):
            if stack[i] is self:
                del stack[i]
                break
        self.end()


class _NopSpan:
    """Shared do-nothing span: the disabled path allocates nothing."""

    __slots__ = ()
    id = 0

    def event(self, name: str, **fields) -> None:
        pass

    def set(self, **fields) -> None:
        pass

    def end(self, **fields) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOP_SPAN = _NopSpan()


def span(name: str, parent: "Span | None" = None, **fields):
    """A span for ``with`` use; parent = innermost entered span, or
    ``parent`` where the work belongs to a span of another thread (a
    coalescer window's phases on the executor and the drain thread)."""
    if not _enabled:
        return NOP_SPAN
    if parent is not None:
        return Span(name, parent.id, fields or None)
    stack = getattr(_tls, "spans", None)
    return Span(name, stack[-1].id if stack else 0, fields or None)


def current() -> "Span | None":
    """The innermost with-entered span on this thread (None when there
    is none or tracing is off): what a caller hands to :func:`begin` as
    the explicit parent of a span that ends on another thread."""
    if not _enabled:
        return None
    stack = getattr(_tls, "spans", None)
    return stack[-1] if stack else None


def begin(name: str, parent: "Span | None" = None, **fields):
    """Start a manually-ended span (see :class:`Span`)."""
    if not _enabled:
        return NOP_SPAN
    parent_id = parent.id if parent is not None else 0
    return Span(name, parent_id, fields or None)


# ------------------------------------------------------------ file sink


class _FileSink:
    """JSONL writer on a rotating autofile Group.

    Emitters append records to a bounded deque (lossy under extreme
    backlog — tracing must shed load, never apply backpressure); the
    dedicated writer thread drains it and owns all file I/O, so no
    engine lock is ever held across a write or rotation.
    """

    BUFFER = 1 << 16

    def __init__(self, path: str):
        self.path = path
        self.group = autofile.Group(path)
        self._buf: deque = deque(maxlen=self.BUFFER)
        self._wake = threading.Event()
        self._stop = False
        self._thread = threading.Thread(
            target=self._run, name="trace-sink", daemon=True
        )
        self._thread.start()

    def put(self, rec: dict) -> None:
        self._buf.append(rec)
        self._wake.set()

    def _drain(self) -> None:
        lines = []
        while True:
            try:
                lines.append(self._buf.popleft())
            except IndexError:
                break
        if lines:
            data = "".join(
                json.dumps(rec, default=str) + "\n" for rec in lines
            ).encode()
            self.group.write(data)
            self.group.flush()
            self.group.check_head_size_limit()

    def _run(self) -> None:
        while True:
            self._wake.wait(0.1)
            self._wake.clear()
            try:
                self._drain()
            except Exception as e:
                # a failing sink must never take down tracing or the
                # engine: drop to ring-only AND deregister, so status()
                # stops claiming an active sink and a fresh
                # start_file_sink isn't blocked by the corpse
                sys.stderr.write(f"trace sink failed, stopping: {e!r}\n")
                _deregister_sink(self)
                return
            if self._stop and not self._buf:
                return

    def close(self) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout=2)
        if self._thread.is_alive():
            # writer wedged inside a write (hung disk): it still owns
            # the group — racing it with a caller-thread drain/close
            # would interleave records and write on a closed file.
            # Leak the handle; the daemon thread dies with the process.
            sys.stderr.write(
                f"trace sink writer stuck; abandoning {self.path}\n"
            )
            return
        try:
            self._drain()  # writer exited: final drain on this thread
            self.group.close()
        except Exception:
            sys.stderr.write(f"trace sink close failed: {self.path}\n")


def _deregister_sink(sink: "_FileSink") -> None:
    """Clear ``sink`` from the module slot if it still owns it (writer
    self-removal on a fatal I/O error)."""
    global _sink
    with _mtx:
        if _sink is sink:
            _sink = None


def start_file_sink(path: str) -> bool:
    """Tee records to a rotating JSONL file. False if a sink is already
    active (stop it first)."""
    global _sink
    new = None
    with _mtx:
        if _sink is not None:
            return False
        new = _sink = _FileSink(path)
    return new is not None


def stop_file_sink() -> bool:
    """Stop and flush the file sink. False when none was active."""
    global _sink
    with _mtx:
        s, _sink = _sink, None
    if s is None:
        return False
    s.close()  # outside the lock: close joins the writer thread
    return True


def _autostart_sink_from_env() -> None:
    path = os.environ.get(_ENV_TRACE_FILE, "")
    if _enabled and path:
        try:
            start_file_sink(path)
        except Exception as e:
            sys.stderr.write(
                f"trace: cannot open {_ENV_TRACE_FILE}={path!r}: {e!r}\n"
            )


_autostart_sink_from_env()
