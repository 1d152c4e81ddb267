"""Background compilation of cold kernel shapes.

A routed caller waits at most a few seconds for its ticket, holding
engine mutexes (crypto/coalesce._RESULT_TIMEOUT_S is the consensus
stall bound), and an XLA or Mosaic compile takes longer than that. So
the coalescing planes never dispatch a window whose kernel shape has no
executable in this process yet: :meth:`WarmSet.ready` answers False,
the window runs on host, and one daemon worker compiles the shape by
driving the plane's own launch path on dummy lanes. Later windows of
that shape take the device. A cold node therefore serves from host for
as long as compilation takes and is never stalled by it; both outcomes
are counted (``cold`` answers here, host/device windows in the planes).

Direct callers (``ops.verify.verify_batch`` on a whole commit) have no
ticket bound and still compile inline.
"""

from __future__ import annotations

import threading
import time
from collections import deque

from ..libs import sync as libsync


class WarmSet:
    """Keys whose executables exist, plus the worker that makes more.

    ``compile_fn(key)`` must run the real launch path for ``key`` to
    completion (materialize the result): returning means every jit that
    a window of that shape touches is compiled. A key whose compile
    raised stays cold for the life of the process — logged and counted
    in ``failed``, never retried in a loop.
    """

    def __init__(self, name: str, compile_fn):
        self.name = name
        self._compile = compile_fn
        self._mtx = libsync.Mutex("ops.warm._mtx")
        self._idle = libsync.Condition(self._mtx, name="ops.warm._mtx")
        # lockfree: ready()'s fast path reads the set without the lock; adds happen under it and membership is GIL-atomic — a stale miss costs one more host window
        self._warm: set = set()
        self._queue: deque = deque()
        self._busy = None  # key being compiled right now
        self._thread: threading.Thread | None = None
        self.failed: dict = {}  # key -> repr(exception)
        self.seconds: dict = {}  # key -> wall seconds its warm-up took
        self.cold = 0  # ready() calls answered False

    def ready(self, key) -> bool:
        """True when ``key`` is compiled; otherwise queue it (once)."""
        if key in self._warm:
            return True
        with self._mtx:
            if key in self._warm:
                return True
            self.cold += 1
            if (
                key not in self.failed
                and key != self._busy
                and key not in self._queue
            ):
                self._queue.append(key)
                if self._thread is None or not self._thread.is_alive():
                    self._thread = threading.Thread(
                        target=self._run,
                        name=f"warm-{self.name}",
                        daemon=True,
                    )
                    self._thread.start()
        return False

    def _run(self) -> None:
        while True:
            with self._mtx:
                if not self._queue:
                    self._busy = None
                    self._thread = None
                    self._idle.notify_all()
                    return
                key = self._busy = self._queue.popleft()
            t0 = time.perf_counter()
            try:
                self._compile(key)
            except Exception as e:
                from ..libs import log as _log

                _log.default_logger().with_module("ops.warm").error(
                    "background compile failed; shape stays on host",
                    plane=self.name,
                    key=repr(key),
                    err=repr(e)[:300],
                )
                with self._mtx:
                    self.failed[key] = repr(e)[:300]
            else:
                with self._mtx:
                    self._warm.add(key)
                    self.seconds[key] = time.perf_counter() - t0

    def wait_idle(self, timeout: float) -> bool:
        """Block until nothing is queued or compiling (tests, the smoke
        and a clean shutdown: a process must not exit mid-compile)."""
        deadline = time.monotonic() + timeout
        with self._mtx:
            while self._queue or self._busy is not None:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    return False
                self._idle.wait(rem)
        return True

    def snapshot(self) -> dict:
        with self._mtx:
            return {
                "warm": sorted(map(repr, self._warm)),
                "queued": len(self._queue) + (self._busy is not None),
                "failed": {repr(k): v for k, v in self.failed.items()},
                "cold_answers": self.cold,
                "seconds": {
                    repr(k): round(v, 3) for k, v in self.seconds.items()
                },
            }
