"""The benchmark's own device trace: start, stop, and the reduction from the
profiler's events to busy time, kernel time and attributed idle gaps.

The reduction works on a plain event list (``reduce_events``), so it can be
checked against a small recorded list with a known answer
(``run.py --selfcheck``, fixtures/trace_small.json) and gives every PR the
same number in the same way.

Event list:
  {"device":  {"<plane>": [[op, start_ns, dur_ns], ...]},
   "modules": {"<plane>": [[program, start_ns, dur_ns], ...]},
   "host":    [[span, thread, start_ns, dur_ns], ...],
   "t0_ns": ..., "t1_ns": ...}
``device`` holds the executed operations of each chip (the profiler's "XLA
Ops" line; a ``while`` and the operations of its body both appear, so they
are united for busy time and never summed across names), ``modules`` each
execution of a whole program ("XLA Modules": a kernel's time is summed from
these), ``host`` the harness's own ``bench.*`` TraceAnnotation spans; t0..t1
the traced window on the same clock.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import threading
import time

SPAN_PREFIX = "bench."
_WINDOW_SPAN = SPAN_PREFIX + "window"


@contextlib.contextmanager
def span(name: str, enabled: bool = True):
    """A host span around a call into one layer, written into the
    profiler's own trace so that idle gaps can be named after it."""
    if not enabled:
        yield
        return
    import jax

    with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
        yield


class Tracer:
    """Traces the first ``trace_seconds`` of the window from a thread of its
    own (start, the ``bench.window`` span, stop: all on that thread, so the
    driver's dispatcher never waits for the profiler), then reduces once
    the window has closed. With ``enabled`` false every method is a no-op,
    so drivers call it unconditionally."""

    def __init__(self, enabled: bool, out_dir: str, trace_seconds: float):
        self.enabled = enabled
        self.out_dir = out_dir
        self.trace_seconds = trace_seconds
        self.events = None
        self.on_counters = None  # set by run.py: snapshot at trace stop
        self.counters_at_stop: dict = {}
        self._thread = None
        self._started = threading.Event()
        self._error = None

    def _run(self) -> None:
        import jax

        try:
            shutil.rmtree(self.out_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # the harness's spans, not frames
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(_WINDOW_SPAN):
                    self._started.set()
                    time.sleep(self.trace_seconds)
                if self.on_counters is not None:
                    self.counters_at_stop = self.on_counters()
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # reported by stop(), on the driver's thread
            self._error = e
            self._started.set()

    def start(self) -> None:
        """Returns once the profiler is running."""
        if not self.enabled:
            return
        self._thread = threading.Thread(
            target=self._run, name="bench-tracer", daemon=True
        )
        self._thread.start()
        self._started.wait(120)

    def stop(self) -> None:
        """Waits for the traced part to end (call it after the window) and
        reads the trace."""
        if not self.enabled or self._thread is None:
            return
        self._thread.join(timeout=300)
        self._thread = None
        if self._error is not None:
            raise self._error
        self.events = load_events(self.out_dir)
        shutil.rmtree(self.out_dir, ignore_errors=True)


def load_events(trace_dir: str) -> dict:
    """The profiler's .xplane.pb as the plain event list above."""
    import jax

    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    device: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list = []
    window = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            mods = lines.get("XLA Modules")
            ops = lines.get("XLA Ops") or mods
            if ops is None or mods is None:
                continue
            device[plane.name] = [
                [_op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                for e in ops.events
            ]
            modules[plane.name] = [
                [re.sub(r"\(\d+\)$", "", e.name), int(e.start_ns),
                 int(e.duration_ns)]
                for e in mods.events
            ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == _WINDOW_SPAN:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    elif e.name.startswith(SPAN_PREFIX):
                        host.append([
                            e.name[len(SPAN_PREFIX):], line.name,
                            int(e.start_ns), int(e.duration_ns),
                        ])
    if window is None:
        raise RuntimeError("the trace holds no bench.window span")
    return {"device": device, "modules": modules, "host": host,
            "t0_ns": window[0], "t1_ns": window[1]}


def _op_name(text: str) -> str:
    """``%fusion.3 = ...`` -> ``fusion.3``: the operation's own name, without
    the HLO text that follows it."""
    return text.split(" = ", 1)[0].lstrip("%")[:80]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clipped(rows, t0, t1):
    for name, start, dur in rows:
        s, e = max(start, t0), min(start + dur, t1)
        if e > s:
            yield name, s, e


def reduce_events(ev: dict) -> dict:
    """busy_s (union of the intervals in which an operation ran, averaged
    over the chips), window_s, seconds per operation name and per program,
    and the idle gaps of the first chip named after the host span that
    covered most of each."""
    t0, t1 = ev["t0_ns"], ev["t1_ns"]
    busy_ns = []
    ops: dict[str, int] = {}
    programs: dict[str, int] = {}
    first_union = None
    for plane in sorted(ev["device"]):
        spans = []
        for name, s, e in _clipped(ev["device"][plane], t0, t1):
            spans.append((s, e))
            ops[name] = ops.get(name, 0) + (e - s)
        for name, s, e in _clipped(ev["modules"].get(plane, []), t0, t1):
            programs[name] = programs.get(name, 0) + (e - s)
        u = _union(spans)
        busy_ns.append(sum(e - s for s, e in u))
        if first_union is None:
            first_union = u
    n = max(1, len(busy_ns))
    gaps: dict[str, int] = {}
    edges = [t0] + [x for iv in (first_union or []) for x in iv] + [t1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, cover = "no-span", 0
        for name, _thread, start, dur in ev["host"]:
            c = min(g1, start + dur) - max(g0, start)
            if c > cover:
                best, cover = name, c
        gaps[best] = gaps.get(best, 0) + (g1 - g0)
    top = lambda d: [  # noqa: E731
        [k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]
    ]
    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "chips": len(busy_ns),
        "module_seconds": {k: v / 1e9 for k, v in programs.items()},
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }


def module_seconds(reduced: dict, pattern: str) -> float:
    """Device seconds of the executions of the programs whose name matches
    ``pattern`` ("XLA Modules" events, clipped to the window)."""
    rx = re.compile(pattern)
    return sum(s for m, s in reduced["module_seconds"].items() if rx.search(m))
