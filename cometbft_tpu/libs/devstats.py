"""Device/compilation telemetry: the XLA side of observability.

PR 3 made the HOST side of the verify pipeline legible (spans, phase
histograms); this layer makes the DEVICE side legible. Three concerns:

* **Compile accounting.** Every jit entry point in ``ops/verify.py``
  is wrapped in :func:`track`, so each XLA compilation is counted and
  timed per kernel x shape bucket (``xla_compile_total{kernel,bucket}``,
  ``xla_compile_seconds{kernel}``), persistent-compilation-cache hits
  are distinguished from real compiles (``xla_cache_hit_total{outcome}``
  via ``jax.monitoring``), and a process-wide recompile counter
  (``xla_recompile_total``) flags a compile for an ALREADY-compiled
  kernel x bucket — the signature of a shape-bucket leak or a dtype
  drift past CLNT003 that would silently destroy steady-state
  throughput. Compiles also emit ``xla.compile`` trace events so the
  one-time cost shows up in ``/debug/trace`` next to pack/dispatch/
  readback (a first dispatch that "takes 10 s" is one compile).

* **Device gauges on the metrics path.** :func:`sample` is a pull-time
  collector (called from the node's refresh hook and the Prometheus
  listener): ``device.memory_stats()`` byte gauges per device
  (``device_memory_bytes{device,kind}``), expanded-pubkey arena
  occupancy/lookup/eviction counters (``pubkey_arena_*``), and the
  host<->device transfer byte/op counters recorded at the pack and
  readback edges (``device_transfer_bytes_total{direction}``).

* **A scrape endpoint.** :class:`PrometheusServer` (a
  ``libs/service.BaseService``, like ``libs/pprof.PprofServer``) serves
  the node registry's exposition at ``COMETBFT_TPU_PROM_ADDR`` — the
  analog of the reference's dedicated Instrumentation listener
  (config/config.go ``prometheus_listen_addr``, ``:26660``).

Design constraints (same priority order as ``libs/trace``):

* **Zero cost when off.** ``COMETBFT_TPU_DEVSTATS`` unset means every
  entry point is one module-flag check and an immediate return — no
  allocation retained, no lock touched, no clock read (pinned by the
  tracemalloc guard in tests/test_observability.py). The node flips it
  on automatically when it starts a Prometheus listener.
* **Never block an engine thread.** The launch-path entry points (the
  tracked-jit wrapper's compile detection, which can run with
  ``ops.verify._lock`` held — the arena scatter launches under it)
  touch NO lock at all: a detected compile appends one record to a
  lock-free deque (plus a lock-free trace event); the ledger folding
  (:func:`_drain_compiles`) and the per-registry metric replay
  (:func:`_publish_compiles`) happen on the READ paths only (scrape,
  snapshot, tests). The one lock here
  (``libs.devstats._mtx``) serializes the ledger ints on those read
  paths and is never held across a metrics/trace/jax call — it is a
  LEAF of the lock-order graph like ``libs.trace._mtx`` (asserted in
  tests/test_lint_graph.py). :func:`sample` never *initializes* a jax
  backend: a scrape must not be the thing that first touches PJRT
  init (a host-only node may never pay for it).

Knobs (registered in config.ENV_KNOBS, enforced by cometlint CLNT007):
``COMETBFT_TPU_DEVSTATS`` (1/on enables accounting + sampling),
``COMETBFT_TPU_PROM_ADDR`` (scrape listener address).
"""

from __future__ import annotations

import json
import os
import time
from collections import deque

from . import health as libhealth
from . import metrics as libmetrics
from . import sync as libsync
from . import trace as libtrace
from .service import HTTPService

_ENV_DEVSTATS = "COMETBFT_TPU_DEVSTATS"
_ENV_PROM_ADDR = "COMETBFT_TPU_PROM_ADDR"

_ON_VALUES = ("1", "on", "true", "yes")


def _env_on() -> bool:
    return os.environ.get(_ENV_DEVSTATS, "").lower() in _ON_VALUES


_enabled: bool = _env_on()
# reference count of node-lifecycle holders (Prometheus-serving nodes
# acquire on start, release on stop) — telemetry turns itself off when
# the last holder stops, unless the env knob keeps it on
_acquirers = 0

_mtx = libsync.Mutex("libs.devstats._mtx")  # read-path ledger folding only

# Launch-path staging: detected compiles land here LOCK-FREE (deque
# append is GIL-atomic) because the launch may hold an engine lock
# (the arena scatter jits under ops.verify._lock). Unbounded by design:
# growth is bounded by the total compile count, which the whole layer
# exists to keep near-zero. _drain_compiles folds it into the ledger
# from read paths only.
_pending_compiles: deque = deque()

# (kernel, bucket) -> in-process compile count. A count > 1 means the
# same kernel x bucket compiled AGAIN — a steady-state recompile.
_compiled: dict[tuple[str, int], int] = {}
# Every COUNTED compile, in drain order. Publishing to a registry
# replays this log from the registry's own high-water index (stored on
# the NodeMetrics instance), so every scraped node sees the full
# compile series no matter how many nodes scrape, and a registry's
# watermark dies with it. Bounded by the total compile count, which
# this layer exists to keep near-zero.
_compile_log: list = []
# last drained executable-cache size per kernel: dedupes the race where
# two threads dispatch the same cold kernel concurrently and BOTH see
# the jit cache grow — only real growth past the drained watermark
# counts, so a healthy concurrent cold boot can never fire the
# recompile alarm.
_jit_sizes: dict[str, int] = {}
_c = {
    "compiles": 0,
    "recompiles": 0,
    "compile_seconds": 0.0,
    "pcache_hits": 0,
    "pcache_misses": 0,
    "h2d_ops": 0,
    "h2d_bytes": 0,
    "d2h_ops": 0,
    "d2h_bytes": 0,
}
# (The arena counter bridge and the compile-log replay both keep their
# per-registry watermarks ON the target NodeMetrics instance — see
# _bridge_delta / _publish_compiles — so nothing global grows per
# registry and a recycled object id can never inherit a watermark.)

# jax.monitoring persistent-compilation-cache tallies. The listener is
# registered once per process and always counts (two int increments per
# COMPILE, not per dispatch — negligible); classification into the
# metrics happens in the tracked-jit wrapper only when enabled.
_mon_hits = 0
_mon_requests = 0
_mon_registered = False


def _on_jax_event(event: str, **kwargs) -> None:
    global _mon_hits, _mon_requests
    if event == "/jax/compilation_cache/cache_hits":
        _mon_hits += 1
    elif event == "/jax/compilation_cache/compile_requests_use_cache":
        _mon_requests += 1


def _register_monitoring() -> None:
    global _mon_registered
    if _mon_registered:
        return
    _mon_registered = True
    import jax.monitoring

    jax.monitoring.register_event_listener(_on_jax_event)


def enabled() -> bool:
    """The one check hot paths make before any telemetry work."""
    return _enabled


def enable() -> None:
    """Turn device telemetry on (node boot with a Prometheus listener,
    tests, bench captures)."""
    global _enabled
    _register_monitoring()
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def acquire() -> None:
    """Reference-counted enable for node lifecycles: a Prometheus-
    serving node acquires on start and releases on stop, so telemetry
    stays on exactly while someone can scrape it — an in-process
    multi-node net doesn't keep paying per-launch accounting after the
    instrumented node is gone."""
    global _acquirers
    _acquirers += 1
    enable()


def release() -> None:
    global _acquirers
    _acquirers = max(0, _acquirers - 1)
    if _acquirers == 0 and not _env_on():
        disable()


# --------------------------------------------------------- compile ledger


class _TrackedJit:
    """Per-launch compile detector around one jitted callable.

    Each call compares the jit executable-cache size before/after: a
    growth IS a compilation (trace + lower + compile happened inside
    this call), regardless of which shape/dtype signature triggered it
    — so a dtype drift recompiling an already-seen bucket is caught,
    not just new buckets. The wrapped callable stays drop-in (bench.py
    and tests call these directly).
    """

    __slots__ = ("fn", "kernel", "axis")

    def __init__(self, fn, kernel: str, axis: int):
        self.fn = fn
        self.kernel = kernel
        self.axis = axis

    def _cache_size(self):
        return self.fn._cache_size()

    def __call__(self, *args):
        fn = self.fn
        if not _enabled:
            return fn(*args)
        # read the bucket BEFORE dispatch: with buffer donation the
        # launch may consume args[axis]
        bucket = int(args[self.axis].shape[-1])
        before = fn._cache_size()
        hits0, reqs0 = _mon_hits, _mon_requests
        t0 = time.perf_counter()
        out = fn(*args)
        dt = time.perf_counter() - t0
        after = fn._cache_size()
        if after > before:
            # LOCK-FREE staging: this call may run under an engine
            # mutex (the arena scatter launches under ops.verify._lock)
            # — no ledger/metrics lock may be touched here. Folding
            # happens in _drain_compiles on the read paths.
            _pending_compiles.append(
                (
                    self.kernel,
                    bucket,
                    dt,
                    before,
                    after,
                    _mon_hits > hits0,
                    _mon_requests > reqs0,
                )
            )
            if libtrace.enabled():
                # trace emission is lock-free by design (libs/trace);
                # the recompile flag is best-effort from drained state
                cache = "off"
                if _mon_hits > hits0:
                    cache = "hit"
                elif _mon_requests > reqs0:
                    cache = "miss"
                libtrace.event(
                    "xla.compile",
                    kernel=self.kernel,
                    bucket=bucket,
                    cache=cache,
                    recompile=(self.kernel, bucket) in _compiled,
                    dur_ns=int(dt * 1e9),
                )
        return out


def track(kernel: str, fn, axis: int = 0) -> _TrackedJit:
    """Wrap a jitted callable for compile accounting. ``axis`` is the
    positional arg whose LAST dimension is the lane bucket.

    The recompile detector keys on ``(kernel, lane-bucket)`` — a
    kernel whose compile shape varies on a SECOND axis must encode
    that axis into the kernel name (one tracked jit per value, like
    ops/sha256's ``sha256.xla.b<block-bucket>``), or a fresh sibling
    shape at an already-seen lane bucket reads as a phantom
    steady-state recompile and feeds the recompile-storm watchdog."""
    return _TrackedJit(fn, kernel, axis)


def _drain_compiles() -> None:
    """Fold staged compile records into the process-wide ledger.

    Runs ONLY from read paths (scrape refresh, snapshot, counters,
    bench/tests) — never from the launch path — so the ledger mutex
    stays off the engine lock hierarchy. Touches NO metrics: registries
    catch up via :func:`_publish_compiles`. Dedupe: a record only
    counts if the kernel's executable cache actually grew past the
    drained watermark, so two threads racing the same cold compile
    produce ONE count (and never a phantom recompile)."""
    records = []
    while True:
        try:
            records.append(_pending_compiles.popleft())
        except IndexError:
            break
    if not records:
        return
    with _mtx:
        for kernel, bucket, seconds, before, after, p_hit, cons in records:
            prev = _jit_sizes.get(kernel)
            base = before if prev is None else prev
            if after > base:
                _jit_sizes[kernel] = after
            elif (kernel, bucket) in _compiled:
                # no growth past the watermark AND this bucket is
                # already on the ledger: a duplicate record of an
                # already-counted compile (two threads racing the
                # same cold pair). An UNSEEN bucket with no visible
                # growth still counts — a concurrent compile of a
                # sibling bucket consumed the growth, and dropping
                # it would desync the recompile detector for this
                # bucket forever.
                continue
            n_prior = _compiled.get((kernel, bucket), 0)
            _compiled[(kernel, bucket)] = n_prior + 1
            _c["compiles"] += 1
            _c["compile_seconds"] += seconds
            if n_prior:
                _c["recompiles"] += 1
                # health hook: a steady-state recompile lands in the
                # flight recorder so the black-box bundle and the
                # recompile-storm watchdog see it (libhealth.record is
                # lock-free — _mtx stays a leaf)
                libhealth.record(libhealth.EV_RECOMPILE, a=bucket)
            if p_hit:
                _c["pcache_hits"] += 1
            elif cons:
                _c["pcache_misses"] += 1
            _compile_log.append(
                (kernel, bucket, seconds, n_prior, p_hit, cons)
            )


def _publish_compiles(m) -> None:
    """Replay ledger compiles into ``m``'s counter families from m's
    own high-water index (an attribute on the NodeMetrics — its
    lifetime is the registry's, so nothing global grows or aliases a
    recycled object id). Metric updates happen OUTSIDE the ledger lock:
    _mtx stays a leaf."""
    with _mtx:
        start = m.__dict__.get("_devstats_compile_idx", 0)
        fresh = _compile_log[start:]
        m._devstats_compile_idx = start + len(fresh)
    for kernel, bucket, seconds, n_prior, p_hit, cons in fresh:
        m.xla_compiles.labels(kernel, str(bucket)).inc()
        m.xla_compile_seconds.labels(kernel).observe(seconds)
        if n_prior:
            m.xla_recompiles.inc()
        if p_hit:
            m.xla_cache.labels("hit").inc()
        elif cons:
            m.xla_cache.labels("miss").inc()


def compile_log() -> list[dict]:
    """Every counted compile in order: which kernel at which lane
    bucket, how long the compiling call took, and whether the
    persistent cache served it (``hit``), really compiled (``miss``)
    or was not consulted (``off``)."""
    _drain_compiles()
    with _mtx:
        rows = list(_compile_log)
    return [
        {
            "kernel": kernel,
            "bucket": bucket,
            "seconds": round(seconds, 3),
            "recompile": bool(n_prior),
            "cache": "hit" if p_hit else "miss" if cons else "off",
        }
        for kernel, bucket, seconds, n_prior, p_hit, cons in rows
    ]


def cache_events() -> dict:
    """Process-wide persistent-compilation-cache tallies from
    jax.monitoring — every jit in the process, tracked or not:
    ``requests`` compiles consulted the cache, ``hits`` were served by
    it; the difference really compiled."""
    return {"requests": _mon_requests, "hits": _mon_hits}


def compile_count() -> int:
    """Total in-process XLA compiles (the no-recompile guard's number)."""
    _drain_compiles()
    with _mtx:
        return _c["compiles"]


def compile_seconds_total() -> float:
    _drain_compiles()
    with _mtx:
        return _c["compile_seconds"]


# ------------------------------------------------------ transfer counters


def record_h2d(nbytes: int) -> None:
    """One host->device shipment at the pack edge (wire buffer, arena
    slot indices, builder pubkey rows). Ledger only — registries catch
    up per-scrape via the :func:`sample` bridge, so the launch path
    never touches a metrics mutex and every scraped node sees the full
    series."""
    if not _enabled:
        return
    with _mtx:
        _c["h2d_ops"] += 1
        _c["h2d_bytes"] += nbytes


def record_d2h(nbytes: int) -> None:
    """One device->host materialization at the readback edge (ledger
    only, like :func:`record_h2d`)."""
    if not _enabled:
        return
    with _mtx:
        _c["d2h_ops"] += 1
        _c["d2h_bytes"] += nbytes


def counters() -> dict:
    """Copy of the raw process-wide tallies (tests, /debug/devstats)."""
    _drain_compiles()
    with _mtx:
        return dict(_c)


# -------------------------------------------------------- pull-time gauges


def _devices_if_initialized():
    """Live jax devices, WITHOUT forcing backend init: a metrics scrape
    must never be the first thing to touch PJRT (backend init takes
    seconds, and on a host that shares its chip it would claim it)."""
    try:
        from jax._src import xla_bridge

        if not xla_bridge.backends_are_initialized():
            return []
        import jax

        return jax.devices()
    except Exception:
        return []


def _sample_device_memory(m) -> list[dict]:
    out = []
    for d in _devices_if_initialized():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue  # CPU backend: memory_stats() is None
        dev = str(getattr(d, "id", "?"))
        row = {"device": dev, "kind": getattr(d, "device_kind", "?")}
        for k, v in stats.items():
            if not isinstance(v, (int, float)):
                continue
            row[k] = v
            if "bytes" in k or "size" in k:
                m.device_memory.labels(dev, k).set(v)
        out.append(row)
    return out


def _bridge_delta(store: dict, key: str, current: int) -> int:
    """Advance the last-seen snapshot for a monotone plain int and
    return the delta to feed its Prometheus counter. ``store`` is the
    target NodeMetrics' own watermark dict, so two scraped nodes in one
    process each see the full series and a registry's watermarks die
    with it. Caller holds ``_mtx``; the counter inc itself happens
    OUTSIDE the lock — _mtx stays a leaf."""
    last = store.get(key, 0)
    store[key] = max(last, current)
    return current - last if current > last else 0


def _sample_arena(m) -> dict:
    try:
        from ..ops.verify import _PUBKEY_CACHE as arena
    except Exception:
        return {}
    # unlocked reads: GIL-consistent snapshots of ints/len are fine for
    # gauges, and the scrape path must not contend with verify lookups
    used = len(arena._slots)
    out = {
        "slots_used": used,
        "capacity": arena.capacity,
        "hits": arena.hits,
        "misses": arena.misses,
        "builds": arena.builds,
        "evictions": arena.evictions,
    }
    m.arena_slots.labels("used").set(used)
    m.arena_slots.labels("capacity").set(arena.capacity)
    with _mtx:
        store = m.__dict__.setdefault("_devstats_bridge", {})
        hit_d = _bridge_delta(store, "hits", arena.hits)
        miss_d = _bridge_delta(store, "misses", arena.misses)
        build_d = _bridge_delta(store, "builds", arena.builds)
        evict_d = _bridge_delta(store, "evictions", arena.evictions)
    if hit_d:
        m.arena_lookups.labels("hit").inc(hit_d)
    if miss_d:
        m.arena_lookups.labels("miss").inc(miss_d)
    if build_d:
        m.arena_builds.inc(build_d)
    if evict_d:
        m.arena_evictions.inc(evict_d)
    return out


def _sample_dispatch() -> dict:
    """Launches served per kernel and the fallbacks the dispatch layer
    absorbed (ops/verify.dispatch_counters) — what tells a device that
    served from one a degraded path covered for."""
    try:
        from ..ops.verify import dispatch_counters
    except Exception:
        return {}
    return dispatch_counters()


def _bridge_transfers(m) -> None:
    """Per-registry catch-up of the transfer ledger (same watermark
    store as the arena bridge): the launch-path recorders only touch
    the ledger, so every scraped node gets the full series here."""
    with _mtx:
        store = m.__dict__.setdefault("_devstats_bridge", {})
        deltas = {
            k: _bridge_delta(store, k, _c[k])
            for k in ("h2d_ops", "h2d_bytes", "d2h_ops", "d2h_bytes")
        }
    for direction in ("h2d", "d2h"):
        if deltas[direction + "_bytes"]:
            m.transfer_bytes.labels(direction).inc(
                deltas[direction + "_bytes"]
            )
        if deltas[direction + "_ops"]:
            m.transfer_ops.labels(direction).inc(deltas[direction + "_ops"])


def sample(metrics=None) -> dict:
    """Pull-time collector: device memory + arena gauges into
    ``metrics`` (a NodeMetrics — the node being scraped passes its own,
    so a multi-node process never writes one node's gauges into
    another's registry) or, by default, the process-wide node_metrics()
    top. Called at scrape (Prometheus listener, RPC /metrics refresh)
    and by :func:`snapshot`. No-op when disabled."""
    if not _enabled:
        return {}
    _drain_compiles()  # scrape shows compiles staged since the last read
    m = metrics if metrics is not None else libmetrics.node_metrics()
    _publish_compiles(m)
    _bridge_transfers(m)
    return {
        "device_memory": _sample_device_memory(m),
        "pubkey_arena": _sample_arena(m),
        "verify_dispatch": _sample_dispatch(),
    }


def snapshot() -> dict:
    """The /debug/devstats JSON: ledger + live sample, one dict."""
    _drain_compiles()
    with _mtx:
        per = {
            f"{kernel}:{bucket}": n
            for (kernel, bucket), n in sorted(_compiled.items())
        }
        c = dict(_c)
    return {
        "enabled": _enabled,
        "xla": {
            "compiles": c["compiles"],
            "recompiles": c["recompiles"],
            "compile_seconds": round(c["compile_seconds"], 3),
            "per_kernel_bucket": per,
            "persistent_cache": {
                "hits": c["pcache_hits"],
                "misses": c["pcache_misses"],
            },
        },
        "transfers": {
            "h2d_ops": c["h2d_ops"],
            "h2d_bytes": c["h2d_bytes"],
            "d2h_ops": c["d2h_ops"],
            "d2h_bytes": c["d2h_bytes"],
        },
        # who used the device: the per-(plane, caller) time/lane ledger
        # and its occupancy view (libs/devledger; full budget plane at
        # /debug/budget)
        "device_ledger": _ledger_block(),
        **sample(),
    }


def _ledger_block() -> dict:
    try:
        from . import devledger as libdevledger

        return libdevledger.snapshot()
    except Exception as e:  # a ledger fault must not sink a bundle
        return {"error": repr(e)}


# --------------------------------------------------------- scrape server


def prometheus_addr(config=None) -> str:
    """The scrape listener address: COMETBFT_TPU_PROM_ADDR wins, then
    the config Instrumentation section, else "" (no listener)."""
    addr = os.environ.get(_ENV_PROM_ADDR, "")
    if addr:
        return addr
    if config is not None and config.instrumentation.prometheus:
        return config.instrumentation.prometheus_listen_addr
    return ""


class PrometheusServer(HTTPService):
    """Dedicated /metrics listener (the reference's Instrumentation
    server, node/node.go:630): serves ``registry.render()`` with the
    exposition content type; ``refresh`` (the node's pull-time gauge
    hook, which includes :func:`sample`) runs before each render."""

    CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"
    # the reference Instrumentation listener binds ALL interfaces on its
    # ":26660" default (a scrape target, not a loopback debug server)
    DEFAULT_HOST = "0.0.0.0"

    def __init__(self, addr: str, registry, refresh=None, logger=None):
        super().__init__("prometheus", addr, logger)
        self.registry = registry
        self._refresh = refresh
        # scrape self-metric (one shared family definition so the
        # NodeMetrics registration and this one dedupe to ONE instance)
        self._scrape_hist = libmetrics.scrape_duration_histogram(registry)

    def handle_get(self, path: str, query: dict) -> tuple[str, str]:
        if path == "/":
            return (
                "text/plain; charset=utf-8",
                "cometbft-tpu prometheus exporter\n"
                "/metrics  registry exposition\n",
            )
        if path != "/metrics":
            raise KeyError(path)
        t0 = time.perf_counter()
        if self._refresh is not None:
            try:
                self._refresh()
            except Exception as e:
                # pull-time gauges are best-effort; the counters and
                # histograms must still scrape
                if self.logger is not None:
                    self.logger.error(
                        "metrics refresh failed", err=repr(e)[:200]
                    )
        body = self.registry.render()
        # observed BEFORE the final render would be invisible to THIS
        # scrape; the one-scrape lag on the self-metric is the standard
        # exporter trade (prometheus client libs do the same)
        self._scrape_hist.labels("prometheus").observe(
            time.perf_counter() - t0
        )
        return self.CONTENT_TYPE, body


def debug_devstats_json() -> str:
    """Body of the pprof server's /debug/devstats route."""
    t0 = time.perf_counter()
    body = json.dumps(snapshot(), default=str)
    libmetrics.node_metrics().health_scrape_seconds.labels(
        "devstats"
    ).observe(time.perf_counter() - t0)
    return body


# Env-enabled processes (COMETBFT_TPU_DEVSTATS=1 with no node/listener
# ever calling enable()) still need the jax.monitoring listener, or the
# persistent-cache hit/miss classification would silently read 0.
if _enabled:
    _register_monitoring()
