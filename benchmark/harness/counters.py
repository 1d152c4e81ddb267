"""One flat snapshot of the program's own counters, and deltas over a window.

Keys:
  devstats.<k>            libs/devstats.counters(): h2d/d2h bytes and ops,
                          compiles, persistent-cache hits and misses
  launches.<kernel>       ops.verify.dispatch_counters()["launches"]
  faults.<kind>           ops.verify.dispatch_counters()["faults"]
  ledger.<plane>.<k>      libs/devledger.occupancy(): windows, device_windows,
                          window_lanes of the verify and hash planes
  prom.<series>           every series of the active NodeMetrics registry, as
                          its Prometheus text renders it (histograms give
                          _sum and _count)
  spans.<name>.<backend>.lanes   lanes carried by the program's own
                          ``verify.*`` trace events (libs/trace), summed
                          per event name and backend; only in a traced run,
                          where run.py switches libs/trace on
  <prefix>.<k>            whatever the driver adds (a cache's stats, a
                          plane's window counts)
"""

from __future__ import annotations


def _prom(registry) -> dict:
    out = {}
    for line in registry.render().splitlines():
        if not line or line[0] == "#":
            continue
        name, _, value = line.rpartition(" ")
        try:
            out["prom." + name] = float(value)
        except ValueError:
            continue
    return out


def _spans() -> dict:
    from cometbft_tpu.libs import trace as libtrace

    out: dict = {}
    if not libtrace.enabled():
        return out
    for ev in libtrace.ring_dump():
        name = ev.get("name", "")
        if name.startswith("verify.") and "lanes" in ev:
            key = f"spans.{name}.{ev.get('backend')}.lanes"
            out[key] = out.get(key, 0) + ev["lanes"]
    return out


def snapshot(extra: dict | None = None) -> dict:
    from cometbft_tpu.libs import devledger, devstats
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.ops import verify as ov

    snap = {f"devstats.{k}": v for k, v in devstats.counters().items()}
    disp = ov.dispatch_counters()
    snap.update({f"launches.{k}": v for k, v in disp["launches"].items()})
    snap.update({f"faults.{k}": v for k, v in disp["faults"].items()})
    for plane, row in devledger.occupancy().items():
        for k in ("windows", "device_windows", "window_lanes"):
            snap[f"ledger.{plane}.{k}"] = row[k]
    snap.update(_prom(libmetrics.node_metrics().registry))
    snap.update(_spans())
    for prefix, values in (extra or {}).items():
        for k, v in values.items():
            if isinstance(v, (int, float)):
                snap[f"{prefix}.{k}"] = v
    return snap


def delta(before: dict, after: dict) -> dict:
    return {
        k: after[k] - before.get(k, 0)
        for k in after
        if isinstance(after[k], (int, float))
    }


def total(d: dict, keys) -> float:
    """Sum of the deltas whose key equals, or starts with, one of ``keys``
    (a trailing ``*`` makes a prefix)."""
    s = 0.0
    for want in keys:
        if want.endswith("*"):
            s += sum(v for k, v in d.items() if k.startswith(want[:-1]))
        else:
            s += d.get(want, 0.0)
    return s
