"""Root-cause attribution over a merged cross-node timeline.

For every **slow height** (committed in > 1 round, or commit latency at
or above the run's p99 and well above its median) — and once for the
whole run — a panel of detectors scores the causes the observability
stack can actually see, and the ranked result is the **verdict**:

    injected_drop       link faults ate messages (simnet drop faults)
    injected_latency    one-hop gossip lag far above the healthy floor
    injected_partition  a partition overlapped the window
    injected_churn      a node was killed/restarted in the window
    injected_crash      an armed crash point fired in the window
    gray_partition      a one-DIRECTIONAL sever overlapped the window
    slow_disk           a slow-but-alive disk fault overlapped the window
    peer_evicted        a node-side defense evicted a peer (suspicion /
                        statesync chunk rotation) in the window
    laggard_proposer    the proposal arrived long after its round opened
    slow_gossip_hop     one hop's lag dwarfs the window's typical lag
    verify_stall        the verify-coalescer breaker was open
    recompile_storm     steady-state XLA recompiles burned the window
    wal_fsync_outlier   one WAL fsync consumed a large latency share
    mempool_backlog     sampled txs committed in the window waited far
                        longer in the mempool than the run's typical
                        submit->commit wait (libs/txtrace rows)
    lock_contention     threads spent a large share of the window
                        blocked on one engine mutex (libs/lockprof
                        EV_LOCK wait rows name the hot lock and the
                        blocking holder's acquire site)
    cpu_saturated       one subsystem's GIL-bound Python burned most
                        of the window's wall time (libs/profile
                        EV_PROF windows: the subsystem's kernel CPU —
                        the commit was compute-gated, not waiting)

Scores live in [0, 1]; only findings at or above the report threshold
make the verdict, so a healthy run yields **no verdict at all** — the
contract the fault-matrix acceptance test pins: every faulty simnet
cell's top-ranked cause names the injected fault, the clean cell stays
silent.  All arithmetic is over ring-derived integers/floats, so the
same (seed, scenario) produces the identical report.
"""

from __future__ import annotations

import dataclasses

# findings below this score never make a verdict
REPORT_THRESHOLD = 0.25
# expected healthy one-hop gossip lag; the latency detector scores the
# observed p50 against multiples of this floor (the simnet default link
# is 2 ms +- 0.5 ms jitter; LAN hops sit well under it too).  Override
# per call for exotic nets.
DEFAULT_BASELINE_LAG_S = 0.005

# simnet FAULT_DROP detail high byte (link.py drop reasons): which
# drops are INJECTED link faults vs partition/churn side effects
_DROP_INJECTED = frozenset({0, 1, 2})  # random / channel / class
_DROP_PARTITION = 3
_DROP_DEAD = 4

_FAULT = "simnet.fault"
_BREAKER = "coalesce.breaker"
_RECOMPILE = "xla.recompile"
_FSYNC = "wal.fsync"
_LOCK = "sync.lock"
_PROF = "prof.window"
_WATCHDOG = "health.watchdog"


@dataclasses.dataclass
class Finding:
    cause: str
    score: float
    evidence: dict

    def to_dict(self) -> dict:
        return {
            "cause": self.cause,
            "score": round(self.score, 4),
            "evidence": self.evidence,
        }


@dataclasses.dataclass
class WindowVerdict:
    """One attribution window (a slow height, or the whole run)."""

    window: str  # "height:H" | "run"
    height: int | None
    rounds: int
    latency_s: float | None
    findings: list  # ranked Findings (all, incl. sub-threshold)
    threshold: float

    @property
    def verdict(self) -> Finding | None:
        top = self.findings[0] if self.findings else None
        return top if top is not None and top.score >= self.threshold else None

    def to_dict(self) -> dict:
        v = self.verdict
        return {
            "window": self.window,
            "height": self.height,
            "rounds": self.rounds,
            "latency_s": self.latency_s,
            "verdict": v.to_dict() if v else None,
            "findings": [
                f.to_dict() for f in self.findings
                if f.score >= self.threshold
            ],
        }


@dataclasses.dataclass
class Report:
    run: WindowVerdict
    slow_heights: list  # WindowVerdicts
    threshold: float
    baseline_lag_s: float

    def to_dict(self) -> dict:
        return {
            "threshold": self.threshold,
            "baseline_lag_s": self.baseline_lag_s,
            "run": self.run.to_dict(),
            "slow_heights": [w.to_dict() for w in self.slow_heights],
        }

    def table(self) -> str:
        """The attribution table the simnet ``--postmortem`` flag and
        the CLI print."""
        lines = [
            f"{'window':<12} {'rounds':>6} {'latency':>10}  verdict",
        ]

        def fmt(w: WindowVerdict) -> str:
            v = w.verdict
            lat = f"{w.latency_s * 1e3:.1f}ms" if w.latency_s else "-"
            if v is None:
                cause = "(no cause above threshold)"
            else:
                ev = ", ".join(
                    f"{k}={v.evidence[k]}"
                    for k in sorted(v.evidence)
                    if not isinstance(v.evidence[k], (dict, list))
                )
                cause = f"{v.cause} [{v.score:.2f}] {ev}"
            return f"{w.window:<12} {w.rounds:>6} {lat:>10}  {cause}"

        lines.append(fmt(self.run))
        for w in self.slow_heights:
            lines.append(fmt(w))
        return "\n".join(lines)


# ----------------------------------------------------------- detectors


def _partition_intervals(annotations: list, end_ns: int) -> list:
    """[(start_ns, end_ns)] partition windows from fault annotations
    (an unhealed partition runs to the end of the data)."""
    out = []
    open_ts = None
    for a in annotations:
        if a.get("event") != _FAULT:
            continue
        fname = a.get("fault_name")
        if fname == "partition":
            if open_ts is None:
                open_ts = a.get("ts", 0)
        elif fname == "heal" and open_ts is not None:
            out.append((open_ts, a.get("ts", 0)))
            open_ts = None
    if open_ts is not None:
        out.append((open_ts, end_ns))
    return out


def _fault_intervals(
    annotations: list, end_ns: int, fault_name: str
) -> list:
    """[(start_ns, end_ns, row)] for set/clear fault pairs of one
    gray-failure family (``oneway_sever``/``slow_disk``: ``detail`` > 0
    opens an episode, 0 — or a ``heal`` row — closes it; an unclosed
    episode runs to the end of the data).  Episodes are keyed per
    (src, dst) so concurrent faults of the same family on different
    nodes/links track independently — a clear on node 1 must not close
    node 2's still-active episode.  Only explicit ``detail=0`` rows
    close an episode: ``net.heal()`` emits one per open one-way sever
    before its ``heal`` row, and slow disks are NOT healed by it, so a
    bare ``heal`` must not close a still-charging disk fault."""
    out = []
    open_rows: dict = {}
    for a in annotations:
        if a.get("event") != _FAULT:
            continue
        if a.get("fault_name") == fault_name:
            # fault rows park src/dst (slow_disk: node) in the ring's
            # h/r columns, decoded as height/round
            key = (a.get("height"), a.get("round"))
            if a.get("detail", 0) > 0:
                open_rows.setdefault(key, a)
            elif key in open_rows:
                row = open_rows.pop(key)
                out.append((row.get("ts", 0), a.get("ts", 0), row))
    for row in open_rows.values():
        out.append((row.get("ts", 0), end_ns, row))
    out.sort(key=lambda t: t[0])
    return out


def _window_findings(
    *,
    t0_ns: int,
    end_ns: int,
    annotations: list,
    partitions: list,
    gray_intervals: list = (),
    slow_disk_intervals: list = (),
    lag_samples: list,
    gossip: dict | None,
    proposal_gap_s: float | None,
    median_gap_s: float | None,
    baseline_lag_s: float,
    tx_waits: list = (),
    tx_depths: list = (),
    median_tx_wait_s: float | None = None,
) -> list:
    """Score every cause over one window; returns ALL findings ranked
    by score (the caller applies the report threshold)."""
    findings: list[Finding] = []
    dur_s = max((end_ns - t0_ns) / 1e9, 1e-9)

    def in_window(a) -> bool:
        return t0_ns <= a.get("ts", 0) <= end_ns

    anns = [a for a in annotations if in_window(a)]

    # -- injected link drops (simnet fault plane)
    drops = [
        a for a in anns
        if a.get("event") == _FAULT
        and a.get("fault_name") == "drop"
        and (a.get("detail", 0) >> 8) in _DROP_INJECTED
    ]
    if drops:
        by_ch: dict[str, int] = {}
        for a in drops:
            ch = f"{a.get('detail', 0) & 0xFF:#04x}"
            by_ch[ch] = by_ch.get(ch, 0) + 1
        findings.append(Finding(
            "injected_drop",
            len(drops) / (len(drops) + 3.0),
            {"drops": len(drops), "by_channel": dict(sorted(by_ch.items()))},
        ))

    # -- partition overlap
    overlap_ns = 0
    for s, e in partitions:
        overlap_ns += max(0, min(e, end_ns) - max(s, t0_ns))
    if overlap_ns > 0:
        frac = min(1.0, overlap_ns / (end_ns - t0_ns + 1))
        findings.append(Finding(
            "injected_partition",
            0.6 + 0.35 * frac,
            {"overlap_s": round(overlap_ns / 1e9, 6)},
        ))

    # -- gray (one-directional) partition overlap
    gray_ns = 0
    gray_row = None
    for s, e, row in gray_intervals:
        ov = max(0, min(e, end_ns) - max(s, t0_ns))
        if ov > 0 and gray_row is None:
            gray_row = row
        gray_ns += ov
    if gray_ns > 0:
        frac = min(1.0, gray_ns / (end_ns - t0_ns + 1))
        findings.append(Finding(
            "gray_partition",
            0.6 + 0.35 * frac,
            {
                "overlap_s": round(gray_ns / 1e9, 6),
                # the sever rows park src/dst in the h/r columns
                "src": (gray_row or {}).get("height"),
                "dst": (gray_row or {}).get("round"),
            },
        ))

    # -- slow-but-alive disk overlap
    sd_ns = 0
    sd_row = None
    for s, e, row in slow_disk_intervals:
        ov = max(0, min(e, end_ns) - max(s, t0_ns))
        if ov > 0 and sd_row is None:
            sd_row = row
        sd_ns += ov
    if sd_ns > 0:
        # floor above laggard_proposer's 0.8 cap: a slow disk overlap
        # is a DIRECTLY injected/observed fault, and "the proposer was
        # late" is its symptom, not a competing root cause
        frac = min(1.0, sd_ns / (end_ns - t0_ns + 1))
        findings.append(Finding(
            "slow_disk",
            0.82 + 0.13 * frac,
            {
                "overlap_s": round(sd_ns / 1e9, 6),
                "node": (sd_row or {}).get("height"),
                "latency_ms": (sd_row or {}).get("detail"),
            },
        ))

    # -- a node-side defense acted (suspicion eviction / statesync
    # chunk-peer rotation): named, but scored BELOW the injected
    # faults — the defense is the response, rarely the root cause
    evictions = [
        a for a in anns
        if a.get("event") == _FAULT
        and a.get("fault_name") == "peer_evict"
    ]
    if evictions:
        findings.append(Finding(
            "peer_evicted",
            min(0.5, 0.25 + 0.05 * len(evictions)),
            {"evictions": len(evictions)},
        ))

    # -- churn / crash points
    kills = [
        a for a in anns
        if a.get("event") == _FAULT
        and a.get("fault_name") in ("kill", "restart")
    ]
    if kills:
        findings.append(Finding(
            "injected_churn",
            0.8,
            {
                "events": len(kills),
                "nodes": sorted({a.get("height", 0) for a in kills}),
            },
        ))
    crashes = [
        a for a in anns
        if a.get("event") == _FAULT
        and a.get("fault_name") == "crash_point"
    ]
    if crashes:
        findings.append(Finding(
            "injected_crash", 0.9, {"events": len(crashes)},
        ))

    # -- gossip latency far above the healthy floor
    if lag_samples:
        vs = sorted(lag_samples)
        p50 = vs[min(len(vs) - 1, len(vs) // 2)]
        score = (p50 - 2.0 * baseline_lag_s) / (8.0 * baseline_lag_s)
        if score > 0:
            findings.append(Finding(
                "injected_latency",
                min(1.0, score),
                {
                    "lag_p50_ms": round(p50 * 1e3, 3),
                    "baseline_ms": round(baseline_lag_s * 1e3, 3),
                    "hops": len(vs),
                },
            ))
        # -- one outlier hop (vs the window's own typical lag)
        mx = vs[-1]
        if mx > max(5.0 * p50, 4.0 * baseline_lag_s):
            worst = (gossip or {}).get("worst") or {}
            findings.append(Finding(
                "slow_gossip_hop",
                min(0.6, 0.2 * mx / max(p50, baseline_lag_s) / 5.0),
                {
                    "lag_max_ms": round(mx * 1e3, 3),
                    "lag_p50_ms": round(p50 * 1e3, 3),
                    "phase": worst.get("phase"),
                    "node": worst.get("node"),
                    "src": worst.get("src"),
                },
            ))

    # -- laggard proposer (relative to the run's typical proposal wait)
    if (
        proposal_gap_s is not None
        and median_gap_s is not None
        and proposal_gap_s > 3.0 * median_gap_s
        and proposal_gap_s > 0.2 * dur_s
    ):
        findings.append(Finding(
            "laggard_proposer",
            min(0.8, proposal_gap_s / (6.0 * median_gap_s + 1e-12) * 0.4),
            {
                "proposal_wait_ms": round(proposal_gap_s * 1e3, 3),
                "typical_ms": round(median_gap_s * 1e3, 3),
            },
        ))

    # -- verify-coalescer breaker open
    trips = [a for a in anns if a.get("event") == _BREAKER]
    if any(a.get("open") for a in trips):
        rearmed = any(not a.get("open") for a in trips)
        findings.append(Finding(
            "verify_stall",
            0.5 if rearmed else 0.85,
            {
                "trips": sum(1 for a in trips if a.get("open")),
                "rearmed": rearmed,
            },
        ))

    # -- recompile storm
    recompiles = [a for a in anns if a.get("event") == _RECOMPILE]
    if recompiles:
        findings.append(Finding(
            "recompile_storm",
            min(0.9, 0.3 * len(recompiles)),
            {"recompiles": len(recompiles)},
        ))

    # -- mempool backlog: sampled txs that committed IN this window
    # waited far longer from admission to commit than the run's
    # typical sampled tx — inclusion lagged while the chain ran, the
    # tx-plane signature of a storm-backlogged mempool (tx rows come
    # from libs/txtrace's deterministic sampling, so the comparison is
    # apples-to-apples across heights and nodes)
    if tx_waits and median_tx_wait_s:
        tw = sorted(tx_waits)
        p50 = tw[min(len(tw) - 1, len(tw) // 2)]
        ratio = p50 / median_tx_wait_s
        if ratio > 3.0:
            dp = sorted(tx_depths)
            findings.append(Finding(
                "mempool_backlog",
                min(0.85, 0.2 + 0.1 * ratio),
                {
                    "txs": len(tw),
                    "wait_p50_ms": round(p50 * 1e3, 3),
                    "typical_ms": round(median_tx_wait_s * 1e3, 3),
                    "depth_p50": (
                        dp[min(len(dp) - 1, len(dp) // 2)] if dp else None
                    ),
                },
            ))

    # -- WAL fsync outlier (wall-domain rings only; virtual merges drop
    # fsync rows because real disk time has no virtual meaning)
    fsyncs = [a for a in anns if a.get("event") == _FSYNC]
    if fsyncs:
        mx_s = max(a.get("dur_ns", 0) for a in fsyncs) / 1e9
        frac = mx_s / dur_s
        if frac > 0.15:
            findings.append(Finding(
                "wal_fsync_outlier",
                min(0.9, 2.0 * frac),
                {
                    "fsync_max_ms": round(mx_s * 1e3, 3),
                    "window_share": round(frac, 4),
                },
            ))

    # -- lock contention (wall-domain rings only, like fsync): slow
    # EV_LOCK wait rows in the window sum per lock; when the hottest
    # lock's blocked time is a large share of the window's wall time,
    # the commit chain was serialized behind it — the verdict names
    # the lock and the blocking holder's acquire site
    lock_waits = [
        a for a in anns
        if a.get("event") == _LOCK and a.get("kind_name") == "wait"
    ]
    if lock_waits:
        per_lock: dict[str, float] = {}
        site_of: dict[str, str] = {}
        for a in lock_waits:
            lk = a.get("lock", "?")
            per_lock[lk] = per_lock.get(lk, 0.0) + a.get("dur_ns", 0) / 1e9
            site_of.setdefault(lk, a.get("site", "?"))
        hot = max(per_lock, key=lambda k: per_lock[k])
        frac = per_lock[hot] / dur_s
        if frac > 0.15:
            findings.append(Finding(
                "lock_contention",
                min(0.9, 2.0 * frac),
                {
                    "lock": hot,
                    "holder_site": site_of.get(hot),
                    "wait_ms": round(per_lock[hot] * 1e3, 3),
                    "window_share": round(frac, 4),
                    "waits": len(lock_waits),
                },
            ))

    # -- CPU saturation (wall-domain rings only, like fsync/lock: the
    # threads' CPU clocks are wall-domain, so virtual merges drop
    # EV_PROF rows): the sampling profiler's window rows sum
    # per-subsystem kernel CPU time (the threads' own clocks, so a
    # thread waiting for the interpreter lock or asleep in C adds
    # nothing, however it samples); when one subsystem's GIL-bound Python
    # consumed most of the window's wall clock, the commit was
    # compute-gated — the verdict names the subsystem (the profiler's
    # own sampler thread never counts)
    prof_rows = [
        a for a in anns
        if a.get("event") == _PROF and a.get("subsystem") != "sampler"
    ]
    if prof_rows:
        per_sub: dict[str, float] = {}
        for a in prof_rows:
            sub = a.get("subsystem", "?")
            per_sub[sub] = per_sub.get(sub, 0.0) + (
                a.get("oncpu_ns", 0) / 1e9
            )
        hot_sub = max(per_sub, key=lambda k: per_sub[k])
        frac = per_sub[hot_sub] / dur_s
        if frac > 0.6:
            findings.append(Finding(
                "cpu_saturated",
                min(0.9, 1.2 * frac),
                {
                    "subsystem": hot_sub,
                    "oncpu_ms": round(per_sub[hot_sub] * 1e3, 1),
                    "window_share": round(frac, 4),
                    "samples": sum(
                        a.get("samples", 0) for a in prof_rows
                    ),
                },
            ))

    findings.sort(key=lambda f: (-f.score, f.cause))
    return findings


# ----------------------------------------------------------- attribution


def _height_latency(hv: dict) -> float | None:
    """The height's network-wide latency: the slowest node's view."""
    lats = [c["latency_s"] for c in hv.get("commits", {}).values()]
    return max(lats) if lats else None


def _proposal_gap_s(hv: dict) -> float | None:
    p = hv.get("proposal")
    if p is None:
        return None
    start = hv.get("round_starts", {}).get(str(p["round"]))
    if start is None:
        start = hv.get("t0_ns")
    return max(0.0, (p["ts_ns"] - start) / 1e9)


def attribute(
    timeline,
    baseline_lag_s: float = DEFAULT_BASELINE_LAG_S,
    threshold: float = REPORT_THRESHOLD,
) -> Report:
    """Run the detector panel over a merged Timeline -> Report."""
    data = timeline.data
    heights = data["heights"]
    run = data["run"]
    annotations = run["annotations"]
    partitions = _partition_intervals(annotations, run["end_ns"])
    gray_intervals = _fault_intervals(
        annotations, run["end_ns"], "oneway_sever"
    )
    slow_disk_intervals = _fault_intervals(
        annotations, run["end_ns"], "slow_disk"
    )

    gaps = [g for g in (_proposal_gap_s(hv) for hv in heights)
            if g is not None]
    median_gap = sorted(gaps)[len(gaps) // 2] if gaps else None

    # sampled tx-lifecycle samples (absent on timelines built before
    # the tx plane, and on synthetic test Timelines)
    tx_s = getattr(timeline, "tx_samples", None) or {}
    tx_run = sorted(tx_s.get("run", []))
    median_tx_wait = tx_run[len(tx_run) // 2] if tx_run else None
    tx_heights = tx_s.get("heights", {})
    tx_depths = tx_s.get("depths", {})

    lats = [x for x in (_height_latency(hv) for hv in heights)
            if x is not None]
    lat_sorted = sorted(lats)
    p99 = (
        lat_sorted[min(len(lat_sorted) - 1, int(0.99 * len(lat_sorted)))]
        if lat_sorted else None
    )
    median_lat = (
        lat_sorted[len(lat_sorted) // 2] if lat_sorted else None
    )

    slow: list[WindowVerdict] = []
    for hv in heights:
        lat = _height_latency(hv)
        is_slow = hv["rounds"] > 1 or (
            lat is not None
            and p99 is not None
            and lat >= p99
            and median_lat is not None
            and lat > 1.2 * median_lat
        )
        if not is_slow:
            continue
        findings = _window_findings(
            t0_ns=hv["t0_ns"],
            end_ns=hv["end_ns"],
            annotations=annotations,
            partitions=partitions,
            gray_intervals=gray_intervals,
            slow_disk_intervals=slow_disk_intervals,
            lag_samples=timeline.lag_samples["heights"].get(
                hv["height"], []
            ),
            gossip=hv.get("gossip"),
            proposal_gap_s=_proposal_gap_s(hv),
            median_gap_s=median_gap,
            baseline_lag_s=baseline_lag_s,
            tx_waits=tx_heights.get(hv["height"], ()),
            tx_depths=tx_depths.get(hv["height"], ()),
            median_tx_wait_s=median_tx_wait,
        )
        slow.append(WindowVerdict(
            window=f"height:{hv['height']}",
            height=hv["height"],
            rounds=hv["rounds"],
            latency_s=lat,
            findings=findings,
            threshold=threshold,
        ))

    run_findings = _window_findings(
        t0_ns=run["t0_ns"],
        end_ns=run["end_ns"],
        annotations=annotations,
        partitions=partitions,
        gray_intervals=gray_intervals,
        slow_disk_intervals=slow_disk_intervals,
        lag_samples=timeline.lag_samples["run"],
        gossip=run.get("gossip"),
        proposal_gap_s=max(gaps) if gaps else None,
        median_gap_s=median_gap,
        baseline_lag_s=baseline_lag_s,
    )
    rounds_max = max((hv["rounds"] for hv in heights), default=1)
    run_verdict = WindowVerdict(
        window="run",
        height=None,
        rounds=rounds_max,
        latency_s=p99,
        findings=run_findings,
        threshold=threshold,
    )
    return Report(
        run=run_verdict,
        slow_heights=slow,
        threshold=threshold,
        baseline_lag_s=baseline_lag_s,
    )
