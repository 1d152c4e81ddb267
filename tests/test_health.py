"""libs/health: the always-on consensus flight recorder, the SLO
engine, the watchdogs, and the black-box bundles.

The acceptance gates of this PR live here: a deliberately stalled
single-node run (frozen timeout ticker) trips the stall watchdog within
the configured window and writes a black-box bundle; the same scenario
with watchdogs disabled writes nothing; and a healthy 4-validator burst
runs end to end with zero trips and a non-degraded health score.
"""

import json
import os
import time

import pytest

from cometbft_tpu.libs import health as libhealth
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs.metrics import NodeMetrics

import helpers


@pytest.fixture
def health():
    """Enabled recorder with a clean ring; module state restored —
    including the ring CAPACITY, which reset() deliberately preserves
    (a later module's ring would otherwise silently shrink to 1024 and
    evict rows its assertions depend on)."""
    prev_capacity = libhealth.recorder().capacity
    libhealth.enable(ring=1024)
    libhealth.reset()
    yield libhealth
    libhealth.disable()
    libhealth.set_ring_capacity(prev_capacity)
    libhealth.reset()


def _wait_until(cond, timeout=10.0, step=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


class TestFlightRecorder:
    def test_disabled_records_nothing(self):
        assert not libhealth.enabled()
        libhealth.reset()
        libhealth.record(libhealth.EV_STEP, 1, 0, 3)
        assert libhealth.recorder().dump() == []

    def test_record_decode_roundtrip(self, health):
        libhealth.record(libhealth.EV_STEP, 7, 1, 4)
        libhealth.record(libhealth.EV_VOTE, 7, 1, 2, 3)
        libhealth.record(libhealth.EV_COMMIT, 7, 1, 250_000_000)
        libhealth.record(libhealth.EV_FSYNC, a=4_000_000)
        libhealth.record(libhealth.EV_BREAKER, a=1)
        evs = libhealth.recorder().dump()
        assert [e["event"] for e in evs] == [
            "consensus.step", "consensus.vote", "consensus.commit",
            "wal.fsync", "coalesce.breaker",
        ]
        step, vote, commit, fsync, breaker = evs
        assert step["height"] == 7 and step["round"] == 1
        assert step["step"] == 4 and step["step_name"] == "Prevote"
        assert vote["type"] == 2 and vote["index"] == 3
        assert commit["dur_ns"] == 250_000_000
        assert fsync["dur_ns"] == 4_000_000
        assert breaker["open"] == 1
        assert all(e["ts"] > 0 for e in evs)

    def test_ring_is_bounded_and_wraps(self):
        libhealth.enable(ring=64)
        try:
            for i in range(200):
                libhealth.record(libhealth.EV_VOTE, i, 0, 1, i)
            evs = libhealth.recorder().dump()
            assert len(evs) == 64
            # oldest-first, newest tail preserved
            assert evs[-1]["height"] == 199
            assert evs[0]["height"] == 200 - 64
            assert libhealth.recorder().status()["recorded"] == 200
        finally:
            libhealth.enable(ring=libhealth.DEFAULT_RING_SIZE)
            libhealth.disable()
            libhealth.reset()

    def test_slis_from_ring(self, health):
        for h in range(1, 11):
            libhealth.record(libhealth.EV_STEP, h, 0, 8)
            # heights at 100 ms except one 300 ms straggler on round 2
            dur = 300_000_000 if h == 10 else 100_000_000
            libhealth.record(
                libhealth.EV_COMMIT, h, 2 if h == 10 else 0, dur
            )
        libhealth.record(libhealth.EV_FSYNC, a=2_000_000)
        s = libhealth.slis()
        assert s["commits"] == 10
        assert s["commit_latency_s"]["p50"] == pytest.approx(0.1)
        assert s["commit_latency_s"]["p99"] == pytest.approx(0.3)
        assert s["commit_latency_s"]["last"] == pytest.approx(0.3)
        # nine 1-round heights + one 3-round height
        assert s["rounds_per_height"] == pytest.approx(1.2)
        assert s["wal_fsync_p99_s"] == pytest.approx(0.002)
        assert s["step_age_s"] is not None and s["step_age_s"] < 5

    def test_acquire_release_refcount(self, monkeypatch):
        monkeypatch.delenv("COMETBFT_TPU_HEALTH", raising=False)
        libhealth.disable()
        assert not libhealth.enabled()
        libhealth.acquire()
        libhealth.acquire()
        assert libhealth.enabled()
        libhealth.release()
        assert libhealth.enabled()  # the second node still holds it
        libhealth.release()
        assert not libhealth.enabled()
        # the 0 kill switch wins over acquire
        monkeypatch.setenv("COMETBFT_TPU_HEALTH", "0")
        libhealth.acquire()
        assert not libhealth.enabled()
        assert not libhealth.monitor_enabled()
        # force-on pins across release
        monkeypatch.setenv("COMETBFT_TPU_HEALTH", "1")
        libhealth.acquire()
        libhealth.release()
        assert libhealth.enabled()
        monkeypatch.delenv("COMETBFT_TPU_HEALTH")
        libhealth.disable()

    def test_histogram_quantile_estimate(self):
        from cometbft_tpu.libs.metrics import Histogram

        h = Histogram("t_q_seconds", buckets=(0.001, 0.01, 0.1, 1.0))
        assert libhealth.histogram_quantile(h, 0.99) == 0.0  # empty
        for _ in range(99):
            h.observe(0.005)
        h.observe(0.5)
        assert libhealth.histogram_quantile(h, 0.5) == pytest.approx(0.01)
        assert libhealth.histogram_quantile(h, 0.999) == pytest.approx(1.0)


class TestWatchdogUnits:
    """Each detector in isolation, driven through _check() directly."""

    def _monitor(self, **kw):
        kw.setdefault("stall_base_s", 1000.0)
        kw.setdefault("stall_mult", 1.0)
        kw.setdefault("metrics", NodeMetrics())
        return libhealth.HealthMonitor(**kw)

    def test_stall_detector_fires_and_rebaselines(self, health):
        mon = self._monitor(stall_base_s=0.05)
        libhealth.record(libhealth.EV_STEP, 1, 0, 3)
        assert mon._check() == 0  # fresh progress
        time.sleep(0.12)
        assert mon._check() & 1  # stalled
        assert mon.stalled()
        # one trip per stalled window, not one per tick
        assert mon._check() == 0
        # progress resumed → re-arms
        libhealth.record(libhealth.EV_STEP, 1, 0, 4)
        assert mon._check() == 0
        assert not mon.stalled()

    def test_idle_ok_suppresses_stall(self, health):
        """A legitimately idle node (blocksyncing, or waiting for txs
        with create_empty_blocks=false) must not page: the node-wired
        idle_ok predicate re-baselines the window without a trip, and
        a later window with idle_ok False trips normally."""
        idle = [True]
        mon = self._monitor(
            stall_base_s=0.05, idle_ok=lambda: idle[0]
        )
        time.sleep(0.12)
        assert mon._check() == 0  # silence excused
        assert not mon.stalled()
        idle[0] = False
        time.sleep(0.12)  # a fresh full window of inexcusable silence
        assert mon._check() & 1
        assert mon.stalled()
        # a predicate that raises counts as NOT idle (fail toward
        # alerting, never toward silence)
        def boom():
            raise RuntimeError("sync state unavailable")

        mon2 = self._monitor(stall_base_s=0.05, idle_ok=boom)
        time.sleep(0.12)
        assert mon2._check() & 1

    def test_bundle_retention_keeps_first_and_newest(
        self, health, tmp_path
    ):
        """Retention bounds the total on disk: the oldest bundle (the
        original failure edge) is pinned, the remaining slots hold the
        newest."""
        paths = []
        for i in range(5):
            paths.append(
                os.path.basename(
                    libhealth.write_bundle(str(tmp_path), f"r{i}")
                )
            )
            time.sleep(0.002)  # distinct time_ns prefixes
        libhealth.prune_bundles(str(tmp_path), 3)
        left = sorted(os.listdir(tmp_path))
        assert len(left) == 3
        assert paths[0] in left  # the failure edge survives
        assert paths[-1] in left and paths[-2] in left  # newest two
        # keep<=0 disables pruning
        libhealth.prune_bundles(str(tmp_path), 0)
        assert len(os.listdir(tmp_path)) == 3

    def test_breaker_hook_fires_on_tripped_coalescer(self, health):
        from cometbft_tpu.crypto import coalesce as cco

        mon = self._monitor()
        co = cco.VerifyCoalescer(device=False)
        co.start()
        cco.push_active(co)
        try:
            assert mon._check() == 0
            assert not cco.breaker_open()
            co._trip()
            assert cco.breaker_open()
            assert mon._check() & 2
            evs = [
                e for e in libhealth.recorder().dump()
                if e["event"] == "coalesce.breaker"
            ]
            assert evs and evs[-1]["open"] == 1
            # a second check without a new trip stays quiet
            assert mon._check() == 0
            co._rearm()
            assert not cco.breaker_open()
            evs = [
                e for e in libhealth.recorder().dump()
                if e["event"] == "coalesce.breaker"
            ]
            assert evs[-1]["open"] == 0
        finally:
            cco.pop_active(co)
            co.stop()

    def test_recompile_alarm_on_synthetic_ledger_entries(self, health):
        from cometbft_tpu.libs import devstats

        mon = self._monitor(storm_recompiles=3, storm_window_s=60.0)
        assert mon._check() == 0
        # snapshot the process-wide ledger: synthetic entries must not
        # leak into later tests' registries (every fresh NodeMetrics
        # replays the full compile log from watermark 0)
        with devstats._mtx:
            log0 = len(devstats._compile_log)
            c0 = dict(devstats._c)
        try:
            # synthetic ledger entries: stage one cold compile then
            # three recompiles of the same kernel x bucket through the
            # real drain (the devstats hook also mirrors each into the
            # flight ring)
            devstats._pending_compiles.append(
                ("syn.health", 8, 0.01, 0, 1, False, False)
            )
            devstats._drain_compiles()
            for i in range(3):
                devstats._pending_compiles.append(
                    ("syn.health", 8, 0.01, 1 + i, 2 + i, False, False)
                )
                devstats._drain_compiles()
            assert mon._check() & 4
            evs = [
                e for e in libhealth.recorder().dump()
                if e["event"] == "xla.recompile"
            ]
            assert len(evs) == 3 and all(e["bucket"] == 8 for e in evs)
            # window reset after the trip: no immediate re-trip
            assert mon._check() == 0
        finally:
            with devstats._mtx:
                del devstats._compile_log[log0:]
                devstats._c.clear()
                devstats._c.update(c0)
                devstats._compiled.pop(("syn.health", 8), None)
                devstats._jit_sizes.pop("syn.health", None)

    def test_send_queue_saturation_needs_a_sustained_streak(self, health):
        """The saturated-send-queue watchdog: fresh MConnection.send
        drops on a consensus channel in SATURATION_STREAK consecutive
        checks trip it; a one-off burst drop re-baselines quietly."""
        from cometbft_tpu.libs import netstats as libnetstats

        libnetstats.enable()
        stats = libnetstats.ConnStats("satpeer", [0x22, 0x30])
        libnetstats.register(stats)
        try:
            mon = self._monitor(saturation_streak=3)
            assert mon._check() == 0
            # one burst of drops, then silence: streak resets, no trip
            stats.note_queue_full(stats.slots[0x22])
            assert mon._check() == 0  # streak 1
            assert mon._check() == 0  # no fresh drops -> reset
            # sustained: fresh drops on three consecutive checks
            for i in range(2):
                stats.note_queue_full(stats.slots[0x22])
                assert mon._check() == 0, i  # streak 1, 2
            stats.note_queue_full(stats.slots[0x22])
            assert mon._check() & 8  # streak 3 -> trip
            # the streak restarts after a trip
            assert mon._check() == 0
            # drops on a NON-consensus channel never count
            mon2 = self._monitor(saturation_streak=1)
            stats.note_queue_full(stats.slots[0x30])
            assert mon2._check() == 0
        finally:
            libnetstats.deregister(stats)
            libnetstats.disable()
            libnetstats.reset()

    def test_gossip_event_decodes_with_phase_name(self, health):
        from cometbft_tpu.libs import netstats as libnetstats

        libhealth.record(
            libhealth.EV_GOSSIP, 12,
            a=libnetstats.PHASE_CODES["prevote"], b=1_500_000,
        )
        evs = [
            e for e in libhealth.recorder().dump()
            if e["event"] == "p2p.gossip"
        ]
        assert evs == [
            {
                "ts": evs[0]["ts"],
                "event": "p2p.gossip",
                "height": 12,
                "round": 0,
                "phase": libnetstats.PHASE_CODES["prevote"],
                "lag_ns": 1_500_000,
                "phase_name": "prevote",
            }
        ]

    def test_observe_propagation_feeds_ring_histogram_and_sli(
        self, health
    ):
        """netstats.observe_propagation is the one fan-out point: the
        parked stamp becomes a histogram observation, an EV_GOSSIP
        ring event, and a gossip-lag sample the SLI engine reads."""
        from cometbft_tpu.libs import netstats as libnetstats

        libnetstats.enable()
        libnetstats.reset()
        m = NodeMetrics()
        libmetrics.push_node_metrics(m)
        try:
            wall = time.time_ns() - 2_000_000  # stamped 2 ms ago
            libnetstats.set_current_stamp(("aabbccdd" * 2, 5, wall))
            libnetstats.observe_propagation("proposal", 9)
            libnetstats.clear_current_stamp()
            # unstamped dispatch: no observation
            libnetstats.observe_propagation("proposal", 10)
            h = m.p2p_propagation.labels("proposal")
            assert h._n == 1 and 0.001 < h._sum < 1.0
            evs = [
                e for e in libhealth.recorder().dump()
                if e["event"] == "p2p.gossip"
            ]
            assert len(evs) == 1 and evs[0]["height"] == 9
            assert evs[0]["phase_name"] == "proposal"
            assert libnetstats.gossip_lag_s() > 0.0
            out = libhealth.sample(m)
            assert out["gossip_lag_p99_s"] > 0.0
            assert m.health_gossip_lag.value() > 0.0
        finally:
            libmetrics.pop_node_metrics(m)
            libnetstats.disable()
            libnetstats.reset()

    def test_trips_count_and_ring_events(self, health):
        m = NodeMetrics()
        mon = self._monitor(metrics=m)
        mon._handle_trips(1 | 2)
        assert mon.trips["consensus_stall"] == 1
        assert mon.trips["verify_breaker"] == 1
        assert mon.trips["recompile_storm"] == 0
        assert (
            m.health_watchdog_trips.labels("consensus_stall").value() == 1
        )
        assert (
            m.health_watchdog_trips.labels("verify_breaker").value() == 1
        )
        wd = [
            e for e in libhealth.recorder().dump()
            if e["event"] == "health.watchdog"
        ]
        assert {e["watchdog_name"] for e in wd} == {
            "consensus_stall", "verify_breaker"
        }

    def test_bundle_rate_limiting(self, health, tmp_path):
        m = NodeMetrics()
        mon = self._monitor(
            metrics=m, bundle_dir=str(tmp_path), bundle_rl_s=60.0
        )
        mon._handle_trips(2)
        mon._handle_trips(2)
        dirs = os.listdir(tmp_path)
        assert len(dirs) == 1, dirs  # second bundle rate-limited
        assert mon.trips["verify_breaker"] == 2  # ...but both counted
        assert mon.bundles == 1
        assert m.health_bundles.value() == 1
        # a tiny rate limit lets the next trip write again
        mon2 = self._monitor(
            metrics=m, bundle_dir=str(tmp_path), bundle_rl_s=0.01
        )
        time.sleep(0.02)
        mon2._handle_trips(4)
        assert len(os.listdir(tmp_path)) == 2

    def test_bundle_contents(self, health, tmp_path):
        from cometbft_tpu.libs import profile as libprofile

        libhealth.record(libhealth.EV_STEP, 3, 0, 8)
        libhealth.record(libhealth.EV_COMMIT, 3, 0, 50_000_000)
        # the profiler was sampling before the trip: the bundle must
        # carry those pre-trip samples (the ring, not a fresh window)
        libprofile.acquire()
        try:
            assert _wait_until(
                lambda: libprofile.status()["ring"]["recorded"] > 0,
                timeout=10,
            ), "sampler took no samples"
            path = libhealth.write_bundle(str(tmp_path), "unit-test")
        finally:
            libprofile.release()
        names = set(os.listdir(path))
        assert {
            "manifest.json", "flight.json", "devstats.json",
            "locks.json", "net.json", "threads.txt", "trace.json",
            "profile.json",
        } <= names, names
        prof = json.load(open(os.path.join(path, "profile.json")))
        assert prof["status"]["ring"]["recorded"] > 0
        assert prof["recent"]["samples"] > 0
        assert "collapsed" in prof
        net = json.load(open(os.path.join(path, "net.json")))
        assert set(net) >= {
            "enabled", "stamping", "peers", "gossip_lag_p99_s",
            "consensus_send_queue_full",
        }
        flight = json.load(open(os.path.join(path, "flight.json")))
        assert any(
            e["event"] == "consensus.commit" for e in flight["events"]
        )
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["reason"] == "unit-test"
        assert manifest["slis"]["commits"] == 1
        devstats = json.load(open(os.path.join(path, "devstats.json")))
        assert "xla" in devstats
        locks = json.load(open(os.path.join(path, "locks.json")))
        assert set(locks) == {
            "deadlock_detection", "lock_order_mode", "held"
        }
        trace = json.load(open(os.path.join(path, "trace.json")))
        assert "status" in trace and "events" in trace
        threads = open(os.path.join(path, "threads.txt")).read()
        assert "--- thread" in threads


class TestStalledNodeAcceptance:
    """THE acceptance gate: a frozen timeout ticker stalls a single-node
    run; the stall watchdog trips within the configured window and
    writes a black-box bundle — and with the kill switch set, the same
    scenario writes nothing."""

    def _frozen_node(self, monkeypatch):
        genesis, pvs = helpers.make_genesis(1)
        cs, parts = helpers.make_consensus_node(genesis, pvs[0])
        # the frozen ticker: timeouts are scheduled but never fire, so
        # the FSM never leaves NEW_HEIGHT — the liveness wedge
        monkeypatch.setattr(
            cs.ticker, "schedule_timeout", lambda ti: None
        )
        return cs, parts

    def test_stall_trips_and_writes_bundle(
        self, health, tmp_path, monkeypatch
    ):
        m = NodeMetrics()
        cs, parts = self._frozen_node(monkeypatch)
        mon = libhealth.HealthMonitor(
            metrics=m,
            stall_base_s=0.2,
            stall_mult=1.0,
            bundle_dir=str(tmp_path),
            interval_s=0.02,
        )
        try:
            cs.start()
            mon.start()
            assert _wait_until(
                lambda: mon.trips["consensus_stall"] >= 1, timeout=10
            ), "stall watchdog never tripped on a frozen ticker"
            assert _wait_until(
                lambda: len(os.listdir(tmp_path)) >= 1, timeout=5
            ), "no black-box bundle written"
        finally:
            try:
                mon.stop()
            except Exception:
                pass
            helpers.stop_node(cs, parts)
        assert (
            m.health_watchdog_trips.labels("consensus_stall").value() >= 1
        )
        # the bundle carries the forensic set the issue names: the
        # flight-recorder ring, the devstats snapshot, the trace tail
        bundle = os.path.join(tmp_path, sorted(os.listdir(tmp_path))[0])
        names = set(os.listdir(bundle))
        assert {"flight.json", "devstats.json", "trace.json"} <= names
        flight = json.load(open(os.path.join(bundle, "flight.json")))
        events = {e["event"] for e in flight["events"]}
        assert "health.watchdog" in events
        # the health engine agrees: score zero while stalled
        libhealth._MONITORS.append(mon)  # sample() consults the monitor
        try:
            out = libhealth.sample(m)
        finally:
            libhealth._MONITORS.remove(mon)
        assert out["stalled"] is True
        assert out["score"] == 0.0
        assert m.health_score.value() == 0.0

    def test_disabled_watchdogs_write_nothing(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.setenv("COMETBFT_TPU_HEALTH", "0")
        libhealth.disable()
        cs, parts = self._frozen_node(monkeypatch)
        try:
            cs.start()
            # the node-boot gate: with the kill switch set no monitor
            # starts (node/node.py checks exactly this) and acquire()
            # cannot re-enable the recorder
            assert not libhealth.monitor_enabled()
            libhealth.acquire()
            assert not libhealth.enabled()
            time.sleep(0.6)  # same window the enabled scenario trips in
        finally:
            helpers.stop_node(cs, parts)
        assert os.listdir(tmp_path) == []
        assert libhealth.recorder().dump() == []


class TestHealthyBurst:
    """End-to-end: a real 4-validator in-process burst with a live
    monitor — zero watchdog trips, health score pinned at 1.0."""

    def test_burst_zero_trips_and_perfect_score(self):
        m = NodeMetrics()
        libmetrics.push_node_metrics(m)
        libhealth.enable(ring=1 << 14)
        libhealth.reset()
        genesis, pvs = helpers.make_genesis(4)
        nodes = [helpers.make_consensus_node(genesis, pv) for pv in pvs]
        helpers.wire_perfect_gossip(nodes)
        mon = libhealth.HealthMonitor(
            metrics=m, stall_base_s=30.0, stall_mult=1.0,
            interval_s=0.05,
        )
        scores = []
        try:
            for cs, _ in nodes:
                cs.start()
            mon.start()
            stores = [parts["block_store"] for _, parts in nodes]
            # EVERY node must reach height 3 AND the ring must hold
            # all 3x4 commit rows — the shared hardened wait
            # (helpers.wait_for_commits docstring has the race)
            helpers.wait_for_commits(
                stores, 3, ring_commits=3 * 4,
                on_tick=lambda: scores.append(
                    libhealth.sample(m)["score"]
                ),
            )
        finally:
            try:
                mon.stop()
            except Exception:
                pass
            for cs, parts in nodes:
                helpers.stop_node(cs, parts)
            libmetrics.pop_node_metrics(m)
            final = libhealth.sample(m)
            events = libhealth.recorder().dump()
            libhealth.enable(ring=libhealth.DEFAULT_RING_SIZE)
            libhealth.disable()
            libhealth.reset()

        # zero trips across every watchdog
        assert mon.trips == {
            "consensus_stall": 0,
            "verify_breaker": 0,
            "recompile_storm": 0,
            "send_queue_saturated": 0,
            "slow_disk": 0,
            "consensus_starved": 0,
            "tx_starved": 0,
            "lock_contended": 0,
        }
        assert mon.bundles == 0
        # monotone non-degraded health: every sample along the way AND
        # the final one scored a healthy 1.0
        assert scores and all(s == 1.0 for s in scores), scores
        assert final["score"] == 1.0
        assert final["stalled"] is False
        # the ring captured the burst: steps, votes, commits, fsync-free
        # MemDB nodes still step/commit
        names = {e["event"] for e in events}
        assert {
            "consensus.step", "consensus.vote", "consensus.commit"
        } <= names, names
        commits = [e for e in events if e["event"] == "consensus.commit"]
        assert len(commits) >= 3 * 4  # >=3 heights on each of 4 nodes
        assert all(c["dur_ns"] > 0 for c in commits)
        # the SLI gauges landed in the pushed registry
        text = m.registry.render()
        assert "cometbft_tpu_health_score 1.0" in text
        assert 'cometbft_tpu_health_commit_latency_seconds' in text
        assert final["commit_latency_s"]["p50"] is not None


class TestLockContention:
    """The contention plane's acceptance gates: a deliberately
    contended commit-chain lock trips ``lock_contended`` and the
    bundle's ``contention.json`` names the hot lock; per-lock
    contended-acquire counts reconcile with an instrumented probe
    thread's observed blocks; and the critical-path join names the
    gating lock for a commit window."""

    @pytest.fixture
    def lockprof(self):
        from cometbft_tpu.libs import lockprof as liblockprof

        was = liblockprof.enabled()
        liblockprof.enable()
        liblockprof.reset()
        yield liblockprof
        liblockprof.set_slow_ms(liblockprof.slow_threshold_s() * 1e3)
        if not was:
            liblockprof.disable()
        liblockprof.reset()

    def test_storm_trips_and_bundle_names_hot_lock(
        self, health, lockprof, tmp_path
    ):
        import threading

        from cometbft_tpu.libs import sync as libsync

        # 20 ms holds cross the lowered 5 ms slow threshold, so the
        # storm both feeds the watchdog's windowed p99 AND emits
        # EV_LOCK rows into the ring
        lockprof.set_slow_ms(5.0)
        lock = libsync.Mutex(name="consensus.wal._mtx")
        assert type(lock).__name__ == "_ProfiledMutex"
        m = NodeMetrics()
        mon = libhealth.HealthMonitor(
            metrics=m,
            stall_base_s=30.0,
            stall_mult=1.0,
            interval_s=0.05,
            lock_wait_s=0.01,
            bundle_dir=str(tmp_path),
        )
        stop = threading.Event()

        def holder():
            while not stop.is_set():
                with lock:
                    time.sleep(0.02)
                time.sleep(0.001)

        def victim():
            while not stop.is_set():
                with lock:
                    pass
                time.sleep(0.001)

        threads = [
            threading.Thread(target=f, daemon=True)
            for f in (holder, victim)
        ]
        try:
            for t in threads:
                t.start()
            mon.start()
            assert _wait_until(
                lambda: mon.trips["lock_contended"] >= 1, timeout=15
            ), "lock_contended never tripped on a contended wal mutex"
            assert _wait_until(
                lambda: len(os.listdir(tmp_path)) >= 1, timeout=5
            ), "no bundle written on the contention trip"
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
            try:
                mon.stop()
            except Exception:
                pass
        assert mon.hot_lock() == "consensus.wal._mtx"
        assert mon.status()["hot_lock"] == "consensus.wal._mtx"
        assert (
            m.health_watchdog_trips.labels("lock_contended").value() >= 1
        )
        # the bundle carries contention.json naming the hot lock
        bundle = os.path.join(tmp_path, sorted(os.listdir(tmp_path))[0])
        assert "contention.json" in os.listdir(bundle)
        cont = json.load(open(os.path.join(bundle, "contention.json")))
        assert cont["lockprof"]["hottest"] == "consensus.wal._mtx"
        wal = cont["lockprof"]["locks"]["consensus.wal._mtx"]
        assert wal["contended"] >= 1
        assert wal["wait_s"] > 0
        assert "critical_path" in cont
        # slow holds/waits landed in the ring as decodable EV_LOCK rows
        evs = [
            e
            for e in libhealth.recorder().dump()
            if e["event"] == "sync.lock"
        ]
        assert evs, "no EV_LOCK rows despite 20ms holds at a 5ms bar"
        assert any(e["lock"] == "consensus.wal._mtx" for e in evs)
        assert all(
            e["kind_name"] in ("wait", "hold") for e in evs
        ), evs
        assert all(e["dur_ns"] > 0 for e in evs)
        # holder acquire sites interned and attached (file:line shape)
        assert any(":" in e.get("site", "") for e in evs), evs[:3]

    def test_contended_acquires_reconcile_with_probe(
        self, health, lockprof
    ):
        import threading

        from cometbft_tpu.libs import sync as libsync

        lock = libsync.Mutex(name="consensus.state")
        slot = lockprof.slot_for("consensus.state")
        assert 0 <= slot < lockprof.OTHER_SLOT
        before = lockprof.counts(slot)
        observed_blocks = 0
        for _ in range(3):
            held = threading.Event()
            release = threading.Event()

            def holder():
                with lock:
                    held.set()
                    release.wait(5)

            def probe():
                lock.acquire()
                lock.release()

            t = threading.Thread(target=holder, daemon=True)
            t.start()
            assert held.wait(5)
            p = threading.Thread(target=probe, daemon=True)
            p.start()
            # the probe is observably blocked on the named lock before
            # the holder lets go — that observation IS the ground truth
            # the per-lock contended counter must reconcile against
            assert _wait_until(
                lambda: (
                    libsync.held_locks_snapshot().get(p.ident) or {}
                ).get("blocked_on")
                == "consensus.state",
                timeout=5,
            ), "probe never showed as blocked_on consensus.state"
            observed_blocks += 1
            release.set()
            p.join(5)
            t.join(5)
        after = lockprof.counts(slot)
        assert observed_blocks == 3
        assert after["contended"] - before["contended"] == observed_blocks
        # holder acquires were uncontended: 3 holder + 3 probe acquires
        assert after["acquires"] - before["acquires"] == 6
        assert after["wait_ns"] > before["wait_ns"]
        assert after["hold_ns"] > before["hold_ns"]

    def test_critical_path_names_the_gating_lock(self):
        # synthetic decoded stream: a 200ms commit window whose
        # dominant budget stage (gossip, 130ms) is still smaller than
        # the wal mutex's in-window slow waits (150ms) — the verdict
        # must name the lock, with the holder's acquire site
        t0 = 1_000_000_000
        dur = 200_000_000
        events = [
            {
                "event": "consensus.step", "height": 5, "node": "n0",
                "step": 4, "ts": t0 + 50_000_000,
            },
            {
                "event": "consensus.step", "height": 5, "node": "n0",
                "step": 8, "ts": t0 + 180_000_000,
            },
            {
                "event": "consensus.commit", "height": 5, "node": "n0",
                "ts": t0 + dur, "dur_ns": dur,
            },
            {
                "event": "sync.lock", "kind_name": "wait",
                "lock": "consensus.wal._mtx", "ts": t0 + 100_000_000,
                "dur_ns": 150_000_000, "site": "wal.py:42",
            },
            # hold rows never count toward the wait verdict
            {
                "event": "sync.lock", "kind_name": "hold",
                "lock": "consensus.wal._mtx", "ts": t0 + 100_000_000,
                "dur_ns": 150_000_000, "site": "wal.py:42",
            },
            {
                "event": "sync.lock", "kind_name": "wait",
                "lock": "consensus.state", "ts": t0 + 100_000_000,
                "dur_ns": 10_000_000, "site": "state.py:7",
            },
            # outside the commit window: must be ignored
            {
                "event": "sync.lock", "kind_name": "wait",
                "lock": "store.block_store._mtx",
                "ts": t0 + 10 * dur, "dur_ns": 900_000_000,
                "site": "store.py:9",
            },
        ]
        per = libhealth.critical_path_from_events(events)
        assert set(per) == {5}
        row = per[5]
        assert row["node"] == "n0"
        assert row["stage"] == "gossip"
        assert row["stage_s"] == pytest.approx(0.13)
        assert row["lock"] == "consensus.wal._mtx"
        assert row["lock_wait_s"] == pytest.approx(0.15)
        assert row["lock_site"] == "wal.py:42"
        assert row["gate"] == "lock:consensus.wal._mtx"
        agg = libhealth.critical_path(events)
        assert agg["commits"] == 1
        assert agg["gates"] == {"lock:consensus.wal._mtx": 1}
        assert agg["heights"][0]["height"] == 5
        assert agg["coverage"] == pytest.approx(row["coverage"])

    def test_critical_path_names_the_gating_cpu(self):
        # a commit window whose dominant budget stage (gossip, 60ms) is
        # dwarfed by GIL-bound Python in the FSM: the profiler's
        # in-window flush says consensus burned 170ms on-CPU — the
        # verdict must say cpu:consensus, not stage:gossip
        t0 = 1_000_000_000
        dur = 200_000_000
        events = [
            {
                "event": "consensus.step", "height": 9, "node": "n0",
                "step": 4, "ts": t0 + 50_000_000,
            },
            {
                "event": "consensus.step", "height": 9, "node": "n0",
                "step": 8, "ts": t0 + 110_000_000,
            },
            {
                "event": "consensus.commit", "height": 9, "node": "n0",
                "ts": t0 + dur, "dur_ns": dur,
            },
            {
                "event": "prof.window", "subsystem": "consensus",
                "ts": t0 + 150_000_000, "oncpu_ns": 170_000_000,
                "samples": 12,
            },
            # the profiler's own thread never gates a commit
            {
                "event": "prof.window", "subsystem": "sampler",
                "ts": t0 + 150_000_000, "oncpu_ns": 999_000_000,
                "samples": 66,
            },
            # flushed outside the commit window: must be ignored
            {
                "event": "prof.window", "subsystem": "mempool",
                "ts": t0 + 10 * dur, "oncpu_ns": 900_000_000,
                "samples": 60,
            },
        ]
        per = libhealth.critical_path_from_events(events)
        assert set(per) == {9}
        row = per[9]
        assert row["cpu"] == "consensus"
        assert row["cpu_s"] == pytest.approx(0.17)
        assert row["gate"] == "cpu:consensus"
        agg = libhealth.critical_path(events)
        assert agg["gates"] == {"cpu:consensus": 1}

    def test_critical_path_reads_kernel_cpu_not_samples(self):
        # EV_PROF's oncpu_ns is the subsystem's kernel CPU over the
        # window (libs/profile reads its threads' CPU clocks): threads
        # that sampled on-CPU the whole window (asleep in C, or waiting
        # for the interpreter lock) but used 3 ms of CPU never gate the
        # commit, however many samples they left
        t0 = 1_000_000_000
        dur = 200_000_000
        events = [
            {
                "event": "consensus.step", "height": 9, "node": "n0",
                "step": 4, "ts": t0 + 50_000_000,
            },
            {
                "event": "consensus.step", "height": 9, "node": "n0",
                "step": 8, "ts": t0 + 110_000_000,
            },
            {
                "event": "consensus.commit", "height": 9, "node": "n0",
                "ts": t0 + dur, "dur_ns": dur,
            },
            {
                "event": "prof.window", "subsystem": "mempool",
                "ts": t0 + 150_000_000, "oncpu_ns": 3_000_000,
                "samples": 1_340,
            },
        ]
        row = libhealth.critical_path_from_events(events)[9]
        assert row["cpu"] == "mempool"
        assert row["cpu_s"] == pytest.approx(0.003)
        assert not row["gate"].startswith("cpu:"), row["gate"]


class TestHealthSample:
    def test_sample_sets_gauges_and_score_degrades(self, health):
        from cometbft_tpu.crypto import coalesce as cco

        m = NodeMetrics()
        libhealth.record(libhealth.EV_STEP, 2, 0, 8)
        libhealth.record(libhealth.EV_COMMIT, 2, 0, 80_000_000)
        libhealth.record(libhealth.EV_FSYNC, a=1_500_000)
        out = libhealth.sample(m)
        assert out["score"] == 1.0
        text = m.registry.render()
        assert "cometbft_tpu_health_score 1.0" in text
        assert (
            'cometbft_tpu_health_commit_latency_seconds'
            '{quantile="p50"} 0.08' in text
        )
        assert "cometbft_tpu_health_rounds_per_height 1.0" in text
        assert "cometbft_tpu_health_wal_fsync_seconds 0.0015" in text
        assert "cometbft_tpu_health_breaker_open 0.0" in text
        # an open breaker degrades the score by 0.3
        co = cco.VerifyCoalescer(device=False)
        co.start()
        cco.push_active(co)
        try:
            co._trip()
            out = libhealth.sample(m)
            assert out["breaker_open"] is True
            assert out["score"] == pytest.approx(0.7)
            assert m.health_breaker_open.value() == 1.0
        finally:
            cco.pop_active(co)
            co.stop()

    def test_debug_health_json_shape(self, health):
        libhealth.record(libhealth.EV_STEP, 1, 0, 3)
        out = json.loads(libhealth.debug_health_json(tail=10))
        assert out["enabled"] is True
        assert out["ring"]["capacity"] >= 64
        assert "score" in out["health"]
        assert out["watchdogs"] is None  # no monitor running
        assert out["events"][-1]["event"] == "consensus.step"


class TestSlowDiskDefense:
    """Gray-failure defense (PR 13): WAL fsync-latency EWMA →
    disk_degraded hysteresis → widened propose timeouts + the
    slow_disk watchdog."""

    def _wal(self, tmp_path, monkeypatch, threshold_ms=50.0, window=8):
        from cometbft_tpu.consensus.wal import WAL

        monkeypatch.setenv("COMETBFT_TPU_HEALTH_DISK_MS",
                           str(threshold_ms))
        monkeypatch.setenv("COMETBFT_TPU_HEALTH_DISK_EWMA", str(window))
        return WAL(str(tmp_path / "wal"))

    def test_ewma_and_hysteresis(self, tmp_path, monkeypatch):
        wal = self._wal(tmp_path, monkeypatch, threshold_ms=50.0,
                        window=1)  # alpha=1: EWMA tracks the last sample
        assert not wal.disk_degraded()
        assert wal.fsync_ewma_s() == 0.0
        wal._note_fsync(10_000_000)  # 10 ms: healthy
        assert not wal.disk_degraded()
        wal._note_fsync(80_000_000)  # 80 ms > 50 ms: degrade
        assert wal.disk_degraded()
        assert wal.fsync_ewma_s() == pytest.approx(0.08)
        # hysteresis: 30 ms is under the threshold but above half of
        # it — the state must NOT flap back yet
        wal._note_fsync(30_000_000)
        assert wal.disk_degraded()
        wal._note_fsync(10_000_000)  # under half: clears
        assert not wal.disk_degraded()
        wal.close()

    def test_measured_fsyncs_feed_the_ewma(self, tmp_path, monkeypatch,
                                           health):
        from cometbft_tpu.consensus.wal import EndHeightMessage

        wal = self._wal(tmp_path, monkeypatch)
        wal.write_sync(EndHeightMessage(1))
        assert wal.fsync_ewma_s() > 0.0  # a real measured fsync landed
        wal.close()

    def test_propose_timeout_widens_only_live_and_degraded(self):
        import types as _types

        from cometbft_tpu.config import test_config
        from cometbft_tpu.consensus.state import ConsensusState

        cfg = test_config().consensus

        class _Wal:
            def __init__(self, degraded, ewma_s):
                self._d, self._e = degraded, ewma_s

            def disk_degraded(self):
                return self._d

            def fsync_ewma_s(self):
                return self._e

        def timeout(degraded, ewma_s, sim=False):
            ns = _types.SimpleNamespace(
                config=cfg, wal=_Wal(degraded, ewma_s), sim_driven=sim
            )
            return ConsensusState._propose_timeout(ns, 0)

        base = cfg.propose_timeout(0)
        assert timeout(False, 0.5) == base
        # degraded: widened by 4x the smoothed fsync
        assert timeout(True, 0.002) == pytest.approx(base + 0.008)
        # capped at one extra base
        assert timeout(True, 10.0) == pytest.approx(2 * base)
        # NEVER widened for a sim-driven FSM (wall EWMA must not leak
        # into virtual-time scheduling)
        assert timeout(True, 0.002, sim=True) == base

    def test_slow_disk_watchdog_trips_on_the_edge(self, health):
        state = {"degraded": False}
        mon = TestWatchdogUnits()._monitor(
            disk_degraded_fn=lambda: state["degraded"]
        )
        assert mon._check() & 16 == 0
        state["degraded"] = True
        assert mon._check() & 16  # fresh episode: trip
        assert mon.disk_degraded()
        assert mon._check() & 16 == 0  # same episode: no re-trip
        state["degraded"] = False
        assert mon._check() & 16 == 0
        assert not mon.disk_degraded()
        state["degraded"] = True
        assert mon._check() & 16  # NEW episode: trips again

    def test_slow_disk_trip_counts_and_bundles(self, health, tmp_path):
        state = {"degraded": True}
        mon = TestWatchdogUnits()._monitor(
            disk_degraded_fn=lambda: state["degraded"],
            bundle_dir=str(tmp_path),
        )
        mask = mon._check()
        assert mask & 16
        mon._handle_trips(mask)
        assert mon.trips["slow_disk"] == 1
        names = [p for p in tmp_path.iterdir() if "slow_disk" in p.name]
        assert names, "no slow_disk bundle written"

    def test_raising_probe_fails_toward_alerting(self, health):
        def boom():
            raise RuntimeError("probe exploded")

        mon = TestWatchdogUnits()._monitor(disk_degraded_fn=boom)
        assert mon._check() & 16
