"""Device-time ledger + latency budget plane (libs/devledger,
libs/health.budget, the consensus-starvation watchdog, bench --compare).

The acceptance gates of this PR live here:

* ledger reconciliation pinned in tier-1 — in a warmed 4-validator
  burst with a routed coalescer, per-caller lanes/time sum to the
  window counters (time within 1%) and traced dispatch phases, and
  every consensus-caller ticket is correctly classed;
* the healthy burst's per-height budget stages sum to >= 90% of the
  measured commit latency;
* the starvation watchdog acceptance pair — a light-storm-starved
  plane trips ``consensus_starved`` and writes a bundle containing
  ``budget.json``; a healthy consensus-dominated burst trips nothing.
"""

import json
import threading
import time

import pytest

from cometbft_tpu.crypto import coalesce as crypto_coalesce
from cometbft_tpu.crypto import hashplane as crypto_hashplane
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.libs import devledger
from cometbft_tpu.libs import health as libhealth
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs.metrics import NodeMetrics

import helpers


@pytest.fixture
def ledger():
    """Enabled ledger with clean columns; module state restored."""
    was = devledger.enabled()
    devledger.enable()
    devledger.reset()
    yield devledger
    devledger.reset()
    devledger.enable() if was else devledger.disable()


@pytest.fixture
def fresh_metrics():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    yield m
    libmetrics.pop_node_metrics(m)


def _ed_lanes(n, seed=b"\x11"):
    k = Ed25519PrivKey.from_seed(seed * 32)
    pub = k.pub_key().data
    msgs = [b"msg-%d" % i for i in range(n)]
    return [pub] * n, msgs, [k.sign(m) for m in msgs]


class TestCallerClass:
    def test_default_is_other(self):
        assert devledger.current_caller() == 0
        assert devledger.caller_name(0) == "other"

    def test_outermost_wins(self):
        with devledger.caller_class("light"):
            lid = devledger.CALLER_CODES["light"]
            assert devledger.current_caller() == lid
            with devledger.caller_class("commit-verify"):
                # nested declaration is a no-op: the tenant that
                # entered the engine keeps the attribution
                assert devledger.current_caller() == lid
            assert devledger.current_caller() == lid
        assert devledger.current_caller() == 0

    def test_unknown_name_maps_to_other(self):
        with devledger.caller_class("no-such-tenant"):
            assert devledger.current_caller() == 0

    def test_thread_isolation(self):
        seen = {}

        def probe():
            seen["in_thread"] = devledger.current_caller()

        with devledger.caller_class("mempool"):
            t = threading.Thread(target=probe)
            t.start()
            t.join()
        assert seen["in_thread"] == 0


class TestLedgerColumns:
    def test_disabled_records_nothing(self):
        was = devledger.enabled()
        devledger.disable()
        devledger.reset()
        try:
            devledger.note_resolve(0, 1, 8, 1000, 2000, 0)
            devledger.note_window(0, 8, True)
            devledger.note_window_time(0, 5000)
            assert devledger.cell(0, 1)["lanes"] == 0
            assert devledger.occupancy()["verify"]["windows"] == 0
        finally:
            devledger.enable() if was else devledger.disable()

    def test_cells_and_reconcile(self, ledger):
        cid = devledger.CALLER_CODES["light"]
        devledger.note_window(devledger.PLANE_VERIFY, 12, True)
        devledger.note_window_time(devledger.PLANE_VERIFY, 9000)
        devledger.note_resolve(
            devledger.PLANE_VERIFY, cid, 8, 500, 6000, 0
        )
        devledger.note_resolve(
            devledger.PLANE_VERIFY, 0, 4, 100, 0, 3000
        )
        c = devledger.cell(devledger.PLANE_VERIFY, cid)
        assert c["lanes"] == 8 and c["tickets"] == 1
        assert c["wait_ns"] == 500 and c["exec_ns"] == 6000
        r = devledger.reconcile()["verify"]
        assert r["attributed_ns"] == 9000
        assert r["window_ns"] == 9000
        assert r["ratio"] == 1.0
        split = devledger.verify_lanes_split()
        assert split == (0, 12)  # light + other are both non-consensus

    def test_snapshot_shape(self, ledger):
        devledger.note_window(devledger.PLANE_HASH, 4, False)
        devledger.note_window_time(devledger.PLANE_HASH, 1000)
        devledger.note_resolve(
            devledger.PLANE_HASH,
            devledger.CALLER_CODES["merkle"], 4, 10, 0, 1000,
        )
        snap = devledger.snapshot()
        assert snap["enabled"] is True
        assert snap["callers"]["hash"]["merkle"]["lanes"] == 4
        assert "occupancy" in snap and "reconciliation" in snap


class TestQuantileFromBuckets:
    def test_matches_health_histogram_quantile(self):
        h = libmetrics.Histogram("q_test", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.005, 0.05, 0.5, 2.0):
            h.observe(v)
        assert libhealth.histogram_quantile(h, 0.99) == (
            libmetrics.quantile_from_buckets(
                h.buckets, list(h._counts), 0.99
            )
        )
        assert libmetrics.quantile_from_buckets((1.0,), [0, 0], 0.99) == 0.0
        # everything above the top edge reports the top edge
        assert (
            libmetrics.quantile_from_buckets((0.01,), [0, 5], 0.99) == 0.01
        )


class TestCoalescerAttribution:
    def test_callers_attributed_and_reconciled(
        self, ledger, fresh_metrics
    ):
        libhealth.enable(ring=1024)
        libhealth.reset()
        co = crypto_coalesce.VerifyCoalescer(
            device=False, window_us=200, min_device_lanes=1 << 30
        )
        co.start()
        try:
            pubs, msgs, sigs = _ed_lanes(4)
            with devledger.caller_class("consensus-vote"):
                bits = co.try_verify(pubs, msgs, sigs)
            assert bits == [True] * 4
            with devledger.caller_class("light"):
                bits = co.try_verify(pubs[:2], msgs[:2], sigs[:2])
            assert bits == [True] * 2
        finally:
            co.stop()
            libhealth.disable()
        cons = devledger.cell(
            devledger.PLANE_VERIFY,
            devledger.CALLER_CODES["consensus-vote"],
        )
        light = devledger.cell(
            devledger.PLANE_VERIFY, devledger.CALLER_CODES["light"]
        )
        assert cons["lanes"] == 4 and light["lanes"] == 2
        assert cons["host_ns"] > 0  # host window time attributed
        r = devledger.reconcile()["verify"]
        assert r["caller_lanes"] == r["window_lanes"] == 6
        assert abs(1.0 - r["ratio"]) <= 0.01
        # consensus tickets left an EV_BUDGET overlay row; the light
        # ticket alone must not (non-budget caller)
        rows = [
            e for e in libhealth.recorder().dump()
            if e["event"] == "plane.budget"
        ]
        assert rows and all(r["plane"] == "verify" for r in rows)
        assert sum(r["exec_ns"] for r in rows) <= cons["host_ns"]
        # the queue-wait histogram carries both caller series
        fam = fresh_metrics.device_queue_wait
        assert fam.labels("verify", "consensus-vote")._n == 1
        assert fam.labels("verify", "light")._n == 1
        libhealth.reset()

    def test_hashplane_attribution(self, ledger, fresh_metrics):
        co = crypto_hashplane.HashCoalescer(device=False, window_us=200)
        co.start()
        try:
            with devledger.caller_class("mempool"):
                t = co.submit([b"a" * 100, b"b" * 3000])
                t.result(5)
        finally:
            co.stop()
        c = devledger.cell(
            devledger.PLANE_HASH, devledger.CALLER_CODES["mempool"]
        )
        assert c["lanes"] == 2 and c["tickets"] == 1
        r = devledger.reconcile()["hash"]
        assert r["window_lanes"] == 2
        assert abs(1.0 - r["ratio"]) <= 0.01
        assert (
            fresh_metrics.device_queue_wait.labels("hash", "mempool")._n
            == 1
        )


class TestBudgetDecomposition:
    def test_stages_tile_the_height(self):
        per = libhealth.budget_from_events([
            {"event": "consensus.step", "ts": 1_000, "height": 7,
             "step": 4},
            {"event": "consensus.step", "ts": 6_000, "height": 7,
             "step": 8},
            {"event": "consensus.commit", "ts": 10_000, "height": 7,
             "dur_ns": 10_000},
            {"event": "plane.budget", "ts": 2_000, "plane": "verify",
             "wait_ns": 500, "exec_ns": 1_500},
            {"event": "plane.budget", "ts": 3_000, "plane": "hash",
             "wait_ns": 100, "exec_ns": 400},
            {"event": "wal.fsync", "ts": 9_000, "dur_ns": 1_000},
        ])
        hv = per[7]
        s = {k: round(v * 1e9) for k, v in hv["stages"].items()}
        assert s["proposal_wait"] == 1_000  # t0 -> prevote step
        assert s["verify_queue"] == 500
        assert s["verify_execute"] == 1_500
        assert s["hash"] == 500
        assert s["wal_fsync"] == 1_000
        # gossip = votes span (5000) - overlays in it (2500)
        assert s["gossip"] == 2_500
        # apply = post span (4000) - fsync (1000)
        assert s["apply"] == 3_000
        assert s["residual"] == 0
        assert hv["coverage"] == 1.0

    def test_overlay_clamped_to_span(self):
        # a shared multi-node ring can assign more overlay time to a
        # window than its wall length — the tiling must not exceed 1.0
        per = libhealth.budget_from_events([
            {"event": "consensus.step", "ts": 1_000, "height": 3,
             "step": 4},
            {"event": "consensus.step", "ts": 2_000, "height": 3,
             "step": 8},
            {"event": "consensus.commit", "ts": 3_000, "height": 3,
             "dur_ns": 3_000},
            {"event": "plane.budget", "ts": 1_500, "plane": "verify",
             "wait_ns": 50_000, "exec_ns": 50_000},
        ])
        assert per[3]["coverage"] <= 1.01

    def test_missing_steps_degrade_to_residual(self):
        # no step rows = no protocol attribution: the wall time lands
        # in residual (the honest "decomposition gap" stage), never in
        # proposal_wait
        per = libhealth.budget_from_events([
            {"event": "consensus.commit", "ts": 5_000, "height": 2,
             "dur_ns": 4_000},
        ])
        hv = per[2]
        assert hv["coverage"] == 1.0
        assert hv["stages"]["proposal_wait"] == 0.0
        assert hv["stages"]["residual"] == pytest.approx(4e-6)

    def test_budget_cache_invalidates_on_new_records(self, ledger):
        libhealth.enable(ring=256)
        try:
            libhealth.reset()
            libhealth.record(libhealth.EV_COMMIT, 1, 0, 1_000_000)
            b1 = libhealth.budget()
            assert libhealth.budget() is b1  # unchanged ring: memoized
            libhealth.record(libhealth.EV_COMMIT, 2, 0, 1_000_000)
            b2 = libhealth.budget()
            assert b2 is not b1 and b2["commits"] == 2
        finally:
            libhealth.disable()
            libhealth.set_ring_capacity(libhealth.DEFAULT_RING_SIZE)
            libhealth.reset()

    def test_budget_view_aggregates(self):
        out = libhealth.budget(events=[
            {"event": "consensus.commit", "ts": 2_000, "height": 1,
             "dur_ns": 1_000},
            {"event": "consensus.commit", "ts": 4_000, "height": 2,
             "dur_ns": 1_000},
        ])
        assert out["commits"] == 2
        assert out["coverage"] == pytest.approx(1.0)
        assert set(out["stages_total_s"]) == set(libhealth.BUDGET_STAGES)

    def test_debug_budget_json_shape(self, ledger):
        out = json.loads(libhealth.debug_budget_json())
        assert "ledger" in out and "budget" in out
        assert "occupancy" in out["ledger"]

    def test_budget_route_registered(self):
        from cometbft_tpu.libs.pprof import PprofServer

        srv = PprofServer("tcp://127.0.0.1:0")
        assert "/debug/budget" in srv._route_map


class TestBurstReconciliation:
    """THE tier-1 reconciliation acceptance: a warmed 4-validator burst
    over a routed coalescer — per-caller lanes/time sum to the window
    counters and traced dispatch phases, every consensus ticket is
    correctly classed, and the budget stages explain >= 90% of each
    commit's measured latency."""

    def test_burst_reconciles_and_classes_consensus(self):
        from cometbft_tpu.libs import trace as libtrace

        m = NodeMetrics()
        libmetrics.push_node_metrics(m)
        was = devledger.enabled()
        devledger.enable()
        devledger.reset()
        libhealth.enable(ring=1 << 14)
        libhealth.reset()
        libtrace.enable()
        co = crypto_coalesce.VerifyCoalescer(
            device=False, min_device_lanes=1 << 30
        )
        co.start()
        crypto_coalesce.push_active(co)
        genesis, pvs = helpers.make_genesis(4)
        nodes = [helpers.make_consensus_node(genesis, pv) for pv in pvs]
        helpers.wire_perfect_gossip(nodes)
        try:
            for cs, _ in nodes:
                cs.start()
            stores = [parts["block_store"] for _, parts in nodes]
            # the shared hardened wait: heights AND the 4x4 ring
            # commit rows (the EV_BUDGET assertion below reads the
            # ring, and save_block leads EV_COMMIT)
            helpers.wait_for_commits(
                stores, 4, ring_commits=4 * 4, tick=0.02
            )
        finally:
            for cs, parts in nodes:
                helpers.stop_node(cs, parts)
            crypto_coalesce.pop_active(co)
            co.stop()
            trace_events = libtrace.ring_dump()
            ring = libhealth.recorder().dump()
            libtrace.disable()
            libhealth.disable()
            libhealth.set_ring_capacity(libhealth.DEFAULT_RING_SIZE)
            libhealth.reset()
            libmetrics.pop_node_metrics(m)

        try:
            # every routed verify ticket carried a consensus caller
            # class — nothing in this burst is unattributed
            base = devledger.PLANE_VERIFY * devledger.N_CALLERS
            per_caller = {
                name: devledger.cell(devledger.PLANE_VERIFY, cid)
                for name, cid in devledger.CALLER_CODES.items()
            }
            assert per_caller["other"]["lanes"] == 0, per_caller
            consensus_lanes = sum(
                per_caller[n]["lanes"]
                for n in ("consensus-vote", "commit-verify", "proposal")
            )
            assert consensus_lanes > 0
            del base
            # lanes reconcile EXACTLY, time within 1%
            r = devledger.reconcile()["verify"]
            assert r["caller_lanes"] == r["window_lanes"]
            assert r["window_ns"] > 0
            assert abs(1.0 - r["ratio"]) <= 0.01, r
            # the ledger's window lanes reconcile with the traced
            # coalesce.window spans and the coalescer's own window
            # counters
            flush_lanes = sum(
                e.get("lanes", 0)
                for e in trace_events
                if e.get("name") == "coalesce.window"
            )
            occ = devledger.occupancy()["verify"]
            assert flush_lanes == occ["window_lanes"]
            assert occ["windows"] == co.windows
            # the burst left EV_BUDGET rows on the ring for the budget
            assert any(
                e["event"] == "plane.budget" and e["plane"] == "verify"
                for e in ring
            )
        finally:
            devledger.reset()
            devledger.enable() if was else devledger.disable()

    def test_burst_budget_covers_commit_latency(self):
        """Healthy 4-val burst: budget stages sum to >= 90% of each
        measured commit latency (the acceptance bound)."""
        m = NodeMetrics()
        libmetrics.push_node_metrics(m)
        was = devledger.enabled()
        devledger.enable()
        devledger.reset()
        libhealth.enable(ring=1 << 14)
        libhealth.reset()
        genesis, pvs = helpers.make_genesis(4)
        nodes = [helpers.make_consensus_node(genesis, pv) for pv in pvs]
        helpers.wire_perfect_gossip(nodes)
        try:
            for cs, _ in nodes:
                cs.start()
            stores = [parts["block_store"] for _, parts in nodes]
            # shared hardened wait: the budget read below decodes the
            # ring, so the laggard's commit rows must be in it
            helpers.wait_for_commits(
                stores, 4, ring_commits=4 * 4, tick=0.02
            )
        finally:
            for cs, parts in nodes:
                helpers.stop_node(cs, parts)
            bud = libhealth.budget()
            libhealth.disable()
            libhealth.set_ring_capacity(libhealth.DEFAULT_RING_SIZE)
            libhealth.reset()
            libmetrics.pop_node_metrics(m)
            devledger.reset()
            devledger.enable() if was else devledger.disable()
        assert bud["commits"] >= 3
        assert bud["coverage"] is not None and bud["coverage"] >= 0.9
        for hv in bud["heights"]:
            stage_sum = sum(hv["stages"].values())
            assert stage_sum >= 0.9 * hv["latency_s"], hv
        # the sample path publishes the latest height's stage gauges
        libhealth.enable(ring=1024)
        try:
            libhealth.reset()
            libhealth.record(
                libhealth.EV_COMMIT, 9, 0, 50_000_000
            )
            out = libhealth.sample(m)
            assert out is not None
            text = m.registry.render()
            assert "cometbft_tpu_height_budget_seconds" in text
        finally:
            libhealth.disable()
            libhealth.reset()


class TestStarvationWatchdog:
    """THE acceptance pair: a light-storm-starved plane trips
    consensus_starved with a budget.json-bearing bundle; a healthy
    consensus-dominated burst trips nothing."""

    def _monitor(self, m, tmp_path, starve_s=0.02):
        return libhealth.HealthMonitor(
            metrics=m,
            stall_base_s=1000.0, stall_mult=1.0,
            bundle_dir=str(tmp_path),
            starve_s=starve_s,
            starve_min_lanes=16,
        )

    def test_light_storm_starves_consensus(
        self, ledger, fresh_metrics, tmp_path, monkeypatch
    ):
        from cometbft_tpu.crypto import host_batch

        m = fresh_metrics
        mon = self._monitor(m, tmp_path)
        # a slow shared plane: every host window takes ~40 ms
        real_verify = host_batch.verify_many

        def slow_verify(pks, msgs, sigs):
            time.sleep(0.04)
            return real_verify(pks, msgs, sigs)

        monkeypatch.setattr(host_batch, "verify_many", slow_verify)
        co = crypto_coalesce.VerifyCoalescer(
            device=False, window_us=200, min_device_lanes=1 << 30
        )
        co.start()
        pubs, msgs, sigs = _ed_lanes(8)
        stop = threading.Event()

        def light_flood():
            while not stop.is_set():
                with devledger.caller_class("light"):
                    co.try_verify(pubs, msgs, sigs)

        threads = [
            threading.Thread(target=light_flood, daemon=True)
            for _ in range(4)
        ]
        try:
            for t in threads:
                t.start()
            cpub, cmsg, csig = _ed_lanes(1, seed=b"\x22")
            deadline = time.monotonic() + 30
            tripped = 0
            while time.monotonic() < deadline and not tripped:
                with devledger.caller_class("consensus-vote"):
                    co.try_verify(cpub, cmsg, csig)
                tripped = mon._check() & 32
            assert tripped, "consensus_starved never tripped"
            mon._handle_trips(tripped)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            co.stop()
        assert mon.trips["consensus_starved"] == 1
        assert mon.starved() is True
        assert mon.status()["consensus_starved"] is True
        # the black-box bundle carries the ledger + budget plane
        bundles = sorted(tmp_path.iterdir())
        assert bundles, "no bundle written"
        budget_file = bundles[0] / "budget.json"
        assert budget_file.exists()
        body = json.loads(budget_file.read_text())
        assert "ledger" in body and "budget" in body
        assert body["ledger"]["callers"]["verify"]["light"]["lanes"] > 0

    def test_healthy_mixed_burst_trips_nothing(
        self, ledger, fresh_metrics, tmp_path
    ):
        m = fresh_metrics
        mon = self._monitor(m, tmp_path)
        co = crypto_coalesce.VerifyCoalescer(
            device=False, window_us=200, min_device_lanes=1 << 30
        )
        co.start()
        try:
            pubs, msgs, sigs = _ed_lanes(8)
            for _ in range(8):
                with devledger.caller_class("consensus-vote"):
                    assert co.try_verify(pubs, msgs, sigs)
                with devledger.caller_class("light"):
                    assert co.try_verify(pubs[:2], msgs[:2], sigs[:2])
        finally:
            co.stop()
        mask = mon._check()
        assert mask & 32 == 0
        assert mon.trips["consensus_starved"] == 0
        assert mon.starved() is False
        assert list(tmp_path.iterdir()) == []

    def test_starvation_requires_dominance(
        self, ledger, fresh_metrics, tmp_path
    ):
        """Slow waits alone must not page: with consensus dominating
        the lane share there is no tenant to blame — not starvation."""
        m = fresh_metrics
        mon = self._monitor(m, tmp_path)
        cid = devledger.CALLER_CODES["consensus-vote"]
        devledger.note_window(devledger.PLANE_VERIFY, 64, False)
        devledger.note_window_time(devledger.PLANE_VERIFY, 10_000_000)
        devledger.note_resolve(
            devledger.PLANE_VERIFY, cid, 60, 100_000_000, 0,
            9_000_000,
        )
        devledger.note_resolve(
            devledger.PLANE_VERIFY, devledger.CALLER_CODES["light"],
            4, 100_000_000, 0, 1_000_000,
        )
        for _ in range(10):
            m.device_queue_wait.labels(
                "verify", "consensus-vote"
            ).observe(0.5)
        assert mon._check() & 32 == 0

    def test_starvation_disabled_by_threshold(
        self, ledger, fresh_metrics, tmp_path
    ):
        mon = self._monitor(fresh_metrics, tmp_path, starve_s=0.0)
        devledger.note_resolve(
            devledger.PLANE_VERIFY, devledger.CALLER_CODES["light"],
            1000, 1, 0, 1,
        )
        assert mon._check() & 32 == 0


class TestBenchCompare:
    def _write(self, tmp_path, name, obj):
        p = tmp_path / name
        p.write_text(json.dumps(obj))
        return str(p)

    def test_regression_flagged_beyond_noise(self, tmp_path):
        import bench

        a = self._write(tmp_path, "a.json", [
            {"config": "1_batch64", "sigs_per_sec": 1000.0},
            {"config": "13_health_overhead", "ab_noise_floor_pct": 8.0},
        ])
        b = self._write(tmp_path, "b.json", [
            {"config": "1_batch64", "sigs_per_sec": 700.0},
            {"config": "13_health_overhead", "ab_noise_floor_pct": 8.0},
        ])
        out = bench.bench_compare(a, b)
        assert out["noise_floor_pct"] == 8.0
        assert [r["metric"] for r in out["regressions"]] == [
            "sigs_per_sec"
        ]

    def test_within_noise_stays_silent(self, tmp_path):
        import bench

        a = self._write(tmp_path, "a.json", [
            {"config": "1_batch64", "sigs_per_sec": 1000.0,
             "latency_ms": 10.0},
            {"config": "13_health_overhead", "ab_noise_floor_pct": 12.0},
        ])
        b = self._write(tmp_path, "b.json", [
            {"config": "1_batch64", "sigs_per_sec": 950.0,
             "latency_ms": 10.8},
            {"config": "13_health_overhead", "ab_noise_floor_pct": 12.0},
        ])
        out = bench.bench_compare(a, b)
        assert out["regressions"] == []
        assert out["compared"] >= 2

    def test_improvement_not_flagged(self, tmp_path):
        import bench

        a = self._write(tmp_path, "a.json", [
            {"config": "1_batch64", "sigs_per_sec": 1000.0},
        ])
        b = self._write(tmp_path, "b.json", [
            {"config": "1_batch64", "sigs_per_sec": 2000.0},
        ])
        out = bench.bench_compare(a, b)
        assert out["regressions"] == []

    def test_capture_wrapper_tail_parses(self, tmp_path):
        import bench

        rows = json.dumps({"config": "1_batch64", "latency_ms": 5.0})
        a = self._write(
            tmp_path, "BENCH_r01.json",
            {"n": 1, "tail": "garbage\n" + rows + "\n"},
        )
        b = self._write(tmp_path, "b.json", [
            {"config": "1_batch64", "latency_ms": 50.0},
        ])
        out = bench.bench_compare(a, b)
        assert [r["metric"] for r in out["regressions"]] == [
            "latency_ms"
        ]


class TestKnobsAndDocs:
    def test_ledger_knobs_registered_and_documented(self):
        import os

        from cometbft_tpu.config import ENV_KNOBS

        doc = open(
            os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "docs",
                "observability.md",
            )
        ).read()
        for knob in (
            "COMETBFT_TPU_LEDGER",
            "COMETBFT_TPU_LEDGER_STARVE_MS",
        ):
            assert knob in ENV_KNOBS, knob
            assert knob in doc, f"{knob} missing from docs"
        # budget-stage + caller vocabularies are documented
        for name in libhealth.BUDGET_STAGES:
            assert name in doc, f"budget stage {name} missing from docs"
        for name in devledger.CALLERS:
            assert name in doc, f"caller class {name} missing from docs"
