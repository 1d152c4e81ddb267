"""Cross-node causal timelines (cometbft_tpu/postmortem): the ring
event-code registry gate, the netstamp clock-skew estimator, merge and
attribution units over synthetic rings, the simnet determinism pins
(same (seed, scenario) => byte-identical merged timeline + identical
verdicts), and THE fault-matrix acceptance: every faulty 16_fault_matrix
cell's top-ranked cause names the injected fault while the healthy cell
stays silent."""

import json
import os
import time
import urllib.request

import pytest

from cometbft_tpu.libs import health as libhealth
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import netstats as libnetstats
from cometbft_tpu import postmortem
from cometbft_tpu.postmortem import (
    REPORT_THRESHOLD,
    Source,
    attribute,
    merge,
    merge_ring_export,
    report_from_ring,
    sources_from_obj,
)

_DOCS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "docs", "observability.md",
)


# ------------------------------------------------- ring registry gate


class TestRingEventRegistry:
    """Tier-1 gate: a new EV_* code cannot ship without a decoder
    entry, a docs catalog name, and a working encode->decode path."""

    def test_every_code_has_name_fields_and_docs(self):
        codes = libhealth.ring_event_codes()
        assert codes, "no EV_* codes found"
        doc = open(_DOCS).read()
        for const, code in codes.items():
            assert code in libhealth._CODE_NAMES, (
                f"{const} has no _CODE_NAMES decoder entry"
            )
            assert code in libhealth._CODE_FIELDS, (
                f"{const} has no _CODE_FIELDS decoder entry"
            )
            name = libhealth._CODE_NAMES[code]
            assert name in doc, (
                f"{const} ({name}) missing from the docs/observability.md "
                "event catalog"
            )

    def test_every_code_round_trips_through_encode_decode(self):
        codes = libhealth.ring_event_codes()
        rec = libhealth.FlightRecorder(64)
        for const, code in sorted(codes.items(), key=lambda kv: kv[1]):
            rec.record(code, 5, 1, 2, 3)
        rows = rec.dump()
        assert len(rows) == len(codes)
        by_name = {r["event"] for r in rows}
        for code in codes.values():
            assert libhealth._CODE_NAMES[code] in by_name
        for r in rows:
            assert r["height"] == 5
            assert r["round"] == 1
            assert r["ts"] > 0

    def test_every_fault_kind_has_decode_name_and_docs(self):
        """EV_FAULT decode completeness: every FAULT_* kind must decode
        to a ``fault_name`` and appear in the docs fault vocabulary —
        a new fault family cannot ship dark."""
        kinds = libhealth.fault_kind_codes()
        assert kinds, "no FAULT_* kinds found"
        doc = open(_DOCS).read()
        for const, kind in kinds.items():
            name = libhealth._FAULT_NAMES.get(kind)
            assert name is not None, (
                f"{const} has no _FAULT_NAMES decode entry"
            )
            assert name in doc, (
                f"{const} ({name}) missing from the docs fault catalog"
            )
            # and the decode path round-trips
            rec = libhealth.FlightRecorder(8)
            rec.record(libhealth.EV_FAULT, 1, 2, kind, 3)
            row = rec.dump()[0]
            assert row["fault_name"] == name

    def test_decoder_survives_missing_field_entry(self):
        """Hardening: a code present in _CODE_NAMES but absent from
        _CODE_FIELDS decodes as a bare row instead of KeyError-ing the
        scrape/bundle path."""
        rec = libhealth.FlightRecorder(64)
        rec.record(libhealth.EV_COMMIT, 7, 0, 11, 4)
        fields = libhealth._CODE_FIELDS.pop(libhealth.EV_COMMIT)
        try:
            rows = rec.dump()
        finally:
            libhealth._CODE_FIELDS[libhealth.EV_COMMIT] = fields
        assert rows[0]["event"] == "consensus.commit"
        assert "dur_ns" not in rows[0]

    def test_commit_row_carries_tx_count(self):
        rec = libhealth.FlightRecorder(64)
        rec.record(libhealth.EV_COMMIT, 9, 1, 123_000_000, 42)
        row = rec.dump()[0]
        assert row["dur_ns"] == 123_000_000
        assert row["txs"] == 42

    def test_postmortem_knobs_registered_and_documented(self):
        from cometbft_tpu.config import ENV_KNOBS

        doc = open(_DOCS).read()
        for knob in (
            "COMETBFT_TPU_POSTMORTEM",
            "COMETBFT_TPU_POSTMORTEM_PEERS",
        ):
            assert knob in ENV_KNOBS, knob
            assert knob in doc, f"{knob} missing from docs"


# ------------------------------------------------- origins + clock


class TestOriginsAndClock:
    def test_origin_interning_dedupes(self):
        a = libhealth.register_origin("pm-test-node")
        b = libhealth.register_origin("pm-test-node")
        assert a == b
        assert libhealth.origin_name(a) == "pm-test-node"
        assert libhealth.origin_name(0) == "local"
        assert libhealth.origin_name(10**9) == "?"

    def test_thread_origin_lands_in_rows(self):
        oid = libhealth.register_origin("pm-origin-row")
        rec = libhealth.FlightRecorder(64)
        prev = libhealth.current_thread_origin()
        libhealth.set_thread_origin(oid)
        try:
            rec.record(libhealth.EV_STEP, 1, 0, 3)
        finally:
            libhealth.set_thread_origin(prev)
        rec.record(libhealth.EV_STEP, 1, 0, 4)
        rows = rec.dump()
        assert rows[0]["node"] == "pm-origin-row"
        assert "node" not in rows[1] or rows[1]["node"] != "pm-origin-row"

    def test_set_clock_swaps_ring_timestamps(self):
        rec = libhealth.FlightRecorder(64)
        prev = libhealth.set_clock(lambda: 123_456, domain="virtual")
        try:
            assert libhealth.clock_domain() == "virtual"
            rec.record(libhealth.EV_STEP, 1, 0, 3)
        finally:
            libhealth.set_clock(*prev)
        assert rec.dump()[0]["ts"] == 123_456
        assert libhealth.clock_domain() == "wall"

    def test_export_ring_shape(self):
        was = libhealth.enabled()
        libhealth.reset()
        libhealth.enable()
        try:
            libhealth.record(libhealth.EV_COMMIT, 3, 0, 1_000_000, 2)
            export = libhealth.export_ring(node="me")
        finally:
            if not was:
                libhealth.disable()
            libhealth.reset()
        assert export["schema"] == 1
        assert export["node"] == "me"
        assert export["domain"] in ("wall", "virtual")
        assert isinstance(export["origins"], list)
        assert isinstance(export["skews"], dict)
        assert any(
            e["event"] == "consensus.commit" for e in export["events"]
        )


# ------------------------------------------------- skew estimator


class TestSkewEstimator:
    def _stats(self):
        return libnetstats.ConnStats("abcdef1234", [0x22])

    def test_round_trip_pair_bounds_offset(self):
        st = self._stats()
        t1 = time.time_ns()
        st.stamp_tx_wall[0] = t1
        offset_ns = 250_000_000  # pretend the peer runs 250ms ahead
        libnetstats.set_current_stamp(
            ("00" * 8, 1, time.time_ns() + offset_ns), st
        )
        libnetstats.clear_current_stamp()
        row = st.skew_row()
        assert row is not None
        assert row["pairs"] == 1
        assert row["bound_s"] > 0
        assert row["rt_s"] >= 2 * row["bound_s"] - 1e-9
        # offset ~ +250ms (the tiny real rt is the error budget)
        assert abs(row["offset_s"] - 0.25) < 0.1

    def test_no_pair_before_any_send(self):
        st = self._stats()
        libnetstats.set_current_stamp(("00" * 8, 1, time.time_ns()), st)
        libnetstats.clear_current_stamp()
        assert st.skew_row() is None

    def test_min_rt_pair_wins(self):
        st = self._stats()
        now = time.time_ns()
        # loose pair: 2s round trip
        st.stamp_tx_wall[0] = now - 2_000_000_000
        st._note_skew_pair(now - 1_000_000_000, now)
        loose = st.skew_row()
        # tight pair: 10ms round trip
        st.stamp_tx_wall[0] = now - 10_000_000
        st._note_skew_pair(now - 5_000_000, now)
        tight = st.skew_row()
        assert tight["pairs"] == 2
        assert tight["bound_s"] < loose["bound_s"]
        assert tight["rt_s"] == pytest.approx(0.01)

    def test_crossed_pair_rejected_against_sound_floor(self):
        """A crossed message (emitted before our send, arriving just
        after it) fakes a tiny round trip and an understated offset;
        the causality-free floor offset >= t2 - t3 exposes it."""
        st = self._stats()
        now = time.time_ns()
        s = 1_000_000_000
        # honest inbound first: peer is ~+2s ahead, 100ms delivery ->
        # sound floor ~= +1.9s
        st.stamp_tx_wall[0] = now - 200_000_000
        st._note_skew_pair(now + 2 * s - 100_000_000, now)
        good = st.skew_row()
        assert good is not None
        assert good["floor_s"] >= 1.8
        # crossed pairing: emitted long before our send, arrives 1ms
        # after it -> rt = 1ms, offset estimate ~ +0.9s, which the
        # floor proves impossible -> rejected, the honest pair stays
        st.stamp_tx_wall[0] = now - 1_000_000
        st._note_skew_pair(now + 2 * s - 1_100_000_000, now)
        kept = st.skew_row()
        assert kept["rt_s"] == good["rt_s"]
        assert kept["offset_s"] == good["offset_s"]
        assert kept["pairs"] == 2

    def test_later_floor_evicts_inconsistent_stored_pair(self):
        st = self._stats()
        now = time.time_ns()
        s = 1_000_000_000
        # a crossed pair sneaks in first (tiny rt, understated offset)
        st.stamp_tx_wall[0] = now - 1_000_000
        st._note_skew_pair(now + 1_000_000, now)
        assert st.skew_row() is not None
        # an honest inbound then raises the sound floor above the
        # stored pair's whole offset range -> the stored pair is
        # evicted rather than locked in forever
        st.stamp_tx_wall[0] = 0
        st._note_skew_pair(now + 2 * s, now + 100_000_000)
        assert st.skew_row() is None

    def test_skew_table_and_gauge_lifecycle(self):
        st = self._stats()
        st.stamp_tx_wall[0] = time.time_ns()
        libnetstats.set_current_stamp(
            ("00" * 8, 1, time.time_ns()), st
        )
        libnetstats.clear_current_stamp()
        libnetstats.register(st)
        try:
            table = libnetstats.skew_table()
            assert "abcdef1234" in table
            m = libmetrics.NodeMetrics(libmetrics.Registry())
            libnetstats.sample(m)
            assert ("abcdef1234",) in m.p2p_peer_clock_skew._children
            assert (
                m.p2p_peer_clock_skew_bound.labels("abcdef1234").value()
                > 0
            )
        finally:
            libnetstats.deregister(st)
        # departed peer: the series is removed on the next scrape
        libnetstats.sample(m)
        assert ("abcdef1234",) not in m.p2p_peer_clock_skew._children
        from cometbft_tpu.libs.metrics import audit_label_cardinality

        assert audit_label_cardinality(m.registry) == []


# ------------------------------------------------- merge units


def _ev(event, ts, h=0, r=0, node=None, **kw):
    d = {"event": event, "ts": ts, "height": h, "round": r, **kw}
    if node:
        d["node"] = node
    return d


def _height_events(node, h, t0, lat_ns=20_000_000, txs=0):
    """One node's minimal height h trace starting at t0."""
    return [
        _ev("consensus.step", t0, h, 0, node, step=2, step_name="NewRound"),
        _ev("consensus.proposal", t0 + 2_000_000, h, 0, node, accepted=1),
        _ev("consensus.vote", t0 + 4_000_000, h, 0, node, type=1, index=0),
        _ev("consensus.vote", t0 + 6_000_000, h, 0, node, type=2, index=0),
        _ev(
            "consensus.commit", t0 + lat_ns, h, 0, node,
            dur_ns=lat_ns, txs=txs,
        ),
    ]


class TestMergeUnits:
    def test_two_node_merge_aggregates_heights(self):
        a = Source("nodeA", _height_events("nodeA", 1, 1000_000_000, txs=3)
                   + _height_events("nodeA", 2, 1100_000_000))
        b = Source("nodeB", _height_events("nodeB", 1, 1001_000_000)
                   + _height_events("nodeB", 2, 1101_000_000))
        tl = merge([a, b])
        assert tl.domain == "wall"
        assert [h["height"] for h in tl.heights] == [1, 2]
        h1 = tl.heights[0]
        assert set(h1["commits"]) == {"nodeA", "nodeB"}
        assert h1["commits"]["nodeA"]["txs"] == 3
        assert h1["proposal"]["node"] == "nodeA"  # earliest accepted
        assert h1["commit_spread_s"] == pytest.approx(0.001)
        assert h1["votes"]["nodeB"]["prevotes"] == 1
        assert h1["votes"]["nodeB"]["precommit_ns"] is not None

    def test_virtual_domain_drops_wall_durations_and_zeroes_skew(self):
        evs = _height_events("node0", 1, 10_000_000) + [
            _ev("wal.fsync", 12_000_000, node="node0", dur_ns=5_000_000),
        ]
        tl = merge([Source("node0", evs, domain="virtual")])
        assert tl.domain == "virtual"
        assert all(
            a["event"] != "wal.fsync" for a in tl.run["annotations"]
        )
        assert tl.heights[0]["skew_bound_s"] == 0.0
        assert tl.data["skew"]["max_bound_s"] == 0.0

    def test_wall_domain_keeps_fsync_and_tags_skew(self):
        skews = {"nodeB": {"offset_s": 0.001, "bound_s": 0.002,
                           "rt_s": 0.004, "pairs": 3}}
        a = Source(
            "nodeA",
            _height_events("nodeA", 1, 1000_000_000)
            + [_ev("wal.fsync", 1010_000_000, node="nodeA",
                   dur_ns=9_000_000)],
            skews=skews,
        )
        b = Source("nodeB", _height_events("nodeB", 1, 1001_000_000))
        tl = merge([a, b])
        assert any(
            x["event"] == "wal.fsync" for x in tl.run["annotations"]
        )
        assert tl.data["skew"]["edges"]["nodeA|nodeB"]["bound_s"] == 0.002
        assert tl.data["skew"]["max_bound_s"] == 0.002
        h1 = tl.heights[0]
        assert h1["skew_bound_s"] == 0.002
        assert h1["skew_complete"] is True

    def test_missing_skew_pair_reads_unbounded(self):
        a = Source("nodeA", _height_events("nodeA", 1, 1000_000_000))
        b = Source("nodeB", _height_events("nodeB", 1, 1001_000_000))
        tl = merge([a, b])
        assert tl.data["skew"]["edges"]["nodeA|nodeB"]["bound_s"] is None
        assert tl.data["skew"]["complete"] is False
        assert tl.heights[0]["skew_bound_s"] is None
        assert tl.heights[0]["skew_complete"] is False

    def test_annotations_assign_to_the_height_they_delayed(self):
        evs = (
            _height_events("node0", 1, 1_000_000_000)
            # fault in the gap AFTER height 1's commit -> height 2
            + [_ev("simnet.fault", 1_050_000_000, 3, 0,
                   fault_name="drop", kind=5, detail=0x22)]
            + _height_events("node0", 2, 1_100_000_000)
        )
        tl = merge([Source("node0", evs, domain="virtual")])
        h2 = tl.heights[1]
        assert any(
            a["event"] == "simnet.fault" for a in h2["annotations"]
        )
        assert all(
            a["event"] != "simnet.fault"
            for a in tl.heights[0]["annotations"]
        )

    def test_tx_stage_rows_become_per_height_tx_tables(self):
        """Sampled tx.stage rows join into each height's ``txs`` table
        (commit rows per node + first-seen non-commit stamps per key)
        and never pollute the annotation stream."""
        key = "00aabbccddeeff11"
        evs = (
            _height_events("node0", 1, 1_000_000_000, txs=1)
            + [
                _ev("tx.stage", 1_002_000_000, 0, 1, node="node0",
                    stage_name="admit", key=key, val=7),
                _ev("tx.stage", 1_003_000_000, 0, 2, node="node0",
                    stage_name="gossip_send", key=key, val=1_000_000),
                _ev("tx.stage", 1_019_000_000, 1, 5, node="node0",
                    stage_name="commit", key=key, val=17_000_000),
            ]
        )
        evs.sort(key=lambda e: e["ts"])
        tl = merge([Source("node0", evs, domain="virtual")])
        h1 = tl.heights[0]
        assert len(h1["txs"]) == 1
        row = h1["txs"][0]
        assert row["key"] == key
        assert row["commits"]["node0"]["since_admit_s"] == (
            pytest.approx(0.017)
        )
        assert set(row["stages"]) == {"admit", "gossip_send"}
        assert all(
            a["event"] != "tx.stage" for a in h1["annotations"]
        )
        # the attribution samples rode along
        assert tl.tx_samples["heights"][1] == [pytest.approx(0.017)]
        assert tl.tx_samples["depths"][1] == [7]

    def test_lock_rows_become_per_height_critical_path(self):
        """EV_LOCK slow-wait rows join the budget tiles into each
        height's ``critical_path`` verdict naming the gating lock; in a
        virtual-domain merge the wall-measured rows drop (like
        wal.fsync) and the verdict degrades to the stage view."""
        evs = (
            _height_events("node0", 1, 1_000_000_000)
            + [
                _ev("sync.lock", 1_010_000_000, node="node0",
                    dur_ns=15_000_000, lock="consensus.wal._mtx",
                    kind_name="wait", site="wal.py:42"),
                _ev("sync.lock", 1_011_000_000, node="node0",
                    dur_ns=2_000_000, lock="consensus.state",
                    kind_name="wait", site="state.py:7"),
            ]
        )
        evs.sort(key=lambda e: e["ts"])
        tl = merge([Source("node0", evs)])
        h1 = tl.heights[0]
        cp = h1["critical_path"]
        assert cp is not None
        assert cp["lock"] == "consensus.wal._mtx"
        assert cp["lock_wait_s"] == pytest.approx(0.015)
        assert cp["lock_site"] == "wal.py:42"
        assert cp["gate"] == "lock:consensus.wal._mtx"
        # per-height rows carry no redundant height/node keys
        assert "height" not in cp and "node" not in cp
        # wall-domain merges keep the slow-lock rows as annotations
        assert any(
            a["event"] == "sync.lock" for a in h1["annotations"]
        )
        # a virtual-domain merge drops the wall-measured rows exactly
        # like wal.fsync, and the verdict falls back to the stage view
        tlv = merge([Source("node0", evs, domain="virtual")])
        hv = tlv.heights[0]
        assert all(
            a["event"] != "sync.lock"
            for a in tlv.run["annotations"] + hv["annotations"]
        )
        assert hv["critical_path"]["lock"] is None
        assert hv["critical_path"]["gate"].startswith("stage:")

    def test_mempool_backlog_detector_names_the_backlogged_height(self):
        """A slow height whose sampled txs waited >> the run's typical
        submit->commit wait attributes to mempool_backlog; the healthy
        heights stay silent."""
        evs = []
        t = 1_000_000_000
        for h in range(1, 5):
            evs += _height_events("node0", h, t, txs=2)
            for i in range(2):
                evs.append(_ev(
                    "tx.stage", t + 19_000_000, h, 5, node="node0",
                    stage_name="commit", key=f"{h:02x}{i:02x}" + "0" * 12,
                    val=10_000_000,  # 10 ms typical wait
                ))
            t += 100_000_000
        # height 5: 2 rounds (slow) + txs that waited 600 ms
        evs += [
            _ev("consensus.step", t, 5, 0, "node0", step=2),
            _ev("consensus.step", t + 30_000_000, 5, 1, "node0", step=2),
            _ev("consensus.proposal", t + 32_000_000, 5, 1, "node0",
                accepted=1),
            _ev("tx.stage", t + 10_000_000, 0, 1, node="node0",
                stage_name="admit", key="ff00" + "0" * 12, val=55),
            _ev("consensus.commit", t + 60_000_000, 5, 1, "node0",
                dur_ns=60_000_000, txs=2),
        ]
        for i in range(2):
            evs.append(_ev(
                "tx.stage", t + 59_000_000, 5, 5, node="node0",
                stage_name="commit", key=f"ff{i:02x}" + "0" * 12,
                val=600_000_000,
            ))
        evs.sort(key=lambda e: e["ts"])
        tl = merge([Source("node0", evs, domain="virtual")])
        rep = attribute(tl)
        slow = {w.height: w for w in rep.slow_heights}
        assert 5 in slow
        v = slow[5].verdict
        assert v is not None and v.cause == "mempool_backlog", (
            slow[5].findings
        )
        assert v.evidence["txs"] == 2
        assert v.evidence["wait_p50_ms"] == pytest.approx(600.0)
        assert v.evidence["typical_ms"] == pytest.approx(10.0)
        assert v.evidence["depth_p50"] == 55
        # healthy heights: nothing above threshold
        for h in range(1, 5):
            assert h not in slow or slow[h].verdict is None

    def test_gossip_rows_aggregate_per_window(self):
        evs = _height_events("node0", 1, 1_000_000_000) + [
            _ev("p2p.gossip", 1_005_000_000, 0, 0, node="node0",
                phase=9, lag_ns=2_000_000, phase_name="vote",
                src="node1"),
            _ev("p2p.gossip", 1_006_000_000, 0, 0, node="node0",
                phase=9, lag_ns=4_000_000, phase_name="vote",
                src="node2"),
        ]
        tl = merge([Source("node0", evs, domain="virtual")])
        g = tl.heights[0]["gossip"]
        assert g["count"] == 2
        assert g["max_s"] == pytest.approx(0.004)
        assert g["worst"]["src"] == "node2"
        assert "vote" in g["by_phase"]
        assert tl.lag_samples["heights"][1] == [0.002, 0.004]

    def test_sources_from_obj_splits_by_origin(self):
        obj = {
            "domain": "virtual",
            "node": None,
            "skews": {},
            "events": (
                _height_events("node0", 1, 1_000_000_000)
                + _height_events("node1", 1, 1_000_500_000)
                + [_ev("simnet.fault", 1_001_000_000,
                       fault_name="heal", kind=2, detail=0)]
            ),
        }
        srcs = sources_from_obj(obj)
        assert [s.name for s in srcs] == ["node0", "node1", "local"]
        assert all(s.domain == "virtual" for s in srcs)
        # the origin-0 remainder is annotations, not a node
        assert [s.attributed for s in srcs] == [True, True, False]
        tl = merge(srcs)
        assert tl.data["nodes"] == ["node0", "node1"]

    def test_single_unattributed_ring_is_one_node(self):
        obj = {"events": _height_events(None, 1, 1_000_000_000)}
        srcs = sources_from_obj(obj, name="solo")
        assert [s.name for s in srcs] == ["solo"]
        assert srcs[0].attributed is True
        assert merge(srcs).data["nodes"] == ["solo"]

    def test_canonical_json_is_stable(self):
        evs = _height_events("node0", 1, 1_000_000_000)
        t1 = merge([Source("node0", evs, domain="virtual")]).to_json()
        t2 = merge([Source("node0", list(evs), domain="virtual")]).to_json()
        assert t1 == t2


# ------------------------------------------------- attribution units


class TestAttributionUnits:
    def _tl(self, extra, lat_ns=20_000_000):
        evs = _height_events("node0", 1, 1_000_000_000) + _height_events(
            "node0", 2, 1_100_000_000, lat_ns=lat_ns
        ) + extra
        return merge([Source("node0", evs, domain="virtual")])

    def test_clean_run_yields_no_verdict(self):
        rep = attribute(self._tl([]))
        assert rep.run.verdict is None
        for w in rep.slow_heights:
            assert w.verdict is None

    def test_drop_flood_names_injected_drop(self):
        drops = [
            _ev("simnet.fault", 1_100_000_000 + i * 1_000_000, 0, 1,
                fault_name="drop", kind=5, detail=0x22)
            for i in range(20)
        ]
        rep = attribute(self._tl(drops, lat_ns=900_000_000))
        v = rep.run.verdict
        assert v is not None and v.cause == "injected_drop"
        assert v.evidence["drops"] == 20

    def test_partition_side_effect_drops_do_not_count_as_injected(self):
        drops = [
            _ev("simnet.fault", 1_100_000_000 + i * 1_000_000, 0, 1,
                fault_name="drop", kind=5, detail=(3 << 8) | 0x22)
            for i in range(20)
        ]
        rep = attribute(self._tl(drops))
        assert all(
            f.cause != "injected_drop" for f in rep.run.findings
        )

    def test_oneway_sever_names_gray_partition(self):
        anns = [
            _ev("simnet.fault", 1_100_000_000, 0, 1,
                fault_name="oneway_sever", kind=8, detail=1),
            _ev("simnet.fault", 1_118_000_000, 0, 1,
                fault_name="oneway_sever", kind=8, detail=0),
        ]
        rep = attribute(self._tl(anns, lat_ns=900_000_000))
        v = rep.run.verdict
        assert v is not None and v.cause == "gray_partition"
        assert (v.evidence["src"], v.evidence["dst"]) == (0, 1)

    def test_slow_disk_outranks_laggard_proposer(self):
        """The slow_disk interval is a directly-injected fault — it
        must top-rank even when the symptom (a laggard proposer) also
        scores at its 0.8 cap."""
        anns = [
            _ev("simnet.fault", 1_050_000_000, 1, 0,
                fault_name="slow_disk", kind=9, detail=120),
        ]
        rep = attribute(self._tl(anns, lat_ns=900_000_000))
        v = rep.run.verdict
        assert v is not None and v.cause == "slow_disk"
        assert v.score > 0.8
        assert v.evidence["node"] == 1
        assert v.evidence["latency_ms"] == 120

    def test_slow_disk_cleared_interval_bounds_overlap(self):
        """A cleared slow disk (detail=0) closes the episode: a HEIGHT
        window entirely after the clear scores no slow_disk."""
        anns = [
            _ev("simnet.fault", 900_000_000, 1, 0,
                fault_name="slow_disk", kind=9, detail=120),
            _ev("simnet.fault", 950_000_000, 1, 0,
                fault_name="slow_disk", kind=9, detail=0),
        ]
        evs = (
            _height_events("node0", 1, 1_000_000_000)
            + _height_events("node0", 2, 1_100_000_000)
            + _height_events(
                "node0", 3, 1_200_000_000, lat_ns=900_000_000
            )
            + anns
        )
        rep = attribute(merge([Source("node0", evs, domain="virtual")]))
        assert rep.slow_heights, "the 900 ms height must read as slow"
        for w in rep.slow_heights:
            assert all(f.cause != "slow_disk" for f in w.findings), (
                f"{w.window} scored a cleared slow-disk episode"
            )

    def test_peer_evicted_named_but_below_injected_faults(self):
        anns = [
            _ev("simnet.fault", 1_100_000_000, 0, 0,
                fault_name="peer_evict", kind=11, detail=1),
            _ev("simnet.fault", 1_105_000_000, 1, 0,
                fault_name="kill", kind=3),
        ]
        rep = attribute(self._tl(anns, lat_ns=900_000_000))
        v = rep.run.verdict
        assert v is not None and v.cause == "injected_churn"
        named = {f.cause: f for f in rep.run.findings}
        assert "peer_evicted" in named
        assert named["peer_evicted"].score < named["injected_churn"].score

    def test_breaker_open_names_verify_stall(self):
        trips = [
            _ev("coalesce.breaker", 1_105_000_000, open=1),
        ]
        rep = attribute(self._tl(trips, lat_ns=900_000_000))
        assert rep.run.verdict.cause == "verify_stall"
        assert rep.run.verdict.score == pytest.approx(0.85)

    def test_recompile_storm_detected(self):
        recs = [
            _ev("xla.recompile", 1_104_000_000 + i, bucket=256)
            for i in range(3)
        ]
        rep = attribute(self._tl(recs, lat_ns=900_000_000))
        assert rep.run.verdict.cause == "recompile_storm"

    def test_fsync_outlier_wall_domain_only(self):
        evs = _height_events("node0", 1, 1_000_000_000) + _height_events(
            "node0", 2, 1_100_000_000, lat_ns=900_000_000
        ) + [
            _ev("wal.fsync", 1_500_000_000, dur_ns=400_000_000),
        ]
        tl = merge([Source("node0", evs, domain="wall")])
        rep = attribute(tl)
        assert rep.run.verdict.cause == "wal_fsync_outlier"

    def test_lock_contention_names_the_hot_lock(self):
        """A slow window whose annotations carry EV_LOCK waits
        dominating the wall scores lock_contention naming the hot lock
        and the blocking holder's acquire site; hold rows and sub-15%
        wait shares stay silent."""
        evs = _height_events("node0", 1, 1_000_000_000) + _height_events(
            "node0", 2, 1_100_000_000, lat_ns=900_000_000
        ) + [
            _ev("sync.lock", 1_500_000_000 + i * 1_000_000,
                dur_ns=80_000_000, lock="consensus.wal._mtx",
                kind_name="wait", site="wal.py:88")
            for i in range(3)
        ] + [
            # a hold row never counts toward the wait verdict
            _ev("sync.lock", 1_510_000_000, dur_ns=500_000_000,
                lock="consensus.wal._mtx", kind_name="hold",
                site="wal.py:88"),
        ]
        rep = attribute(merge([Source("node0", evs, domain="wall")]))
        v = rep.run.verdict
        assert v is not None and v.cause == "lock_contention"
        assert v.evidence["lock"] == "consensus.wal._mtx"
        assert v.evidence["holder_site"] == "wal.py:88"
        assert v.evidence["waits"] == 3
        # the same waits against a window they cannot dominate: silent
        quiet = [
            _ev("sync.lock", 1_115_000_000, dur_ns=10_000_000,
                lock="consensus.wal._mtx", kind_name="wait",
                site="wal.py:88"),
        ]
        evs2 = _height_events("node0", 1, 1_000_000_000) + _height_events(
            "node0", 2, 1_100_000_000, lat_ns=900_000_000
        ) + quiet
        rep2 = attribute(merge([Source("node0", evs2, domain="wall")]))
        assert all(
            f.cause != "lock_contention" for f in rep2.run.findings
        )

    def test_cpu_saturated_names_the_hot_subsystem(self):
        """A slow window whose profiler flush windows show one
        subsystem's GIL-bound Python burning most of the wall scores
        cpu_saturated naming the subsystem; the sampler's own thread
        never counts, and a small on-CPU share stays silent."""
        burn = [
            _ev("prof.window", 1_200_000_000 + i * 250_000_000,
                subsystem="consensus", oncpu_ns=250_000_000,
                samples=17)
            for i in range(3)
        ] + [
            # the profiler's own thread never gates a commit
            _ev("prof.window", 1_300_000_000, subsystem="sampler",
                oncpu_ns=900_000_000, samples=60),
        ]
        evs = _height_events("node0", 1, 1_000_000_000) + _height_events(
            "node0", 2, 1_100_000_000, lat_ns=900_000_000
        ) + burn
        rep = attribute(merge([Source("node0", evs, domain="wall")]))
        v = rep.run.verdict
        assert v is not None and v.cause == "cpu_saturated"
        assert v.evidence["subsystem"] == "consensus"
        assert v.evidence["oncpu_ms"] == pytest.approx(750.0)
        assert v.evidence["window_share"] > 0.6
        assert v.evidence["samples"] == 51
        # the same rows in a virtual-domain ring (simnet) are dropped
        # by the merge: wall-measured payloads mean nothing there
        rep2 = attribute(
            merge([Source("node0", evs, domain="virtual")])
        )
        assert all(
            f.cause != "cpu_saturated" for f in rep2.run.findings
        )
        # a sub-dominant on-CPU share against the same window: silent
        quiet = [
            _ev("prof.window", 1_200_000_000, subsystem="consensus",
                oncpu_ns=100_000_000, samples=7),
        ]
        evs3 = _height_events("node0", 1, 1_000_000_000) + _height_events(
            "node0", 2, 1_100_000_000, lat_ns=900_000_000
        ) + quiet
        rep3 = attribute(merge([Source("node0", evs3, domain="wall")]))
        assert all(
            f.cause != "cpu_saturated" for f in rep3.run.findings
        )

    def test_cpu_saturated_reads_kernel_cpu_not_samples(self):
        """EV_PROF's oncpu_ns is the subsystem's kernel CPU over the
        window: 200 threads that sampled on-CPU all window (asleep in C,
        or waiting for the interpreter lock) and used 40 ms of CPU in
        all are not a saturated subsystem, however many samples."""
        parked = [
            _ev("prof.window", 1_200_000_000 + i * 250_000_000,
                subsystem="consensus", oncpu_ns=10_000_000,
                samples=200 * 17)
            for i in range(4)
        ]
        evs = _height_events("node0", 1, 1_000_000_000) + _height_events(
            "node0", 2, 1_100_000_000, lat_ns=900_000_000
        ) + parked
        rep = attribute(merge([Source("node0", evs, domain="wall")]))
        assert all(
            f.cause != "cpu_saturated" for f in rep.run.findings
        )

    def test_latency_detector_scores_against_baseline(self):
        slow_hops = [
            _ev("p2p.gossip", 1_101_000_000 + i * 100_000, 0, 0,
                phase=9, lag_ns=40_000_000, phase_name="vote")
            for i in range(10)
        ]
        rep = attribute(self._tl(slow_hops))
        assert rep.run.verdict.cause == "injected_latency"
        # same timeline, generous baseline: silent
        rep2 = attribute(self._tl(slow_hops), baseline_lag_s=0.05)
        assert all(
            f.cause != "injected_latency" for f in rep2.run.findings
            if f.score >= REPORT_THRESHOLD
        )

    def test_report_table_renders(self):
        rep = attribute(self._tl([]))
        text = rep.table()
        assert "run" in text and "verdict" in text


# ------------------------------------------- simnet determinism pins


def _scenario_postmortem(name, seed):
    from cometbft_tpu.simnet.scenarios import run_scenario

    r = run_scenario(name, seed)
    assert r.ok, r.failures
    tl, rep = report_from_ring(r.ring)
    return tl, rep


class TestScenarioTimelineDeterminism:
    """Same (seed, scenario) => byte-identical merged timeline and
    identical root-cause verdicts (the virtual clock makes the merge
    exact, so this is an equality, not an approximation)."""

    def test_byzantine_double_sign_pinned(self):
        tl1, rep1 = _scenario_postmortem("byzantine_double_sign", 7)
        tl2, rep2 = _scenario_postmortem("byzantine_double_sign", 7)
        assert tl1.to_json() == tl2.to_json()
        assert rep1.to_dict() == rep2.to_dict()
        assert tl1.domain == "virtual"
        assert set(tl1.data["nodes"]) >= {"node0", "node1", "node2",
                                          "node3"}

    def test_partition_heal_pinned_and_attributed(self):
        tl1, rep1 = _scenario_postmortem("partition_heal", 7)
        tl2, rep2 = _scenario_postmortem("partition_heal", 7)
        assert tl1.to_json() == tl2.to_json()
        assert rep1.to_dict() == rep2.to_dict()
        # the partition must be visible as the cause of at least one
        # slow height AND of the run
        assert rep1.run.verdict is not None
        assert rep1.run.verdict.cause == "injected_partition"
        causes = [
            w.verdict.cause for w in rep1.slow_heights
            if w.verdict is not None
        ]
        assert "injected_partition" in causes


# --------------------------------------------- fault-matrix acceptance


class TestFaultMatrixAcceptance:
    """THE acceptance criterion: for every faulty cell in the
    16_fault_matrix grid run under simnet, the attributor's top-ranked
    root cause names the injected fault (drop/latency/partition),
    deterministically per seed; the healthy cell yields no verdict
    above the report threshold."""

    def test_every_faulty_cell_attributes_to_its_fault(self):
        import bench

        heights = 4
        reports = {}
        for name, link, special in bench._fault_matrix_cells():
            _cell, export = bench._run_fault_cell(
                name, link, special, heights
            )
            _tl, rep = report_from_ring(export)
            reports[name] = rep
        for name, expected in bench._FAULT_CELL_EXPECTED.items():
            top = reports[name].run.verdict
            assert top is not None, f"{name}: no verdict"
            assert top.cause in expected, (
                f"{name}: top cause {top.cause} not in {expected}"
            )
        assert reports["clean"].run.verdict is None
        for w in reports["clean"].slow_heights:
            assert w.verdict is None

    def test_cell_attribution_deterministic_per_seed(self):
        import bench

        cells = {n: (l, s) for n, l, s in bench._fault_matrix_cells()}
        link, special = cells["drop05"]
        outs = []
        for _ in range(2):
            # a cache hit would make this a tautology: force a real
            # re-simulation each time
            bench._FAULT_CELL_CACHE.clear()
            _cell, export = bench._run_fault_cell(
                "drop05", link, special, 4
            )
            tl, rep = report_from_ring(export)
            outs.append((tl.to_json(), json.dumps(
                rep.to_dict(), sort_keys=True
            )))
        assert outs[0] == outs[1]


# ------------------------------------------------- CLI + pprof routes


class TestCliAndRoutes:
    def test_cli_merge_files(self, tmp_path, capsys):
        from cometbft_tpu.postmortem.__main__ import main

        export = {
            "schema": 1, "node": "n0", "domain": "virtual",
            "origins": [], "skews": {},
            "events": _height_events("n0", 1, 1_000_000_000),
        }
        p = tmp_path / "flight.json"
        p.write_text(json.dumps(export))
        rc = main(["merge", str(p)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "verdict" in out
        rc = main(["merge", str(p), "--json"])
        assert rc == 0
        out = capsys.readouterr().out
        payload = json.loads(out)
        assert payload["timeline"]["heights"][0]["height"] == 1
        assert "report" in payload

    def test_debug_flight_and_timeline_routes(self):
        from cometbft_tpu.libs.pprof import PprofServer

        was = libhealth.enabled()
        libhealth.reset()
        libhealth.enable()
        srv = PprofServer("tcp://127.0.0.1:0")
        srv.start()
        try:
            libhealth.record(libhealth.EV_STEP, 1, 0, 2)
            libhealth.record(
                libhealth.EV_COMMIT, 1, 0, 25_000_000, 1
            )
            base = f"http://127.0.0.1:{srv.bound_port}"
            with urllib.request.urlopen(base + "/debug/flight") as r:
                flight = json.loads(r.read().decode())
            assert flight["schema"] == 1
            assert any(
                e["event"] == "consensus.commit"
                for e in flight["events"]
            )
            with urllib.request.urlopen(base + "/debug/timeline") as r:
                body = json.loads(r.read().decode())
            assert "timeline" in body and "report" in body
            assert body["peers_merged"] == []
            hs = body["timeline"]["heights"]
            assert hs and hs[0]["height"] == 1
        finally:
            srv.stop()
            if not was:
                libhealth.disable()
            libhealth.reset()

    def test_debug_timeline_merges_reachable_peers(self):
        """?peer= fan-in: a second 'node' served over another pprof
        port merges into the local view; an unreachable peer degrades
        to an error note, never a failure."""
        from cometbft_tpu.libs.pprof import PprofServer

        was = libhealth.enabled()
        libhealth.reset()
        libhealth.enable()
        srv = PprofServer("tcp://127.0.0.1:0")
        srv.start()
        try:
            libhealth.record(libhealth.EV_COMMIT, 1, 0, 25_000_000, 0)
            peer_url = f"127.0.0.1:{srv.bound_port}"
            out = postmortem.debug_timeline(
                peers=[peer_url, "127.0.0.1:1/debug/flight"],
                fetch_timeout=1.0,
            )
            assert peer_url in out["peers_merged"]
            assert "127.0.0.1:1/debug/flight" in out["peer_errors"]
        finally:
            srv.stop()
            if not was:
                libhealth.disable()
            libhealth.reset()


# ------------------------------------------------- bundle integration


class TestBundleTimeline:
    def test_write_bundle_includes_timeline_json(self, tmp_path):
        was = libhealth.enabled()
        libhealth.reset()
        libhealth.enable()
        try:
            libhealth.record(libhealth.EV_STEP, 3, 0, 8)
            libhealth.record(libhealth.EV_COMMIT, 3, 0, 50_000_000, 2)
            path = libhealth.write_bundle(str(tmp_path), "pm-test")
        finally:
            if not was:
                libhealth.disable()
            libhealth.reset()
        names = set(os.listdir(path))
        assert "timeline.json" in names, names
        tl = json.load(open(os.path.join(path, "timeline.json")))
        assert "timeline" in tl and "report" in tl
        assert any(
            h["height"] == 3 for h in tl["timeline"]["heights"]
        )
        flight = json.load(open(os.path.join(path, "flight.json")))
        assert flight["schema"] == 1
        assert "skews" in flight

    def test_postmortem_kill_switch_skips_timeline(self, tmp_path,
                                                   monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_POSTMORTEM", "0")
        was = libhealth.enabled()
        libhealth.enable()
        try:
            path = libhealth.write_bundle(str(tmp_path), "pm-off")
        finally:
            if not was:
                libhealth.disable()
        assert "timeline.json" not in set(os.listdir(path))


# ------------------------------------------------- live TCP burst


class TestLiveTcpTimeline:
    """Satellite acceptance on a real (wall-clock) net: a 4-validator
    TCP burst merges into a per-height cross-node timeline with
    per-node spans and bounded skew tags."""

    @pytest.mark.slow
    def test_four_node_tcp_burst_merged_timeline(self, tmp_path):
        import dataclasses

        from tests import helpers
        from cometbft_tpu.config import default_config
        from cometbft_tpu.node import Node, init_files

        _MS = 1_000_000
        genesis, pvs = helpers.make_genesis(4)
        libnetstats.reset()
        libhealth.reset()
        was = libhealth.enabled()
        libhealth.enable()
        nodes = []
        try:
            for i, pv in enumerate(pvs):
                cfg = default_config()
                cfg.base.home = str(tmp_path / f"node{i}")
                cfg.p2p.laddr = "tcp://127.0.0.1:0"
                cfg.rpc.laddr = "tcp://127.0.0.1:0"
                cfg.consensus = dataclasses.replace(
                    cfg.consensus,
                    timeout_propose_ns=800 * _MS,
                    timeout_propose_delta_ns=100 * _MS,
                    timeout_prevote_ns=400 * _MS,
                    timeout_prevote_delta_ns=100 * _MS,
                    timeout_precommit_ns=400 * _MS,
                    timeout_precommit_delta_ns=100 * _MS,
                    timeout_commit_ns=200 * _MS,
                    skip_timeout_commit=True,
                    peer_gossip_sleep_duration_ns=20 * _MS,
                )
                init_files(cfg)
                nodes.append(Node(cfg, genesis, pv))
            nodes[0].start()
            seed_addr = (
                f"{nodes[0].node_key.node_id}@"
                f"{nodes[0].transport.listen_addr[len('tcp://'):]}"
            )
            for node in nodes[1:]:
                node.config.p2p.persistent_peers = seed_addr
                node.start()
            # shared hardened wait: the export below decodes the ring,
            # and save_block leads EV_COMMIT — wait for the 2x4 commit
            # rows too, not just the store heights
            helpers.wait_for_commits(
                [n.block_store for n in nodes], 2, ring_commits=2 * 4
            )
            export = libhealth.export_ring()
        finally:
            for n in reversed(nodes):
                try:
                    if n.is_running():
                        n.stop()
                except Exception:
                    pass
            if not was:
                libhealth.disable()
            libhealth.reset()
            libnetstats.reset()

        node_ids = {n.node_key.node_id[:10] for n in nodes}
        # the shared ring splits into per-node sources by origin
        srcs = sources_from_obj(export)
        assert node_ids <= {s.name for s in srcs}, (
            [s.name for s in srcs]
        )
        # the export carries measured skew bounds toward the peers
        assert export["skews"], "no skew pairs measured"
        for row in export["skews"].values():
            assert 0 < row["bound_s"] < 5.0
            assert row["pairs"] >= 1

        tl = merge_ring_export(export)
        assert tl.domain == "wall"
        # per-height spans: some height committed on >= 2 nodes with
        # admission + commit data per node
        spanned = [
            h for h in tl.heights if len(h["commits"]) >= 2
        ]
        assert spanned, "no height committed on 2+ nodes"
        h = spanned[0]
        assert h["proposal"] is not None
        assert h["proposal"]["node"] in node_ids
        for node, c in h["commits"].items():
            assert node in node_ids
            assert c["latency_s"] > 0
        assert h["commit_spread_s"] is not None
        assert any(v["prevotes"] > 0 for v in h["votes"].values())
        # cross-node edges carry a bounded skew tag
        tagged = [
            x for x in tl.heights
            if len(x["commits"]) >= 2 and x["skew_bound_s"] is not None
        ]
        assert tagged, "no height carries a measured skew bound"
        for x in tagged:
            assert 0 < x["skew_bound_s"] < 5.0
        # and the report runs end-to-end on a wall-domain merge
        rep = attribute(tl)
        assert rep.run is not None
