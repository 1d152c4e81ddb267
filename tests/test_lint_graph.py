"""Whole-program lock-order analysis (devtools/lint/graph): synthetic
ABBA / blocking-under-lock / publish-under-lock fixtures, the
suppression contract, the libs/sync record/enforce sanitizer, and the
engine-wide gates (zero unbaselined CLNT008-010; shipped lockorder.json
artifact in sync with the tree).
"""

from __future__ import annotations

import json
import os
import textwrap

import pytest

from cometbft_tpu.devtools.lint import lint_root, ALL_CHECKERS
from cometbft_tpu.devtools.lint.engine import parse_root
from cometbft_tpu.devtools.lint.graph import GRAPH_RULES, analyze_contexts
from cometbft_tpu.libs import sync as libsync

pytestmark = pytest.mark.quick

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "cometbft_tpu")
SHIPPED_GRAPH = os.path.join(
    PKG, "devtools", "lint", "graph", "lockorder.json"
)

# a minimal libs/sync stand-in so fixture trees look like the engine
SYNC_STUB = """
import threading
def Mutex(name=""):
    return threading.Lock()
def RLock(name=""):
    return threading.RLock()
def Condition(lock=None, name=""):
    return threading.Condition(lock)
"""


def run_graph(tmp_path, files: dict[str, str]):
    files = dict(files)
    files.setdefault("libs/sync.py", SYNC_STUB)
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    contexts, errors = parse_root(str(tmp_path))
    assert not errors, errors
    return analyze_contexts(contexts)


def codes(findings):
    return sorted(f.code for f in findings)


# ------------------------------------------------------- CLNT008 (ABBA)


class TestLockOrderInversion:
    ABBA = {
        "a.py": """
        from .libs import sync as libsync
        from . import b

        LOCK_A = libsync.Mutex("fix.a")

        def fa():
            with LOCK_A:
                b.fb_inner()

        def fa_inner():
            with LOCK_A:
                pass
        """,
        "b.py": """
        from .libs import sync as libsync
        from . import a

        LOCK_B = libsync.Mutex("fix.b")

        def fb():
            with LOCK_B:
                a.fa_inner()

        def fb_inner():
            with LOCK_B:
                pass
        """,
    }

    def test_interprocedural_abba_detected(self, tmp_path):
        analysis = run_graph(tmp_path, self.ABBA)
        fs = [f for f in analysis.findings() if f.code == "CLNT008"]
        assert len(fs) == 2, [f.render() for f in fs]
        msgs = " ".join(f.message for f in fs)
        assert "fix.a" in msgs and "fix.b" in msgs
        # both edges are flagged in the cycle, each at its witness site
        assert {f.path for f in fs} == {"a.py", "b.py"}

    def test_edges_and_cycle_marked_in_artifact(self, tmp_path):
        analysis = run_graph(tmp_path, self.ABBA)
        d = analysis.graph_dict()
        pairs = {(e["from"], e["to"]) for e in d["edges"]}
        assert ("fix.a", "fix.b") in pairs and ("fix.b", "fix.a") in pairs
        assert all(
            e["in_cycle"]
            for e in d["edges"]
            if (e["from"], e["to"]) in {("fix.a", "fix.b"), ("fix.b", "fix.a")}
        )
        dot = analysis.to_dot()
        assert '"fix.a" -> "fix.b"' in dot and "color=red" in dot

    def test_one_way_nesting_is_clean(self, tmp_path):
        analysis = run_graph(
            tmp_path,
            {
                "mod.py": """
                from .libs import sync as libsync
                A = libsync.Mutex("one.a")
                B = libsync.Mutex("one.b")

                def f():
                    with A:
                        with B:
                            pass

                def g():
                    with A:
                        with B:
                            pass
                """
            },
        )
        assert [f for f in analysis.findings() if f.code == "CLNT008"] == []
        pairs = {(e["from"], e["to"]) for e in analysis.graph_dict()["edges"]}
        assert pairs == {("one.a", "one.b")}


# ------------------------------------------- CLNT009 (blocking under lock)


class TestBlockingUnderLock:
    def test_direct_and_interprocedural_blocking(self, tmp_path):
        analysis = run_graph(
            tmp_path,
            {
                "mod.py": """
                import socket
                import time
                from .libs import sync as libsync

                class S:
                    def __init__(self):
                        self._mtx = libsync.Mutex("blk.mtx")
                        self._sock = socket.create_connection(("h", 1))

                    def direct(self):
                        with self._mtx:
                            self._sock.sendall(b"x")

                    def indirect(self):
                        with self._mtx:
                            self._helper()

                    def _helper(self):
                        time.sleep(0.1)

                    def fine(self):
                        with self._mtx:
                            pass
                        self._sock.sendall(b"y")
                """
            },
        )
        fs = [f for f in analysis.findings() if f.code == "CLNT009"]
        assert len(fs) == 2, [f.render() for f in fs]
        kinds = " ".join(f.message for f in fs)
        assert "socket-send" in kinds and "sleep" in kinds
        assert "_helper" in kinds  # the chain is named

    def test_queue_and_wait_classification(self, tmp_path):
        analysis = run_graph(
            tmp_path,
            {
                "mod.py": """
                import queue
                from .libs import sync as libsync

                class Q:
                    def __init__(self):
                        self._mtx = libsync.Mutex("q.mtx")
                        self._q = queue.Queue()

                    def blocking_get(self):
                        with self._mtx:
                            return self._q.get(timeout=1)

                    def poll_is_fine(self):
                        with self._mtx:
                            return self._q.get(block=False)
                """
            },
        )
        fs = [f for f in analysis.findings() if f.code == "CLNT009"]
        assert len(fs) == 1 and "queue-get" in fs[0].message

    def test_condition_wait_exempts_own_lock_only(self, tmp_path):
        analysis = run_graph(
            tmp_path,
            {
                "mod.py": """
                from .libs import sync as libsync

                class C:
                    def __init__(self):
                        self._mtx = libsync.Mutex("cv.own")
                        self._cv = libsync.Condition(self._mtx)
                        self._other = libsync.Mutex("cv.other")

                    def ok(self):
                        with self._cv:
                            self._cv.wait()

                    def bad(self):
                        with self._other:
                            with self._cv:
                                self._cv.wait()
                """
            },
        )
        fs = [f for f in analysis.findings() if f.code == "CLNT009"]
        # only the wait under the UNRELATED lock is flagged
        assert len(fs) == 1, [f.render() for f in fs]
        assert "'cv.other'" in fs[0].message


# --------------------------------------------- CLNT010 (publish under lock)


class TestPublishUnderLock:
    def test_publish_and_fire_event_flagged(self, tmp_path):
        analysis = run_graph(
            tmp_path,
            {
                "mod.py": """
                from .libs import sync as libsync

                class P:
                    def __init__(self, bus, evsw):
                        self._mtx = libsync.Mutex("pub.mtx")
                        self.bus = bus
                        self.evsw = evsw

                    def bad_pub(self):
                        with self._mtx:
                            self.bus.publish_vote("ev")

                    def bad_fire(self):
                        with self._mtx:
                            self.evsw.fire_event("k", None)

                    def fine(self):
                        with self._mtx:
                            data = "ev"
                        self.bus.publish_vote(data)
                """
            },
        )
        fs = [f for f in analysis.findings() if f.code == "CLNT010"]
        assert len(fs) == 2, [f.render() for f in fs]


# ------------------------------------------------------- suppressions


class TestGraphSuppressions:
    def test_site_suppression_with_reason(self, tmp_path):
        analysis = run_graph(
            tmp_path,
            {
                "mod.py": """
                import time
                from .libs import sync as libsync
                M = libsync.Mutex("sup.m")

                def f():
                    with M:  # cometlint: disable=CLNT009 -- sanctioned: test fixture
                        time.sleep(0.1)
                """
            },
        )
        assert [f for f in analysis.findings() if f.code == "CLNT009"] == []

    def test_bare_suppression_is_ignored(self, tmp_path):
        analysis = run_graph(
            tmp_path,
            {
                "mod.py": """
                import time
                from .libs import sync as libsync
                M = libsync.Mutex("sup.m")

                def f():
                    with M:  # cometlint: disable=CLNT009
                        time.sleep(0.1)
                """
            },
        )
        assert codes(analysis.findings()) == ["CLNT009"]

    def test_source_suppression_clears_all_callers(self, tmp_path):
        analysis = run_graph(
            tmp_path,
            {
                "mod.py": """
                import queue
                from .libs import sync as libsync
                M = libsync.Mutex("src.m")
                Q = queue.Queue()

                def sanctioned_put(item):
                    Q.put(item)  # cometlint: disable=CLNT009 -- unbounded queue: put cannot block

                def f():
                    with M:
                        sanctioned_put(1)
                """
            },
        )
        assert [f for f in analysis.findings() if f.code == "CLNT009"] == []


# ------------------------------------------------ libs/sync record/enforce


class TestLockOrderRuntime:
    def _reset(self):
        libsync.set_lock_order_mode("off")
        libsync.reset_lock_order()
        libsync._order_graph_path = None
        libsync._allowed_edges = None

    def test_record_mode_observes_edges(self):
        try:
            libsync.set_lock_order_mode("record")
            libsync.reset_lock_order()
            a = libsync.Mutex("rt.a")
            b = libsync.RLock("rt.b")
            with a:
                with b:
                    pass
            with b:
                pass  # no edge: nothing else held
            edges = libsync.observed_lock_order()
            assert ("rt.a", "rt.b") in edges
            assert ("rt.b", "rt.a") not in edges
            # witness points at this test file
            assert "test_lint_graph" in edges[("rt.a", "rt.b")]
        finally:
            self._reset()

    def test_record_skips_same_name_edges(self):
        try:
            libsync.set_lock_order_mode("record")
            libsync.reset_lock_order()
            a1 = libsync.Mutex("rt.same")
            a2 = libsync.Mutex("rt.same")
            with a1:
                with a2:
                    pass
            assert libsync.observed_lock_order() == {}
        finally:
            self._reset()

    def test_enforce_raises_on_unknown_edge(self, tmp_path):
        graph = tmp_path / "lockorder.json"
        graph.write_text(
            json.dumps(
                {"version": 1, "edges": [{"from": "en.a", "to": "en.b"}]}
            )
        )
        try:
            libsync.set_lock_order_mode("enforce", graph_path=str(graph))
            a = libsync.Mutex("en.a")
            b = libsync.Mutex("en.b")
            with a:
                with b:  # allowed edge: fine
                    pass
            with pytest.raises(libsync.LockOrderError):
                with b:
                    with a:  # en.b -> en.a is not in the graph
                        pass
        finally:
            self._reset()

    def test_deadlock_and_order_instrumentation_compose(self):
        # order mode alone must instrument (factories return wrappers)
        try:
            libsync.set_lock_order_mode("record")
            m = libsync.Mutex("rt.inst")
            assert hasattr(m, "_name")
        finally:
            self._reset()
        assert isinstance(
            libsync.Mutex("rt.raw"), type(libsync.Mutex("rt.raw2"))
        )


# ------------------------------------------------------ engine-wide gates


class TestEngineWideGate:
    @pytest.fixture(scope="class")
    def analysis(self):
        contexts, errors = parse_root(PKG)
        assert not errors, errors
        return analyze_contexts(contexts)

    def test_zero_unbaselined_graph_findings(self):
        """The full-tree gate for the whole-program rules alone: every
        CLNT008-010 finding is either fixed or justified in the
        baseline (test_lint.py::test_full_tree_gate enforces the
        justification text)."""
        from cometbft_tpu.devtools.lint import apply_baseline, load_baseline

        findings, errors = lint_root(PKG, ALL_CHECKERS)
        assert not errors, errors
        graph_findings = [f for f in findings if f.code in GRAPH_RULES]
        baseline = load_baseline(
            os.path.join(REPO, ".cometlint-baseline.json")
        )
        new, _matched, _stale = apply_baseline(graph_findings, baseline)
        assert new == [], "unbaselined CLNT008-010:\n" + "\n".join(
            f.render() for f in new
        )

    def test_no_lock_order_cycles_in_engine(self, analysis):
        assert analysis._sccs() == [], (
            "the engine lock-order graph must stay acyclic"
        )

    def test_shipped_artifact_is_fresh(self, analysis):
        """lockorder.json (the graph COMETBFT_TPU_LOCK_ORDER=enforce
        validates against) must match the tree — regenerate with
        `python -m cometbft_tpu.devtools.lint --graph <path>`."""
        with open(SHIPPED_GRAPH, encoding="utf-8") as f:
            shipped = json.load(f)
        assert shipped == analysis.graph_dict(), (
            "stale lockorder.json — regenerate via "
            "python -m cometbft_tpu.devtools.lint --graph "
            "cometbft_tpu/devtools/lint/graph/lockorder.json"
        )

    def test_graph_is_deterministic(self, analysis):
        contexts, _ = parse_root(PKG)
        again = analyze_contexts(contexts).graph_dict()
        assert again == analysis.graph_dict()

    def test_engine_hierarchy_edges_present(self, analysis):
        """Spot-check load-bearing hierarchy edges the runtime sanitizer
        will observe in any consensus run."""
        pairs = {(e["from"], e["to"]) for e in analysis.graph_dict()["edges"]}
        for edge in [
            ("consensus.state", "vote_set"),
            ("consensus.state", "consensus.height_vote_set._mtx"),
            ("consensus.state", "libs.pubsub._mtx"),
            ("consensus.state", "store.block_store._mtx"),
            ("mempool.update", "abci.client"),
            ("store.block_store._mtx", "libs.db._mtx"),
        ]:
            assert edge in pairs, f"missing hierarchy edge {edge}"

    def test_trace_lock_registered_and_leaf(self, analysis):
        """The tracer's sink-management mutex is in the shipped artifact
        (so the freshness gate covers it) and participates in NO
        acquisition-order edges: trace emission is lock-free by design
        — a trace.* edge appearing here means someone made the hot-path
        tracer take a lock under (or over) engine mutexes."""
        d = analysis.graph_dict()
        assert "libs.trace._mtx" in {lk["name"] for lk in d["locks"]}
        trace_edges = [
            (e["from"], e["to"])
            for e in d["edges"]
            if "libs.trace._mtx" in (e["from"], e["to"])
        ]
        assert trace_edges == [], trace_edges

    def test_txtrace_lock_registered_and_leaf(self, analysis):
        """The tx-lifecycle plane's mempool-probe registry mutex is in
        the shipped artifact and participates in NO acquisition-order
        edges: the record path (admit/send/recv/proposal/commit
        stamps) is lock-free by construction — a txtrace.* edge
        appearing here means someone made a per-tx stamp take a lock
        under (or over) engine mutexes."""
        d = analysis.graph_dict()
        assert "libs.txtrace._mtx" in {lk["name"] for lk in d["locks"]}
        tx_edges = [
            (e["from"], e["to"])
            for e in d["edges"]
            if "libs.txtrace._mtx" in (e["from"], e["to"])
        ]
        assert tx_edges == [], tx_edges

    def test_profile_lock_registered_and_leaf(self, analysis):
        """The sampling profiler's setup mutex is in the shipped
        artifact and participates in NO acquisition-order edges: the
        sample path (the ~67 Hz stack walk) and every snapshot reader
        are lock-free by construction — a profile.* edge appearing
        here means someone made the sampler or a snapshot take a lock
        under (or over) engine mutexes."""
        d = analysis.graph_dict()
        assert "libs.profile._mtx" in {lk["name"] for lk in d["locks"]}
        prof_edges = [
            (e["from"], e["to"])
            for e in d["edges"]
            if "libs.profile._mtx" in (e["from"], e["to"])
        ]
        assert prof_edges == [], prof_edges

    def test_lockprof_recorder_is_lock_free(self, analysis):
        """The lock-contention profiler must never appear in the very
        hierarchy it measures: libs/lockprof owns NO lock in the
        shipped artifact (its slow-path site-intern meta-lock is a
        deliberately raw, CLNT001-suppressed threading.Lock outside the
        sync tier), so the record path — called inside every profiled
        acquire/release — can deadlock with nothing.  A lockprof-owned
        lock or edge appearing here means someone routed the profiler's
        internals through the factories it instruments."""
        d = analysis.graph_dict()
        owned = [
            lk["name"] for lk in d["locks"]
            if "lockprof" in lk.get("path", "") or "lockprof" in lk["name"]
        ]
        assert owned == [], owned
        edges = [
            (e["from"], e["to"])
            for e in d["edges"]
            if "lockprof" in e["from"] or "lockprof" in e["to"]
        ]
        assert edges == [], edges

    def test_coalescer_lock_registered_and_flush_never_blocks_under_it(
        self, analysis
    ):
        """The verify coalescer's queue mutex is modeled in the shipped
        artifact, and the flush path holds no engine mutex while
        blocking on the device: 'crypto.coalesce._mtx' may be acquired
        UNDER caller locks (submit runs inside vote_set / consensus
        admission), but it must never be the OUTER lock of any
        acquisition-order edge — the executor pops a window under it
        and releases it before pack, dispatch and the materializing
        readback — and no CLNT009 blocking-under-lock finding may name
        it (its own condition wait is the sanctioned exempt case)."""
        d = analysis.graph_dict()
        assert "crypto.coalesce._mtx" in {lk["name"] for lk in d["locks"]}
        outgoing = [
            (e["from"], e["to"])
            for e in d["edges"]
            if e["from"] == "crypto.coalesce._mtx"
        ]
        assert outgoing == [], (
            "the coalescer flush path acquired a lock while holding "
            f"its queue mutex: {outgoing}"
        )
        blocked = [
            f.render()
            for f in analysis.findings()
            if f.code == "CLNT009"
            and "'crypto.coalesce._mtx'" in f.message
        ]
        assert blocked == [], blocked

    def test_hashplane_lock_registered_and_flush_never_blocks_under_it(
        self, analysis
    ):
        """The hash plane's queue mutex carries the verify coalescer's
        contract: 'crypto.hashplane._mtx' may be acquired UNDER caller
        locks (TxKey routing near mempool.update, merkle hashing under
        consensus.state), but it must never be the OUTER lock of any
        acquisition-order edge — the executor pops a window under it
        and releases it before pack, dispatch and the materializing
        readback — and no CLNT009 blocking-under-lock finding may name
        it (its own condition wait is the sanctioned exempt case)."""
        d = analysis.graph_dict()
        assert "crypto.hashplane._mtx" in {lk["name"] for lk in d["locks"]}
        outgoing = [
            (e["from"], e["to"])
            for e in d["edges"]
            if e["from"] == "crypto.hashplane._mtx"
        ]
        assert outgoing == [], (
            "the hash-plane flush path acquired a lock while holding "
            f"its queue mutex: {outgoing}"
        )
        blocked = [
            f.render()
            for f in analysis.findings()
            if f.code == "CLNT009"
            and "'crypto.hashplane._mtx'" in f.message
        ]
        assert blocked == [], blocked

    def test_readback_drain_locks_registered_and_leaf(self, analysis):
        """The readback-drain handoff mutexes of both planes
        ('crypto.coalesce._rb_mtx', 'crypto.hashplane._rb_mtx') are in
        the shipped artifact and participate in NO acquisition-order
        edges: the drain thread pops a window under its mutex and
        releases it BEFORE the materializing readback and ticket
        resolution, and the executor's depth wait is its own condition
        — an edge appearing here means the drain handoff started
        holding its lock into device waits or engine code, and the
        overlap (execute of window N+1 over d2h of window N) turned
        into a contention point."""
        d = analysis.graph_dict()
        names = {lk["name"] for lk in d["locks"]}
        for lock in (
            "crypto.coalesce._rb_mtx",
            "crypto.hashplane._rb_mtx",
        ):
            assert lock in names, lock
            edges = [
                (e["from"], e["to"])
                for e in d["edges"]
                if lock in (e["from"], e["to"])
            ]
            assert edges == [], (lock, edges)

    def test_lane_arena_lock_is_retired(self, analysis):
        """The lane staging arena's slot mutex ('ops.verify._lane_mtx')
        went with the arena (PR 26): a launch takes no lock between its
        pack and its kernel call, so nothing of the verify dispatch can
        splice into the engine lock hierarchy there. The pubkey table
        cache's lock is the one ops/verify lock left."""
        d = analysis.graph_dict()
        names = {lk["name"] for lk in d["locks"]}
        assert "ops.verify._lane_mtx" not in names
        assert "ops.verify._lock" in names

    def test_health_lock_registered_and_leaf(self, analysis):
        """libs/health's bundle-rate-limit mutex carries the same
        contract as the tracer's and devstats': present in the shipped
        artifact, participating in NO acquisition-order edges. The
        flight recorder's record path is lock-free BY DESIGN (it runs
        inside the consensus FSM under 'consensus.state' and inside
        the devstats drain under 'libs.devstats._mtx'); an edge
        appearing here means someone made the always-on record path
        take a lock under an engine mutex."""
        d = analysis.graph_dict()
        assert "libs.health._mtx" in {lk["name"] for lk in d["locks"]}
        health_edges = [
            (e["from"], e["to"])
            for e in d["edges"]
            if "libs.health._mtx" in (e["from"], e["to"])
        ]
        assert health_edges == [], health_edges

    def test_light_cache_lock_registered_and_leaf(self, analysis):
        """The light proof service's commit-result cache lock carries
        the same contract as libs.trace._mtx: present in the shipped
        artifact, participating in NO acquisition-order edges. The
        cache sits on every proof request's commit-check path and its
        bodies are pure dict bookkeeping BY DESIGN — the single-flight
        leader verifies outside it, metrics are incremented outside it,
        waiters block on a flight event outside it. An edge appearing
        here means someone made a cache body take a lock (or a lock
        holder enter the cache) and the thousands-of-clients hot path
        grew a contention point."""
        d = analysis.graph_dict()
        assert "light.service._cache_mtx" in {
            lk["name"] for lk in d["locks"]
        }
        cache_edges = [
            (e["from"], e["to"])
            for e in d["edges"]
            if "light.service._cache_mtx" in (e["from"], e["to"])
        ]
        assert cache_edges == [], cache_edges

    def test_netstats_lock_registered_and_leaf(self, analysis):
        """libs/netstats' connection-registry mutex carries the same
        contract as the tracer's: present in the shipped artifact,
        participating in NO acquisition-order edges. The per-packet
        record path is lock-free BY DESIGN (single-writer array
        columns inside the wire routines; registration happens only at
        connection start/stop) — an edge appearing here means someone
        made the packet path take a lock."""
        d = analysis.graph_dict()
        assert "libs.netstats._mtx" in {lk["name"] for lk in d["locks"]}
        net_edges = [
            (e["from"], e["to"])
            for e in d["edges"]
            if "libs.netstats._mtx" in (e["from"], e["to"])
        ]
        assert net_edges == [], net_edges

    def test_simnet_scheduler_lock_registered_and_leaf(self, analysis):
        """The simnet scheduler's heap mutex carries the tracer-lock
        contract: present in the shipped artifact, participating in NO
        acquisition-order edges.  Every event callback — consensus FSM
        steps under 'consensus.state', reactor receives, WAL writes —
        runs AFTER pop_due releases the heap lock; an edge appearing
        here means a scheduler body started executing engine code (or
        an engine path started scheduling while holding its own lock
        THROUGH a callback), which would let the deterministic run loop
        deadlock against the very components it drives."""
        d = analysis.graph_dict()
        assert "simnet.sched._mtx" in {lk["name"] for lk in d["locks"]}
        sched_edges = [
            (e["from"], e["to"])
            for e in d["edges"]
            if "simnet.sched._mtx" in (e["from"], e["to"])
        ]
        assert sched_edges == [], sched_edges

    def test_devstats_lock_registered_and_leaf(self, analysis):
        """libs/devstats' compile-ledger mutex has the same contract as
        the tracer's: present in the shipped artifact, edge-free. The
        telemetry layer records compiles/transfers from inside the
        verify hot path — metrics and trace emission happen OUTSIDE the
        ledger lock, so it must never gain an acquisition-order edge."""
        d = analysis.graph_dict()
        assert "libs.devstats._mtx" in {lk["name"] for lk in d["locks"]}
        devstats_edges = [
            (e["from"], e["to"])
            for e in d["edges"]
            if "libs.devstats._mtx" in (e["from"], e["to"])
        ]
        assert devstats_edges == [], devstats_edges
