"""Pipelined heights (consensus/pipeline.py): the commit-boundary
overlap engine — speculative FinalizeBlock, the ordered commit-writer
with its durability barrier, and next-height prestaging.

The acceptance gates of this PR live here:

* speculation protocol units — hit / miss / supersede-abort semantics,
  the snapshot/restore sandwich leaving the app bit-identical, and the
  unsupported-client permanent opt-out;
* commit-writer units — FIFO ordering, the durability barrier
  releasing exactly at fsync-complete, barrier wedge and writer
  failure both fail-stopping instead of silently running ahead;
* a LIVE pipelined 4-validator burst reconciling on the device ledger
  (zero ``other``-classed lanes from the new workers, speculation
  hits recorded) with per-height budget coverage >= 0.9;
* pipelined and serial single-validator runs landing on the IDENTICAL
  application state for the same transactions;
* the concurrency soak: the same burst under
  ``COMETBFT_TPU_LOCKSET=enforce`` + ``COMETBFT_TPU_LOCK_ORDER=enforce``
  against the repo's regenerated artifacts, zero violations.
"""

import os
import threading
import time

import pytest

from cometbft_tpu.abci.client import SpeculationUnsupported
from cometbft_tpu.abci.kvstore import KVStoreApplication
from cometbft_tpu.consensus.pipeline import (
    CommitPipeline,
    PipelineError,
    pipeline_mode,
    spec_mode,
)
from cometbft_tpu.libs import db as dbm
from cometbft_tpu.libs import devledger
from cometbft_tpu.libs import health as libhealth
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import sync as libsync
from cometbft_tpu.libs.metrics import NodeMetrics

import helpers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRAPH = os.path.join(
    REPO, "cometbft_tpu", "devtools", "lint", "graph", "lockorder.json"
)
FIELDS = os.path.join(
    REPO, "cometbft_tpu", "devtools", "lint", "graph", "fieldguards.json"
)


@pytest.fixture
def fresh_metrics():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    yield m
    libmetrics.pop_node_metrics(m)


def _spec_counts(m):
    return {
        k: m.spec_exec.labels(k).value() for k in ("hit", "miss", "abort")
    }


# ------------------------------------------------------- knob parsing


def test_mode_knob_parsing(monkeypatch):
    monkeypatch.delenv("COMETBFT_TPU_PIPELINE", raising=False)
    monkeypatch.delenv("COMETBFT_TPU_SPEC_EXEC", raising=False)
    assert pipeline_mode() == "auto"
    assert spec_mode() == "auto"
    monkeypatch.setenv("COMETBFT_TPU_PIPELINE", "inline")
    assert pipeline_mode() == "inline"
    monkeypatch.setenv("COMETBFT_TPU_PIPELINE", "0")
    assert pipeline_mode() == "off"
    monkeypatch.setenv("COMETBFT_TPU_PIPELINE", "on")
    assert pipeline_mode() == "on"
    monkeypatch.setenv("COMETBFT_TPU_SPEC_EXEC", "1")
    assert spec_mode() == "on"
    monkeypatch.setenv("COMETBFT_TPU_SPEC_EXEC", "no")
    assert spec_mode() == "off"


# ------------------------------------------------- speculation units


class TestSpeculationSlot:
    def _pipe(self, spec=True, inline=True):
        pipe = CommitPipeline(block_exec=None, wal=None)
        pipe.inline = inline
        pipe.enabled = True
        pipe.spec_enabled = spec
        return pipe

    def test_hit_returns_memoized_result(self, fresh_metrics):
        pipe = self._pipe()
        calls = []
        pipe.submit_speculation(
            5, b"\xaa" * 32, lambda: calls.append(1) or ("resp", "post")
        )
        assert calls == [1]  # inline: executed on the spot
        got = pipe.consume_speculation(5, 0, b"\xaa" * 32)
        assert got == ("resp", "post")
        c = _spec_counts(fresh_metrics)
        assert (c["hit"], c["miss"], c["abort"]) == (1, 0, 0)
        # the slot is cleared: a second consume is a plain miss
        assert pipe.consume_speculation(5, 0, b"\xaa" * 32) is None
        assert _spec_counts(fresh_metrics)["miss"] == 1

    def test_resubmit_same_key_is_noop(self, fresh_metrics):
        pipe = self._pipe()
        calls = []
        thunk = lambda: calls.append(1) or ("r", "p")  # noqa: E731
        pipe.submit_speculation(5, b"\xaa" * 32, thunk)
        pipe.submit_speculation(5, b"\xaa" * 32, thunk)
        assert calls == [1]
        assert pipe.consume_speculation(5, 0, b"\xaa" * 32) == ("r", "p")

    def test_wrong_block_misses_and_aborts_stored(self, fresh_metrics):
        pipe = self._pipe()
        pipe.submit_speculation(5, b"\xaa" * 32, lambda: ("r", "p"))
        # a DIFFERENT block won precommit: miss for the winner, abort
        # for the speculated loser, slot cleared either way
        assert pipe.consume_speculation(5, 0, b"\xbb" * 32) is None
        c = _spec_counts(fresh_metrics)
        assert (c["hit"], c["miss"], c["abort"]) == (0, 1, 1)
        assert pipe.consume_speculation(5, 0, b"\xaa" * 32) is None

    def test_supersede_records_abort(self, fresh_metrics):
        pipe = self._pipe()
        pipe.submit_speculation(5, b"\xaa" * 32, lambda: ("rA", "pA"))
        # round bumped, new proposal: the new key supersedes
        pipe.submit_speculation(5, b"\xbb" * 32, lambda: ("rB", "pB"))
        assert _spec_counts(fresh_metrics)["abort"] == 1
        assert pipe.consume_speculation(5, 1, b"\xbb" * 32) == ("rB", "pB")

    def test_unsupported_disables_forever(self, fresh_metrics):
        pipe = self._pipe()

        def boom():
            raise SpeculationUnsupported("remote transport")

        pipe.submit_speculation(5, b"\xaa" * 32, boom)
        assert pipe.spec_enabled is False
        # no abort noise for a capability miss, and later submits are
        # free no-ops
        assert _spec_counts(fresh_metrics)["abort"] == 0
        pipe.submit_speculation(6, b"\xcc" * 32, lambda: ("r", "p"))
        assert pipe.consume_speculation(6, 0, b"\xcc" * 32) is None

    def test_spec_error_degrades_to_miss(self, fresh_metrics):
        pipe = self._pipe()

        def boom():
            raise RuntimeError("app exploded speculatively")

        pipe.submit_speculation(5, b"\xaa" * 32, boom)
        assert pipe.spec_enabled is True  # real errors don't opt out
        assert pipe.consume_speculation(5, 0, b"\xaa" * 32) is None
        c = _spec_counts(fresh_metrics)
        assert c["abort"] == 1 and c["miss"] == 1 and c["hit"] == 0

    def test_threaded_consume_waits_for_inflight(self, fresh_metrics):
        pipe = self._pipe(inline=False)
        release = threading.Event()

        def slow():
            release.wait(5)
            return ("r", "p")

        try:
            pipe.submit_speculation(5, b"\xaa" * 32, slow)
            release.set()
            # the work already happened (or is about to finish):
            # consume must claim it, not discard and re-execute
            assert pipe.consume_speculation(5, 0, b"\xaa" * 32) == (
                "r",
                "p",
            )
            assert _spec_counts(fresh_metrics)["hit"] == 1
        finally:
            release.set()
            pipe.stop(drain_s=1)

    def test_disabled_pipe_never_speculates(self, fresh_metrics):
        pipe = self._pipe(spec=False)
        pipe.submit_speculation(5, b"\xaa" * 32, lambda: ("r", "p"))
        assert pipe.consume_speculation(5, 0, b"\xaa" * 32) is None
        assert _spec_counts(fresh_metrics) == {
            "hit": 0,
            "miss": 0,
            "abort": 0,
        }


def test_local_client_speculation_is_state_neutral():
    """The snapshot/finalize/restore sandwich: speculate_finalize
    leaves the app BIT-IDENTICAL, and apply_speculation(post) lands on
    exactly the state a direct FinalizeBlock produces."""
    from cometbft_tpu import proxy
    from cometbft_tpu.abci import types as abci

    def mk():
        app = KVStoreApplication(dbm.MemDB())
        conns = proxy.AppConns(proxy.local_client_creator(app))
        conns.start()
        return app, conns

    req = abci.RequestFinalizeBlock(
        txs=[b"k1=v1", b"k2=v2"],
        decided_last_commit=abci.CommitInfo(round=0, votes=[]),
        misbehavior=[],
        hash=b"\x01" * 32,
        height=1,
        time_ns=0,
        next_validators_hash=b"\x02" * 32,
        proposer_address=b"\x03" * 20,
    )

    app_a, conns_a = mk()
    app_b, conns_b = mk()
    try:
        assert conns_a.consensus.supports_speculation()
        pre = app_a.snapshot_spec_state()
        resp, post = conns_a.consensus.speculate_finalize(req)
        # neutral: the app came out exactly as it went in
        assert app_a.snapshot_spec_state() == pre
        # applying the memoized post-state == running finalize directly
        resp_b = conns_b.consensus.finalize_block(req)
        conns_a.consensus.apply_speculation(post)
        assert app_a.snapshot_spec_state() == app_b.snapshot_spec_state()
        assert [r.code for r in resp.tx_results] == [
            r.code for r in resp_b.tx_results
        ]
        assert resp.app_hash == resp_b.app_hash
        assert resp.app_hash != pre["app_hash"]  # the txs changed state
    finally:
        conns_a.stop()
        conns_b.stop()


# ----------------------------------------------- commit-writer units


class TestCommitWriter:
    def test_inline_runs_synchronously(self):
        pipe = CommitPipeline(None, None)
        pipe.enabled = True
        pipe.inline = True
        ran = []
        pipe.note_base(4)
        pipe.enqueue_commit(5, lambda: ran.append(5))
        assert ran == [5]
        assert pipe.durable_height() == 5

    def test_fifo_order_and_barrier(self):
        pipe = CommitPipeline(None, None)
        pipe.enabled = True
        ran = []
        gate = threading.Event()
        try:
            pipe.enqueue_commit(
                1, lambda: (gate.wait(5), ran.append(1))
            )
            pipe.enqueue_commit(2, lambda: ran.append(2))
            pipe.enqueue_commit(3, lambda: ran.append(3))
            assert pipe.durable_height() == 0  # writer gated on job 1
            gate.set()
            pipe.wait_durable(3, timeout_s=10)
            assert ran == [1, 2, 3]
            assert pipe.durable_height() == 3
            # an already-durable height returns immediately
            pipe.wait_durable(1, timeout_s=0.01)
        finally:
            gate.set()
            pipe.stop(drain_s=1)

    def test_barrier_wedge_raises(self):
        pipe = CommitPipeline(None, None)
        pipe.enabled = True
        gate = threading.Event()
        try:
            pipe.enqueue_commit(1, lambda: gate.wait(10))
            with pytest.raises(PipelineError, match="wedged"):
                pipe.wait_durable(1, timeout_s=0.3)
        finally:
            gate.set()
            pipe.stop(drain_s=2)

    def test_writer_failure_fail_stops(self):
        fatals = []
        pipe = CommitPipeline(None, None, on_fatal=fatals.append)
        pipe.enabled = True

        def boom():
            raise RuntimeError("fsync exploded")

        pipe.enqueue_commit(1, boom)
        deadline = time.monotonic() + 5
        while not fatals and time.monotonic() < deadline:
            time.sleep(0.01)
        assert fatals and "fsync exploded" in repr(fatals[0])
        with pytest.raises(PipelineError, match="failed"):
            pipe.wait_durable(1, timeout_s=1)
        # the pipe is poisoned: later enqueues refuse instead of
        # silently queueing behind a dead writer
        with pytest.raises(PipelineError):
            pipe.enqueue_commit(2, lambda: None)
        pipe.stop(drain_s=0.5)

    def test_note_base_seeds_durable(self):
        pipe = CommitPipeline(None, None)
        pipe.note_base(7)
        assert pipe.durable_height() == 7
        pipe.wait_durable(7, timeout_s=0.01)  # immediate
        pipe.note_base(3)  # never regresses
        assert pipe.durable_height() == 7


# --------------------------------------------- live pipelined bursts


def _wire_pipeline(cs, parts, spec=True):
    """Mirror node/node.py's boot wiring onto a helper-built core."""
    pipe = CommitPipeline(parts["executor"], cs.wal)
    pipe.enabled = True
    pipe.spec_enabled = (
        spec and parts["conns"].consensus.supports_speculation()
    )
    pipe.note_base(cs.state.last_block_height)
    parts["executor"].prune_gate = pipe.durable_height
    cs.pipeline = pipe
    return pipe


def _run_single_validator(pipelined, txs, heights=3):
    """One-validator burst committing ``txs``; returns the app's final
    state (app_hash, kv store) after >= ``heights`` commits."""
    genesis, pvs = helpers.make_genesis(1)
    cs, parts = helpers.make_consensus_node(genesis, pvs[0])
    from cometbft_tpu.simnet.node import SimListMempool

    mp = SimListMempool()
    for tx in txs:
        mp.push_tx(tx)
    parts["executor"].mempool = mp
    fatals = []
    cs.on_fatal = fatals.append
    if pipelined:
        pipe = _wire_pipeline(cs, parts)
        pipe.on_fatal = fatals.append
        assert pipe.spec_enabled  # kvstore over LocalClient sandboxes
    cs.start()
    try:
        assert helpers.wait_for_height(parts, heights, timeout=60), (
            f"stalled at {parts['block_store'].height()} "
            f"(pipelined={pipelined})"
        )
    finally:
        helpers.stop_node(cs, parts)
    assert not fatals, fatals
    app = parts["app"]
    from cometbft_tpu.abci import types as abci

    kv = {
        tx.split(b"=")[0]: app.query(
            abci.RequestQuery(data=tx.split(b"=")[0])
        ).value
        for tx in txs
    }
    return app.app_hash, kv


def test_pipelined_matches_serial_app_state(fresh_metrics):
    """THE state-identity acceptance: the pipelined chain (speculative
    execution + off-thread durable suffix) commits the SAME transactions
    to the IDENTICAL application state as the serial reference chain —
    and actually speculated (hits recorded), so the equality covers the
    speculative path, not a silent fallback."""
    txs = [b"alpha=1", b"bravo=2", b"charlie=3"]
    serial_hash, serial_store = _run_single_validator(False, txs)
    pre = _spec_counts(fresh_metrics)
    assert pre["hit"] == 0  # serial run never touched the slot
    pipe_hash, pipe_store = _run_single_validator(True, txs)
    assert _spec_counts(fresh_metrics)["hit"] >= 1
    assert pipe_hash == serial_hash
    assert pipe_store == serial_store
    assert serial_store[b"alpha"] == b"1"


def test_budget_credits_an_overlapped_fsync_beside_the_stages():
    """An fsync flagged ``overlapped`` inside a height's commit window is
    that height's ``overlapped.wal_fsync`` and no part of its stages;
    one outside every window is nobody's; an unflagged one is the
    ``wal_fsync`` stage. Pure function, hand-made events."""
    ms = 1_000_000
    events = [
        {"event": "consensus.commit", "height": 7, "node": 1,
         "ts": 100 * ms, "dur_ns": 50 * ms},
        {"event": "wal.fsync", "ts": 70 * ms, "dur_ns": 4 * ms,
         "overlapped": 1},
        {"event": "wal.fsync", "ts": 80 * ms, "dur_ns": 2 * ms,
         "overlapped": 0},
        {"event": "wal.fsync", "ts": 120 * ms, "dur_ns": 8 * ms,
         "overlapped": 1},
    ]
    hv = libhealth.budget_from_events(events)[7]
    assert hv["overlapped"] == {"wal_fsync": 0.004, "spec_exec": 0.0}
    assert hv["stages"]["wal_fsync"] == pytest.approx(0.002)
    assert sum(hv["stages"].values()) == pytest.approx(hv["latency_s"])
    del events[1]
    assert "overlapped" not in libhealth.budget_from_events(events)[7]


def test_budget_credits_a_speculation_longer_than_its_span():
    """A winning speculation's execute time counts in its span's stages
    up to the span's length; the rest is ``overlapped.spec_exec``. One
    that fits its span is all stage and no credit."""
    ms = 1_000_000
    commit = {"event": "consensus.commit", "height": 3, "node": 1,
              "ts": 100 * ms, "dur_ns": 20 * ms}

    def budget_with(spec_ms):
        return libhealth.budget_from_events([commit, {
            "event": "spec.exec", "ts": 90 * ms,
            "outcome": libhealth.SPEC_HIT, "dur_ns": spec_ms * ms,
        }])[3]

    hv = budget_with(50)
    assert hv["stages"]["spec_exec"] == pytest.approx(0.020)
    assert hv["overlapped"] == {"wal_fsync": 0.0, "spec_exec": 0.03}
    assert sum(hv["stages"].values()) == pytest.approx(hv["latency_s"])
    hv = budget_with(5)
    assert hv["stages"]["spec_exec"] == pytest.approx(0.005)
    assert "overlapped" not in hv


def test_pipelined_burst_reconciles_and_covers(fresh_metrics):
    """Live pipelined 4-validator burst over a routed coalescer: the
    new workers (cs-commit-writer, cs-spec-exec, cs-prestage-next)
    declare caller classes — ZERO ``other``-classed verify lanes — the
    ledger reconciles, speculation hits land (as many ring rows as
    the metric counts), what the budget credits as overlapped is beside
    the stages and no more than was recorded, and the stages still
    explain >= 90% of each commit's measured latency."""
    from cometbft_tpu.crypto import coalesce as crypto_coalesce

    was = devledger.enabled()
    devledger.enable()
    devledger.reset()
    libhealth.enable(ring=1 << 14)
    libhealth.reset()
    co = crypto_coalesce.VerifyCoalescer(
        device=False, min_device_lanes=1 << 30
    )
    co.start()
    crypto_coalesce.push_active(co)
    genesis, pvs = helpers.make_genesis(4)
    nodes = [helpers.make_consensus_node(genesis, pv) for pv in pvs]
    helpers.wire_perfect_gossip(nodes)
    fatals = []
    for cs, parts in nodes:
        cs.on_fatal = fatals.append
        _wire_pipeline(cs, parts).on_fatal = fatals.append
    try:
        for cs, _ in nodes:
            cs.start()
        stores = [parts["block_store"] for _, parts in nodes]
        helpers.wait_for_commits(stores, 4, ring_commits=4 * 4, tick=0.02)
    finally:
        for cs, parts in nodes:
            helpers.stop_node(cs, parts)
        crypto_coalesce.pop_active(co)
        co.stop()
        bud = libhealth.budget()
        spec_rows = [
            e for e in libhealth.recorder().dump() if e["event"] == "spec.exec"
        ]
        libhealth.disable()
        libhealth.set_ring_capacity(libhealth.DEFAULT_RING_SIZE)
        libhealth.reset()

    try:
        assert not fatals, fatals
        # no fork, and every node landed on one app state
        assert len({s.load_block(1).hash() for s in stores}) == 1
        assert len({p["app"].app_hash for _, p in nodes}) == 1
        # zero unattributed lanes with the pipeline workers live
        per_caller = {
            name: devledger.cell(devledger.PLANE_VERIFY, cid)
            for name, cid in devledger.CALLER_CODES.items()
        }
        assert per_caller["other"]["lanes"] == 0, per_caller
        r = devledger.reconcile()["verify"]
        assert r["caller_lanes"] == r["window_lanes"]
        # the speculative path actually ran and won
        c = _spec_counts(fresh_metrics)
        assert c["hit"] >= 1, c
        # budget: stages still tile each height >= 90% with the fsync
        # and apply spans moved OFF the serial window
        assert bud["commits"] >= 3
        assert bud["coverage"] is not None and bud["coverage"] >= 0.9, bud
        for hv in bud["heights"]:
            stage_sum = sum(hv["stages"].values())
            assert stage_sum >= 0.9 * hv["latency_s"], hv
        # every consumed speculation left one ring row, by outcome: the
        # same counts as the metric's, whatever the load
        for code, name in libhealth._SPEC_OUTCOMES.items():
            rows = [e for e in spec_rows if e["outcome"] == code]
            assert len(rows) == c[name], (name, len(rows), c)
        # Where the budget credits work that ran off the FSM's thread
        # (these nodes have no WAL to fsync, so: a winning speculation's
        # execute time beyond what its span can hold), it is beside the
        # stages (the sidebar is NOT part of the tiling sum above) and
        # no more than was recorded. WHETHER a height is credited is
        # wall clock against wall clock: a warm 0.4 s burst had none
        # (tier-1, PR 31's run), so the credit itself is pinned on the
        # hand-made event lists above.
        hit_s = sum(
            e["dur_ns"] for e in spec_rows
            if e["outcome"] == libhealth.SPEC_HIT
        ) / 1e9
        credited_s = 0.0
        for hv in bud["heights"]:
            ov = hv.get("overlapped")
            if ov is not None:
                assert set(ov) == {"wal_fsync", "spec_exec"}
                assert ov["wal_fsync"] == 0 and ov["spec_exec"] >= 0
                credited_s += ov["spec_exec"]
        assert credited_s <= hit_s + 1e-6, (credited_s, hit_s)
    finally:
        devledger.reset()
        devledger.enable() if was else devledger.disable()


def test_enforce_soak_pipelined_burst():
    """CI concurrency gate: a pipelined 4-validator burst under BOTH
    runtime sanitizers in enforce mode against the repo's committed
    artifacts — any lock-order edge or guarded-field access the static
    analyses didn't bless raises and fails the test."""
    assert os.path.exists(GRAPH) and os.path.exists(FIELDS)
    prev_order = libsync.lock_order_mode()
    prev_set = libsync.lockset_mode()
    libsync.set_lock_order_mode("enforce", graph_path=GRAPH)
    libsync.set_lockset_mode("enforce", fields_path=FIELDS)
    libsync.reset_locksets()
    genesis, pvs = helpers.make_genesis(4)
    nodes = [helpers.make_consensus_node(genesis, pv) for pv in pvs]
    helpers.wire_perfect_gossip(nodes)
    fatals = []
    for cs, parts in nodes:
        cs.on_fatal = fatals.append
        _wire_pipeline(cs, parts).on_fatal = fatals.append
    try:
        for cs, _ in nodes:
            cs.start()
        stores = [parts["block_store"] for _, parts in nodes]
        helpers.wait_for_commits(stores, 4, tick=0.02)
    finally:
        for cs, parts in nodes:
            helpers.stop_node(cs, parts)
        libsync.set_lock_order_mode(prev_order)
        libsync.set_lockset_mode(prev_set)
    assert not fatals, fatals
    assert len({s.load_block(1).hash() for s in stores}) == 1
