"""E2E harness tests: process testnet + load generator + perturbations
(reference: test/e2e/runner, runner/perturb.go:16-31, test/loadtime).

A real 3-validator testnet of OS processes takes tx load while one node
is paused (SIGSTOP) and another is crash-killed and restarted; afterwards
every node must agree on app hashes at all common heights, the chain must
keep advancing, and the load report must account for committed load txs
with sane latencies.
"""

import dataclasses
import os
import socket
import subprocess
import sys
import time

import pytest

# Process-level testnets: every node is a subprocess with its own jax
# import; on small CI hosts the convergence timeouts only hold with
# the full machine — keep the perturbation harness in the slow tier.
pytestmark = pytest.mark.slow

from cometbft_tpu.e2e import (
    EventLoadMonitor,
    LoadGenerator,
    Testnet,
    load_report,
)
from cometbft_tpu.e2e.load import block_interval_stats
from cometbft_tpu.e2e.load import make_tx, parse_tx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MS = 1_000_000


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _free_port_block(n: int = 10) -> int:
    """A starting port with n free consecutive ports (best effort)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        base = s.getsockname()[1]
    return base if base + n < 65000 else 20000


def _speed_up(testnet: Testnet) -> None:
    from cometbft_tpu import config_file

    for node in testnet.nodes:
        path = os.path.join(node.home, "config", "config.toml")
        cfg = config_file.load_toml(path)
        cfg.consensus = dataclasses.replace(
            cfg.consensus,
            timeout_propose_ns=500 * _MS,
            timeout_prevote_ns=250 * _MS,
            timeout_precommit_ns=250 * _MS,
            timeout_commit_ns=200 * _MS,
            skip_timeout_commit=False,
            create_empty_blocks=True,
        )
        config_file.save_toml(cfg, path)


def test_load_tx_roundtrip():
    tx = make_tx("run1", 7, size=64)
    run_id, seq, sent_ns = parse_tx(tx)
    assert (run_id, seq) == ("run1", 7)
    assert abs(time.time_ns() - sent_ns) < 5e9
    assert parse_tx(b"other=1") is None
    assert b"=" in tx  # kvstore-accepted shape


@pytest.mark.slow
def test_restart_in_full_quorum_net_keeps_liveness(tmp_path):
    """Regression: restarting ANY validator of a 3-node net (ALL three
    needed for +2/3) must not wedge consensus. This caught three real
    bugs: (1) blocksync demanding height == maxPeerHeight deadlocks at
    the tip (the last block is only verifiable by consensus catch-up,
    pool.go IsCaughtUp uses maxPeerHeight-1); (2) announcing our round
    step in add_peer while wait_sync invites vote gossip that is dropped
    but marked delivered (reference AddPeer skips the announcement);
    (3) apply_vote_set_bits could only SET has-vote marks, never CLEAR
    them, disabling the maj23-query self-heal."""
    port = _free_port_block()
    net = Testnet.generate(str(tmp_path / "net"), 3, port)
    _speed_up(net)
    for node in net.nodes:
        node.env = _env()
    net.start()
    try:
        assert all(n.wait_rpc(60.0) for n in net.nodes)
        assert net.wait_all_height(3, 90.0), "testnet never made blocks"
        for i in (0, 1):  # restart two different nodes in sequence
            pre = max(n.height() for n in net.live_nodes())
            net.nodes[i].restart()
            assert net.nodes[i].wait_rpc(60.0), f"node{i} never came back"
            assert net.wait_all_height(pre + 2, 90.0), (
                f"wedged after restarting node{i}: "
                f"{[n.height() for n in net.live_nodes()]}"
            )
        net.check_app_hash_agreement()
    finally:
        net.stop()


@pytest.mark.slow
def test_generated_topology_with_upgrade(tmp_path):
    """The reference's generator + upgrade story (test/e2e/README.md:36-60,
    runner/perturb.go:16-31): a SEEDED randomized manifest (validator
    count, topology, timeouts, storage backend) runs under load while one
    node is upgraded mid-run — clean stop, restart under a bumped
    advertised version + new-version config defaults, SAME data dir. The
    upgraded node must rejoin via handshake replay, the chain must keep
    advancing, app hashes must agree, and mixed versions must interoperate.
    """
    port = _free_port_block()
    net = Testnet.generate_randomized(str(tmp_path / "net"), seed=1337,
                                      starting_port=port)
    assert os.path.exists(str(tmp_path / "net" / "manifest.json"))
    _speed_up(net)  # keep CI time bounded regardless of drawn timeouts
    for node in net.nodes:
        node.env = _env()
    net.start()
    try:
        assert all(n.wait_rpc(60.0) for n in net.nodes), "RPC never came up"
        assert net.wait_all_height(2, 90.0), "testnet never made blocks"

        gen = LoadGenerator(
            [n.rpc_addr for n in net.nodes],
            rate=10,
            connections=1,
            run_id="upg1",
        )
        gen.start()
        try:
            time.sleep(1.5)
            pre_h = net.nodes[0].height()

            def v2_config(cfg):
                cfg.consensus = dataclasses.replace(
                    cfg.consensus, timeout_commit_ns=150 * _MS
                )

            net.nodes[0].upgrade(
                "cometbft-tpu/0.2.0-rc1", config_mutator=v2_config
            )
            assert net.nodes[0].wait_rpc(60.0), "upgraded node never rejoined"
            assert net.nodes[0].advertised_version() == "cometbft-tpu/0.2.0-rc1"
            # chain continuity: the upgraded node resumes FROM its data
            # dir (handshake replay), it does not restart at zero
            assert net.nodes[0].wait_height(pre_h, 60.0), (
                "upgraded node lost its chain"
            )
            time.sleep(1.5)
        finally:
            gen.stop()
        assert gen.sent > 0

        net.check_progress(blocks=2, timeout=90.0)
        net.check_app_hash_agreement()
    finally:
        net.stop()


@pytest.mark.slow
def test_perturbed_testnet_under_load(tmp_path):
    port = _free_port_block()
    # 4 validators: the smallest BFT net that tolerates one faulty
    # node (+2/3 of 40 = 30 = 3 validators), so kill/pause of a single
    # node must not halt the chain (e2e networks/ci.toml topology).
    net = Testnet.generate(str(tmp_path / "net"), 4, port)
    _speed_up(net)
    for node in net.nodes:
        node.env = _env()
    net.start()
    try:
        assert all(n.wait_rpc(60.0) for n in net.nodes), "RPC never came up"
        assert net.wait_all_height(2, 90.0), "testnet never made blocks"

        gen = LoadGenerator(
            [n.rpc_addr for n in net.nodes],
            rate=20,
            connections=2,
            run_id="perturb1",
        )
        # live per-tx commit latency via the Tx-event subscription
        # (ws_client; replaces the block-timestamp method as primary)
        mon = EventLoadMonitor(net.nodes[0].rpc_addr, "perturb1")
        gen.start()
        try:
            time.sleep(2.0)

            # perturbation 1: pause node2 (docker pause analog)
            net.nodes[2].pause()
            time.sleep(2.0)
            net.nodes[2].unpause()

            # perturbation 2: crash-kill node1, restart it
            net.nodes[1].kill()
            time.sleep(1.5)
            net.nodes[1].start()
            assert net.nodes[1].wait_rpc(60.0), "killed node never restarted"

            time.sleep(2.0)
        finally:
            gen.stop()
        assert gen.sent > 0, "load generator sent nothing"

        # invariants (test/e2e/tests): progress + app-hash agreement
        net.check_progress(blocks=2, timeout=90.0)
        net.check_app_hash_agreement()

        # PRIMARY: per-tx commit latency from Tx events, one clock
        ev_rep = mon.finish(drain_s=3.0)
        ev_summary = ev_rep.summary()
        assert ev_rep.txs > 0, f"no Tx events observed: {ev_summary}"
        assert 0 < ev_rep.mean_s < 60, ev_summary
        assert (
            ev_rep.quantile(0.99) >= ev_rep.quantile(0.5) > 0
        ), ev_summary

        # cross-check: the offline block-timestamp method still agrees
        # on tx counts (it sees only committed txs; events may include a
        # few more from the drain window)
        rep = load_report(net.nodes[0].rpc_addr, "perturb1")
        summary = rep.summary()
        assert rep.txs > 0, f"no load txs committed: {summary}"
        assert 0 < rep.mean_s < 60, summary

        # block-production stats (runner/benchmark.go analog)
        stats = block_interval_stats(net.nodes[0].rpc_addr)
        assert stats["blocks"] >= 4
        assert 0 < stats["interval_mean_s"] < 30, stats
        assert stats["interval_min_s"] <= stats["interval_max_s"], stats
    finally:
        net.stop()


def test_partition_heal_convergence_under_load(tmp_path):
    """The `disconnect` perturbation over a REAL multi-process net
    (perturb.go:16-31): every p2p link rides a severable relay; node 2
    is partitioned under tx load, the 3-validator chain STALLS (no +2/3
    without it), healing restores progress, and all nodes converge on
    app hashes."""
    port = _free_port_block(12)
    net = Testnet.generate_relayed(str(tmp_path / "net"), 3, port)
    assert len(net.relays) >= 4, "directed links must be relayed"
    _speed_up(net)
    for node in net.nodes:
        node.env = _env()
    net.start()
    try:
        assert all(n.wait_rpc(60.0) for n in net.nodes), "RPC never came up"
        assert net.wait_all_height(2, 90.0), (
            "relayed testnet never made blocks (relay wiring broken?)"
        )

        gen = LoadGenerator(
            [net.nodes[0].rpc_addr, net.nodes[1].rpc_addr],
            rate=10,
            connections=1,
            run_id="partition1",
        )
        gen.start()
        try:
            time.sleep(1.0)
            # partition node 2: with 2/3 validators live there is no +2/3
            # quorum (2*10 = 20, need > 20): the chain must STALL
            net.partition(2)
            time.sleep(1.5)  # let in-flight rounds drain
            h_stall = max(n.height() for n in (net.nodes[0], net.nodes[1]))
            time.sleep(4.0)
            h_after = max(n.height() for n in (net.nodes[0], net.nodes[1]))
            assert h_after <= h_stall + 1, (
                f"chain advanced {h_stall}->{h_after} during a no-quorum "
                "partition: the relay did not actually sever links"
            )

            # heal: progress must resume and the partitioned node rejoin
            net.heal(2)
            net.check_progress(blocks=2, timeout=90.0)
            assert net.nodes[2].wait_height(h_after + 1, 90.0), (
                "partitioned node never caught up after heal"
            )
        finally:
            gen.stop()
        net.check_app_hash_agreement()
    finally:
        net.stop()
