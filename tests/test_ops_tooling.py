"""Ops tooling tests: deadlock-detection tier (libs/sync), pprof server
(libs/pprof), debug dump/kill CLI (cmd debug-*).

Reference analogs: libs/sync/deadlock.go (go-deadlock build tag),
node/node.go:651 startPprofServer, cmd/cometbft/commands/debug/.
"""

import dataclasses
import io
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from cometbft_tpu.libs import pprof as pprof_mod
from cometbft_tpu.libs import sync as libsync


class TestDeadlockTier:
    def test_disabled_returns_profiled_then_plain_locks(self, monkeypatch):
        # with diagnostics off the factories hand out the contention-
        # profiled production tier (libs/lockprof; constructed even
        # while recording is off so a later enable() sees every lock)…
        libsync.disable()
        m = libsync.Mutex()
        assert type(m).__name__ == "_ProfiledMutex"
        r = libsync.RLock()
        with r:
            with r:  # reentrant
                pass
        # …and the COMETBFT_TPU_LOCKPROF=0 kill switch strips the
        # engine back to raw threading primitives
        monkeypatch.setenv("COMETBFT_TPU_LOCKPROF", "0")
        m = libsync.Mutex()
        assert type(m).__name__ in ("lock", "LockType")  # raw threading.Lock
        r = libsync.RLock()
        with r:
            with r:  # reentrant
                pass

    def test_self_deadlock_detected(self):
        libsync.enable(timeout=1.0)
        try:
            m = libsync.Mutex("t.self")
            m.acquire()
            with pytest.raises(libsync.DeadlockError):
                m.acquire()
            m.release()
        finally:
            libsync.disable()

    def test_instrumented_rlock_is_reentrant(self):
        libsync.enable(timeout=1.0)
        try:
            r = libsync.RLock("t.rlock")
            with r:
                with r:
                    assert r.locked()
            assert not r.locked()
        finally:
            libsync.disable()

    def test_long_wait_reports(self, capsys):
        libsync.enable(timeout=0.3)
        try:
            m = libsync.Mutex("t.wait")
            m.acquire()

            got = {}

            def contender():
                # acquire blocks past the detection threshold, reports,
                # then succeeds once the holder releases
                m.acquire()
                got["ok"] = True
                m.release()

            t = threading.Thread(target=contender, daemon=True)
            old_err, sys.stderr = sys.stderr, io.StringIO()
            try:
                t.start()
                time.sleep(0.8)  # past the 0.3s threshold -> report
                m.release()
                t.join(2.0)
                err = sys.stderr.getvalue()
            finally:
                sys.stderr = old_err
            assert got.get("ok")
            assert "POSSIBLE DEADLOCK" in err
            assert "t.wait" in err
        finally:
            libsync.disable()

    def test_cross_thread_mutual_exclusion(self):
        libsync.enable(timeout=5.0)
        try:
            m = libsync.Mutex("t.mutex")
            counter = {"v": 0}

            def work():
                for _ in range(200):
                    with m:
                        v = counter["v"]
                        counter["v"] = v + 1

            ts = [threading.Thread(target=work) for _ in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert counter["v"] == 800
        finally:
            libsync.disable()


class TestPprofServer:
    @pytest.fixture(scope="class")
    def server(self):
        s = pprof_mod.PprofServer("127.0.0.1:0")
        s.start()
        yield s
        s.stop()

    def _get(self, server, path: str) -> str:
        url = f"http://127.0.0.1:{server.bound_port}{path}"
        with urllib.request.urlopen(url, timeout=5) as r:
            return r.read().decode()

    def test_goroutine_dump_lists_threads(self, server):
        body = self._get(server, "/debug/pprof/goroutine")
        assert "thread" in body and "MainThread" in body

    def test_heap_endpoint(self, server):
        # scraping never flips tracemalloc on (allocation tracking has
        # interpreter-wide cost); rss is always reported
        off = self._get(server, "/debug/pprof/heap")
        assert "max rss" in off and "tracemalloc off" in off
        import tracemalloc

        assert not tracemalloc.is_tracing()
        assert "started" in self._get(server, "/debug/heap/start")
        try:
            on = self._get(server, "/debug/pprof/heap")
            assert "total traced" in on
        finally:
            assert "stopped" in self._get(server, "/debug/heap/stop")
        assert not tracemalloc.is_tracing()

    def test_locks_endpoint(self, server):
        body = json.loads(self._get(server, "/debug/locks"))
        assert "deadlock_detection" in body

    def test_404(self, server):
        with pytest.raises(urllib.error.HTTPError):
            self._get(server, "/nope")


@pytest.mark.slow
class TestDebugCLI:
    def test_debug_dump_against_live_node(self, tmp_path):
        from cometbft_tpu.cmd.__main__ import main
        from cometbft_tpu.config import default_config
        from cometbft_tpu.node import Node, init_files

        from helpers import make_genesis

        _MS = 1_000_000
        cfg = default_config()
        cfg.base.home = str(tmp_path / "home")
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.rpc.laddr = "tcp://127.0.0.1:0"
        cfg.rpc = dataclasses.replace(
            cfg.rpc, pprof_laddr="tcp://127.0.0.1:0"
        )
        cfg.consensus = dataclasses.replace(
            cfg.consensus,
            timeout_propose_ns=400 * _MS,
            timeout_prevote_ns=200 * _MS,
            timeout_precommit_ns=200 * _MS,
            timeout_commit_ns=150 * _MS,
            skip_timeout_commit=False,
            create_empty_blocks=True,
        )
        init_files(cfg)
        genesis, pvs = make_genesis(1)
        n = Node(cfg, genesis, pvs[0])
        n.start()
        try:
            deadline = time.monotonic() + 20
            while (
                n.block_store.height() < 2 and time.monotonic() < deadline
            ):
                time.sleep(0.05)
            assert n.block_store.height() >= 2

            out = str(tmp_path / "bundle")
            rc = main(
                [
                    "debug-dump",
                    "--rpc-laddr",
                    n.rpc_server.bound_addr,
                    "--pprof-laddr",
                    f"127.0.0.1:{n.pprof_server.bound_port}",
                    "--output-dir",
                    out,
                    "--count",
                    "1",
                ]
            )
            assert rc == 0
            (bundle,) = os.listdir(out)
            files = set(os.listdir(os.path.join(out, bundle)))
            assert {
                "status.json",
                "net_info.json",
                "consensus_state.json",
                "goroutines.txt",
                "heap.txt",
                "locks.json",
                "devstats.json",
                "trace.json",
            } <= files
            devstats_snap = json.load(
                open(os.path.join(out, bundle, "devstats.json"))
            )
            assert "xla" in devstats_snap and "transfers" in devstats_snap
            status = json.load(
                open(os.path.join(out, bundle, "status.json"))
            )
            assert int(status["sync_info"]["latest_block_height"]) >= 2
            dump = open(
                os.path.join(out, bundle, "goroutines.txt")
            ).read()
            assert "consensus" in dump or "thread" in dump
        finally:
            n.stop()


def test_bucket_size_grid():
    """Compile buckets: powers of two plus the 3*2^k midpoints that are
    512-block multiples (the Pallas wrappers require n % 512 == 0 at or
    above one block). Mid buckets bound padding waste by 1.5x where the
    kernel is lane-proportional."""
    from cometbft_tpu.ops.verify import _CHUNK, bucket_size

    table = {
        1: 8, 8: 8, 9: 16, 12: 16, 100: 128, 513: 1024, 1000: 1024,
        1025: 1536, 1536: 1536, 1537: 2048, 2049: 3072, 3073: 4096,
        4097: 6144, 6145: 8192, 8193: 12288, 10000: 12288,
        12289: 16384, 16384: 16384,
    }
    for n, want in table.items():
        got = bucket_size(n)
        assert got == want, (n, got, want)
        assert n <= got <= _CHUNK
        # every bucket at/above one Pallas block divides into blocks
        assert got < 512 or got % 512 == 0


@pytest.mark.parametrize(
    "accelerator,lanes,faulted,want",
    [
        (True, 512, False, "pallas"),  # one Pallas block: the floor
        (True, 8192, False, "pallas"),
        (True, 256, False, "xla"),  # under a block: the small-grid jit
        (True, 8192, True, "xla"),  # Pallas has faulted in this process
        (False, 8192, False, "xla"),  # a CPU backend
        (False, 64, False, "xla"),
    ],
)
@pytest.mark.parametrize("route", ["verify", "verify_cached"])
def test_pallas_flavor_selection(
    monkeypatch, route, accelerator, lanes, faulted, want
):
    """The one rule of ops/verify: accelerator backend x bucket x
    "Pallas has faulted" -> the program a launch runs, the same on both
    routes, on the bucket's grid."""
    import numpy as np

    from cometbft_tpu.libs import accel as libaccel
    from cometbft_tpu.ops import verify as ov

    monkeypatch.setattr(
        libaccel, "accelerator_backend", lambda required=False: accelerator
    )
    monkeypatch.setattr(ov, "_PALLAS_BROKEN", faulted)
    monkeypatch.setattr(ov, "_LAUNCHES", {})
    assert ov._pallas_wanted(lanes) is (want == "pallas")
    assert ov.dispatch_counters()["pallas_broken"] == (
        ["pallas"] if faulted else []
    )

    class Out:
        def copy_to_host_async(self):
            pass

    class Program:
        kernel = "fetched"

        def __call__(self, *args):
            return Out()

    fetched = []

    def getter(*key):
        fetched.append(key)
        return Program()

    monkeypatch.setattr(ov, "_jitted_kernel", getter)
    rows = np.zeros((96, lanes), np.uint8)
    _out, which = ov._launch(route, (), (rows,))
    assert which == want
    assert fetched == [(route, want, lanes if lanes <= 256 else None)]
    assert ov.dispatch_counters()["launches"] == {"fetched": 1}
