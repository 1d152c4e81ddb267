"""The launch path of ops/verify: every launch owns its inputs (the
persistent lane staging arena that stood here went in PR 26; the file
keeps its name), the narrowed index/mask dtypes, and the small-grid jit
split. Verdict identity is the bar everywhere: the device path must
answer exactly what ``pub_key.verify_signature`` does, from any number
of threads at once.
"""

from __future__ import annotations

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
from cometbft_tpu.libs import devstats
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs.metrics import NodeMetrics
from cometbft_tpu.ops import verify as ov

pytestmark = pytest.mark.quick


def _lanes(n: int, seed: int = 1):
    pvs = [
        Ed25519PrivKey.from_seed((seed * 1000 + i).to_bytes(32, "big"))
        for i in range(n)
    ]
    msgs = [b"arena-%d-%d" % (seed, i) for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    return [pv.pub_key().data for pv in pvs], msgs, sigs


@pytest.fixture
def device_path(monkeypatch):
    """Every batch takes the device path (XLA-CPU here), unsharded."""
    monkeypatch.setenv("COMETBFT_TPU_SHARD", "0")
    monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", 2)


class TestLaunchIdentity:
    def test_device_verdicts_match_unrouted_verify(self, device_path):
        pks, msgs, sigs = _lanes(8, seed=2)
        sigs[2] = bytes(64)  # zero sig
        sigs[5] = sigs[4]  # wrong message for that key
        pubs = [Ed25519PubKey(p) for p in pks]
        oracle = [
            p.verify_signature(m, s)
            for p, m, s in zip(pubs, msgs, sigs)
        ]
        ok, bits = ov.verify_batch(pks, msgs, sigs)
        assert list(bits) == oracle
        assert ok is all(oracle)

    def test_host_rows_survive_a_launch_for_the_retry(self, device_path):
        # a launch is handed host arrays (donation consumes the device
        # copy, never the caller's numpy rows): the Pallas-fault retry
        # in materialize() launches the same rows again
        pks, msgs, sigs = _lanes(4, seed=3)
        sigs[1] = bytes(64)
        buf, host_ok = ov.pack_bytes(pks, msgs, sigs)
        idxs, arena, arena_ok = ov._PUBKEY_CACHE.lookup(pks)
        rows, before = np.ascontiguousarray(buf[32:]), buf[32:].copy()
        first = ov.verify_rsk_async(rows, idxs, arena, arena_ok, 4)()
        again = ov.verify_rsk_async(rows, idxs, arena, arena_ok, 4)()
        assert list(first) == list(again) == [True, False, True, True]
        assert (rows == before).all()


class TestLaunchAccounting:
    def test_each_launch_is_served_once_and_no_fault_kind_is_stage(
        self, device_path
    ):
        pks, msgs, sigs = _lanes(6, seed=4)
        ov.verify_batch(pks, msgs, sigs)
        c0 = ov.dispatch_counters()
        for _ in range(5):
            ov.verify_batch(pks, msgs, sigs)
        c1 = ov.dispatch_counters()
        served = {
            k: v - c0["launches"].get(k, 0)
            for k, v in c1["launches"].items()
            if v != c0["launches"].get(k, 0)
        }
        assert served == {"verify_cached.xla.g8": 5}
        assert c1["faults"] == c0["faults"]
        assert set(c1["faults"]) == {"pallas", "prestage"}

    def test_no_recompile_across_windows(self, device_path):
        pks, msgs, sigs = _lanes(6, seed=5)
        devstats.enable()
        try:
            ov.verify_batch(pks, msgs, sigs)  # warm: compiles
            ov.verify_batch(pks, msgs, sigs)
            before = devstats.compile_count()
            for _ in range(3):
                ok, bits = ov.verify_batch(pks, msgs, sigs)
                assert ok
            assert devstats.compile_count() == before, (
                "steady-state windows recompiled:\n"
                + str(devstats.snapshot()["xla"]["per_kernel_bucket"])
            )
        finally:
            devstats.disable()

    def test_transfer_reconciliation_cached_path(self, device_path):
        # the cached-arena launch counts exactly ONE h2d op per launch,
        # and its bytes are the 96 B/lane wire rows plus the NARROWED
        # uint16 slot indexes — 2 B/lane, half the old int32 lanes
        # (this is the dtype-shrink proof at launch grain)
        pks, msgs, sigs = _lanes(8, seed=6)
        assert ov._PUBKEY_CACHE.lookup(pks) is not None  # prestage
        devstats.enable()
        try:
            ov.verify_batch(pks, msgs, sigs)  # warm the jits
            c0 = devstats.counters()
            ok, _bits = ov.verify_batch(pks, msgs, sigs)
            assert ok
            c1 = devstats.counters()
            assert c1["h2d_ops"] - c0["h2d_ops"] == 1
            assert c1["h2d_bytes"] - c0["h2d_bytes"] == 96 * 8 + 8 * 2
            assert c1["d2h_ops"] - c0["d2h_ops"] == 1
            assert c1["d2h_bytes"] - c0["d2h_bytes"] == 8 // 8
        finally:
            devstats.disable()


class TestConcurrentLaunches:
    """Every launch owns its inputs, so any number of threads may launch
    one shape at once and each gets the verdicts of its own rows. (Until
    PR 26 a staging arena handed the third concurrent stager of a shape
    a buffer another thread was about to launch with.)"""

    @pytest.mark.parametrize("n_threads", [4, 8])
    def test_threads_launching_one_shape_get_their_own_verdicts(
        self, device_path, n_threads
    ):
        import threading

        pks, msgs, sigs = _lanes(8, seed=11)
        idxs, arena, arena_ok = ov._PUBKEY_CACHE.lookup(pks)
        ov.verify_batch(pks, msgs, sigs)  # compile once, on this thread
        wrong: list = []
        start = threading.Barrier(n_threads)

        def launcher(k: int) -> None:
            # thread k's rows differ from every other thread's in the
            # one lane it breaks
            mine = list(sigs)
            mine[k % 8] = bytes(64)
            want = [i != k % 8 for i in range(8)]
            buf, host_ok = ov.pack_bytes(pks, msgs, mine)
            start.wait(30)
            for _ in range(10):
                finish = ov.verify_rsk_async(
                    buf[32:], idxs, arena, arena_ok, 8
                )
                if list(finish() & host_ok) != want:
                    wrong.append(k)

        threads = [
            threading.Thread(target=launcher, args=(k,))
            for k in range(n_threads)
        ]
        faults0 = ov.dispatch_counters()["faults"]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not wrong, f"threads {sorted(set(wrong))} read foreign rows"
        assert ov.dispatch_counters()["faults"] == faults0

    def test_concurrent_verifies_of_one_shape_agree_with_the_oracle(
        self, device_path
    ):
        import threading

        pks, msgs, sigs = _lanes(8, seed=9)
        sigs[3] = bytes(64)
        want = [True] * 3 + [False] + [True] * 4
        ov.verify_batch(pks, msgs, sigs)  # compile once, on this thread
        got: list = []

        def run() -> None:
            for _ in range(5):
                got.append(list(ov.verify_batch(pks, msgs, sigs)[1]))

        threads = [threading.Thread(target=run) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert got == [want] * 20


class TestDtypeShrink:
    def test_idx_dtype_uint16_for_default_capacity(self):
        cache = ov.PubkeyTableCache()
        assert cache.idx_dtype == np.uint16
        # the scratch slot (index == capacity) must stay addressable
        assert cache.capacity <= np.iinfo(np.uint16).max

    def test_idx_dtype_widens_past_uint16(self):
        assert ov.PubkeyTableCache(capacity=1 << 16).idx_dtype == np.int32
        assert (
            ov.PubkeyTableCache(capacity=(1 << 16) - 1).idx_dtype
            == np.uint16
        )

    def test_lookup_returns_narrow_idxs_and_verifies(self):
        pks, msgs, sigs = _lanes(5, seed=7)
        hit = ov._PUBKEY_CACHE.lookup(pks)
        assert hit is not None
        idxs, arena, arena_ok = hit
        assert idxs.dtype == ov._PUBKEY_CACHE.idx_dtype
        buf, host_ok = ov.pack_bytes(pks, msgs, sigs)
        bits = ov.verify_rsk_async(buf[32:], idxs, arena, arena_ok, 5)()
        assert (bits & host_ok).all()

    def test_sha256_mask_lanes_are_uint16(self):
        from cometbft_tpu.ops import sha256 as osha

        _blocks, nblocks = osha.pack_messages([b"x" * 100, b"y"])
        assert nblocks.dtype == np.uint16
        digs = osha.sha256_many_async([b"x" * 100, b"y"])()
        import hashlib

        assert digs == [
            hashlib.sha256(b"x" * 100).digest(),
            hashlib.sha256(b"y").digest(),
        ]


class TestSmallGridSplit:
    def test_grid_selection(self):
        assert ov._small_grid(8) == 8
        assert ov._small_grid(256) == 256
        assert ov._small_grid(512) is None
        assert ov._small_grid(16384) is None

    def test_small_bucket_launch_routes_to_dedicated_jit(
        self, monkeypatch
    ):
        calls: list[tuple] = []
        real = ov._jitted_kernel

        def spy(route, which, grid=None):
            calls.append((route, which, grid))
            return real(route, which, grid)

        monkeypatch.setattr(ov, "_jitted_kernel", spy)
        pks, msgs, sigs = _lanes(4, seed=8)
        buf, host_ok = ov.pack_bytes(pks, msgs, sigs)
        bits = ov.verify_bytes_async(buf, 4)()
        assert (bits & host_ok).all()
        assert calls == [("verify", "xla", 8)], calls
        # the dedicated jit carries its own devstats kernel identity,
        # so small-window compiles/launches attribute per bucket
        assert real("verify", "xla", 8).kernel == "verify.xla.g8"
        assert real("verify", "xla", None).kernel == "verify.xla"
        assert (
            real("verify_cached", "pallas", None).kernel
            == "verify_cached.pallas"
        )



class TestKnobsRegisteredAndDocumented:
    def test_device_floor_knobs_in_registry_and_docs(self):
        import os

        from cometbft_tpu.config import ENV_KNOBS

        doc = open(
            os.path.join(os.path.dirname(__file__), "..", "docs", "perf.md")
        ).read()
        for knob in (
            "COMETBFT_TPU_COALESCE_INFLIGHT",
            "COMETBFT_TPU_HASH_INFLIGHT",
        ):
            assert knob in ENV_KNOBS, knob
            assert knob in doc, f"{knob} missing from docs/perf.md"
        # retired with the staging arena it switched (PR 26)
        assert "COMETBFT_TPU_LANE_ARENA" not in ENV_KNOBS


class TestRetiredArena:
    def test_retired_knob_changes_nothing(self, device_path, monkeypatch):
        monkeypatch.setenv("COMETBFT_TPU_LANE_ARENA", "1")
        pks, msgs, sigs = _lanes(4, seed=9)
        devstats.enable()
        try:
            ok, _ = ov.verify_batch(pks, msgs, sigs)
            assert ok
            kernels = {row["kernel"] for row in devstats.compile_log()}
            assert not [
                k for k in kernels if k.startswith("stage.")
            ]
        finally:
            devstats.disable()

    def test_devstats_sample_has_no_lane_arena_block(self, device_path):
        pks, msgs, sigs = _lanes(4, seed=10)
        ov.verify_batch(pks, msgs, sigs)
        devstats.enable()
        m = NodeMetrics()
        libmetrics.push_node_metrics(m)
        try:
            got = devstats.sample(m)
        finally:
            libmetrics.pop_node_metrics(m)
            devstats.disable()
        assert "lane_arena" not in got
        assert {"pubkey_arena", "verify_dispatch"} <= set(got)
        assert "lane_arena" not in m.registry.render()
