"""Block sync of a mixed ed25519 + sr25519 chain, against the benchmark's
plain reference (benchmark/reference/blocksync_ref: its own sign-bytes, the
``cryptography`` wheel for ed25519 lanes, sr25519_ref for sr25519 lanes, the
block-sync walk; nothing of the program).

A fresh node built as ``cmd start`` builds it catches a 16-validator chain
(8 + 8) up through the real reactor and pool, from scripted peers of which
two serve an altered block each; the sync loop waits on the pool's news,
not on a sleep; the sr25519 and mixed verifiers' phases are spans that nest
as ops/verify's do, and the ed25519 path's spans are as they were.
"""

from __future__ import annotations

import random
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from benchmark.drivers import blocksync_catchup, sync_script
from benchmark.harness import chain as rawchain
from benchmark.harness import counters as bench_counters
from benchmark.harness import spec
from benchmark.reference import blocksync_ref as ref
from benchmark.reference import sr25519_ref
from cometbft_tpu.blocksync import reactor as bs_reactor
from cometbft_tpu.blocksync.pool import BlockPool
from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import host_batch
from cometbft_tpu.crypto import sr25519 as prog_sr
from cometbft_tpu.crypto.keys import Ed25519PrivKey
from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import trace as libtrace

SEED = 2147499001


class _NoTracer:
    enabled = False

    def start(self):
        pass

    def stop(self):
        pass


@pytest.fixture(scope="module")
def synced():
    """One catch-up of a 12-height chain (16 validators, 8 + 8), faults at
    heights 5 (an ed25519 lane) and 8 (an sr25519 lane); the driver's
    pools are threads here."""
    cell = spec.load_cell("mixed4096-catchup", rehearsal=True)
    # 2 warm + 2 spare + 8 scripted = 12 heights for a 1 s window
    cell.mix.update(knee_sigs_per_s=72, list_over_knee=3, warmup_heights=2,
                    fault_offsets=[3, 6])
    saved = rawchain.spawn_pool
    rawchain.spawn_pool = lambda: ThreadPoolExecutor(4)
    d = blocksync_catchup.Driver(cell, SEED, _NoTracer())
    try:
        d.setup(1.0)
        window = d.run_window(1.0)
        window["checks"] = d.check(window, "", _Ctx())
        window["store"] = {
            h: d.node.block_store.load_block_meta(h) for h in range(1, 12)}
        window["requests"] = list(d.net.requests)
        yield d, window
    finally:
        rawchain.spawn_pool = saved
        d.close()


class _Ctx:
    counters: dict = {}


def test_catchup_matches_the_walk(synced):
    d, w = synced
    assert d.n_heights == 12
    # every block but the tip's last (it has no successor) applied, and
    # each is the script's
    assert w["notes"]["tip"] == 11
    for h in range(1, 12):
        meta = w["store"][h]
        assert meta.block_id == d.script[h].block_id
        assert meta.header.app_hash == d.script[h].app_hash
    checks = w["checks"]
    for name in ("walk_mismatches", "stored_block_or_commit_faults",
                 "hand_faults", "dispatch_faults"):
        assert checks[name]["value"] == 0, (name, w["notes"])


def test_each_altered_lane_refused_and_redone(synced):
    d, w = synced
    notes = w["notes"]
    assert [f["scheme"] for f in d.plan] == [ref.ED, ref.SR]
    # both altered blocks were refused by their light check, each pair
    # (x - 1, x) once, and both heights were asked for again
    assert notes["altered_refused"] == {5: True, 8: True}
    assert sorted(h for h, _p1, _p2 in notes["walk_refused"]) == [4, 7]
    asked: dict = {}
    for _t, h, _peer in w["requests"]:
        asked[h] = asked.get(h, 0) + 1
    for x in (5, 8):
        assert asked[x - 1] >= 2 and asked[x] >= 2
    assert notes["removed"] == notes["walk_removed"] != []


def test_per_lane_verdicts_match_the_reference(synced):
    """The program's mixed verifier and the plain oracles give the same
    verdict lane by lane on a commit of the chain with both altered lanes
    in it."""
    d, _w = synced
    h = 6
    n = len(d.pubkeys)
    sigs = list(d.script[h].sigs)
    stamps = sync_script.commit_timestamps(h, n)
    bad = {f["lane"]: f["altered_sig"] for f in d.plan}
    # the altered signatures were made over another commit: any lane
    # carrying one fails
    for lane, sig in bad.items():
        sigs[lane] = sig
    lanes = ref.commit_lanes(d.chain_id, h, d.script[h].plain_block(),
                             d.schemes, d.pubkeys, stamps, sigs, n)
    want = ref.verify_lanes(lanes)
    bv = cbatch.create_commit_batch_verifier(d.vals)
    assert isinstance(bv, cbatch.MixedBatchVerifier)
    bv.add_many([v.pub_key for v in d.vals.validators],
                [m for _s, _p, m, _sig in lanes], sigs)
    ok, bits = bv.verify()
    assert not ok and list(bits) == want
    assert sorted(i for i, b in enumerate(want) if not b) == sorted(bad)


def test_sync_loop_waits_on_news_not_a_sleep(monkeypatch):
    """The pool routine, idle, waits on the pool's news: a block's arrival
    wakes it at once, and nothing on the path sleeps."""

    class _Store:
        def height(self):
            return 0

        def base(self):
            return 0

    class _State:
        chain_id = "wake"

    r = bs_reactor.BlocksyncReactor(_State(), None, _Store(), True)
    applied = []

    def apply_first(first, _ext, _second):
        applied.append((time.monotonic(), first.header.height))
        r.pool.pop_request()

    real_sleep, slept = time.sleep, []

    def watched_sleep(s):
        if threading.current_thread() is th:
            slept.append(s)
        real_sleep(s)

    monkeypatch.setattr(r, "_apply_first", apply_first)
    monkeypatch.setattr(bs_reactor.time, "sleep", watched_sleep)
    r.pool.set_peer_range("p", 1, 100)
    th = threading.Thread(target=r._pool_routine, daemon=True)
    th.start()
    try:
        time.sleep(0.3)  # the loop has made its requests and waits
        assert not applied
        t0 = time.monotonic()
        r.pool.add_block("p", _Block(1))
        r.pool.add_block("p", _Block(2))
        deadline = t0 + 5
        while not applied and time.monotonic() < deadline:
            time.sleep(0.001)
        assert applied and applied[0][1] == 1
        # well inside the loop's 1 s switch cadence: the arrival woke it
        assert applied[0][0] - t0 < 0.5
    finally:
        r.quit_event().set()
        r.pool._news.set()
        th.join(timeout=5)
    assert not th.is_alive()
    assert slept == []


class _Block:
    def __init__(self, height):
        class H:
            pass

        self.header = H()
        self.header.height = height


def test_refused_sr25519_lane_builds_no_key_table(monkeypatch):
    """An sr25519 lane whose R does not decode has no key: the launch
    reads a live lane's table for it and the arena builds nothing."""
    from cometbft_tpu.ops import verify as ov

    monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", 2)
    privs = _keys(2, 3, 61)
    bv = cbatch.MixedBatchVerifier()
    for i, pv in enumerate(privs):
        msg = b"lane %d" % i
        sig = pv.sign(msg)
        if i == 4:
            sig = b"\xff" * 32 + sig[32:]  # not a ristretto encoding
        bv.add(pv.pub_key(), msg, sig)
    ok, bits = bv.verify()
    assert not ok and bits == [True, True, True, True, False]
    assert ov._PUBKEY_CACHE.missing([b""]) == 1


def test_pool_news_is_armed_before_a_step():
    """News that lands between arm_wait and wait is not lost; arm_wait
    forgets what came before it."""
    pool = BlockPool(1, send_request=lambda h, p: None)
    pool.set_peer_range("p", 1, 5)
    pool.make_requests()
    pool.arm_wait()
    assert not pool.wait(0.01)
    pool.add_block("p", _Block(1))
    t0 = time.monotonic()
    assert pool.wait(2.0) and time.monotonic() - t0 < 0.5
    pool.arm_wait()
    pool.redo_request(1)
    assert pool.wait(0.01)
    pool.arm_wait()
    pool.remove_peer("p")
    assert pool.wait(0.01)


# --- spans: the sr25519 and mixed verifiers as ops/verify's phases -------


def _keys(n_ed, n_sr, salt):
    return ([Ed25519PrivKey.from_seed(bytes([salt + i]) * 32)
             for i in range(n_ed)]
            + [Sr25519PrivKey.from_seed(bytes([salt + 100 + i]) * 32)
               for i in range(n_sr)])


def _traced_verify(bv, privs, monkeypatch):
    """One traced verify on this thread: the ring's records of this
    thread alone (a node another test left running in this worker may
    still write to the process's ring)."""
    monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", 2)
    for i, pv in enumerate(privs):
        msg = b"lane %d" % i
        bv.add(pv.pub_key(), msg, pv.sign(msg))
    libtrace.enable(ring=4096)
    libtrace.reset()
    try:
        ok, _bits = bv.verify()
        me = threading.current_thread().name
        return ok, [r for r in libtrace.ring_dump() if r.get("thread") == me]
    finally:
        libtrace.disable()


def _spans(ring):
    return {r["span"]: r for r in ring if r.get("kind") == "span"}


@pytest.mark.parametrize("kind", ["mixed", "sr25519"])
def test_sr25519_and_mixed_phases_nest_as_spans(kind, monkeypatch):
    if kind == "mixed":
        privs, bv, backend = _keys(3, 3, 1), cbatch.MixedBatchVerifier(), \
            "mixed-tpu"
    else:
        privs, bv, backend = _keys(0, 5, 21), cbatch.Sr25519BatchVerifier(), \
            "sr25519-tpu"
    ok, ring = _traced_verify(bv, privs, monkeypatch)
    assert ok
    spans = _spans(ring)
    by_name: dict = {}
    for s in spans.values():
        by_name.setdefault(s["name"], []).append(s)
    pack, = by_name["verify.pack"]
    prep, = by_name["verify.sr_prep"]
    disp, = by_name["verify.dispatch"]
    read, = by_name["verify.readback"]
    wait, = by_name["verify.kernel_wait"]
    n_sr = sum(1 for p in privs if isinstance(p, Sr25519PrivKey))
    assert (pack["backend"], pack["ed_lanes"], pack["sr_lanes"]) == (
        backend, len(privs) - n_sr, n_sr)
    assert prep["parent"] == pack["span"] and prep["lanes"] == n_sr
    assert (disp["backend"], disp["lanes"]) == (backend, len(privs))
    assert read["backend"] == backend and wait["parent"] == read["span"]
    assert "parent" not in pack and "parent" not in disp
    assert not [r for r in ring if r.get("kind") == "event"
                and r["name"].startswith("verify.")]
    # the roofline reader counts the dispatch span's lanes
    monkeypatch.setattr(libtrace, "ring_dump", lambda: ring)
    monkeypatch.setattr(libtrace, "enabled", lambda: True)
    got = bench_counters._spans()
    assert got[f"spans.verify.dispatch.{backend}.lanes"] == len(privs)


def test_ed25519_spans_unchanged(monkeypatch):
    """The ed25519 device path keeps its span names, fields and
    histogram series; its keys are new to the (fresh) key arena, so the
    pack holds one builder launch."""
    from cometbft_tpu.ops import verify as ov

    monkeypatch.setattr(ov, "_PUBKEY_CACHE", ov.PubkeyTableCache(64))
    privs = _keys(6, 0, 41)
    ok, ring = _traced_verify(cbatch.Ed25519BatchVerifier(), privs,
                              monkeypatch)
    assert ok
    spans = _spans(ring)
    shape = sorted((s["name"], tuple(sorted(
        k for k in s if k not in ("ts", "kind", "name", "thread", "span",
                                  "parent", "root", "start_ns", "dur_ns",
                                  "cpu_ns"))))
        for s in spans.values())
    assert shape == [
        ("verify.dispatch", ("arena", "backend", "lanes")),
        ("verify.kernel_wait", ("backend", "lanes")),
        ("verify.pack", ("arena", "backend", "lanes", "path", "slots")),
        ("verify.readback", ("arena", "backend", "lanes")),
        ("verify.table_build", ("backend", "keys")),
    ]
    assert {s["backend"] for s in spans.values()} == {"ed25519-tpu",
                                                      "arena"}
    text = libmetrics.node_metrics().registry.render()
    assert ('crypto_verify_phase_seconds_count{phase="pack",'
            'backend="ed25519-tpu"}') in text
    assert 'phase="sr_prep",backend="ed25519-tpu"' not in text


# --- the plain sr25519 oracle against the program's pure-Python verifier --

# published: merlin's protocol test vector; RFC 9496's generator
# multiples; the schnorrkel signature of "this is a message" under the
# substrate context that sr25519-crust and go-schnorrkel test against
MERLIN = ("test protocol", "some label", "some data",
          "d5a21972d0d5fe320c0d263fac7fffb8145aa640af6e9bca177c03c7efcf0615")
RISTRETTO_B = [
    "e2f2ae0a6abc4e71a884a961c500515f58e30b6aa582dd8db6a65945e08d2d76",
    "6a493210f7499cd17fecb510ae0cea23a110e8d5b901f8acadd3095c73a3b919",
    "94741f5d5d52755ece4f23f044ee27d5d1ea1e2bd196b462166b16152a9d0259",
]
SCHNORRKEL = (
    "46ebddef8cd9bb167dc30878d7113b7e168e6f0646beffd77d69d39bad76b47a",
    b"this is a message",
    "4e172314444b8f820bb54c22e95076f220ed25373e5c178234aa6c211d2927124"
    "4b947e3ff3418ff6b45fd1df1140c8cbff69fc58ee6dc96df70936a2bb74b82",
)


@pytest.fixture
def pure_python(monkeypatch):
    """The program's sr25519 without its native engine: the pure-Python
    transcript, decode and scalar multiplications."""
    monkeypatch.setattr(host_batch, "_load", lambda: None)


def test_reference_on_published_vectors(pure_python):
    t = sr25519_ref.Transcript(MERLIN[0].encode())
    t.append_message(MERLIN[1].encode(), MERLIN[2].encode())
    assert t.challenge_bytes(b"challenge", 32).hex() == MERLIN[3]
    pt = sr25519_ref.IDENTITY
    for want in RISTRETTO_B:
        pt = sr25519_ref.point_add(pt, sr25519_ref.BASE)
        assert sr25519_ref.ristretto_encode(pt).hex() == want
        assert sr25519_ref.ristretto_decode(bytes.fromhex(want)) is not None
    pk, msg, sig = bytes.fromhex(SCHNORRKEL[0]), SCHNORRKEL[1], \
        bytes.fromhex(SCHNORRKEL[2])
    assert sr25519_ref.verify(pk, msg, sig)
    assert prog_sr.verify(pk, msg, sig)
    assert not sr25519_ref.verify(pk, msg + b"!", sig)
    assert not prog_sr.verify(pk, msg + b"!", sig)


def _altered(rng, sig: bytes) -> bytes:
    out = bytearray(sig)
    out[rng.randrange(64)] ^= 1 << rng.randrange(8)
    return bytes(out)


@pytest.mark.parametrize("native", [False, True])
def test_reference_agrees_with_the_program_on_seeded_lanes(native,
                                                           monkeypatch):
    if not native:
        monkeypatch.setattr(host_batch, "_load", lambda: None)
    rng = random.Random(SEED)
    agree = 0
    for i in range(12):
        pv = Sr25519PrivKey.from_seed(rng.randbytes(32))
        pk = pv.pub_key().data
        msg = rng.randbytes(rng.randrange(1, 160))
        sig = pv.sign(msg)
        lanes = [(pk, msg, sig), (pk, msg, _altered(rng, sig)),
                 (pk, msg + b"\x00", sig)]
        for p, m, s in lanes:
            want = sr25519_ref.verify(p, m, s)
            assert prog_sr.verify(p, m, s) == want
            agree += 1
        assert sr25519_ref.verify(pk, msg, sig)
    assert agree == 36


def test_reference_reads_keys_the_driver_makes():
    """The set-up's sr25519 keys and nonce points, from the program's
    native base multiplication, decode in the plain reference to the
    points its own scalar multiplication gives."""
    for key in range(3):
        x = sync_script.sr_scalar(SEED, key)
        enc = blocksync_catchup._ristretto_base_mult(x)
        want = sr25519_ref.ristretto_encode(sr25519_ref.double_scalar_mult(
            x, sr25519_ref.BASE, 0, sr25519_ref.BASE))
        assert enc == want
