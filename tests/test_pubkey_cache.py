"""Expanded-pubkey cache tests (HBM arena of Niels tables).

Reference analog: the 4096-entry expanded-pubkey LRU in
crypto/ed25519/ed25519.go:31,56 — validators recur every round, so the
decompression + table build is paid once per key, not once per launch.
Covers: cached verify == uncached verify == oracle (incl. ZIP-215 edge
lanes), LRU eviction + rebuild, malformed-key lanes, thread safety, and
the Pallas cached kernel in interpret mode.
"""

import threading

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.ops import curve, verify

from test_curve import make_batch


@pytest.fixture(autouse=True)
def fresh_cache(monkeypatch):
    cache = verify.PubkeyTableCache(capacity=64)
    monkeypatch.setattr(verify, "_PUBKEY_CACHE", cache)
    yield cache


def _edge_batch(n=12):
    """Valid lanes + corrupted sig/msg/pk + malformed + repeated keys."""
    pks, msgs, sigs = make_batch(n)
    pks[4] = pks[0]  # repeated key, different msg
    sigs[4] = ref.sign(bytes([1]) + bytes(31), msgs[4])  # wrong key now
    sigs[1] = bytes([sigs[1][0] ^ 1]) + sigs[1][1:]
    msgs[2] = b"tampered"
    pks[5] = b"short"  # malformed pubkey
    pks[6] = (2).to_bytes(32, "little")  # not on curve
    expect = [
        len(pks[i]) == 32 and ref.verify(pks[i], msgs[i], sigs[i])
        for i in range(n)
    ]
    return pks, msgs, sigs, expect


def test_cached_matches_oracle_and_uncached(fresh_cache, monkeypatch):
    pks, msgs, sigs, expect = _edge_batch()
    ok_all, bitmap = verify.verify_batch(pks, msgs, sigs)
    assert list(bitmap) == expect
    assert fresh_cache.misses > 0 and fresh_cache.hits == 0

    # second call: all hits, identical result
    _, bitmap2 = verify.verify_batch(pks, msgs, sigs)
    assert list(bitmap2) == expect
    assert fresh_cache.hits > 0

    # uncached path agrees lane for lane
    monkeypatch.setenv("COMETBFT_TPU_PUBKEY_CACHE", "0")
    _, bitmap3 = verify.verify_batch(pks, msgs, sigs)
    assert list(bitmap3) == list(bitmap)


def test_lru_eviction_and_rebuild(monkeypatch):
    cache = verify.PubkeyTableCache(capacity=8)
    monkeypatch.setattr(verify, "_PUBKEY_CACHE", cache)
    pks, msgs, sigs = make_batch(20)  # 20 distinct keys > capacity 8
    # chunk overflows the arena -> lookup declines, uncached fallback
    _, bitmap = verify.verify_batch(pks, msgs, sigs)
    assert bitmap.all()
    assert len(cache._slots) == 0  # declined: nothing half-inserted
    # fill 8, then 4 NEW keys: 4 oldest evicted, everything verifies
    _, bm = verify.verify_batch(pks[:8], msgs[:8], sigs[:8])
    assert bm.all() and len(cache._slots) == 8
    _, bm2 = verify.verify_batch(pks[8:12], msgs[8:12], sigs[8:12])
    assert bm2.all() and len(cache._slots) == 8
    # evicted keys rebuild transparently and still verify
    _, bm3 = verify.verify_batch(pks[:4], msgs[:4], sigs[:4])
    assert bm3.all()
    # mixed call: 6 resident (pinned) + 4 new — eviction must not free
    # any slot this call gathers from
    _, bm4 = verify.verify_batch(pks[:10], msgs[:10], sigs[:10])
    assert bm4.all()


def test_scratch_slot_never_aliases(fresh_cache):
    """Bucket padding lanes scatter into the scratch slot, not slot 0:
    after a 1-key build (bucket 8, 7 pad lanes) slot 0 must still hold a
    valid table."""
    pks, msgs, sigs = make_batch(1)
    _, bm = verify.verify_batch(pks, msgs, sigs)
    assert bm.all()
    pks2, msgs2, sigs2 = make_batch(3)
    _, bm2 = verify.verify_batch(
        [pks[0], pks2[1]], [msgs[0], msgs2[1]], [sigs[0], sigs2[1]]
    )
    assert bm2.all()


def test_concurrent_lookups_consistent(fresh_cache):
    pks, msgs, sigs = make_batch(24)
    errs = []

    def worker(lo, hi):
        try:
            for _ in range(3):
                _, bm = verify.verify_batch(pks[lo:hi], msgs[lo:hi], sigs[lo:hi])
                assert bm.all()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [
        threading.Thread(target=worker, args=(0, 12)),
        threading.Thread(target=worker, args=(6, 18)),
        threading.Thread(target=worker, args=(12, 24)),
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs


@pytest.mark.slow  # pallas interpret mode: minutes per launch on CPU
def test_pallas_cached_kernel_matches_xla():
    """Pallas cached ladder (interpret mode) == XLA cached ladder ==
    oracle over edge lanes, sharing one trace like test_pallas_verify."""
    import jax.numpy as jnp

    from cometbft_tpu.ops import pallas_verify

    pks, msgs, sigs, expect = _edge_batch(8)
    # build tables directly (bypassing the arena) from packed pubkeys
    arrays, host_ok = verify.pack_inputs(pks, msgs, sigs)
    table, ok_a = curve.build_pubkey_tables(
        jnp.asarray(arrays["y_a"]), jnp.asarray(arrays["sign_a"])
    )
    xla = np.asarray(
        curve.verify_kernel_cached(
            table,
            jnp.asarray(arrays["y_r"]),
            jnp.asarray(arrays["sign_r"]),
            jnp.asarray(arrays["s_nibs"]),
            jnp.asarray(arrays["kneg_nibs"]),
        )
        & ok_a
    )
    pal = np.asarray(
        pallas_verify.verify_kernel_cached(
            table,
            ok_a,
            arrays["y_r"],
            arrays["sign_r"],
            arrays["s_nibs"],
            arrays["kneg_nibs"],
            interpret=True,
        )
    )
    assert np.array_equal(xla & host_ok, pal & host_ok)
    assert list(pal & host_ok) == expect


def test_eviction_churn_with_out_of_lock_builds(monkeypatch):
    """Round-4 lock refactor: builder launches run OUTSIDE the cache
    lock, with a re-check loop when another thread evicts mid-build.
    Force that window: a tiny arena (capacity 8) + 3 threads churning
    overlapping 6-key sets (18 distinct keys > capacity), so every
    lookup both evicts and rebuilds while the others are mid-flight.
    Correctness bar: every bitmap still matches the oracle, and the
    in_use pinning holds (a thread's own keys are never redirected)."""
    cache = verify.PubkeyTableCache(capacity=8)
    monkeypatch.setattr(verify, "_PUBKEY_CACHE", cache)
    pks, msgs, sigs = make_batch(18)
    expect = [True] * 18
    errs = []

    def worker(base):
        idx = [(base * 5 + j) % 18 for j in range(6)]
        p = [pks[i] for i in idx]
        m = [msgs[i] for i in idx]
        s = [sigs[i] for i in idx]
        try:
            for _ in range(4):
                ok, bm = verify.verify_batch(p, m, s)
                assert ok and bm.all(), bm
        except Exception as e:  # pragma: no cover
            errs.append(e)

    ts = [threading.Thread(target=worker, args=(b,)) for b in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    assert cache.builds >= 1
    # arena never exceeds capacity (evictions kept up under churn)
    assert len(cache._slots) <= cache.capacity
    del expect


# --- the slot-vector memo ---------------------------------------------------
# A batch whose key column repeats (a validator set's commit checks,
# header after header) is answered from the slot vector kept for that
# column, as long as no slot has been assigned or evicted since.


def _tables_of(pks):
    """The arena's content for each key, built apart from any cache."""
    buf = np.zeros((32, verify._builder_bucket(len(pks))), np.uint8)
    for j, pk in enumerate(pks):
        buf[:, j] = np.frombuffer(pk, np.uint8)
    tables, oks = verify._cached_jits()[0](buf)
    assert np.asarray(oks)[: len(pks)].all()
    return np.asarray(tables)


def _assert_slots_hold(hit, want_tables, at):
    """Every slot of ``hit`` holds, in the arena handed out WITH it, the
    table of its own key (``want_tables[..., at[i]]``)."""
    idxs, arena, arena_ok = hit
    n = len(at)
    got = np.asarray(arena[:, :, :, np.asarray(idxs[:n], np.int32)])
    assert np.array_equal(got, want_tables[:, :, :, at])
    assert np.asarray(arena_ok)[np.asarray(idxs[:n], np.int32)].all()


def test_memo_answers_as_the_walk_does(fresh_cache):
    pks, _, _ = make_batch(12)
    pks[7] = pks[2]  # a key twice in one column
    walked = fresh_cache.lookup(pks)
    assert fresh_cache.hits == 0 and fresh_cache.misses == 12
    again = fresh_cache.lookup(pks)
    assert fresh_cache.hits == 12 and fresh_cache.builds == 1
    assert again[0] is walked[0]  # the kept vector itself, read-only
    assert not again[0].flags.writeable
    assert again[1] is walked[1] and again[2] is walked[2]
    assert list(again[0]) == [fresh_cache._slots[pk] for pk in pks]
    assert again[0].dtype == fresh_cache.idx_dtype
    # the same keys as bytearrays and memoryviews: the same column
    other = [(bytes, bytearray, memoryview)[i % 3](pk)
             for i, pk in enumerate(pks)]
    assert fresh_cache.lookup(other)[0] is walked[0]
    # another order, a prefix, a longer batch: other columns, walked
    for cut in (pks[::-1], pks[:5], pks + pks[:1]):
        got = fresh_cache.lookup(cut)
        assert got[0] is not walked[0]
        assert list(got[0]) == [fresh_cache._slots[pk] for pk in cut]
    assert fresh_cache.builds == 1


def test_memo_keeps_a_vector_per_width(fresh_cache):
    """``width`` is the launch bucket: the slots past the keys read slot
    0, and a column asked for at two widths is kept at both."""
    pks, _, _ = make_batch(12)
    wide = fresh_cache.lookup(pks, 16)
    assert wide[0].shape == (16,) and not wide[0][12:].any()
    narrow = fresh_cache.lookup(pks)
    assert narrow[0].shape == (12,)
    assert list(wide[0][:12]) == list(narrow[0])
    assert fresh_cache.lookup(pks, 16)[0] is wide[0]
    assert fresh_cache.lookup(pks)[0] is narrow[0]


def test_memo_keeps_a_handful_of_columns(fresh_cache):
    pks, _, _ = make_batch(verify._MEMO_ENTRIES + 3)
    fresh_cache.lookup(pks)
    columns = [pks[i:] for i in range(verify._MEMO_ENTRIES + 1)]
    for cut in columns:
        fresh_cache.lookup(cut)
    assert len(fresh_cache._memo) == verify._MEMO_ENTRIES
    hits = fresh_cache.hits
    fresh_cache.lookup(columns[-1])  # the newest is kept
    assert fresh_cache.hits == hits + len(columns[-1])


@pytest.mark.parametrize("change", ["build", "eviction"])
def test_memo_entry_dies_with_any_change_of_the_slots(monkeypatch, change):
    """A build (a slot assigned) or an eviction between two lookups of
    one column: the kept vector is dropped, never served, and the second
    answer is right against the NEW arena."""
    cache = verify.PubkeyTableCache(capacity=8)
    monkeypatch.setattr(verify, "_PUBKEY_CACHE", cache)
    pks, msgs, sigs = make_batch(14)
    want = _tables_of(pks)
    column = pks[:6] if change == "build" else pks[:8]
    at = list(range(len(column)))
    first = cache.lookup(column)
    assert cache.lookup(column)[0] is first[0]
    gen = cache._gen
    if change == "build":
        cache.lookup(pks[6:8])  # two free slots taken: no eviction
        assert cache.evictions == 0
    else:
        cache.lookup(pks[8:14])  # six of the column's keys evicted
        assert cache.evictions == 6
    assert cache._gen > gen and not any(
        kept == b"".join(column) for kept, *_ in cache._memo)
    second = cache.lookup(column)
    assert second[0] is not first[0]
    assert list(second[0]) == [cache._slots[pk] for pk in column]
    _assert_slots_hold(second, want, at)
    if change == "build":
        assert list(second[0]) == list(first[0])  # nothing moved
        assert second[1] is not first[1]  # a later arena all the same
    else:
        assert cache.builds == 3  # the evicted keys were built again
    # and the launch against them verifies
    n = len(column)
    ok, bits = verify.verify_batch(column, msgs[:n], sigs[:n])
    assert ok and bits.all()


def test_set_served_from_the_memo_is_not_the_oldest_by_accident(monkeypatch):
    """Lookups answered from the memo touch no LRU entry, so the kept
    columns' keys are touched before an eviction picks its victim: the
    set checked last stays, the one not seen since goes."""
    cache = verify.PubkeyTableCache(capacity=8)
    monkeypatch.setattr(verify, "_PUBKEY_CACHE", cache)
    pks, _, _ = make_batch(12)
    served, idle = pks[:4], pks[4:8]
    cache.lookup(served)
    cache.lookup(idle)  # by the LRU alone, ``served`` is now the older
    for _ in range(3):
        cache.lookup(served)  # from the memo
    assert cache.hits == 12
    cache.lookup(pks[8:12])  # four new keys: four victims
    assert cache.evictions == 4
    assert all(pk in cache._slots for pk in served)
    assert not any(pk in cache._slots for pk in idle)


def test_memo_under_churn_never_hands_out_a_foreign_slot(monkeypatch):
    """8 threads alternating two columns while a third party churns the
    arena (capacity 16 against 42 keys): every answer's slots hold, in
    the arena handed out with them, the tables of the keys asked for."""
    cache = verify.PubkeyTableCache(capacity=16)
    monkeypatch.setattr(verify, "_PUBKEY_CACHE", cache)
    pks, _, _ = make_batch(42)
    want = _tables_of(pks)
    columns = [list(range(0, 6)), list(range(3, 9))]
    errs, stop = [], threading.Event()

    def worker(k):
        try:
            for turn in range(12):
                at = columns[(k + turn) % 2]
                hit = cache.lookup([pks[i] for i in at], 8)
                assert hit is not None
                _assert_slots_hold(hit, want, at)
        except Exception as e:  # pragma: no cover
            errs.append(e)

    def churn():
        try:
            turn = 0
            while not stop.is_set():
                at = [9 + (turn * 5 + j) % 33 for j in range(6)]
                hit = cache.lookup([pks[i] for i in at])
                if hit is not None:
                    _assert_slots_hold(hit, want, at)
                turn += 1
        except Exception as e:  # pragma: no cover
            errs.append(e)

    churner = threading.Thread(target=churn)
    churner.start()
    ts = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    stop.set()
    churner.join()
    assert not errs, errs
    assert cache.evictions > 0 and cache.hits > 0
    assert len(cache._slots) <= cache.capacity


def test_builds_and_give_ups_are_counted(fresh_cache):
    """ops_pubkey_tables_built_total counts the keys of each builder launch
    (not its bucket's padding) and the table_build phase times it; a lookup
    that gives up counts its lanes as "uncached" in
    ops_pubkey_lookup_lanes_total."""
    from cometbft_tpu.libs import metrics as libmetrics

    m = libmetrics.NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        pks, _msgs, _sigs = make_batch(5)
        assert fresh_cache.lookup(pks) is not None
        assert m.pubkey_tables_built_total.value() == 5
        phase = m.verify_phase_seconds.labels("table_build", "arena")
        assert phase._n == 1
        assert fresh_cache.lookup(pks) is not None  # all resident
        assert m.pubkey_tables_built_total.value() == 5
        lanes = m.pubkey_lookup_lanes_total.labels
        assert lanes("uncached").value() == 0
        too_many = [i.to_bytes(32, "little") for i in range(65)]
        assert fresh_cache.lookup(too_many) is None
        assert lanes("uncached").value() == 65
        assert m.pubkey_tables_built_total.value() == 5
    finally:
        libmetrics.pop_node_metrics(m)
