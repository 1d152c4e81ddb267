"""The two routes of ops/verify — cached arena and uncached bytes — held
to crypto/ed25519_ref on the same inputs, and the fault discipline of
the one launcher both go through (Pallas first where the rule wants it,
XLA after a Pallas fault at trace time or at materialisation, an XLA
fault propagates).

The Pallas side is driven on the CPU by handing the launcher a program
that raises: an interpret-mode launch is what test_pallas_verify.py
marks slow, and what is under test here is the launcher, not Mosaic.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np
import pytest

from cometbft_tpu.crypto import ed25519_ref as ref
from cometbft_tpu.libs import accel as libaccel
from cometbft_tpu.ops import verify as ov

ROUTES = {"cached": ("1", "verify_cached"), "uncached": ("0", "verify")}


def _signed_batch(n, seed, n_keys=None):
    seeds = [
        bytes([seed]) + i.to_bytes(4, "little") + bytes(27)
        for i in range(n_keys or n)
    ]
    pks, msgs, sigs = [], [], []
    for i in range(n):
        sd = seeds[i % len(seeds)]
        m = b"msg-%d-%d" % (seed, i)
        pks.append(ref.pubkey_from_seed(sd))
        msgs.append(m)
        sigs.append(ref.sign(sd, m))
    return pks, msgs, sigs


def _flip(sig: bytes, byte: int, bit: int) -> bytes:
    out = bytearray(sig)
    out[byte] ^= bit
    return bytes(out)


def _undecodable_r() -> bytes:
    for y in range(2, 300):
        enc = y.to_bytes(32, "little")
        if ref.decompress(enc) is None:
            return enc
    raise AssertionError("no undecodable y under 300")


def _all_valid():
    return _signed_batch(13, seed=1), []


def _shared_keys():
    # 3 distinct keys across 20 lanes: one arena slot serves many lanes
    return _signed_batch(20, seed=2, n_keys=3), []


def _one_invalid():
    pks, msgs, sigs = _signed_batch(9, seed=3)
    sigs[4] = _flip(sigs[4], 2, 0x40)
    return (pks, msgs, sigs), [4]


def _wrong_message():
    pks, msgs, sigs = _signed_batch(8, seed=4)
    msgs[0] = b"tampered"
    return (pks, msgs, sigs), [0]


def _undecodable():
    pks, msgs, sigs = _signed_batch(8, seed=5)
    sigs[3] = _undecodable_r() + sigs[3][32:]
    return (pks, msgs, sigs), [3]


def _malformed_lane():
    pks, msgs, sigs = _signed_batch(8, seed=6)
    sigs[2] = b"short"
    return (pks, msgs, sigs), [2]


def _noncanonical_s():
    pks, msgs, sigs = _signed_batch(8, seed=7)
    s = int.from_bytes(sigs[1][32:], "little") + ref.L
    sigs[1] = sigs[1][:32] + s.to_bytes(32, "little")
    return (pks, msgs, sigs), [1]


def _empty():
    return ([], [], []), []


def _single_lane():
    return _signed_batch(1, seed=8), []


def _mixed_validity():
    pks, msgs, sigs = _signed_batch(40, seed=9, n_keys=5)
    for i in (7, 31):
        sigs[i] = _flip(sigs[i], 40, 1)
    return (pks, msgs, sigs), [7, 31]


def _malformed_key():
    # a key of the wrong length never reaches the arena's builder as a
    # point: the lane is rejected on the host on both routes
    pks, msgs, sigs = _signed_batch(8, seed=10)
    pks[5] = pks[5][:31]
    return (pks, msgs, sigs), [5]


CASES = {
    "all_valid": _all_valid,
    "shared_keys": _shared_keys,
    "one_invalid_attributed": _one_invalid,
    "wrong_message": _wrong_message,
    "undecodable_r": _undecodable,
    "malformed_lane": _malformed_lane,
    "noncanonical_s": _noncanonical_s,
    "empty": _empty,
    "single_lane": _single_lane,
    "large_mixed_validity": _mixed_validity,
    "malformed_key": _malformed_key,
}


@lru_cache(maxsize=None)
def _case(name):
    """(lanes, the lanes the case broke, the oracle's verdict per lane):
    built once, shared by both routes."""
    (pks, msgs, sigs), bad = CASES[name]()
    oracle = [ref.verify(p, m, s) for p, m, s in zip(pks, msgs, sigs)]
    return (pks, msgs, sigs), bad, oracle


@pytest.fixture
def counters(monkeypatch):
    """Fresh dispatch counters and an unbroken Pallas for one test."""
    monkeypatch.setattr(ov, "_LAUNCHES", {})
    monkeypatch.setattr(ov, "_FAULTS", {"pallas": 0, "prestage": 0})
    monkeypatch.setattr(ov, "_PALLAS_BROKEN", False)


@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("name", list(CASES))
def test_route_matches_the_oracle(monkeypatch, counters, name, route):
    cache, prefix = ROUTES[route]
    monkeypatch.setenv("COMETBFT_TPU_PUBKEY_CACHE", cache)
    monkeypatch.setenv("COMETBFT_TPU_SHARD", "0")
    (pks, msgs, sigs), bad, oracle = _case(name)
    assert [i for i, ok in enumerate(oracle) if not ok] == bad
    ok, bitmap = ov.verify_batch(pks, msgs, sigs)
    assert list(bitmap) == oracle and len(bitmap) == len(pks)
    assert ok is all(oracle)
    disp = ov.dispatch_counters()
    # one launch on this route's XLA program (the CPU has no Pallas),
    # none where there was nothing to launch
    assert sum(disp["launches"].values()) == (1 if pks else 0)
    assert all(k.startswith(prefix + ".xla") for k in disp["launches"])
    assert disp["faults"] == {"pallas": 0, "prestage": 0}


# ------------------------------------------------- the launcher's faults


class _Boom(RuntimeError):
    pass


class _FaultyOut:
    """A launch's output whose kernel faults on the device: the fault
    surfaces only when the result is waited for."""

    def copy_to_host_async(self):
        pass

    def block_until_ready(self):
        raise _Boom("device fault at materialisation")


class _FaultyKernel:
    def __init__(self, kernel: str, when: str):
        self.kernel, self.when = kernel, when

    def __call__(self, *args):
        if self.when == "launch":
            raise _Boom("mosaic balked at trace time")
        return _FaultyOut()


@pytest.fixture
def programs(monkeypatch, counters):
    """Hands the launcher a faulty program in place of the named ones
    and records what it fetched; the rest are the real jits."""
    fetched: list[tuple] = []
    faulty: dict[str, str] = {}
    real = ov._jitted_kernel

    def getter(route, which, grid=None):
        fetched.append((route, which, grid))
        tracked = real(route, which, grid)
        if which in faulty:
            return _FaultyKernel(tracked.kernel, faulty[which])
        return tracked

    monkeypatch.setattr(ov, "_jitted_kernel", getter)
    monkeypatch.setenv("COMETBFT_TPU_SHARD", "0")
    return fetched, faulty


def _claim_accelerator(monkeypatch):
    monkeypatch.setattr(
        libaccel, "accelerator_backend", lambda required=False: True
    )
    monkeypatch.setattr(ov, "_PALLAS_MIN_LANES", 8)


@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("when", ["launch", "materialize"])
def test_pallas_fault_is_served_by_xla_once(
    monkeypatch, programs, route, when
):
    fetched, faulty = programs
    cache, prefix = ROUTES[route]
    monkeypatch.setenv("COMETBFT_TPU_PUBKEY_CACHE", cache)
    _claim_accelerator(monkeypatch)
    faulty["pallas"] = when
    (pks, msgs, sigs), _bad, oracle = _case("one_invalid_attributed")
    ok, bitmap = ov.verify_batch(pks, msgs, sigs)
    assert list(bitmap) == oracle and not ok
    disp = ov.dispatch_counters()
    assert disp["faults"] == {"pallas": 1, "prestage": 0}
    assert disp["pallas_broken"] == ["pallas"]
    # a fault at trace time served nothing; one that surfaces at
    # materialisation had been launched (and counted) before it showed
    want = {prefix + ".xla.g16": 1}
    if when == "materialize":
        want[prefix + ".pallas.g16"] = 1
    assert disp["launches"] == want
    assert fetched == [(prefix, "pallas", 16), (prefix, "xla", 16)]
    # Pallas is retired for the process: the next launch asks for XLA
    # alone, and nothing more is counted as a fault
    del fetched[:]
    ok, bitmap = ov.verify_batch(pks, msgs, sigs)
    assert list(bitmap) == oracle
    assert fetched == [(prefix, "xla", 16)]
    assert ov.dispatch_counters()["faults"]["pallas"] == 1


@pytest.mark.parametrize("route", list(ROUTES))
def test_retry_launches_the_host_rows_it_kept(monkeypatch, programs, route):
    """A fault at materialisation comes after the launch consumed (on
    the chip: donated) its device copy; the retry launches the caller's
    host arrays, which must be what they were."""
    fetched, faulty = programs
    _cache, prefix = ROUTES[route]
    _claim_accelerator(monkeypatch)
    faulty["pallas"] = "materialize"
    (pks, msgs, sigs), _bad, oracle = _case("wrong_message")
    buf, host_ok = ov.pack_bytes(pks, msgs, sigs)
    before = buf.copy()
    if route == "cached":
        idxs, arena, arena_ok = ov._PUBKEY_CACHE.lookup(pks)
        rows = np.ascontiguousarray(buf[32:])
        finish = ov.verify_rsk_async(rows, idxs, arena, arena_ok, 8)
    else:
        rows = buf
        finish = ov.verify_bytes_async(rows, 8)
    assert ov.dispatch_counters()["faults"]["pallas"] == 0  # not yet seen
    assert list(finish() & host_ok) == oracle
    assert (rows == before[-rows.shape[0]:]).all()
    assert ov.dispatch_counters()["pallas_broken"] == ["pallas"]
    assert fetched == [(prefix, "pallas", 8), (prefix, "xla", 8)]


@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("when", ["launch", "materialize"])
def test_xla_fault_propagates(monkeypatch, programs, route, when):
    fetched, faulty = programs
    cache, prefix = ROUTES[route]
    monkeypatch.setenv("COMETBFT_TPU_PUBKEY_CACHE", cache)
    faulty["xla"] = when
    (pks, msgs, sigs), _bad, _oracle = _case("single_lane")
    with pytest.raises(_Boom):
        ov.verify_batch(pks, msgs, sigs)
    disp = ov.dispatch_counters()
    assert disp["faults"] == {"pallas": 0, "prestage": 0}
    assert disp["pallas_broken"] == []
    assert fetched == [(prefix, "xla", 8)]  # no second attempt
