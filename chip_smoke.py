#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpu-bft still starts on the chip.

One process, one chip, three legs in sequence, all through the entry
points a user of the system calls:

  A. commit verification at north-star width: a 10,000-validator
     ed25519 ``ValidatorSet`` + signed ``Commit`` through
     ``types.validation.verify_commit_light`` / ``verify_commit``, one
     16,384-lane ``create_batch_verifier`` batch carrying the ZIP-215
     edge vectors, and one 4,096-lane mixed ed25519+sr25519
     ``MixedBatchVerifier`` batch;
  B. a served node: home from ``cmd init``, node from
     ``default_new_node(cfg)`` exactly as ``cmd start`` builds it,
     planes left in ``auto``, 1 KiB txs over the RPC client for >= 10
     heights, every acknowledged tx read back, every block's hashes
     recomputed with plain ``hashlib``;
  C. vote-path windows at QA width: 175 validators, prevote +
     precommit, through ``VoteSet.add_vote`` / ``add_votes_batch`` and
     a routed ``VerifyCoalescer``.

Every verdict is compared with a reference that shares no code with
``cometbft_tpu/ops`` (the ``cryptography`` wheel, the analytic ZIP-215
corpus, the pure-Python sr25519 verifier, ``hashlib``). The run fails
if any leg failed, or if a fallback that hides the device fired.

It needs a TPU: without one (or under ``JAX_PLATFORMS=cpu``) it exits
non-zero before running a leg and prints no result. ``--cpu-dry-run``
runs the same legs at tiny sizes on the CPU, Pallas in interpret mode,
for debugging; its last line carries no ``"ok"`` key and says
``"platform": "cpu"``, so it can never pass for the chip.

Wall times printed here are labelled set-up (first call: compile) or
warm and carry the device name. They are not performance claims.

The last line of stdout is one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The line before it (``summary: {...}``) and ``summary.json`` in the
output directory carry the legs, the versions and ``"claim": null``.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
import traceback

_HERE = os.path.dirname(os.path.abspath(__file__))


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument(
        "--cpu-dry-run",
        action="store_true",
        help="tiny sizes on the CPU, interpret-mode Pallas; never a pass",
    )
    ap.add_argument(
        "--legs", default="A,B,C", help="comma-separated subset of A,B,C"
    )
    ap.add_argument(
        "--out",
        default=os.path.join(_HERE, "chiprun_out", "chip_smoke"),
        help="output directory (summary.json, devstats.json)",
    )
    ap.add_argument(
        "--require-warm-cache",
        action="store_true",
        help="the second run against the same cache directory: fail "
        "if any compile of leg A (whose shapes do not depend on timing) "
        "missed the persistent cache",
    )
    return ap.parse_args(argv)


ARGS = _parse_args() if __name__ == "__main__" else _parse_args([])
DRY = ARGS.cpu_dry_run

# ---------------------------------------------------------------- device
# Before jax is imported. A missing chip must raise, not fall back.
if DRY:
    os.environ["JAX_PLATFORMS"] = "cpu"
    # The dry run has no accelerator, so the gates that read the backend
    # are pinned by their existing knobs (and the accelerator probe is
    # patched in _dry_run_patches); on the chip nothing is forced.
    os.environ["COMETBFT_TPU_PRESTAGE"] = "1"
    os.environ["COMETBFT_TPU_HASH_MIN_DEVICE_LANES"] = "2"
    os.environ["COMETBFT_TPU_HOST_THRESHOLD"] = "2"  # tiny batches -> device
else:
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")

import jax  # noqa: E402

_DEV = jax.devices()[0]
DEVICE = {
    "platform": _DEV.platform,
    "kind": _DEV.device_kind,
    "count": len(jax.devices()),
}
if not DRY and DEVICE["platform"] != "tpu":
    print(
        f"chip_smoke: no TPU (jax reports {DEVICE}); nothing was run. "
        "Debug on the CPU with --cpu-dry-run.",
        file=sys.stderr,
    )
    sys.exit(3)

import numpy as np  # noqa: E402

try:
    sys.path.insert(0, _HERE)
    from cometbft_tpu.crypto import batch as crypto_batch  # noqa: E402
    from cometbft_tpu.crypto import coalesce as crypto_coalesce  # noqa: E402
    from cometbft_tpu.crypto import host_batch  # noqa: E402
    from cometbft_tpu.libs import accel as libaccel  # noqa: E402
    from cometbft_tpu.libs import devstats  # noqa: E402
    from cometbft_tpu.ops import sha256 as osha  # noqa: E402
    from cometbft_tpu.ops import verify as ov  # noqa: E402
except ImportError as e:
    print(f"chip_smoke: the program is not here: {e!r}", file=sys.stderr)
    sys.exit(4)


def _versions() -> dict:
    import jaxlib

    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        from importlib.metadata import version

        out["libtpu"] = version("libtpu")
    except Exception:
        out["libtpu"] = None
    return out


# ------------------------------------------------------------- reporting

_T0 = time.perf_counter()
_DEVNAME = f"{DEVICE['platform']}:{DEVICE['kind']}"


def say(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


class LegFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise LegFailure(what)
    say(f"    ok: {what}")


def timed(label: str, kind: str, fn):
    """Run fn; print its wall time labelled set-up or warm."""
    t = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t
    say(f"    {label}: {dt:.3f} s ({kind}, {_DEVNAME}; not a claim)")
    return out, dt


def _dry_run_patches() -> None:
    """CPU dry run only: claim an accelerator so the auto-mode gates
    take the device path (XLA-CPU executes it), and run the routed
    Pallas launches in interpret mode from 8 lanes up."""
    libaccel.accelerator_backend = lambda required=False: True
    libaccel.accelerator_backend_live = lambda: True
    ov._PALLAS_INTERPRET = True
    ov._PALLAS_MIN_LANES = 8


# ---------------------------------------------- references (no ops code)


def oracle_ed25519(pubkey: bytes, msg: bytes, sig: bytes) -> bool:
    """The ``cryptography`` wheel (OpenSSL). Used only on lanes that
    are honestly signed or bit-flipped, where RFC 8032 and ZIP-215
    agree; the edge vectors carry their own analytic verdicts."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PublicKey,
    )

    try:
        Ed25519PublicKey.from_public_bytes(pubkey).verify(sig, msg)
        return True
    except (InvalidSignature, ValueError):
        return False


def rfc6962_root(items: list[bytes]) -> bytes:
    """Plain recursive RFC-6962 tree over ``hashlib``."""
    n = len(items)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return hashlib.sha256(b"\x00" + items[0]).digest()
    k = 1
    while k * 2 < n:
        k *= 2
    return hashlib.sha256(
        b"\x01" + rfc6962_root(items[:k]) + rfc6962_root(items[k:])
    ).digest()


def zip215_corpus():
    """The edge vectors of tests/test_zip215_conformance.py, each with
    its analytically derived verdict (read from no backend)."""
    path = os.path.join(_HERE, "tests", "test_zip215_conformance.py")
    spec = importlib.util.spec_from_file_location("_zip215_corpus", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return [
        v for v in mod.build_corpus() if len(v[1]) == 32 and len(v[3]) == 64
    ]


# ------------------------------------------------------------ seeded data


def _seed_bytes(*parts) -> bytes:
    return hashlib.sha256(
        b"|".join(str(p).encode() for p in (ARGS.seed, *parts))
    ).digest()


def make_valset(n_vals: int, tag: str):
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.types.priv_validator import MockPV
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet

    pvs = [
        MockPV(Ed25519PrivKey.from_seed(_seed_bytes(tag, i)))
        for i in range(n_vals)
    ]
    vals = ValidatorSet(
        [Validator(pv.get_pub_key(), voting_power=10) for pv in pvs]
    )
    by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
    return vals, [by_addr[bytes(v.address)] for v in vals.validators]


def block_id(tag: int):
    from cometbft_tpu.types.block import BlockID, PartSetHeader

    return BlockID(
        hash=_seed_bytes("block", tag),
        part_set_header=PartSetHeader(total=1, hash=_seed_bytes("psh", tag)),
    )


def signed_votes(chain_id, vals, pvs, height, bid, msg_type, limit=None):
    from cometbft_tpu.types.vote import Vote

    base_ns = 1_700_000_000_000_000_000
    votes = []
    for idx, (val, pv) in enumerate(zip(vals.validators, pvs)):
        if limit is not None and idx >= limit:
            break
        v = Vote(
            msg_type=msg_type,
            height=height,
            round=0,
            block_id=bid,
            timestamp_ns=base_ns + idx,
            validator_address=val.address,
            validator_index=idx,
        )
        pv.sign_vote(chain_id, v, sign_extension=False)
        votes.append(v)
    return votes


def flip(sig: bytes) -> bytes:
    return bytes([sig[0] ^ 1]) + sig[1:]


compile_ledger = devstats.compile_log


def ledger_has(kernel_prefix: str, bucket: int | None = None) -> bool:
    return any(
        r["kernel"].startswith(kernel_prefix)
        and (bucket is None or r["bucket"] == bucket)
        for r in compile_ledger()
    )


# =================================================================== leg A


def leg_a() -> dict:
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey
    from cometbft_tpu.crypto import sr25519 as sr
    from cometbft_tpu.types import canonical, validation
    from cometbft_tpu.types.block import Commit
    import dataclasses

    n_vals = 12 if DRY else 10_000
    big = 16_384  # lanes of the create_batch_verifier batch (chip)
    n_mixed = 16 if DRY else 4_096
    k_tamper = 2 if DRY else 7
    chain = "smoke-chain"
    rng = np.random.default_rng(ARGS.seed)
    out: dict = {"validators": n_vals}

    say(f"  building {n_vals} validators and two signed commits")
    vals, pvs = make_valset(n_vals, "A")
    bid7, bid8 = block_id(7), block_id(8)
    pre7 = signed_votes(chain, vals, pvs, 7, bid7, canonical.PRECOMMIT_TYPE)
    commit = Commit(
        height=7, round=0, block_id=bid7,
        signatures=[v.commit_sig() for v in pre7],
    )
    launches0 = dict(ov.dispatch_counters()["launches"])
    c0 = devstats.counters()

    # -- 1. valid commit accepts (light: +2/3 prefix; full: every lane)
    _, out["light_setup_s"] = timed(
        "verify_commit_light, first call", "set-up",
        lambda: validation.verify_commit_light(chain, vals, bid7, 7, commit),
    )
    _, out["full_setup_s"] = timed(
        "verify_commit, first call", "set-up",
        lambda: validation.verify_commit(chain, vals, bid7, 7, commit),
    )
    check(True, f"valid {n_vals}-validator commit accepted (light and full)")

    # -- 2. K seeded tampered signatures: rejected at the first bad
    # lane, and the per-lane bitmap equals the oracle's
    light_prefix = (vals.total_voting_power() * 2 // 3) // 10 + 1
    bad = sorted(
        int(i) for i in rng.choice(light_prefix, k_tamper, replace=False)
    )
    sigs = list(commit.signatures)
    for i in bad:
        sigs[i] = dataclasses.replace(sigs[i], signature=flip(sigs[i].signature))
    tampered = Commit(height=7, round=0, block_id=bid7, signatures=sigs)
    for name, fn in (
        ("verify_commit", validation.verify_commit),
        ("verify_commit_light", validation.verify_commit_light),
    ):
        try:
            fn(chain, vals, bid7, 7, tampered)
        except validation.VerificationError as e:
            check(
                f"(#{bad[0]})" in str(e),
                f"{name} rejects the tampered commit at lane {bad[0]}",
            )
        else:
            raise LegFailure(f"{name} accepted a tampered commit")
    bv = crypto_batch.create_commit_batch_verifier(vals)
    lanes = []
    for idx, cs in enumerate(tampered.signatures):
        pk = vals.validators[idx].pub_key
        sb = tampered.vote_sign_bytes(chain, idx)
        bv.add(pk, sb, cs.signature)
        lanes.append((pk.data, sb, cs.signature))
    ok_all, bitmap = bv.verify()
    want = [oracle_ed25519(*ln) for ln in lanes]
    check(
        list(bitmap) == want and not ok_all
        and [i for i, b in enumerate(bitmap) if not b] == bad,
        f"per-lane bitmap of the tampered commit equals the "
        f"cryptography-wheel oracle ({k_tamper} bad lanes: {bad})",
    )

    # -- 3. ZIP-215 edge vectors as lanes of one big ed25519 batch, so
    # they reach the device and not the sub-threshold host path
    corpus = zip215_corpus()
    if DRY:
        big = len(corpus) + n_vals + 8
    fill = big - len(corpus) - n_vals
    pre8 = signed_votes(
        chain, vals, pvs, 8, bid8, canonical.PRECOMMIT_TYPE, limit=fill
    )
    big_lanes = [(pk, m, s, exp) for _n, pk, m, s, exp in corpus]
    big_lanes += [(*ln, w) for ln, w in zip(lanes, want)]
    bad8 = set(int(i) for i in rng.choice(len(pre8), k_tamper, replace=False))
    for j, v in enumerate(pre8):
        sig = flip(v.signature) if j in bad8 else v.signature
        pk = vals.validators[v.validator_index].pub_key.data
        big_lanes.append((pk, v.sign_bytes(chain), sig, j not in bad8))
    order = rng.permutation(len(big_lanes))
    big_lanes = [big_lanes[int(i)] for i in order]
    check(len(big_lanes) == big, f"edge-vector batch has {big} lanes")

    def run_big():
        bv = crypto_batch.create_batch_verifier(Ed25519PubKey(big_lanes[0][0]))
        for pk, m, s, _exp in big_lanes:
            bv.add(Ed25519PubKey(pk), m, s)
        return bv.verify()

    (ok_all, bitmap), out["big_setup_s"] = timed(
        f"{big}-lane create_batch_verifier batch, first call", "set-up",
        run_big,
    )
    want_big = [exp for *_x, exp in big_lanes]
    miss = [i for i, (g, w) in enumerate(zip(bitmap, want_big)) if g != w]
    check(
        not miss and not ok_all,
        f"{len(corpus)} ZIP-215 edge vectors + {big - len(corpus)} commit "
        "lanes agree lane for lane with their analytic/oracle verdicts",
    )

    # -- 4. one mixed ed25519 + sr25519 batch (BASELINE config 5)
    half = n_mixed // 2
    uniq = min(64, half)
    sr_keys = [Sr25519PrivKey.from_seed(_seed_bytes("sr", i)) for i in range(uniq)]
    sr_msgs = [b"sr-lane-%d-" % i + _seed_bytes("srm", i) for i in range(uniq)]
    sr_sigs = [k.sign(m) for k, m in zip(sr_keys, sr_msgs)]
    for i in (1, uniq - 1):
        sr_sigs[i] = flip(sr_sigs[i])
    sr_want = [
        sr.verify(k.pub_key().data, m, s)
        for k, m, s in zip(sr_keys, sr_msgs, sr_sigs)
    ]
    check(
        sr_want.count(False) == len({1, uniq - 1}),
        "pure-Python sr25519 oracle rejects exactly the tampered lanes",
    )
    mixed = crypto_batch.MixedBatchVerifier()
    want_mixed = []
    for i in range(half):
        pk, m, s = lanes[i % len(lanes)]
        mixed.add(Ed25519PubKey(pk), m, s)
        want_mixed.append(want[i % len(lanes)])
        j = i % uniq
        mixed.add(sr_keys[j].pub_key(), sr_msgs[j], sr_sigs[j])
        want_mixed.append(sr_want[j])
    (ok_all, bitmap), out["mixed_setup_s"] = timed(
        f"{n_mixed}-lane MixedBatchVerifier batch, first call", "set-up",
        mixed.verify,
    )
    check(
        list(bitmap) == want_mixed and not ok_all,
        f"{n_mixed} mixed ed25519+sr25519 lanes equal the oracles "
        f"({want_mixed.count(False)} bad)",
    )

    # -- 5. the device served all of it, through the Pallas cached-arena
    # kernel, with nothing covered by a fallback
    disp = ov.dispatch_counters()
    served = {
        k: v - launches0.get(k, 0)
        for k, v in disp["launches"].items()
        if v - launches0.get(k, 0)
    }
    out["served"] = served
    say(f"    launches served: {served}")
    check(
        served and all(k.startswith("verify_cached.pallas") for k in served),
        "every verify launch was served by the Pallas cached-arena kernel "
        "(no *.xla* kernel, no uncached path, no sharded dispatch)",
    )
    check(disp["pallas_broken"] == [], "Pallas has not faulted in this process")
    check(
        not any(disp["faults"].values()),
        f"no absorbed fault (pallas/stage/prestage): {disp['faults']}",
    )
    check(
        ov._PALLAS_INTERPRET == DRY,
        "interpret mode only because the dry run asked for it" if DRY
        else "no interpret-mode kernel (interpret=False passed explicitly)",
    )
    check(ov._shard_devices() is None, "sharding is off (opt-in only)")
    if not DRY:
        for bucket in (ov.bucket_size(light_prefix), ov.bucket_size(n_vals), big):
            check(
                ledger_has("verify_cached.pallas", bucket),
                f"compile ledger: verify_cached.pallas compiled at bucket {bucket}",
            )
        check(
            not any(
                r["kernel"].startswith(("verify.", "verify_cached."))
                and ".xla" in r["kernel"]
                for r in compile_ledger()
            ),
            "compile ledger: no XLA verify kernel was compiled for leg A",
        )
    c1 = devstats.counters()
    check(
        c1["h2d_bytes"] > c0["h2d_bytes"] and c1["d2h_bytes"] > c0["d2h_bytes"],
        f"h2d/d2h counters moved: +{c1['h2d_bytes'] - c0['h2d_bytes']} B "
        f"/ +{c1['d2h_bytes'] - c0['d2h_bytes']} B",
    )
    arena = ov._PUBKEY_CACHE
    check(
        arena.missing([v.pub_key.data for v in vals.validators]) == 0
        and {d.platform for d in arena._arena.devices()} == {DEVICE["platform"]},
        f"{n_vals} expanded pubkey tables resident in the device arena "
        f"({arena._arena.nbytes / 1e6:.0f} MB incl. free slots)",
    )

    # -- 6. second identical calls: nothing builds, nothing compiles
    compiles, builds = devstats.compile_count(), arena.builds
    _, out["light_warm_s"] = timed(
        "verify_commit_light, repeat", "warm",
        lambda: validation.verify_commit_light(chain, vals, bid7, 7, commit),
    )
    _, out["full_warm_s"] = timed(
        "verify_commit, repeat", "warm",
        lambda: validation.verify_commit(chain, vals, bid7, 7, commit),
    )
    (_, bitmap2), out["big_warm_s"] = timed(
        f"{big}-lane batch, repeat", "warm", run_big
    )
    check(list(bitmap2) == want_big, "repeat verdicts identical")
    check(
        devstats.compile_count() == compiles and arena.builds == builds,
        "repeat: zero new compiles, zero builder launches "
        f"(new: {compile_ledger()[compiles:]})",
    )
    out["persistent_cache"] = devstats.cache_events()
    return out


# =================================================================== leg B


def leg_b() -> dict:
    from argparse import Namespace

    from cometbft_tpu.cmd.__main__ import _config
    from cometbft_tpu.cmd.__main__ import main as cli_main
    from cometbft_tpu.node import default_new_node
    from cometbft_tpu.rpc.client import HTTPClient

    rate = 20 if DRY else 250  # tx/s offered, open loop
    n_tx = 60 if DRY else 3_500
    tx_size = 1024  # QA-38 tx shape (BASELINE.md row 1)
    min_heights = 3 if DRY else 10
    out: dict = {"offered_tx_per_s": rate, "txs": n_tx, "tx_bytes": tx_size}

    for knob in ("COMETBFT_TPU_COALESCE", "COMETBFT_TPU_HASH"):
        check(knob not in os.environ, f"{knob} is unset: the plane is in auto")
    # stores and WAL of a few thousand 1 KiB txs: tens of MB, so not
    # under the output directory the chip tool copies back
    home = os.path.join(_HERE, ".smoke_home")
    shutil.rmtree(home, ignore_errors=True)
    check(cli_main(["--home", home, "init"]) == 0, "cmd init wrote the home")
    cfg = _config(
        Namespace(
            home=home,
            rpc_laddr="tcp://127.0.0.1:36657",
            p2p_laddr="tcp://127.0.0.1:36656",
        )
    )
    check(cfg.base.db_backend == "file", "file-backed stores (fsync on)")
    node = default_new_node(cfg)
    arena = ov._PUBKEY_CACHE
    builds0 = arena.builds
    t_start = time.perf_counter()
    node.start()
    stopped = False
    try:
        say(f"    node started in {time.perf_counter() - t_start:.1f} s")
        check(
            node.verify_coalescer is not None and node.hash_plane is not None,
            "verify coalescer and hash plane started because the chip is "
            "there (auto mode)",
        )
        val_key = node.state.validators.validators[0].pub_key.data

        rpc = HTTPClient(cfg.rpc.laddr, timeout=30.0)
        txs = []
        for seq in range(n_tx):
            key = b"smk-%d-%06d-" % (ARGS.seed, seq)
            pad = _seed_bytes("tx", seq).hex().encode() * 16
            txs.append(key + pad[: tx_size - len(key) - 8] + b"=v%06d" % seq)
        check(all(len(t) == tx_size for t in txs), f"{n_tx} seeded 1 KiB txs")

        acked: list[int] = []
        refused: list[tuple[int, str]] = []
        late = []
        lock = threading.Lock()
        t_load = time.monotonic()

        def sender(worker: int, n_workers: int) -> None:
            client = HTTPClient(cfg.rpc.laddr, timeout=30.0)
            for seq in range(worker, n_tx, n_workers):
                due = t_load + seq / rate
                now = time.monotonic()
                if now < due:
                    time.sleep(due - now)
                else:
                    late.append(now - due)
                try:
                    res = client.call(
                        "broadcast_tx_sync",
                        tx=base64.b64encode(txs[seq]).decode(),
                    )
                    with lock:
                        if res["code"] == 0:
                            acked.append(seq)
                        else:
                            refused.append((seq, str(res.get("log"))))
                except Exception as e:
                    with lock:
                        refused.append((seq, repr(e)[:100]))

        workers = [
            threading.Thread(target=sender, args=(w, 8), daemon=True)
            for w in range(8)
        ]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=n_tx / rate + 120)
        check(
            not any(w.is_alive() for w in workers), "load generator finished"
        )
        out["acked"], out["refused"] = len(acked), len(refused)
        out["generator_max_late_s"] = round(max(late, default=0.0), 3)
        check(
            len(acked) == n_tx,
            f"all {n_tx} txs acknowledged by CheckTx "
            f"(refused: {refused[:3]}; generator ran at most "
            f"{out['generator_max_late_s']} s late)",
        )

        # every acknowledged tx is read back: by hash (`tx`) for all, by
        # key (`abci_query`) for a seeded sample
        deadline = time.monotonic() + 120
        pending = set(acked)
        heights = {}
        while pending and time.monotonic() < deadline:
            for seq in sorted(pending):
                h = hashlib.sha256(txs[seq]).hexdigest()
                try:
                    res = rpc.call("tx", hash=h)
                except Exception:
                    continue
                if (
                    res["hash"].lower() == h
                    and base64.b64decode(res["tx"]) == txs[seq]
                    and res["tx_result"]["code"] == 0
                ):
                    heights[seq] = int(res["height"])
                    pending.discard(seq)
            if pending:
                time.sleep(0.5)
        check(
            not pending,
            f"every acknowledged tx read back by hash over RPC "
            f"({len(heights)} txs, missing {len(pending)})",
        )
        rng = np.random.default_rng(ARGS.seed + 1)
        sample = [int(i) for i in rng.choice(n_tx, min(200, n_tx), replace=False)]
        for seq in sample:
            key, _, value = txs[seq].partition(b"=")
            res = rpc.call("abci_query", data=key.hex())["response"]
            if res["code"] != 0 or base64.b64decode(res["value"]) != value:
                raise LegFailure(f"abci_query lost tx {seq}: {res}")
        check(True, f"{len(sample)} sampled keys read back through abci_query")

        # hold the node up until the required heights are there
        while (
            node.block_store.height() < min_heights
            and time.monotonic() < deadline
        ):
            time.sleep(0.2)
        top = node.block_store.height()
        check(top >= min_heights, f"{top} heights committed (>= {min_heights})")

        # every committed block: tx keys, data_hash and part-set header
        # recomputed with plain hashlib + the recursive RFC-6962 tree
        seen = 0
        with_txs = 0
        for h in range(1, top + 1):
            blk = node.block_store.load_block(h)
            meta = node.block_store.load_block_meta(h)
            btxs = [bytes(t) for t in blk.data.txs]
            leaves = [hashlib.sha256(t).digest() for t in btxs]
            if blk.header.data_hash != rfc6962_root(leaves):
                raise LegFailure(f"height {h}: data_hash mismatch")
            psh = meta.block_id.part_set_header
            parts = [
                bytes(node.block_store.load_block_part(h, i).bytes_)
                for i in range(psh.total)
            ]
            if psh.hash != rfc6962_root(parts):
                raise LegFailure(f"height {h}: part-set header mismatch")
            for t in btxs:
                seq = int(t.split(b"-")[2])
                if t != txs[seq] or heights.get(seq) != h:
                    raise LegFailure(f"height {h}: foreign or misplaced tx")
            seen += len(btxs)
            with_txs += bool(btxs)
        out["heights"], out["heights_with_txs"] = top, with_txs
        check(
            seen == n_tx,
            f"{top} blocks ({with_txs} carrying txs, {seen} txs): data_hash "
            "and part-set header of every block equal the hashlib reference",
        )

        hp, vc = node.hash_plane, node.verify_coalescer
        out["hash_plane"] = {
            "windows": hp.windows, "device_windows": hp.device_windows,
            "cold_buckets": hp.cold_buckets, "trips": hp.trips,
        }
        out["verify_coalescer"] = {
            "windows": vc.windows, "device_windows": vc.device_windows,
            "cold_windows": vc.cold_windows, "trips": vc.trips,
        }
        say(f"    hash plane: {out['hash_plane']}")
        say(f"    verify coalescer: {out['verify_coalescer']}")
        check(hp.device_windows > 0, "hash-plane device windows > 0")
        check(
            hp.trips == 0 and vc.trips == 0,
            "breaker trips = 0 on both planes (cold shapes were served "
            f"from host while compiling: {hp.cold_buckets} hash buckets, "
            f"{vc.cold_windows} verify windows)",
        )
        check(ledger_has("sha256.xla."), "compile ledger: sha256.xla.* compiled")
        # the FSM prestages off-thread at enter-new-round; from a cold
        # cache its builder compile can outlast the load
        t_wait = time.monotonic()
        while arena.missing([val_key]) and time.monotonic() < t_wait + 300:
            time.sleep(0.2)
        check(
            arena.builds > builds0 and arena.missing([val_key]) == 0
            and ov.dispatch_counters()["faults"]["prestage"] == 0
            and {d.platform for d in arena._arena.devices()}
            == {DEVICE["platform"]},
            "prestage_pubkeys built the validator's table on the device "
            f"(waited {time.monotonic() - t_wait:.1f} s for its builder)",
        )
        node.stop()
        stopped = True
        check(True, "node.stop() returned cleanly")
    finally:
        if not stopped:
            try:
                node.stop()
            except Exception:
                traceback.print_exc()
        shutil.rmtree(home, ignore_errors=True)
    check(osha.WARM.wait_idle(300), "hash warm-up worker idle")
    check(
        not osha.WARM.failed, f"no hash shape failed to compile: {osha.WARM.failed}"
    )
    return out


# =================================================================== leg C


def leg_c() -> dict:
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.vote_set import VoteSet

    n_vals = 12 if DRY else 175  # QA-38 validator count
    singles = 4 if DRY else 40  # votes per set admitted one by one
    rounds = 2 if DRY else 4  # rounds after the cold one
    bad_share = 0.06
    chain = "smoke-chain"
    out: dict = {"validators": n_vals}

    # The live crossover would route 175-lane windows to the host, so
    # device windows are pinned on with the existing knob.
    os.environ["COMETBFT_TPU_COALESCE_MIN_DEVICE_LANES"] = "1"
    say(
        "    COMETBFT_TPU_COALESCE_MIN_DEVICE_LANES=1: device windows are "
        "pinned on (the crossover would send QA-width windows to the host)"
    )
    vals, pvs = make_valset(n_vals, "C")
    co = crypto_coalesce.VerifyCoalescer()  # as node boot builds it
    if DRY:
        co._device_ok = lambda: True
    co.start()
    crypto_coalesce.push_active(co)
    rng = np.random.default_rng(ARGS.seed + 2)
    mismatches = []
    try:
        # the FSM prestages the validator set at enter-new-round
        crypto_batch.prestage_validators(vals)
        check(
            ov._PUBKEY_CACHE.missing([v.pub_key.data for v in vals.validators])
            == 0,
            "validator set prestaged into the device arena",
        )

        def run_round(height: int) -> None:
            bid = block_id(height)
            sets = {}
            for t in (canonical.PREVOTE_TYPE, canonical.PRECOMMIT_TYPE):
                votes = signed_votes(chain, vals, pvs, height, bid, t)
                want = []
                for i, v in enumerate(votes):
                    if rng.random() < bad_share:
                        v.signature = flip(v.signature)
                    pk = vals.validators[i].pub_key
                    want.append(
                        pk.verify_signature(v.sign_bytes(chain), v.signature)
                    )
                sets[t] = (VoteSet(chain, height, 0, t, vals), votes, want)

            def feed_singles(t) -> None:
                vs, votes, want = sets[t]
                for i in range(singles):
                    try:
                        got = bool(vs.add_vote(votes[i]))
                    except Exception:
                        got = False
                    if got != want[i]:
                        mismatches.append((height, t, i))

            feeders = [
                threading.Thread(target=feed_singles, args=(t,), daemon=True)
                for t in sets
            ]
            for f in feeders:
                f.start()
            for f in feeders:
                f.join(timeout=120)
                if f.is_alive():
                    raise LegFailure("vote feeder hung")
            for t, (vs, votes, want) in sets.items():
                added, _errs = vs.add_votes_batch(votes[singles:])
                for j, got in enumerate(added):
                    if bool(got) != want[singles + j]:
                        mismatches.append((height, t, singles + j))

        _, out["cold_round_s"] = timed(
            "round 1 (cold shapes: host serves, ops/warm compiles)",
            "set-up", lambda: run_round(3),
        )
        check(not mismatches, "cold round: verdicts identical to verify_signature")
        (_, out["warmup_s"]) = timed(
            "waiting for the window shapes to compile", "set-up",
            lambda: check(ov.WARM.wait_idle(600), "warm-up worker idle"),
        )
        check(not ov.WARM.failed, f"no window shape failed: {ov.WARM.failed}")
        dw0 = co.device_windows
        for r in range(rounds):
            run_round(4 + r)
        out["rounds"] = rounds + 1
        votes_total = (rounds + 1) * 2 * n_vals
        check(
            not mismatches,
            f"{votes_total} votes over {rounds + 1} rounds: admission "
            f"verdicts identical to pub_key.verify_signature ({mismatches[:3]})",
        )

        # the readback drain is FIFO: when the last of several device
        # windows' tickets resolves, every earlier one already has. Each
        # submit waits for its window to LAUNCH (not to resolve) before
        # the next, so none merge into a cold bucket — a host window
        # resolves inline and may overtake device windows in flight.
        width = n_vals - singles  # the big bucket
        pks = [vals.validators[i].pub_key.data for i in range(width)]
        # Make sure THIS coalescer shape is warm first: once the live
        # crossover has calibrated below `width` (it may, from legs A and
        # B's samples), add_votes_batch goes to the device directly and
        # no coalescer window of this width has formed yet.
        co.submit(pks, [b"prime"] * width, [bytes(64)] * width).result(120)
        check(ov.WARM.wait_idle(600), "big-bucket window shape warm")
        out["host_batch_threshold"] = crypto_batch.host_batch_threshold()
        tickets, dw_order = [], co.device_windows
        for g in range(4):
            launched = co.windows
            msgs = [b"order-%d-%d" % (g, i) for i in range(width)]
            tickets.append(co.submit(pks, msgs, [bytes(64)] * width))
            t_spin = time.monotonic()
            while co.windows == launched and time.monotonic() < t_spin + 30:
                time.sleep(0.0005)
        bits = tickets[-1].result(timeout=120)
        n_done = sum(t.done() for t in tickets)
        n_dev = co.device_windows - dw_order
        check(
            n_done == 4 and not any(bits) and n_dev == 4,
            "4 device windows in flight resolved in submission order "
            f"(FIFO readback drain; done {n_done}/4, device {n_dev}/4, live "
            f"crossover {out['host_batch_threshold']} lanes)",
        )
        out["coalescer"] = {
            "windows": co.windows, "device_windows": co.device_windows,
            "cold_windows": co.cold_windows, "trips": co.trips,
        }
        say(f"    coalescer: {out['coalescer']}")
        check(
            co.device_windows > dw0,
            f"device windows > 0 once warm ({co.device_windows - dw0} after "
            f"warm-up; {co.cold_windows} cold windows served from host)",
        )
        check(co.trips == 0, "breaker trips = 0")
        big_bucket = ov.bucket_size(n_vals - singles)
        check(
            ledger_has("verify_cached.", big_bucket),
            f"compile ledger: verify_cached.* at the {big_bucket}-lane "
            "small-grid bucket",
        )
    finally:
        crypto_coalesce.pop_active(co)
        co.stop()
        os.environ.pop("COMETBFT_TPU_COALESCE_MIN_DEVICE_LANES", None)
    check(ov.WARM.wait_idle(600), "verify warm-up worker idle")
    return out


# ==================================================================== main


def main() -> int:
    legs_wanted = [x.strip().upper() for x in ARGS.legs.split(",") if x.strip()]
    os.makedirs(ARGS.out, exist_ok=True)
    versions = _versions()
    print(
        f"platform: {DEVICE['platform']}\ndevice_kind: {DEVICE['kind']}\n"
        f"device_count: {DEVICE['count']}\nversions: {versions}\n"
        f"seed: {ARGS.seed}\nmode: {'CPU DRY RUN' if DRY else 'chip'}",
        flush=True,
    )
    if DRY:
        _dry_run_patches()
    devstats.enable()
    cache_dir = ov._enable_compilation_cache()
    say(f"compile cache: {cache_dir}")
    check(host_batch.available(), "native host batch engine built from source")
    summary: dict = {
        "device": DEVICE, "versions": versions, "seed": ARGS.seed,
        "dry_run": DRY, "cache_dir": cache_dir, "legs": {},
    }
    failed = []
    for name, fn in (("A", leg_a), ("B", leg_b), ("C", leg_c)):
        if name not in legs_wanted:
            continue
        say(f"leg {name} ...")
        t = time.perf_counter()
        try:
            res = fn()
            res["ok"] = True
        except Exception as e:
            traceback.print_exc()
            res = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
            failed.append(name)
        res["wall_s"] = round(time.perf_counter() - t, 1)
        summary["legs"][name] = res
        say(f"leg {name}: {'PASS' if res['ok'] else 'FAIL'} ({res['wall_s']} s)")
    snap = devstats.snapshot()
    cache = devstats.cache_events()
    summary["compile_ledger"] = compile_ledger()
    summary["persistent_cache"] = cache
    summary["transfers"] = snap["transfers"]
    summary["dispatch"] = snap["verify_dispatch"]
    summary["warm"] = {"verify": ov.WARM.snapshot(), "sha256": osha.WARM.snapshot()}
    say(
        f"persistent cache: {cache['hits']} hits / {cache['requests']} "
        f"requests ({cache['requests'] - cache['hits']} real compiles, "
        f"{snap['xla']['compile_seconds']} s in tracked kernels as set-up)"
    )
    # Legs B and C form windows by timing, so a later run can meet a
    # shape an earlier one did not; leg A's shapes are fixed by its
    # sizes, so against a warm cache every one of its compiles must hit.
    cache_a = summary["legs"].get("A", {}).get("persistent_cache")
    if ARGS.require_warm_cache:
        if not cache_a or cache_a["requests"] != cache_a["hits"]:
            say(f"FAIL: --require-warm-cache and leg A really compiled: {cache_a}")
            failed.append("cache")
        else:
            say(
                f"warm cache: all {cache_a['requests']} compile requests of "
                "leg A were cache hits; the rest of the run met "
                f"{cache['requests'] - cache['hits']} shape(s) the earlier "
                "run had not (timing-dependent windows)"
            )
    if sorted(summary["legs"]) != ["A", "B", "C"]:
        say("not every leg was asked for: this run cannot pass")
        failed.append("subset")
    ok = not failed
    summary["ok"] = ok and not DRY
    summary["wall_s"] = round(time.perf_counter() - _T0, 1)
    summary["claim"] = None  # last key: this run measures nothing
    with open(os.path.join(ARGS.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    with open(os.path.join(ARGS.out, "devstats.json"), "w") as f:
        json.dump(snap, f, indent=1, default=str)
    legs_ok = {k: v["ok"] for k, v in summary["legs"].items()}
    if DRY:
        last = {
            "dry_run": True, "legs_ok": ok, "device": DEVICE,
            "legs": legs_ok, "claim": None,
        }
    else:
        brief = {
            "legs": legs_ok, "versions": versions,
            "wall_s": summary["wall_s"], "claim": None,
        }
        print(f"summary: {json.dumps(brief)}", flush=True)
        # The contract's result line: these two keys and no other.
        last = {"ok": ok, "device": DEVICE}
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
