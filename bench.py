"""North-star benchmark suite: the five BASELINE.md configs.

Prints ONE JSON line on stdout (the headline metric, same shape the
driver parses); the full per-config table goes to stderr as extra JSON
lines so the numbers are recorded without confusing the parser.

Headline: ed25519 batch-verify throughput on the 4096-signature flat
batch (BASELINE config 5's size), measured as steady-state host->device
round trips including packing — what a consensus round actually pays.

The bench runs on the chip, in the one process that holds it, or not at
all: without a TPU it exits non-zero (see :func:`_require_device`).

Baseline honesty: the reference's hot path is curve25519-voi *batch*
verification (crypto/ed25519/ed25519.go:196-228), not single verifies.
No Go toolchain exists in this image, so the baseline is the MEASURED
native RLC/Pippenger batch verifier (crypto/host_batch.py over
native/edbatch.cpp — the voi algorithm itself) on one core of this
machine; OpenSSL single-verify is reported alongside for context. The
former "OpenSSL x 2.0" stand-in was retired in round 3.

Configs (BASELINE.md "North-star target", crypto/ed25519/bench_test.go:31-68):
  1. 64-sig batch            (CPU-parity bucket)
  2. 150-validator commit    (types.Commit verify, Cosmos-Hub-sized)
  3. 1000-validator round    (VoteSet prevote+precommit batched ingest)
  4. 10k-validator light replay (verify_commit_light — the north star)
  5. 4096 mixed ed25519+sr25519 (blocksync catch-up shape)
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


_DETAILS: list = []

# COMETBFT_BENCH_TINY=1 shrinks every config so the FULL capture path —
# 5-config table, extras — executes end to end in minutes.
# With JAX_PLATFORMS=cpu it is the only run bench.py makes off the chip
# (tests/test_bench_capture.py); its provenance row says ``cpu``.
_TINY = os.environ.get("COMETBFT_BENCH_TINY") == "1"


def _sz(normal: int, tiny: int) -> int:
    return tiny if _TINY else normal


def _require_device() -> None:
    """The bench measures the chip or it does not run.

    Without a TPU as jax's default backend this exits non-zero — there
    is no host fallback that prints host numbers under device names.
    The one exception is the dry run of the capture path itself:
    ``COMETBFT_BENCH_TINY=1`` together with ``JAX_PLATFORMS=cpu``, whose
    provenance row (and so every reader of the table) says ``cpu``.
    """
    import jax

    from cometbft_tpu.libs.accel import ACCELERATOR_BACKENDS

    backend = jax.default_backend()
    if backend in ACCELERATOR_BACKENDS:
        return
    if _TINY and os.environ.get("JAX_PLATFORMS") == "cpu":
        return
    print(
        f"bench.py needs a TPU (jax default backend is {backend!r}); "
        "the only CPU run is the capture-path dry run: "
        "COMETBFT_BENCH_TINY=1 JAX_PLATFORMS=cpu",
        file=sys.stderr,
    )
    raise SystemExit(2)


def _eprint(obj) -> None:
    print(json.dumps(obj), file=sys.stderr, flush=True)
    _DETAILS.append(obj)
    # persist incrementally: a run that dies keeps the rows it made
    try:
        with open("BENCH_DETAILS.json", "w") as f:
            json.dump(_DETAILS, f, indent=1)
    except OSError:
        pass


def _provenance() -> dict:
    """Software/hardware provenance stamped on every run so rows are
    comparable across hosts: jax/jaxlib versions, the backend platform,
    the device kind and count, as jax reports them."""
    import platform

    import jax
    import jaxlib

    devs = jax.devices()
    return {
        "config": "0_provenance",
        "python": platform.python_version(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_count": len(devs),
        "device_kind": getattr(devs[0], "device_kind", None),
    }


def _headline_provenance(prov: dict) -> dict:
    """The compact provenance subdict carried on the stdout headline."""
    return {
        k: prov[k]
        for k in ("jax", "jaxlib", "backend", "device_kind")
        if k in prov
    }


def _make_ed_batch(n: int, seed: int = 3):
    rng = np.random.default_rng(seed)
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PrivateKey,
        )
        from cryptography.hazmat.primitives import serialization

        raw = serialization.Encoding.Raw
        pub_fmt = serialization.PublicFormat.Raw
        keys = [Ed25519PrivateKey.generate() for _ in range(min(n, 64))]
        pubs = [k.public_key().public_bytes(raw, pub_fmt) for k in keys]
    except ImportError:  # wheel-less container: the engine's own keys
        from cometbft_tpu.crypto.keys import Ed25519PrivKey

        keys = [
            Ed25519PrivKey.from_seed(bytes(rng.bytes(32)))
            for _ in range(min(n, 64))
        ]
        pubs = [k.pub_key().bytes() for k in keys]
    pubkeys, msgs, sigs = [], [], []
    for i in range(n):
        k = keys[i % len(keys)]
        # Distinct message per lane, like commit vote sign-bytes
        # (timestamps differ per validator — types/block.go:871-883).
        msg = rng.bytes(112)
        pubkeys.append(pubs[i % len(keys)])
        msgs.append(msg)
        sigs.append(k.sign(msg))
    return pubkeys, msgs, sigs


def _cpu_single_baseline(n_sample: int = 512) -> tuple[float, str]:
    """Single-verify throughput (sigs/sec, one core) + which backend ran.

    Backends, fastest available wins: "openssl" (the ``cryptography``
    wheel), "native-edbatch" (crypto/fast25519 routing through the C
    engine at n=1), "pure-python-oracle". The capture records the label
    explicitly — magnitudes are NOT comparable across backends."""
    if _TINY:
        n_sample = 32
    try:
        from cryptography.hazmat.primitives.asymmetric.ed25519 import (
            Ed25519PublicKey,
        )
    except ImportError:
        from cometbft_tpu.crypto import fast25519, host_batch

        n_sample = min(n_sample, 32)
        pubkeys, msgs, sigs = _make_ed_batch(n_sample)
        # warm-up OUTSIDE the timed window: the first call may pay the
        # one-time native edbatch build (g++), not verification cost
        fast25519.verify_one(pubkeys[0], msgs[0], sigs[0])
        backend = (
            "native-edbatch" if host_batch.available()
            else "pure-python-oracle"
        )
        t0 = time.perf_counter()
        for p, m, s in zip(pubkeys, msgs, sigs):
            if not fast25519.verify_one(p, m, s):  # not assert: must
                raise RuntimeError("baseline verify failed")  # survive -O
        return n_sample / (time.perf_counter() - t0), backend

    pubkeys, msgs, sigs = _make_ed_batch(n_sample)
    loaded = [Ed25519PublicKey.from_public_bytes(p) for p in pubkeys]
    t0 = time.perf_counter()
    for pk, m, s in zip(loaded, msgs, sigs):
        pk.verify(s, m)
    return n_sample / (time.perf_counter() - t0), "openssl"


def _cpu_batch_baseline(n: int = 4096) -> float:
    """MEASURED host batch-verify throughput (sigs/sec, one core).

    This is the actual voi algorithm — random-linear-combination over
    the cofactored equation, one Pippenger multiscalar multiplication —
    implemented natively (cometbft_tpu/native/edbatch.cpp, driven by
    crypto/host_batch.py). It replaces the former documented guess of
    OpenSSL-single x 2.0 (VOI_BATCH_FACTOR): every vs_baseline below is
    now against a measurement on this machine.
    """
    from cometbft_tpu.crypto import host_batch

    if _TINY:
        n = 256  # dry-run: exercise the path, not the steady state
    pubkeys, msgs, sigs = _make_ed_batch(n)
    assert all(host_batch.verify_many(pubkeys, msgs, sigs))  # warm-up
    # min-of-5, the SAME statistic as the device headline it anchors:
    # dividing a min-of-reps device number by a single-rep host number
    # would bias vs_baseline toward the device on any host transient.
    dt = _best(lambda: host_batch.verify_many(pubkeys, msgs, sigs), 5)
    return n / dt


def _steady(fn, reps: int = 3) -> float:
    """Warm once, then MIN over reps. One statistic everywhere: every
    vs_batch_baseline divides a min-of-reps row by the min-of-reps
    baseline — mixing mean rows with a min baseline would bias the
    ratios downward on any host transient."""
    fn()  # warm-up: compile + caches
    return _best(fn, reps)


def _best(fn, reps: int) -> float:
    """Min individual rep time (caller warms first)."""
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = dt if best is None or dt < best else best
    return best


def bench_flat_batch(n: int, reps: int = 3):
    """Configs 1 (n=64) and the 4096 headline: flat verify_batch,
    MIN over reps (see :func:`_steady`)."""
    from cometbft_tpu.ops import verify as ov

    pubkeys, msgs, sigs = _make_ed_batch(n)
    ok, bitmap = ov.verify_batch(pubkeys, msgs, sigs)
    assert ok and bitmap.all(), "benchmark batch failed verification"
    dt = _best(lambda: ov.verify_batch(pubkeys, msgs, sigs), reps)
    return n / dt, dt


def _make_valset_and_pvs(n_vals: int):
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.types.priv_validator import MockPV
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet

    pvs = [
        MockPV(Ed25519PrivKey.from_seed(i.to_bytes(32, "big")))
        for i in range(1, n_vals + 1)
    ]
    vals = ValidatorSet(
        [Validator(pv.get_pub_key(), voting_power=10) for pv in pvs]
    )
    by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
    ordered = [by_addr[bytes(v.address)] for v in vals.validators]
    return vals, ordered


def _sign_commit(chain_id, vals, pvs, height, block_id):
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import Commit
    from cometbft_tpu.types.vote import Vote

    base_ns = 1_700_000_000_000_000_000
    sigs = []
    for idx, (val, pv) in enumerate(zip(vals.validators, pvs)):
        vote = Vote(
            msg_type=canonical.PRECOMMIT_TYPE,
            height=height,
            round=0,
            block_id=block_id,
            timestamp_ns=base_ns + idx,
            validator_address=val.address,
            validator_index=idx,
        )
        pv.sign_vote(chain_id, vote, sign_extension=False)
        sigs.append(vote.commit_sig())
    return Commit(height=height, round=0, block_id=block_id, signatures=sigs)


def _block_id():
    from cometbft_tpu.types.block import BlockID, PartSetHeader

    return BlockID(
        hash=bytes(range(32)),
        part_set_header=PartSetHeader(total=1, hash=bytes(32)),
    )


def bench_commit_verify(n_vals: int, light: bool):
    """Configs 2 (150 validators, full verify) and 4 (10k, light replay).

    Measures types.verify_commit / verify_commit_light end to end —
    sign-bytes construction, batch packing, device verify — the exact
    work the reference's Commit.VerifySignatures does
    (types/validation.go:26,60,153-257).
    """
    from cometbft_tpu.types import validation

    chain_id = "bench-chain"
    vals, pvs = _make_valset_and_pvs(n_vals)
    bid = _block_id()
    commit = _sign_commit(chain_id, vals, pvs, 7, bid)
    fn = validation.verify_commit_light if light else validation.verify_commit
    dt = _steady(lambda: fn(chain_id, vals, bid, 7, commit))
    return n_vals / dt, dt


def bench_vote_round(n_vals: int):
    """Config 3: a prevote+precommit round through VoteSet batched ingest
    (types/vote_set.py add_votes_batch — the consensus hot path,
    types/vote_set.go:216-231 / consensus/state.go:2086)."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.vote import Vote
    from cometbft_tpu.types.vote_set import VoteSet

    chain_id = "bench-chain"
    vals, pvs = _make_valset_and_pvs(n_vals)
    bid = _block_id()
    base_ns = 1_700_000_000_000_000_000

    def make_votes(msg_type):
        votes = []
        for idx, (val, pv) in enumerate(zip(vals.validators, pvs)):
            v = Vote(
                msg_type=msg_type,
                height=3,
                round=0,
                block_id=bid,
                timestamp_ns=base_ns + idx,
                validator_address=val.address,
                validator_index=idx,
            )
            pv.sign_vote(chain_id, v, sign_extension=False)
            votes.append(v)
        return votes

    prevotes = make_votes(canonical.PREVOTE_TYPE)
    precommits = make_votes(canonical.PRECOMMIT_TYPE)

    def run_round():
        pv_set = VoteSet(
            chain_id, 3, 0, canonical.PREVOTE_TYPE, vals
        )
        pc_set = VoteSet(
            chain_id, 3, 0, canonical.PRECOMMIT_TYPE, vals
        )
        added, _ = pv_set.add_votes_batch(prevotes)
        assert all(added)
        added, _ = pc_set.add_votes_batch(precommits)
        assert all(added)
        assert pv_set.two_thirds_majority() is not None
        assert pc_set.two_thirds_majority() is not None

    dt = _steady(run_round)
    return 2 * n_vals / dt, dt


def bench_mixed(n: int):
    """Config 5: half ed25519, half sr25519 through the crypto.batch
    dispatch (crypto/batch/batch.go:11; sr25519 rides the same cofactored
    TPU kernel — crypto/sr25519/batch.go:14-46)."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey, Sr25519PubKey

    half = n // 2
    ed_pub, ed_msg, ed_sig = _make_ed_batch(half, seed=11)

    # sr25519 signing is pure Python (~ms/sig): sign a small unique set
    # and tile it. Verification cost per lane is unaffected by repeats.
    uniq = 64
    sr_keys = [
        Sr25519PrivKey(i.to_bytes(32, "little")) for i in range(1, uniq + 1)
    ]
    sr_pub, sr_msg, sr_sig = [], [], []
    for i in range(half):
        k = sr_keys[i % uniq]
        msg = b"sr-lane-%d" % (i % uniq)
        sr_pub.append(k.pub_key())
        sr_msg.append(msg)
        sr_sig.append(k.sign(msg) if i < uniq else sr_sig[i % uniq])

    def run():
        # The production mixed-commit path (types/validation.py routes a
        # heterogeneous valset here): ONE verifier, one device launch /
        # one host MSM across both schemes.
        bv = crypto_batch.MixedBatchVerifier()
        for p, m, s in zip(ed_pub, ed_msg, ed_sig):
            bv.add(Ed25519PubKey(p), m, s)
        for p, m, s in zip(sr_pub, sr_msg, sr_sig):
            bv.add(p, m, s)
        ok, _bitmap = bv.verify()
        assert ok, "mixed batch failed"

    dt = _steady(run)
    return n / dt, dt


# Per-field-mul int32 op estimate for the VPU utilization figure: the
# 20x20 schoolbook outer product is 400 MACs, the shear column reduce
# ~740 adds, the fold + three carry passes ~350 more — ~1500 int32 ops.
_INT32_OPS_PER_FIELD_MUL = 1500
# v5e VPU int32 peak, order-of-magnitude: 2 ALUs x (8x128) lanes x
# ~1.6 GHz ~ 3.3e12 op/s. The MXU's 394 int8 TOPS is NOT the unit the
# ladder runs on; utilization is reported against the VPU estimate and
# labeled an estimate.
_VPU_INT32_PEAK = 3.3e12
# Static per-signature field-mul ledger for the 4-bit joint ladder
# (docs/tpu-kernel.md): cached = R decompress 265 + 64 windows x
# (29 dbl-chain + 8 niels + 7 affine) + tail ~31.
_LADDER_MULS_CACHED = 265 + 64 * 44 + 31
_LADDER_MULS_UNCACHED = _LADDER_MULS_CACHED + 265 + 121  # + A decomp/table
# both programs run the same joint ladder
_MULS_UNCACHED_BY_KERNEL = {
    "xla": _LADDER_MULS_UNCACHED,
    "pallas": _LADDER_MULS_UNCACHED,
}


def _est_vpu_util(muls_per_sig: float, n: int, compute_s: float) -> float:
    ops = muls_per_sig * _INT32_OPS_PER_FIELD_MUL * n
    return round(ops / max(compute_s, 1e-9) / _VPU_INT32_PEAK, 4)


_FLOOR_SIZES_FULL = (64, 150, 256, 512, 768, 1024, 2048, 4096, 8192, 16384)
_FLOOR_SIZES_TINY = (64, 150)


def bench_device_floor():
    """Break down the device round trip and derive the host crossover.

    The ~70 ms device floor was asserted as a constant and routed around
    (crypto/batch.HOST_BATCH_THRESHOLD); this measures where it actually
    goes — host packing, dispatch (includes transfer under jit's async
    dispatch), readback sync, and pure device COMPUTE on device-resident
    donated inputs — at realistic commit sizes, for both the uncached
    kernel and the expanded-pubkey cached path,
    and reports the measured crossover against the native host batch
    verifier. est_vpu_util = static op ledger / measured compute vs the
    documented v5e VPU int32 peak estimate (round-4 verdict task 2).
    """
    # devstats compile accounting attributes this config's one-time XLA
    # compiles to their own column: a first timed rep that "dispatches
    # for 10 s" is one compile silently folded into it.
    # Enabled for the sweep only and ALWAYS restored — a mid-sweep
    # failure must not leave the later configs (the headline) running
    # with per-launch telemetry on.
    from cometbft_tpu.libs import devstats as libdevstats

    devstats_was_on = libdevstats.enabled()
    libdevstats.enable()
    try:
        return _bench_device_floor_measured(libdevstats)
    finally:
        if not devstats_was_on:
            libdevstats.disable()


def _bench_device_floor_measured(libdevstats):
    from cometbft_tpu.crypto import host_batch
    from cometbft_tpu.ops import verify as ov

    rows = []
    sizes = _FLOOR_SIZES_TINY if _TINY else _FLOOR_SIZES_FULL
    for n in sizes:
        pubkeys, msgs, sigs = _make_ed_batch(n, seed=n)
        comp_s0 = libdevstats.compile_seconds_total()
        comp_n0 = libdevstats.compile_count()
        # warm both paths (compile + cache build)
        ov.verify_batch(pubkeys, msgs, sigs)
        host_batch.verify_many(pubkeys, msgs, sigs)

        t0 = time.perf_counter()
        buf, host_ok = ov.pack_bytes(pubkeys, msgs, sigs)
        t_pack = time.perf_counter() - t0

        # Explicit UNCACHED warm: the end-to-end warm above routes
        # through the cached-arena kernel once the arena is built, so
        # the uncached lowering for this bucket can still be cold — its
        # compile must land here (in the compile_ms column), never in a
        # timed rep.
        ov.verify_bytes_async(buf, n)()
        compile_s = libdevstats.compile_seconds_total() - comp_s0
        compiles = libdevstats.compile_count() - comp_n0

        # measure BOTH device paths explicitly (the warm-up populated
        # the pubkey cache, so steady state is "cached"; "uncached" is
        # the cold-cache / evicted-validator first-launch cost).
        # Dispatch/readback are EXECUTE-ONLY from here on.
        reps = 3

        def timed(launch):
            t_disp = t_read = 0.0
            for _ in range(reps):
                t0 = time.perf_counter()
                fin = launch()
                t1 = time.perf_counter()
                fin()
                t2 = time.perf_counter()
                t_disp += t1 - t0
                t_read += t2 - t1
            return t_disp / reps, t_read / reps

        d_unc, r_unc = timed(lambda: ov.verify_bytes_async(buf, n))
        # per-window transfer bytes, straight from the devstats ledger
        # around ONE warmed uncached launch: what actually crossed the
        # edge at this bucket (reconciles with the narrowed idx/mask
        # dtypes — the no-recompile guard pins the exact arithmetic)
        c_a = libdevstats.counters()
        ov.verify_bytes_async(buf, n)()
        c_b = libdevstats.counters()
        h2d_bytes = c_b["h2d_bytes"] - c_a["h2d_bytes"]
        d2h_bytes = c_b["d2h_bytes"] - c_a["d2h_bytes"]
        hit = ov._PUBKEY_CACHE.lookup(pubkeys)
        if hit is not None:
            idxs, arena, arena_ok = hit
            d_cac, r_cac = timed(
                lambda: ov.verify_rsk_async(
                    buf[32:], idxs, arena, arena_ok, n
                )
            )
        else:
            d_cac = r_cac = None

        # Pure device COMPUTE: inputs already HBM-resident, timing only
        # launch -> block_until_ready. The gap to the end-to-end numbers
        # above is transfer + sync overhead (PCIe on a directly
        # attached chip).
        t_compute = None
        t_transfer_sync = None  # measured, same-kernel (see below)
        t_h2d = None  # pure host->device commit of the wire buffer
        t_d2h = None  # transfer_sync minus the measured h2d share
        transfer_probe_compile_s = None
        probe_lanes = None  # lanes the timed kernel actually covered
        probe_kernel = None
        try:
            if _TINY:
                raise RuntimeError("skip compute probe in tiny mode")
            import jax

            size = ov.bucket_size(n) if n <= ov._CHUNK else ov._CHUNK
            bufp = buf
            if size != n and n <= ov._CHUNK:
                bufp = np.pad(buf, [(0, 0), (0, size - n)])
            # Time the kernel production would actually pick for this
            # bucket (ops/verify's one rule: Pallas on the chip from
            # one block up, XLA otherwise) so compute_ms/utilization
            # describe the real path — falling back to XLA so a broken
            # Pallas can't erase the whole decomposition this probe
            # exists to capture. The live path's jit IDENTITY matters
            # too: small buckets launch their dedicated small-grid
            # jits — probe the exact (program, grid) pair live windows
            # launch, or the n<=256 rows (the crossover's home) would
            # time a kernel the production path never runs.
            probe_grid = ov._small_grid(min(size, ov._CHUNK))
            cands = ["pallas", "xla"] if ov._pallas_wanted(size) else ["xla"]
            fn = None
            for probe_try in cands:
                try:
                    fn = ov._jitted_kernel(
                        "verify", probe_try, probe_grid
                    )
                    # fresh device buffer per attempt: the kernels jit
                    # with input donation on TPU, so a faulting
                    # candidate consumes its warm buffer — reusing one
                    # would fail every later candidate on a deleted
                    # Array and defeat this fallback chain
                    dev_buf = jax.device_put(
                        bufp[:, : min(size, ov._CHUNK)]
                    )
                    dev_buf.block_until_ready()
                    fn(dev_buf).block_until_ready()  # warm
                    probe_kernel = probe_try
                    break
                except Exception:
                    fn = None
            if fn is None:
                raise RuntimeError("no kernel probed")
            t_c = []
            for _ in range(reps):
                dev_buf2 = jax.device_put(bufp[:, : min(size, ov._CHUNK)])
                dev_buf2.block_until_ready()
                t0 = time.perf_counter()
                fn(dev_buf2).block_until_ready()
                t_c.append(time.perf_counter() - t0)
            t_compute = min(t_c)
            # padded bucket lanes do full ladder work: utilization must
            # count them, not the logical n (n=150 pads to 256)
            probe_lanes = min(size, ov._CHUNK)
            # Transfer+sync: measured with the SAME kernel as the
            # compute probe — warmed end-to-end launch from a
            # host-resident buffer (h2d staging + execute + packed-mask
            # readback) minus the device-resident compute time above.
            # The old derivation subtracted t_compute from dispatch
            # timings of a possibly DIFFERENT kernel flavor and, in
            # r05, of a window still paying one-time compile — hence
            # the 9-10 s (and negative) transfer_sync_ms rows. Any
            # compile this probe itself pays is reported separately.
            xfer_comp_s0 = libdevstats.compile_seconds_total()
            host_in = bufp[:, : min(size, ov._CHUNK)]
            np.asarray(fn(host_in))  # warm the host-input path
            transfer_probe_compile_s = (
                libdevstats.compile_seconds_total() - xfer_comp_s0
            )
            t_x = []
            for _ in range(reps):
                t0 = time.perf_counter()
                np.asarray(fn(host_in))
                t_x.append(time.perf_counter() - t0)
            t_transfer_sync = max(0.0, min(t_x) - t_compute)
            # decompose transfer_sync into its h2d and d2h shares: the
            # h2d leg is measured directly (device_put + block of the
            # same wire buffer); the d2h leg is the remainder — the
            # packed-ok-bits readback plus sync overhead
            t_hs = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.device_put(host_in).block_until_ready()
                t_hs.append(time.perf_counter() - t0)
            t_h2d = min(t_hs)
            t_d2h = max(0.0, t_transfer_sync - t_h2d)
        except Exception:
            pass

        t0 = time.perf_counter()
        host_batch.verify_many(pubkeys, msgs, sigs)
        t_host = time.perf_counter() - t0

        candidates = [d_unc + r_unc]
        if d_cac is not None:
            candidates.append(d_cac + r_cac)
        # est_vpu_util from EXECUTE-ONLY time (never a window that may
        # contain a compile): the compute probe when it ran; otherwise
        # the now-compile-free dispatch+readback, but only when the XLA
        # lowering is the actual launch path (no pallas candidate) so
        # the op ledger matches what executed.
        est_util = est_basis = None
        if t_compute and probe_kernel in _MULS_UNCACHED_BY_KERNEL:
            est_util = _est_vpu_util(
                _MULS_UNCACHED_BY_KERNEL[probe_kernel],
                probe_lanes,
                t_compute,
            )
            est_basis = "compute_probe"
        else:
            lanes = ov.bucket_size(n) if n <= ov._CHUNK else n
            if not ov._pallas_wanted(lanes):
                est_util = _est_vpu_util(
                    _MULS_UNCACHED_BY_KERNEL["xla"],
                    lanes,
                    d_unc + r_unc,
                )
                est_basis = "dispatch_readback"
        dev_total = t_pack + min(candidates)
        rows.append(
            {
                "n": n,
                "pack_ms": round(t_pack * 1e3, 2),
                # one-time XLA compile cost paid while warming THIS
                # bucket (cached + uncached lowerings), measured by
                # libs/devstats — its own column, no longer folded into
                # the dispatch mean
                "compile_ms": round(compile_s * 1e3, 2),
                "compiles": compiles,
                "uncached_dispatch_ms": round(d_unc * 1e3, 2),
                "uncached_readback_ms": round(r_unc * 1e3, 2),
                "cached_dispatch_ms": (
                    round(d_cac * 1e3, 2) if d_cac is not None else None
                ),
                "cached_readback_ms": (
                    round(r_cac * 1e3, 2) if r_cac is not None else None
                ),
                "compute_ms": (
                    round(t_compute * 1e3, 2) if t_compute else None
                ),
                # per-window fixed-cost decomposition (pack / h2d /
                # execute / d2h): pack_ms above is the host staging
                # leg, compute_ms the execute leg (device-resident
                # probe), h2d_ms the measured wire-buffer commit,
                # d2h_ms the transfer_sync remainder (packed-ok-bits
                # readback + sync). Bytes columns come from the
                # devstats ledger around one warmed launch, so dtype
                # narrowing lands here directly.
                "h2d_ms": (
                    round(t_h2d * 1e3, 2) if t_h2d is not None else None
                ),
                "d2h_ms": (
                    round(t_d2h * 1e3, 2) if t_d2h is not None else None
                ),
                "h2d_bytes": h2d_bytes,
                "d2h_bytes": d2h_bytes,
                # same-kernel warmed e2e minus compute (NOT the old
                # cross-kernel subtraction); compile the probe itself
                # paid is its own column, never folded in
                "transfer_sync_ms": (
                    round(t_transfer_sync * 1e3, 2)
                    if t_transfer_sync is not None
                    else None
                ),
                "transfer_probe_compile_ms": (
                    round(transfer_probe_compile_s * 1e3, 2)
                    if transfer_probe_compile_s is not None
                    else None
                ),
                "probe_kernel": probe_kernel,
                # Ledger matched to the executed kernel's window scheme
                # (both lowerings of a scheme run the same algorithm).
                "est_vpu_util_uncached": est_util,
                "est_vpu_util_basis": est_basis,
                "device_total_ms": round(dev_total * 1e3, 2),
                "host_rlc_ms": round(t_host * 1e3, 2),
                "device_wins": bool(dev_total < t_host),
            }
        )
    # Crossover = the boundary after the LAST device loss: the first n
    # that wins AND every larger measured n wins too. A first-win rule
    # would route sizes past a later loss (e.g. a win at 2048 with a
    # loss again at 4096) onto the measured-slower device path.
    crossover = None
    for row in reversed(rows):
        if row["device_wins"]:
            crossover = row["n"]
        else:
            break
    cbatch = __import__("cometbft_tpu.crypto.batch", fromlist=["x"])
    # the fixed per-window cost at the SMALLEST measured size — the
    # quantity the lane arenas / readback overlap / dtype shrink /
    # small-grid split exist to drive down; legible across BENCH
    # revisions as one number per leg
    small = rows[0] if rows else {}
    fixed = {
        "pack_ms": small.get("pack_ms"),
        "h2d_ms": small.get("h2d_ms"),
        "execute_ms": small.get("compute_ms"),
        "d2h_ms": small.get("d2h_ms"),
        "n": small.get("n"),
    }
    known = [v for v in (
        fixed["pack_ms"], fixed["h2d_ms"], fixed["execute_ms"],
        fixed["d2h_ms"],
    ) if v is not None]
    fixed["total_ms"] = round(sum(known), 2) if known else None
    return {
        # measured_crossover_lanes is the load-bearing legacy key (the
        # chip table / crypto/batch._derive_host_threshold read it);
        # crossover_lanes is the same number under the headline's
        # name — the boundary below which the host wins, and the
        # device-floor work is measured by it going DOWN
        "rows": rows,
        "measured_crossover_lanes": crossover,
        "crossover_lanes": crossover,
        "window_fixed_cost_ms": fixed,
        "current_HOST_BATCH_THRESHOLD": cbatch.HOST_BATCH_THRESHOLD,
    }


def bench_wal_decode():
    """WAL encode/decode round trip (consensus/wal_test.go:264-283)."""
    import tempfile

    from cometbft_tpu.consensus.messages import VoteMessage
    from cometbft_tpu.consensus.wal import WAL, MsgInfo
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import BlockID
    from cometbft_tpu.types.vote import Vote

    n = 2000
    path = tempfile.mktemp(suffix="wal")
    wal = WAL(path)
    vote = Vote(
        msg_type=canonical.PREVOTE_TYPE, height=1, round=0,
        block_id=BlockID(), timestamp_ns=1, validator_address=b"\x01" * 20,
        validator_index=0, signature=b"\x02" * 64,
    )
    t0 = time.perf_counter()
    for _ in range(n):
        wal.write(MsgInfo(VoteMessage(vote), "p"))
    wal.flush_and_sync()
    t_write = time.perf_counter() - t0
    t0 = time.perf_counter()
    count = sum(1 for m in wal.iter_messages() if isinstance(m, MsgInfo))
    t_read = time.perf_counter() - t0
    wal.close()
    assert count == n, count
    return {
        "writes_per_sec": round(n / t_write, 1),
        "decodes_per_sec": round(n / t_read, 1),
    }


def bench_mempool():
    """CheckTx ingest + reap (mempool/bench_test.go:20-109)."""
    from cometbft_tpu.abci.client import LocalClient
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import MempoolConfig
    from cometbft_tpu.mempool.clist_mempool import CListMempool

    client = LocalClient(KVStoreApplication())
    client.start()
    try:
        mp = CListMempool(MempoolConfig(size=20000), client)
        n = 5000
        t0 = time.perf_counter()
        for i in range(n):
            mp.check_tx(b"bench-%d=%d" % (i, i))
        t_check = time.perf_counter() - t0
        t0 = time.perf_counter()
        txs = mp.reap_max_bytes_max_gas(1 << 30, -1)
        t_reap = time.perf_counter() - t0
        return {
            "check_tx_per_sec": round(n / t_check, 1),
            "reap_txs": len(txs),
            "reap_ms": round(t_reap * 1e3, 2),
        }
    finally:
        client.stop()


def bench_valset_update():
    """Incremental validator-set updates (types/validator_set_test.go:1550)."""
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.types.validator_set import Validator, ValidatorSet

    n = 150
    vals = ValidatorSet(
        [
            Validator(
                Ed25519PrivKey.from_seed(i.to_bytes(32, "big")).pub_key(),
                voting_power=10,
            )
            for i in range(1, n + 1)
        ]
    )
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        vals = vals.copy_increment_proposer_priority(1)
    dt = time.perf_counter() - t0
    return {"priority_increments_per_sec": round(reps / dt, 1)}


def bench_coalesce_steady_state(
    device: bool | None = None,
    n_threads: int | None = None,
    min_device_lanes: int | None = None,
):
    """Config 12: concurrent single-vote verify storm through the
    cross-caller coalescer (crypto/coalesce.py) vs the serial per-vote
    host path it replaces.

    N threads each verify a stream of single signatures from a
    100-validator set — the steady-state vote-admission shape, where
    each gossiped vote used to pay one serial host verify
    (types/vote.py). The coalesced run routes the SAME calls through
    ``coalesce.verify_signature``; windows fill from all threads at
    once and ride device micro-batches (or one host MSM per window on
    the fallback). ``device=None`` probes the backend; ``device=False``
    pins host windows.
    """
    import threading as _threading

    from cometbft_tpu.crypto import coalesce as cco
    from cometbft_tpu.crypto.keys import Ed25519PubKey
    from cometbft_tpu.ops import verify as ov

    if n_threads is None:
        n_threads = _sz(16, 4)
    n_vals = _sz(100, 8)
    per_thread = _sz(128, 8)  # single-sig verifies per thread
    pub_raw, msgs, sigs = _make_ed_batch(n_vals, seed=12)
    pubs = [Ed25519PubKey(p) for p in pub_raw]

    def storm(verify_one):
        """Run the storm; returns (total_lanes, wall_seconds)."""
        barrier = _threading.Barrier(n_threads + 1)
        fails: list = []

        def worker(tid):
            rng = np.random.default_rng(tid)
            order = rng.permutation(n_vals)
            barrier.wait()
            for i in range(per_thread):
                j = int(order[i % n_vals])
                if not verify_one(pubs[j], msgs[j], sigs[j]):
                    fails.append(j)

        threads = [
            _threading.Thread(target=worker, args=(t,), daemon=True)
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        barrier.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert not fails, f"storm verify failed for validators {fails[:5]}"
        return n_threads * per_thread, dt

    # serial baseline: the exact per-vote host verify the coalescer
    # replaces (pub_key.verify_signature, one lane at a time)
    lanes, dt = storm(lambda pk, m, s: pk.verify_signature(m, s))
    serial_lps = lanes / dt

    # min_device_lanes=None keeps the production routing (live
    # crossover decides host MSM vs device window); pass a small pin to
    # force the device micro-batch path for a chip-floor probe
    co = cco.VerifyCoalescer(device=device, min_device_lanes=min_device_lanes)
    co.start()
    cco.push_active(co)
    try:
        if device is not False:
            # index-only steady state: prestage the validator set like
            # the consensus FSM does at enter-new-round
            ov.prestage_pubkeys(pub_raw)
        # warm: compile the window buckets outside the timed storm
        storm(lambda pk, m, s: cco.verify_signature(pk, m, s))
        w0, dw0 = co.windows, co.device_windows
        lanes, dt = storm(lambda pk, m, s: cco.verify_signature(pk, m, s))
        coalesced_lps = lanes / dt
        windows = co.windows - w0
        device_windows = co.device_windows - dw0
        backend = "device" if device_windows else "host-window"
    finally:
        cco.pop_active(co)
        co.stop()
    return {
        "threads": n_threads,
        "validators": n_vals,
        "lanes": lanes,
        "serial_host_lanes_per_sec": round(serial_lps, 1),
        "coalesced_lanes_per_sec": round(coalesced_lps, 1),
        "coalesced_vs_serial": round(coalesced_lps / serial_lps, 2),
        "coalesce_backend": backend,
        "windows": windows,
        "device_windows": device_windows,
        # the fraction of the TIMED storm's windows that actually took
        # the device path: a device-present container whose crossover
        # sits above the live window size quietly measures 100% host
        # windows — this column makes that visible instead of letting
        # the headline claim a device speedup it never exercised
        "device_window_pct": round(
            100.0 * device_windows / windows, 1
        ) if windows else 0.0,
        "note": "same verdicts, same call sites; coalesced run routes "
        "pub_key.verify_signature through crypto/coalesce windows",
    }


def _perfect_gossip_net(
    chain_id: str,
    n_vals: int = 4,
    pipeline: bool = True,
    home_root: str | None = None,
):
    """One in-process n-validator consensus net with perfect gossip —
    the shared burst harness of configs 13, 19, 21 and 23.  Returns the
    ``[(ConsensusState, parts)]`` list; parts carries conns/bus/
    block_store (plus ``pipe`` when pipelined) for teardown.

    ``pipeline=True`` (the default, matching node boot's
    COMETBFT_TPU_PIPELINE=auto) wires the pipelined commit chain —
    threaded commit-writer + speculative execution — so the burst
    measures the production engine; pass ``pipeline=False`` for the
    pre-PR serial chain.  ``home_root`` switches the stores and the
    consensus WAL onto real files so the wal_fsync budget tile carries
    actual fsync time (config 23 needs that; the MemDB default keeps
    the overhead configs I/O-free)."""
    from cometbft_tpu import proxy
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import test_config
    from cometbft_tpu.consensus import ConsensusState
    from cometbft_tpu.consensus.messages import (
        BlockPartMessage,
        ProposalMessage,
        VoteMessage,
    )
    from cometbft_tpu.consensus.pipeline import CommitPipeline
    from cometbft_tpu.consensus.wal import WAL
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.libs import db as dbm
    from cometbft_tpu.state import BlockExecutor, Store, make_genesis_state
    from cometbft_tpu.store import BlockStore
    from cometbft_tpu.types import GenesisDoc, GenesisValidator, MockPV
    from cometbft_tpu.types.event_bus import EventBus

    pvs = [
        MockPV(Ed25519PrivKey.from_seed(bytes([i + 1]) * 32))
        for i in range(n_vals)
    ]
    doc = GenesisDoc(
        chain_id=chain_id,
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=[
            GenesisValidator(pub_key=pv.get_pub_key(), power=10)
            for pv in pvs
        ],
    )
    vs = doc.validator_set()
    by_addr = {bytes(pv.get_pub_key().address()): pv for pv in pvs}
    pvs = [by_addr[v.address] for v in vs.validators]
    nodes = []
    for i, pv in enumerate(pvs):
        if home_root is None:
            app_db = state_db = block_db = None
            wal = None
        else:
            home = os.path.join(home_root, f"n{i}")
            os.makedirs(home, exist_ok=True)
            app_db = dbm.FileDB(f"{home}/app.db")
            state_db = dbm.FileDB(f"{home}/state.db")
            block_db = dbm.FileDB(f"{home}/blocks.db")
            wal = WAL(f"{home}/cs.wal/wal")
        conns = proxy.AppConns(
            proxy.local_client_creator(
                KVStoreApplication(app_db or dbm.MemDB())
            )
        )
        conns.start()
        state_store = Store(state_db or dbm.MemDB())
        block_store = BlockStore(block_db or dbm.MemDB())
        bus = EventBus()
        bus.start()
        state = make_genesis_state(doc)
        state_store.save(state)
        executor = BlockExecutor(
            state_store, conns.consensus,
            block_store=block_store, event_bus=bus,
        )
        cs = ConsensusState(
            test_config().consensus, state, executor, block_store,
            event_bus=bus, wal=wal,
        )
        cs.set_priv_validator(pv)
        parts = dict(
            conns=conns, bus=bus, block_store=block_store,
            executor=executor,
        )
        if pipeline:
            pipe = CommitPipeline(executor, cs.wal)
            pipe.enabled = True
            pipe.spec_enabled = conns.consensus.supports_speculation()
            pipe.note_base(state.last_block_height)
            executor.prune_gate = pipe.durable_height
            cs.pipeline = pipe
            parts["pipe"] = pipe
        nodes.append((cs, parts))
    css = [cs for cs, _ in nodes]
    for i, cs in enumerate(css):  # perfect gossip, as in the tests
        orig = cs._send_internal

        def send(msg, cs=cs, orig=orig, me=i):
            orig(msg)
            for j, other in enumerate(css):
                if j == me:
                    continue
                if isinstance(msg, VoteMessage):
                    other.add_vote_from_peer(msg.vote, f"n{me}")
                elif isinstance(msg, ProposalMessage):
                    other.set_proposal_from_peer(msg.proposal, f"n{me}")
                elif isinstance(msg, BlockPartMessage):
                    other.add_block_part_from_peer(
                        msg.height, msg.round, msg.part, f"n{me}"
                    )

        cs._send_internal = send
    return nodes


def _stop_net(nodes) -> None:
    for cs, parts in nodes:
        for closer in (cs.stop, parts["bus"].stop, parts["conns"].stop):
            try:
                closer()
            except Exception:
                pass


def bench_health_overhead(n_heights: int | None = None):
    """Config 13: flight-recorder overhead on a warmed 4-validator burst.

    The libs/health flight recorder is ON by default for every node, so
    its record path sits inside the consensus FSM (step transitions,
    vote admission, commit latency) and the WAL fsync path. This config
    runs the SAME in-process 4-validator consensus burst with the
    recorder off and on (min-of-2 each, warmup heights excluded) and
    reports the per-commit latency delta — the headline target is <1%.
    A direct nanosecond cost of one ``record()`` call is reported
    alongside, because the burst delta is dominated by consensus
    timeouts and scheduler noise.
    """
    import threading as _threading  # noqa: F401  (parity with config 12)

    from cometbft_tpu.libs import health as libhealth

    if n_heights is None:
        n_heights = _sz(25, 4)
    warm_heights = _sz(3, 1)

    was_on = libhealth.enabled()
    per_off = []
    per_on = []
    records_on = 0
    commits_on = 0
    nodes = _perfect_gossip_net("bench-health")
    store = nodes[0][1]["block_store"]
    try:
        for cs, _ in nodes:
            cs.start()
        deadline = time.monotonic() + 240
        while (
            store.height() < warm_heights and time.monotonic() < deadline
        ):
            time.sleep(0.002)
        if store.height() < warm_heights:
            raise RuntimeError("burst never warmed")
        # Alternate recorder-off / recorder-on WINDOWS over one live
        # net: same threads, same warmed jit/page-cache state, so the
        # off/on delta isolates the record path instead of measuring
        # node-construction and scheduler noise (a fresh-net A/B showed
        # ±5% run-to-run variance at a ~0.05% expected effect).
        for rep in range(3):
            for on in (False, True):
                if on:
                    libhealth.enable()
                    libhealth.reset()
                else:
                    libhealth.disable()
                h0 = store.height()
                rec0 = libhealth.recorder().status()["recorded"]
                t0 = time.perf_counter()
                while (
                    store.height() < h0 + n_heights
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.002)
                dt = time.perf_counter() - t0
                commits = store.height() - h0
                if commits <= 0:
                    raise RuntimeError("burst stalled mid-measurement")
                (per_on if on else per_off).append(dt / commits)
                if on:
                    records_on += (
                        libhealth.recorder().status()["recorded"] - rec0
                    )
                    commits_on += commits
    finally:
        _stop_net(nodes)
        libhealth.enable() if was_on else libhealth.disable()

    # direct record-path cost: tight loop over the four hot call shapes
    libhealth.enable()
    reps = _sz(200_000, 5_000)
    t0 = time.perf_counter()
    for _ in range(reps // 4):
        libhealth.record(libhealth.EV_STEP, 5, 0, 3)
        libhealth.record(libhealth.EV_VOTE, 5, 0, 1, 2)
        libhealth.record(libhealth.EV_COMMIT, 5, 0, 120_000_000)
        libhealth.record(libhealth.EV_FSYNC, a=3_000_000)
    record_ns = (time.perf_counter() - t0) / ((reps // 4) * 4) * 1e9
    libhealth.reset()
    libhealth.enable() if was_on else libhealth.disable()

    off_s, on_s = min(per_off), min(per_on)
    records_per_commit = records_on / max(1, commits_on)
    # The per-commit cost of the recorder IS records/commit x the
    # measured per-record cost: ~60 events x ~2 us ~ 0.1 ms against a
    # ~100 ms commit. The raw A/B delta cannot resolve that — the off-
    # window spread alone is >10% on a shared container — so the
    # headline number is the mechanism-level bound and the raw delta
    # ships alongside with its noise floor as evidence.
    derived_pct = 100.0 * (records_per_commit * record_ns / 1e9) / off_s
    noise_pct = 100.0 * (max(per_off) - min(per_off)) / min(per_off)
    return {
        "heights_per_window": n_heights,
        "windows": len(per_off) + len(per_on),
        "validators": 4,
        "commit_ms_recorder_off": round(off_s * 1e3, 3),
        "commit_ms_recorder_on": round(on_s * 1e3, 3),
        "overhead_pct": round(derived_pct, 4),
        "measured_delta_pct": round(100.0 * (on_s - off_s) / off_s, 2),
        "ab_noise_floor_pct": round(noise_pct, 2),
        "record_ns": round(record_ns, 1),
        "records_per_commit": round(records_per_commit, 1),
        "stat": "min_of_3_alternating_windows",
        "note": "one live 4-validator net, recorder toggled per "
        "window; overhead_pct = records/commit x record_ns / commit "
        "latency (the raw A/B delta, measured_delta_pct, is noise: "
        "its floor is ab_noise_floor_pct)",
    }


def bench_net_propagation(n_heights: int | None = None):
    """Config 15: per-phase gossip propagation over a real TCP net.

    Boots FOUR full nodes (real sockets, real reactors, provenance
    stamps negotiated at handshake) in one process, commits a burst of
    heights, and reports one-hop propagation quantiles per consensus
    phase (proposal/prevote/precommit/commit, from the
    ``p2p_propagation_seconds{phase}`` histogram the stamps feed) plus
    the peak send-queue depth any peer's channel reached — the baseline
    the thousand-validator scenario harness will be judged against.
    In-process nodes share one clock, so the stamp wall hints carry no
    skew and the quantiles are true one-hop latencies.
    """
    import dataclasses
    import shutil
    import tempfile

    from cometbft_tpu.config import default_config
    from cometbft_tpu.libs import health as libhealth
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.libs import netstats as libnetstats
    from cometbft_tpu.node import Node, init_files
    from cometbft_tpu.types import GenesisDoc, GenesisValidator, MockPV
    from cometbft_tpu.crypto.keys import Ed25519PrivKey

    if n_heights is None:
        n_heights = _sz(8, 2)

    def net_config(home):
        cfg = default_config()
        cfg.base.home = home
        cfg.p2p.laddr = "tcp://127.0.0.1:0"
        cfg.rpc.laddr = ""
        cfg.consensus = dataclasses.replace(
            cfg.consensus,
            timeout_propose_ns=800 * 1_000_000,
            timeout_propose_delta_ns=100 * 1_000_000,
            timeout_prevote_ns=400 * 1_000_000,
            timeout_prevote_delta_ns=100 * 1_000_000,
            timeout_precommit_ns=400 * 1_000_000,
            timeout_precommit_delta_ns=100 * 1_000_000,
            timeout_commit_ns=200 * 1_000_000,
            skip_timeout_commit=True,
            peer_gossip_sleep_duration_ns=20 * 1_000_000,
        )
        return cfg

    pvs = [
        MockPV(Ed25519PrivKey.from_seed(bytes([i + 1]) * 32))
        for i in range(4)
    ]
    doc = GenesisDoc(
        chain_id="bench-netprop",
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=[
            GenesisValidator(pub_key=pv.get_pub_key(), power=10)
            for pv in pvs
        ],
    )
    doc.validate_and_complete()

    tmp = tempfile.mkdtemp(prefix="bench-netprop-")
    libnetstats.reset()
    nodes = []
    peak_depth = 0
    drops = 0
    stamped = 0
    try:
        for i, pv in enumerate(pvs):
            cfg = net_config(f"{tmp}/node{i}")
            init_files(cfg)
            nodes.append(Node(cfg, doc, pv))
        nodes[0].start()
        seed_addr = (
            f"{nodes[0].node_key.node_id}@"
            f"{nodes[0].transport.listen_addr[len('tcp://'):]}"
        )
        for node in nodes[1:]:
            node.config.p2p.persistent_peers = seed_addr
            node.start()
        # observations land on the node-metrics stack top = the node
        # started LAST; its histogram aggregates every stamped hop it
        # receives (the other nodes' hops land on... the same top, so
        # the quantiles cover the whole net)
        m = libmetrics.node_metrics()
        t0 = time.perf_counter()
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if all(n.block_store.height() >= n_heights for n in nodes):
                break
            time.sleep(0.05)
        wall_s = time.perf_counter() - t0
        heights = min(n.block_store.height() for n in nodes)
        if heights < 1:
            raise RuntimeError("net never committed a height")
        # harvest BEFORE stopping: connection stats deregister on stop
        snap = libnetstats.snapshot()
        for peer in snap["peers"]:
            for row in peer["channels"]:
                if int(row["chID"], 16) in libnetstats.CONSENSUS_CHANNELS:
                    peak_depth = max(peak_depth, row["queue_highwater"])
                    drops += row["send_queue_full"]
            stamped = max(stamped, peer["stamp"]["rx_seq"])
        phases = {}
        for phase in ("proposal", "block_part", "prevote", "precommit",
                      "commit", "tx"):
            h = m.p2p_propagation.labels(phase)
            if h._n == 0:
                continue
            phases[phase] = {
                "count": h._n,
                "mean_ms": round(h._sum / h._n * 1e3, 3),
                "p50_ms": round(
                    libhealth.histogram_quantile(h, 0.50) * 1e3, 3
                ),
                "p99_ms": round(
                    libhealth.histogram_quantile(h, 0.99) * 1e3, 3
                ),
            }
    finally:
        for node in nodes:
            try:
                if node.is_running():
                    node.stop()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)
    for required in ("proposal", "prevote", "precommit"):
        if required not in phases:
            raise RuntimeError(
                f"no stamped {required} propagation observed: {phases}"
            )
    return {
        "validators": 4,
        "heights": heights,
        "wall_s": round(wall_s, 2),
        "stamped_msgs_max_seq": stamped,
        "propagation_ms": phases,
        "peak_send_queue_depth": peak_depth,
        "send_queue_full_total": drops,
        "gossip_lag_p99_ms": round(snap["gossip_lag_p99_s"] * 1e3, 3),
        "note": "real TCP p2p, provenance stamps negotiated at "
        "handshake; quantiles are promql-style bucket upper bounds "
        "from p2p_propagation_seconds on the shared in-process clock",
    }


class _LazyLightChain:
    """Light-block provider over a virtual H-height chain (bench twin of
    tests/helpers.LazyLightChainProvider): headers hash-chain
    iteratively, commits are signed only for heights the storm actually
    touches — a 10k-height chain costs signatures for ~the distinct
    trust roots, not 40k sign operations up front."""

    def __init__(self, n_heights: int, n_vals: int = 4,
                 chain_id: str = "bench-light-chain"):
        import threading as _threading

        from cometbft_tpu.types.block import (
            BlockID, Header, PartSetHeader, Version,
        )

        self.n_heights = n_heights
        self._chain_id = chain_id
        self._t0 = 1_700_000_000_000_000_000
        self._vs, self._pvs = _make_valset_and_pvs(n_vals)
        self._Header, self._Version = Header, Version
        self._psh = PartSetHeader(total=1, hash=b"\x07" * 32)
        self._BlockID = BlockID
        self._lock = _threading.Lock()
        self._block_ids: list = [BlockID()]
        self._blocks: dict[int, object] = {}

    def chain_id(self) -> str:
        return self._chain_id

    def light_block(self, height: int):
        from cometbft_tpu.light.errors import LightBlockNotFoundError
        from cometbft_tpu.types.light_block import LightBlock, SignedHeader

        if height == 0:
            height = self.n_heights
        if not 1 <= height <= self.n_heights:
            raise LightBlockNotFoundError(height)
        with self._lock:
            while len(self._block_ids) <= height:
                hh = len(self._block_ids)
                header = self._Header(
                    version=self._Version(block=11, app=1),
                    chain_id=self._chain_id,
                    height=hh,
                    time_ns=self._t0 + hh * 1_000_000_000,
                    last_block_id=self._block_ids[hh - 1],
                    last_commit_hash=b"\x01" * 32,
                    data_hash=b"\x02" * 32,
                    validators_hash=self._vs.hash(),
                    next_validators_hash=self._vs.hash(),
                    consensus_hash=b"\x03" * 32,
                    app_hash=b"\x04" * 32,
                    last_results_hash=b"\x05" * 32,
                    evidence_hash=b"\x06" * 32,
                    proposer_address=self._vs.validators[0].address,
                )
                self._block_ids.append(self._BlockID(
                    hash=header.hash(), part_set_header=self._psh,
                ))
                self._blocks[hh] = header
            cached = self._blocks[height]
            if isinstance(cached, LightBlock):
                return cached
            commit = _sign_commit(
                self._chain_id, self._vs, self._pvs, height,
                self._block_ids[height],
            )
            lb = LightBlock(
                signed_header=SignedHeader(header=cached, commit=commit),
                validator_set=self._vs,
            )
            self._blocks[height] = lb
            return lb

    def report_evidence(self, ev) -> None:
        pass


def bench_light_storm(
    device: bool | None = None,
    n_threads: int | None = None,
    n_heights: int | None = None,
):
    """Config 14: sustained many-client skipping-verification storm
    through the light proof service (light/service.py).

    N client threads each request verification of random targets over a
    10k-height chain from randomized trust heights — the RPC-facing
    "millions of users" workload shape. The storm run serves every
    request through ONE shared LightService (commit-result cache +
    single-flight + the cross-caller coalescer); the serial baseline
    runs the IDENTICAL request list through fresh standalone Clients,
    one at a time, with no cache and no coalescer — the per-client cost
    the service amortizes. Reports cache hit rate, coalesce window
    occupancy, and the storm_vs_serial headline.
    """
    import threading as _threading

    from cometbft_tpu.crypto import coalesce as cco
    from cometbft_tpu.libs import metrics as libmetrics
    from cometbft_tpu.light import LightService, MemStore
    from cometbft_tpu.light.client import Client, TrustOptions

    if n_threads is None:
        n_threads = _sz(256, 8)
    if n_heights is None:
        n_heights = _sz(10_000, 64)
    per_thread = _sz(4, 2)  # verification requests per client thread
    period_ns = 30 * 24 * 3600 * 1_000_000_000
    now_ns = 1_700_000_000_000_000_000 + (n_heights + 2) * 1_000_000_000

    provider = _LazyLightChain(n_heights)
    rng = np.random.default_rng(14)
    # request list: random trust gaps — most clients sync to the tip
    # (the production shape), some to random interior heights
    requests = []
    for _ in range(n_threads * per_thread):
        trust_h = int(rng.integers(1, n_heights // 2))
        target = (
            n_heights
            if rng.random() < 0.8
            else int(rng.integers(n_heights // 2, n_heights))
        )
        requests.append((trust_h, target))

    # pre-sign every height the request list touches OUTSIDE both
    # timed windows: the lazy chain's one-time commit signing is test
    # fixture cost, and whichever run goes first would otherwise absorb
    # it and bias storm_vs_serial
    for trust_h, target in requests:
        provider.light_block(trust_h)
        provider.light_block(target)

    # serial baseline: fresh standalone Client per request — no shared
    # cache, no coalescer, the exact work one client pays alone
    t0 = time.perf_counter()
    for trust_h, target in requests:
        root = provider.light_block(trust_h)
        cl = Client(
            chain_id=provider.chain_id(),
            trust_options=TrustOptions(period_ns, trust_h, root.hash()),
            primary=provider,
            trusted_store=MemStore(),
        )
        lb = cl.verify_light_block_at_height(target, now_ns)
        assert lb.height == target
    serial_dt = time.perf_counter() - t0
    serial_rps = len(requests) / serial_dt

    svc = LightService(
        provider,
        provider.chain_id(),
        trusting_period_ns=period_ns,
        max_inflight=n_threads,
        own_coalescer=True,
        coalescer_device=device,
    )
    svc.start()
    metrics = libmetrics.NodeMetrics()
    libmetrics.push_node_metrics(metrics)
    try:
        barrier = _threading.Barrier(n_threads + 1)
        fails: list = []

        def worker(tid):
            my = requests[tid * per_thread : (tid + 1) * per_thread]
            barrier.wait()
            for trust_h, target in my:
                r = svc.verify_at_height(
                    target, trust_height=trust_h, now_ns=now_ns
                )
                if int(r["height"]) != target:
                    fails.append(tid)

        threads = [
            _threading.Thread(target=worker, args=(t,), daemon=True)
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        barrier.wait()
        t1 = time.perf_counter()
        for t in threads:
            t.join()
        storm_dt = time.perf_counter() - t1
        assert not fails, f"storm verification failed on threads {fails[:5]}"
        storm_rps = len(requests) / storm_dt
        cache = svc.cache.stats()
        lookups = cache["hits"] + cache["misses"] + cache["shared"]
        co = svc._own_coalescer
        lanes_hist = metrics.coalesce_window_lanes
        windows = lanes_hist._n
        lanes = lanes_hist._sum
    finally:
        libmetrics.pop_node_metrics(metrics)
        svc.stop()
    return {
        "threads": n_threads,
        "chain_heights": n_heights,
        "requests": len(requests),
        "serial_requests_per_sec": round(serial_rps, 1),
        "storm_requests_per_sec": round(storm_rps, 1),
        "storm_vs_serial": round(storm_rps / serial_rps, 2),
        "cache_hit_rate": round(
            (cache["hits"] + cache["shared"]) / max(1, lookups), 3
        ),
        "cache": cache,
        "coalesce_windows": windows,
        "coalesce_lanes": int(lanes),
        "coalesce_lanes_per_window": round(lanes / max(1, windows), 2),
        "coalesce_tickets": co.tickets if co else 0,
        "coalesce_backend": (
            "device" if co and co.device_windows else "host-window"
        ),
        "note": "identical request lists; serial = fresh standalone "
        "Client per request (no cache/coalescer), storm = one shared "
        "LightService",
    }


def bench_fault_matrix(n_heights: int | None = None):
    """Config 16: commit latency + rounds-per-height across a fault grid
    on the deterministic simnet plane (cometbft_tpu/simnet).

    Each cell is a 4-validator net under one fault mix — clean links,
    20ms latency with jitter, 5%/10% drop, and a mid-run partition/heal
    cycle — run to the same height from the same seed, so the grid is
    bit-reproducible and cross-round comparable: quantiles are VIRTUAL
    time (the protocol's cost under that fault), wall_s is what the
    simulation itself cost.  Pure host workload.
    """
    if n_heights is None:
        n_heights = _sz(6, 3)
    t0 = time.perf_counter()
    grid = {}
    for name, link, special in _fault_matrix_cells():
        cell, _export = _run_fault_cell(
            name, link, special, n_heights
        )
        m = cell.pop("_commit_metrics")
        grid[name] = {
            **cell,
            "commit_ms_p50": m["commit_ms"]["p50"],
            "commit_ms_p99": m["commit_ms"]["p99"],
            "rounds_mean": m["rounds_per_height"]["mean"],
            "rounds_p99": m["rounds_per_height"]["p99"],
        }
    return {
        "n_nodes": 4,
        "heights": n_heights,
        "seed": 16,
        "grid": grid,
        "wall_s": round(time.perf_counter() - t0, 2),
        "note": "virtual-time quantiles from the seeded simnet; the "
        "same (seed, grid) reproduces identical numbers",
    }


def _fault_matrix_cells():
    """The shared fault grid (configs 16 + 17): one LinkConfig mix per
    cell, same seed, so both benches and the postmortem acceptance test
    read the identical deterministic runs."""
    from cometbft_tpu.simnet import LinkConfig

    ms = 1_000_000
    return [
        ("clean", LinkConfig(), None),
        (
            "lat20_jit10",
            LinkConfig(latency_ns=20 * ms, jitter_ns=10 * ms),
            None,
        ),
        ("drop05", LinkConfig(drop_p=0.05, jitter_ns=3 * ms), None),
        (
            "drop10_lat20",
            LinkConfig(
                drop_p=0.10, latency_ns=20 * ms, jitter_ns=10 * ms
            ),
            None,
        ),
        ("partition_heal", LinkConfig(), "partition"),
        # gray-failure family (PR 13): asymmetric sever, slow-but-alive
        # disk, and a mid-run statesync join that loses a serving peer
        ("gray_partition", LinkConfig(), "oneway"),
        ("slow_disk", LinkConfig(), "slow_disk"),
        ("statesync_join", LinkConfig(), "statesync_join"),
    ]


# (seed, n_heights, cell) -> (cell_row, ring export): configs 16 and
# 17 read the IDENTICAL deterministic runs, so the second config reuses
# the first's results instead of re-simulating the whole grid
_FAULT_CELL_CACHE: dict = {}


def _run_fault_cell(name, link, special, n_heights, seed=16):
    """Run ONE fault cell to ``n_heights``; returns (cell_row,
    flight-ring export).  Timeouts are sized to tolerate the grid's
    worst link latency, so rounds-per-height measures the FAULTS
    (drops, partitions), not a timeout-vs-RTT mismatch.  Results are
    memoized per (seed, heights, cell) — the runs are bit-deterministic
    by construction, so the cache is an identity, not an approximation."""
    key = (seed, n_heights, name)
    hit = _FAULT_CELL_CACHE.get(key)
    if hit is not None:
        cell, export = hit
        return dict(cell), export
    import dataclasses

    from cometbft_tpu.config import test_config
    from cometbft_tpu.libs import health as libhealth
    from cometbft_tpu.simnet import SimNet
    from cometbft_tpu.simnet.scenarios import SCENARIO_RING, commit_metrics

    ms = 1_000_000
    cfg = test_config()
    cfg.consensus = dataclasses.replace(
        cfg.consensus,
        timeout_propose_ns=150 * ms,
        timeout_propose_delta_ns=50 * ms,
        timeout_prevote_ns=80 * ms,
        timeout_prevote_delta_ns=40 * ms,
        timeout_precommit_ns=80 * ms,
        timeout_precommit_delta_ns=40 * ms,
        timeout_commit_ns=20 * ms,
    )
    was_enabled = libhealth.enabled()
    prev_ring = libhealth.recorder().capacity
    libhealth.set_ring_capacity(SCENARIO_RING)
    libhealth.reset()
    libhealth.enable()
    if special == "statesync_join":
        # 4 validators + one LATE full node: grow the chain, then join
        # it mid-run via the real statesync path, killing one serving
        # peer mid-restore (the injected fault the attributor must name)
        from cometbft_tpu.abci.kvstore import KVStoreApplication
        from cometbft_tpu.simnet.net import make_genesis

        genesis, pvs = make_genesis(4)
        net = SimNet(
            5, seed=seed, config=cfg, default_link=link,
            genesis=genesis, pvs=pvs, late=(4,),
            app_factory=lambda idx: KVStoreApplication(snapshot_interval=5),
        )
    else:
        net = SimNet(4, seed=seed, config=cfg, default_link=link)
    try:
        net.start()
        if special == "partition":
            net.run_until_height(2, max_virtual_ms=60_000)
            net.partition([0, 1], [2, 3])
            net.run(max_virtual_ms=1_500)
            net.heal()
        elif special == "oneway":
            net.run_until_height(2, max_virtual_ms=60_000)
            net.sever_oneway(0, 1)
            net.run_until_height(
                max(net.heights()) + 2, max_virtual_ms=240_000
            )
            net.heal()
        elif special == "slow_disk":
            net.run_until_height(2, max_virtual_ms=60_000)
            net.set_slow_disk(1, 120 * ms, 30 * ms)
            net.run_until_height(
                max(net.heights()) + 2, max_virtual_ms=600_000
            )
            net.set_slow_disk(1, 0)
        elif special == "statesync_join":
            vals = [0, 1, 2, 3]
            net.run_until_height(12, nodes=vals, max_virtual_ms=600_000)
            net.join_statesync(4, trust_height=1, chunk_timeout_s=0.5)
            jn = net.nodes[4]
            net.run(
                until=lambda: jn.statesync_state["phase"] != "discover",
                max_virtual_ms=60_000,
            )
            net.kill(1)  # a serving peer dies mid-restore
            net.run(
                until=lambda: (
                    jn.alive
                    and jn.statesync_state["phase"] == "switched"
                ),
                max_virtual_ms=600_000,
            )
        if special == "statesync_join":
            ok = net.run_until_height(
                n_heights,
                nodes=[i for i in range(5) if net.nodes[i].alive],
                max_virtual_ms=600_000,
            )
        else:
            ok = net.run_until_height(n_heights, max_virtual_ms=600_000)
        net.assert_no_fork()
        cell = {
            "ok": ok,
            "virtual_ms": round(net.clock.now_ns / 1e6, 1),
            "events": net._events_run,
            "dropped": net.stats.get("dropped", 0),
            "_commit_metrics": commit_metrics(),
        }
        export = libhealth.export_ring()
    finally:
        net.stop()
        if not was_enabled:
            libhealth.disable()
        libhealth.set_ring_capacity(prev_ring)
    _FAULT_CELL_CACHE[key] = (dict(cell), export)
    return cell, export


# faulty cell -> the cause set the attributor must top-rank (config 17
# + the acceptance test in tests/test_postmortem.py); the combined
# drop+latency cell accepts either of its two injected faults
_FAULT_CELL_EXPECTED = {
    "lat20_jit10": ("injected_latency",),
    "drop05": ("injected_drop",),
    "drop10_lat20": ("injected_drop", "injected_latency"),
    "partition_heal": ("injected_partition",),
    "gray_partition": ("gray_partition",),
    "slow_disk": ("slow_disk",),
    # the join itself is not a fault; the injected fault in that cell
    # is the serving peer killed mid-restore
    "statesync_join": ("injected_churn",),
}


def bench_postmortem_attribution(n_heights: int | None = None):
    """Config 17: the cross-node postmortem attributor over the
    16_fault_matrix grid — each cell's flight ring is merged into a
    per-height timeline (cometbft_tpu/postmortem) and the run verdict
    scored against the fault that was actually injected.

    Headline ``postmortem_attribution_rate`` = fraction of FAULTY cells
    whose top-ranked root cause names the injected fault; the healthy
    cell must stay silent (no verdict above the report threshold).
    Deterministic per (seed, grid); host-only workload."""
    from cometbft_tpu.postmortem import report_from_ring

    if n_heights is None:
        n_heights = _sz(6, 3)
    t0 = time.perf_counter()
    cells = {}
    matched = 0
    healthy_clean = None
    for name, link, special in _fault_matrix_cells():
        _cell, export = _run_fault_cell(name, link, special, n_heights)
        _tl, rep = report_from_ring(export)
        top = rep.run.verdict
        expected = _FAULT_CELL_EXPECTED.get(name)
        row = {
            "top_cause": top.cause if top else None,
            "top_score": round(top.score, 3) if top else None,
            "slow_heights": len(rep.slow_heights),
            "attributed_heights": sum(
                1 for w in rep.slow_heights if w.verdict is not None
            ),
        }
        if expected is None:
            healthy_clean = top is None
            row["expected"] = None
        else:
            row["expected"] = list(expected)
            row["match"] = top is not None and top.cause in expected
            matched += bool(row["match"])
        cells[name] = row
    n_faulty = len(_FAULT_CELL_EXPECTED)
    return {
        "n_nodes": 4,
        "heights": n_heights,
        "seed": 16,
        "cells": cells,
        "postmortem_attribution_rate": round(matched / n_faulty, 3),
        "healthy_clean": healthy_clean,
        "wall_s": round(time.perf_counter() - t0, 2),
        "note": "run-verdict top cause vs the injected fault, per "
        "16_fault_matrix cell; deterministic per (seed, grid)",
    }


def bench_hash_plane(device: bool | None = None, n_threads: int | None = None):
    """Config 18: the device hash plane (crypto/hashplane + ops/sha256)
    on its two hot shapes.

    (a) block-propose -> PartSet build: split a multi-MB block into
        64 KiB parts with merkle proofs (types/part_set.from_data),
        plane-routed vs plain host — the leaf hashing IS the byte-
        hashing bill of proposing a large block;
    (b) a mempool hash storm: concurrent CheckTx threads over a live
        CListMempool + kvstore app, whose per-tx SHA-256 keys coalesce
        into shared windows, vs the identical storm with no plane
        routed (plain hashlib) — the headline carries the ratio as
        ``hash_storm_vs_serial``.

    ``device=None`` probes the backend; under a ``device=False`` pin
    the routed helpers BY DESIGN queue nothing
    (SHA-256 has no host batch win) — that row measures the fallback
    staying at serial parity, not a speedup.
    """
    import threading as _threading

    from cometbft_tpu.abci.client import LocalClient
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import MempoolConfig
    from cometbft_tpu.crypto import hashplane as hpl
    from cometbft_tpu.mempool import CListMempool
    from cometbft_tpu.ops import sha256 as osha
    from cometbft_tpu.types.part_set import PartSet

    if device is None:
        from cometbft_tpu.libs.accel import accelerator_backend

        device = accelerator_backend()
    if n_threads is None:
        n_threads = _sz(32, 4)
    n_parts = _sz(64, 4)
    tx_bytes = 2048  # above the plane's single-message routing floor
    per_thread = _sz(64, 8)
    rng = np.random.default_rng(18)
    block_data = rng.integers(
        0, 256, size=n_parts * 65536 - 7, dtype=np.uint8
    ).tobytes()

    if device:
        # Warm every (block-bucket, lane-bucket) pair the two workloads
        # can launch, via direct kernel calls — cold XLA compiles inside
        # a routed window would trip the plane's wedge breaker and the
        # timed run would measure the cooldown, not the kernel.
        tx_bb = osha.block_bucket(osha.n_blocks(tx_bytes))
        lanes = 8
        while lanes <= osha.lane_bucket(n_threads):
            osha.sha256_many_async([b"w" * tx_bytes] * lanes, tx_bb)()
            lanes *= 2
        leaf_bb = osha.block_bucket(osha.n_blocks(65536 + 1))
        osha.sha256_many_async(
            [b"l" * 65537] * min(8, n_parts), leaf_bb
        )()
        if n_parts > 8:
            osha.sha256_many_async([b"l" * 65537] * n_parts, leaf_bb)()
        osha.sha256_many_async([b"i" * 65] * max(2, n_parts // 2), 2)()

    # -- (a) PartSet build, host then routed ------------------------------
    build_host_s = _steady(lambda: PartSet.from_data(block_data))
    co = hpl.HashCoalescer(device=device, min_device_lanes=8)
    co.start()
    hpl.push_active(co)
    try:
        header_host = PartSet.from_data(block_data).header
        build_routed_s = _steady(lambda: PartSet.from_data(block_data))
        header_routed = PartSet.from_data(block_data).header
        assert header_routed == header_host, "routed PartSet root diverged"

        # -- (b) mempool hash storm ---------------------------------------
        def storm(routed: bool):
            app = KVStoreApplication()
            client = LocalClient(app)
            client.start()
            try:
                mp = CListMempool(
                    MempoolConfig(size=n_threads * per_thread + 16),
                    client,
                )
                barrier = _threading.Barrier(n_threads + 1)
                fails: list = []
                # per-thread payloads, generated before the threads
                # start (the shared Generator is not thread-safe)
                bases = [
                    rng.integers(0, 256, size=tx_bytes,
                                 dtype=np.uint8).tobytes()
                    for _ in range(n_threads)
                ]

                def worker(tid):
                    base = bases[tid]
                    barrier.wait()
                    for i in range(per_thread):
                        tx = b"%d:%d:" % (tid, i) + base
                        try:
                            mp.check_tx(tx[:tx_bytes])
                        except Exception as e:
                            fails.append(repr(e))

                threads = [
                    _threading.Thread(
                        target=worker, args=(t,), daemon=True
                    )
                    for t in range(n_threads)
                ]
                for t in threads:
                    t.start()
                barrier.wait()
                t0 = time.perf_counter()
                for t in threads:
                    t.join()
                dt = time.perf_counter() - t0
                assert not fails, fails[:3]
                assert mp.size() == n_threads * per_thread
                return n_threads * per_thread / dt
            finally:
                client.stop()

        hpl.pop_active(co)
        serial_tps = storm(routed=False)
        hpl.push_active(co)
        storm(routed=True)  # warm the plane's window path
        # classify the STORM's own windows: the PartSet phase above
        # already launched device windows on this coalescer, and an
        # all-time counter would label a host-fallback storm "device"
        w0, dw0 = co.windows, co.device_windows
        storm_tps = storm(routed=True)
        storm_windows = co.windows - w0
        storm_backend = (
            "device" if co.device_windows > dw0 else
            ("host-window" if storm_windows else "unrouted")
        )
        windows = co.windows
    finally:
        hpl.pop_active(co)
        co.stop()
    return {
        "parts": n_parts,
        "block_mb": round(len(block_data) / 2**20, 2),
        "partset_build_host_ms": round(build_host_s * 1e3, 2),
        "partset_build_routed_ms": round(build_routed_s * 1e3, 2),
        "partset_build_vs_host": round(build_host_s / build_routed_s, 2),
        "storm_threads": n_threads,
        "storm_txs": n_threads * per_thread,
        "tx_bytes": tx_bytes,
        "serial_checktx_per_sec": round(serial_tps, 1),
        "coalesced_checktx_per_sec": round(storm_tps, 1),
        "hash_storm_vs_serial": round(storm_tps / serial_tps, 2),
        "storm_backend": storm_backend,
        "storm_windows": storm_windows,
        "windows": windows,
        "note": "same digests, same call sites; routed runs send TxKey "
        "and PartSet/merkle hashing through crypto/hashplane windows",
    }


def bench_device_ledger(
    n_heights: int | None = None,
    device: bool = False,
    light_threads: int | None = None,
    hash_threads: int | None = None,
):
    """Config 19: mixed-tenant storm through the device-time ledger.

    One live 4-validator consensus burst (the config-13 harness) shares
    a routed VerifyCoalescer and HashCoalescer with a light-service
    verify storm and a CheckTx-shaped hash storm, every submit tagged
    with its caller class (libs/devledger).  Headlines: the
    consensus-caller queue-wait p99 under tenant pressure, per-caller
    lane/time shares, the ledger-reconciliation check (caller-
    attributed time sums to total window time within 1%), and the
    per-height budget coverage (stages explain >=90% of measured
    commit latency).  ``device=False`` pins every window to the host
    path — attribution and reconciliation are
    path-independent, which is exactly what this config proves.
    """
    import threading as _threading

    from cometbft_tpu.crypto import coalesce as crypto_coalesce
    from cometbft_tpu.crypto import hashplane as crypto_hashplane
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.libs import devledger as libdevledger
    from cometbft_tpu.libs import health as libhealth
    from cometbft_tpu.libs import metrics as libmetrics

    if n_heights is None:
        n_heights = _sz(12, 3)
    if light_threads is None:
        light_threads = _sz(8, 2)
    if hash_threads is None:
        hash_threads = _sz(4, 1)
    warm_heights = _sz(2, 1)

    ledger_was = libdevledger.enabled()
    health_was = libhealth.enabled()
    prev_ring = libhealth.recorder().capacity
    libdevledger.enable()
    libdevledger.reset()
    libhealth.enable(ring=16384)
    libhealth.reset()
    m = libmetrics.NodeMetrics()
    libmetrics.push_node_metrics(m)
    # EVERYTHING fallible — plane construction, the net, the burst, the
    # derive section — runs inside the restore scope below, so no
    # failure path can leak the pushed metrics, the forced-on
    # ledger/health, or the 4x ring into later configs
    co = crypto_coalesce.VerifyCoalescer(
        device=device,
        # device rounds pin the cut low (the config-12 rationale: storm
        # windows cap at thread count, far below the live crossover);
        # host rounds coalesce into one host MSM per window either way
        min_device_lanes=8 if device else (1 << 30),
    )
    hco = crypto_hashplane.HashCoalescer(
        device=device, min_device_lanes=8 if device else (1 << 30)
    )

    # pre-signed storm material
    lk = Ed25519PrivKey.from_seed(b"\x77" * 32)
    lpub = lk.pub_key().data
    lmsgs = [b"light-proof-%d" % i for i in range(4)]
    lsigs = [lk.sign(msg) for msg in lmsgs]
    lpubs = [lpub] * 4
    tx = b"\xab" * 2048
    stop = _threading.Event()
    storm_counts = {"light": 0, "hash": 0}

    def light_storm():
        n = 0
        while not stop.is_set():
            with libdevledger.caller_class("light"):
                bits = co.try_verify(lpubs, lmsgs, lsigs)
            if bits is not None:
                n += len(bits)
        storm_counts["light"] += n

    def hash_storm():
        n = 0
        while not stop.is_set():
            with libdevledger.caller_class("mempool"):
                digs = hco.try_hash_many([tx] * 8)
            if digs is not None:
                n += len(digs)
        storm_counts["hash"] += n

    threads = []
    nodes = []
    t_burst = 0.0
    routed = False
    try:
        try:
            co.start()
            crypto_coalesce.push_active(co)
            hco.start()
            crypto_hashplane.push_active(hco)
            routed = True
            nodes = _perfect_gossip_net("bench-ledger")
            store = nodes[0][1]["block_store"]
            for cs, _ in nodes:
                cs.start()
            deadline = time.monotonic() + 240
            while (
                store.height() < warm_heights
                and time.monotonic() < deadline
            ):
                time.sleep(0.002)
            if store.height() < warm_heights:
                raise RuntimeError("ledger burst never warmed")
            for fn in (
                [light_storm] * light_threads
                + [hash_storm] * hash_threads
            ):
                t = _threading.Thread(target=fn, daemon=True)
                t.start()
                threads.append(t)
            h0 = store.height()
            t0 = time.perf_counter()
            while (
                store.height() < h0 + n_heights
                and time.monotonic() < deadline
            ):
                time.sleep(0.002)
            t_burst = time.perf_counter() - t0
            commits = store.height() - h0
            if commits <= 0:
                raise RuntimeError("ledger burst stalled")
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            _stop_net(nodes)
            if routed:
                crypto_hashplane.pop_active(hco)
                crypto_coalesce.pop_active(co)
            for svc in (hco, co):
                try:
                    if svc.is_running():
                        svc.stop()
                except Exception:
                    pass
        # -- derive the row from the ledger + ring (still inside the
        # restore scope: a failure here must not leak the pushed
        # metrics, the forced-on ledger/health, or the 4x ring into
        # the configs that run after this one)
        snap = libdevledger.snapshot()
        recon = snap["reconciliation"]
        recon_ok = all(
            r["window_ns"] == 0 or abs(1.0 - r["ratio"]) <= 0.01
            for r in recon.values()
        )

        def _p99_ms(callers) -> float:
            fam = m.device_queue_wait
            nb = len(fam.buckets) + 1
            counts = [0] * nb
            for name in callers:
                child = fam.labels("verify", name)
                for i in range(nb):
                    counts[i] += child._counts[i]
            return round(
                libmetrics.quantile_from_buckets(
                    fam.buckets, counts, 0.99
                )
                * 1e3,
                3,
            )

        cons_p99 = _p99_ms(("consensus-vote", "proposal", "commit-verify"))
        light_p99 = _p99_ms(("light",))
        shares = {}
        for plane, rows in snap["callers"].items():
            total_lanes = sum(r["lanes"] for r in rows.values()) or 1
            total_t = sum(
                r["execute_s"] + r["host_s"] for r in rows.values()
            ) or 1.0
            shares[plane] = {
                name: {
                    "lane_pct": round(
                        100.0 * r["lanes"] / total_lanes, 1
                    ),
                    "time_pct": round(
                        100.0 * (r["execute_s"] + r["host_s"]) / total_t,
                        1,
                    ),
                }
                for name, r in rows.items()
            }
        bud = libhealth.budget()
    finally:
        libmetrics.pop_node_metrics(m)
        libdevledger.enable() if ledger_was else libdevledger.disable()
        libhealth.enable() if health_was else libhealth.disable()
        # the 4x ring this config sized for its own burst must not tax
        # (or pollute) every config that runs after it in the process
        libhealth.set_ring_capacity(prev_ring)
    return {
        "heights": n_heights,
        "burst_s": round(t_burst, 2),
        "light_threads": light_threads,
        "hash_threads": hash_threads,
        "light_lanes": storm_counts["light"],
        "hash_lanes": storm_counts["hash"],
        "consensus_wait_p99_ms": cons_p99,
        "light_wait_p99_ms": light_p99,
        "caller_share_pct": shares,
        "reconciliation": {
            plane: {
                "ratio": r["ratio"],
                "window_ms": round(r["window_ns"] / 1e6, 2),
            }
            for plane, r in recon.items()
        },
        "reconciled_within_1pct": recon_ok,
        "budget_coverage": bud["coverage"],
        "budget_stage_fractions": bud["stage_fractions"],
        "occupancy": snap["occupancy"],
        "note": "4-val burst + light verify storm + CheckTx hash storm "
        "over shared planes; shares/reconciliation from the lock-free "
        "devledger columns, budget from the flight ring",
    }


def bench_lock_contention(
    n_heights: int | None = None,
    device: bool = False,
    verify_threads: int | None = None,
    hash_threads: int | None = None,
):
    """Config 21: per-lock wait shares + commit-chain serial occupancy.

    One live 4-validator consensus burst (the config-13 harness) runs
    with the lock-contention profiler on while a routed verify storm
    and a CheckTx-shaped hash storm pressure the shared coalescer
    planes — the mixed-tenant shape of config 19, instrumented for
    locks instead of device time.  Headlines: each engine lock's share
    of total blocked time, the commit chain's serial occupancy (hold
    time of consensus.state / consensus.wal._mtx / store.block_store's
    mutex over burst wall time — the ceiling the pipelined-heights
    refactor attacks), and a critical-path verdict (stage x lock x
    plane) for every committed height with its budget coverage.  The
    record-path overhead is bounded mechanism-level, the config-13
    methodology: measured per-acquire profiled-vs-raw delta x acquires
    per commit / commit latency.  The burst runs the live default
    engine — since the pipelined-heights PR that means the pipelined
    commit chain — so diffing this row against the PR 17 round with
    ``bench.py --compare`` shows the occupancy drop the refactor
    bought (lock_wait*/contended*/occupancy fragments classify
    lower-better there); config 23 carries the explicit
    serial-vs-pipelined A/B on one net.
    """
    import threading as _threading

    from cometbft_tpu.crypto import coalesce as crypto_coalesce
    from cometbft_tpu.crypto import hashplane as crypto_hashplane
    from cometbft_tpu.crypto.keys import Ed25519PrivKey
    from cometbft_tpu.libs import health as libhealth
    from cometbft_tpu.libs import lockprof as liblockprof
    from cometbft_tpu.libs import sync as libsync

    if n_heights is None:
        n_heights = _sz(12, 3)
    if verify_threads is None:
        verify_threads = _sz(8, 2)
    if hash_threads is None:
        hash_threads = _sz(4, 1)
    warm_heights = _sz(2, 1)

    prof_was = liblockprof.enabled()
    health_was = libhealth.enabled()
    prev_ring = libhealth.recorder().capacity
    liblockprof.enable()
    liblockprof.reset()
    # a 5 ms slow line (vs the 50 ms default) so the burst's contended
    # waits actually emit EV_LOCK rows for the per-height lock join
    liblockprof.set_slow_ms(5.0)
    libhealth.enable(ring=16384)
    libhealth.reset()

    co = crypto_coalesce.VerifyCoalescer(
        device=device,
        min_device_lanes=8 if device else (1 << 30),
    )
    hco = crypto_hashplane.HashCoalescer(
        device=device, min_device_lanes=8 if device else (1 << 30)
    )
    lk = Ed25519PrivKey.from_seed(b"\x55" * 32)
    lpub = lk.pub_key().data
    lmsgs = [b"contention-%d" % i for i in range(4)]
    lsigs = [lk.sign(msg) for msg in lmsgs]
    lpubs = [lpub] * 4
    tx = b"\xcd" * 2048
    stop = _threading.Event()

    def verify_storm():
        while not stop.is_set():
            co.try_verify(lpubs, lmsgs, lsigs)

    def hash_storm():
        while not stop.is_set():
            hco.try_hash_many([tx] * 8)

    threads = []
    nodes = []
    t_burst = 0.0
    commits = 0
    routed = False
    try:
        try:
            co.start()
            crypto_coalesce.push_active(co)
            hco.start()
            crypto_hashplane.push_active(hco)
            routed = True
            nodes = _perfect_gossip_net("bench-lockprof")
            store = nodes[0][1]["block_store"]
            for cs, _ in nodes:
                cs.start()
            deadline = time.monotonic() + 240
            while (
                store.height() < warm_heights
                and time.monotonic() < deadline
            ):
                time.sleep(0.002)
            if store.height() < warm_heights:
                raise RuntimeError("contention burst never warmed")
            for fn in (
                [verify_storm] * verify_threads
                + [hash_storm] * hash_threads
            ):
                t = _threading.Thread(target=fn, daemon=True)
                t.start()
                threads.append(t)
            liblockprof.reset()  # the measured columns start here
            h0 = store.height()
            t0 = time.perf_counter()
            while (
                store.height() < h0 + n_heights
                and time.monotonic() < deadline
            ):
                time.sleep(0.002)
            t_burst = time.perf_counter() - t0
            commits = store.height() - h0
            if commits <= 0:
                raise RuntimeError("contention burst stalled")
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            _stop_net(nodes)
            if routed:
                crypto_hashplane.pop_active(hco)
                crypto_coalesce.pop_active(co)
            for svc in (hco, co):
                try:
                    if svc.is_running():
                        svc.stop()
                except Exception:
                    pass
        # -- derive the row (still inside the restore scope)
        snap = liblockprof.snapshot()
        total_wait = snap["total_wait_s"] or 1e-12
        wait_shares = {
            name: round(100.0 * row["wait_s"] / total_wait, 1)
            for name, row in sorted(
                snap["locks"].items(),
                key=lambda kv: -kv[1]["wait_s"],
            )
            if row["wait_s"] > 0
        }
        total_acquires = sum(
            row["acquires"] for row in snap["locks"].values()
        )
        # commit-chain serial occupancy: the single-writer
        # save->fsync->apply chain's lock holds over burst wall time
        chain_locks = (
            "consensus.state", "consensus.wal._mtx",
            "store.block_store._mtx",
        )
        chain_hold_s = sum(
            snap["locks"].get(name, {}).get("hold_s", 0.0)
            for name in chain_locks
        )
        chain_acquires = sum(
            snap["locks"].get(name, {}).get("acquires", 0)
            for name in chain_locks
        )
        cp = libhealth.critical_path()

        # mechanism-level record-path overhead (the config-13
        # methodology): per-acquire profiled-vs-raw delta from tight
        # uncontended loops x acquires/commit / commit latency
        reps = _sz(100_000, 5_000)
        probe = libsync.Mutex(name="bench.lockprof_probe")
        raw = _threading.Lock()
        t0 = time.perf_counter()
        for _ in range(reps):
            with probe:
                pass
        profiled_ns = (time.perf_counter() - t0) / reps * 1e9
        t0 = time.perf_counter()
        for _ in range(reps):
            with raw:
                pass
        raw_ns = (time.perf_counter() - t0) / reps * 1e9
        commit_s = t_burst / commits
        acquires_per_commit = total_acquires / commits
        # only commit-chain acquires serialize into commit latency —
        # storm/plane threads' acquires overlap the FSM wall on other
        # threads, so charging ALL acquires to the commit would
        # overstate the record path's cost by the storm's fan-out
        chain_acquires_per_commit = chain_acquires / commits
        overhead_pct = (
            100.0
            * chain_acquires_per_commit
            * max(0.0, profiled_ns - raw_ns)
            / 1e9
            / commit_s
        )
    finally:
        liblockprof.set_slow_ms(liblockprof.slow_threshold_s() * 1e3)
        liblockprof.enable() if prof_was else liblockprof.disable()
        libhealth.enable() if health_was else libhealth.disable()
        libhealth.set_ring_capacity(prev_ring)
    return {
        "heights": commits,
        "burst_s": round(t_burst, 2),
        "validators": 4,
        "verify_threads": verify_threads,
        "hash_threads": hash_threads,
        "commit_ms": round(commit_s * 1e3, 2),
        "lock_wait_total_s": snap["total_wait_s"],
        "lock_hold_total_s": snap["total_hold_s"],
        "lock_wait_share_pct": wait_shares,
        "hottest_lock": snap["hottest"],
        "contended_acquires": sum(
            row["contended"] for row in snap["locks"].values()
        ),
        # per-validator serial fraction: 4 validators each run their
        # own save->fsync->apply chain over the one shared wall
        "commit_chain_occupancy_pct": round(
            100.0 * chain_hold_s / (t_burst * 4), 1
        ),
        "critical_path_heights": cp["commits"],
        "critical_path_coverage": cp["coverage"],
        "critical_path_gates": cp["gates"],
        "verdict_every_commit": cp["commits"] >= commits,
        "profiled_acquire_ns": round(profiled_ns, 1),
        "raw_acquire_ns": round(raw_ns, 1),
        "acquires_per_commit": round(acquires_per_commit, 1),
        "chain_acquires_per_commit": round(chain_acquires_per_commit, 1),
        "overhead_pct": round(overhead_pct, 4),
        "note": "4-val burst + routed verify/hash storms with the lock "
        "profiler on; wait shares / per-validator chain occupancy from "
        "the lock-free lockprof columns, per-height verdicts from "
        "libs/health.critical_path; overhead_pct = commit-chain "
        "acquires/commit x (profiled - raw) acquire cost / commit "
        "latency (the config-13 mechanism bound; plane-thread acquires "
        "overlap the wall and are reported via acquires_per_commit)",
    }


def bench_profile_overhead(n_heights: int | None = None):
    """Config 22: sampling-profiler overhead on a warmed 4-validator
    burst, plus a profiled fault-matrix clean cell.

    The libs/profile sampler is refcounted into node boot (the
    devstats pattern), so its stack walk sits against every running
    node.  This config runs the config-13 harness — one live net,
    alternating sampler-off/on windows, min-of-window per-commit
    latency — and reports the mechanism-level bound as the headline:
    the sampler taxes the engine through the GIL at hz x the measured
    per-tick walk cost (taken against the live net's REAL thread
    count), and that interpreter share IS the commit-latency tax; the
    raw A/B delta cannot resolve ~0.1% against a >10% window noise
    floor, so it ships alongside as evidence.  The clean
    16_fault_matrix cell then runs under the profiler:
    scheduler-vs-verify-vs-engine wall shares (frame-module
    classification — a simnet run executes on one scheduler thread)
    plus the silence contract that the profiled healthy cell still
    yields no verdict (cpu_saturated or otherwise).
    """
    from cometbft_tpu.libs import profile as libprofile
    from cometbft_tpu.postmortem import report_from_ring
    from cometbft_tpu.simnet import LinkConfig

    if n_heights is None:
        n_heights = _sz(25, 4)
    warm_heights = _sz(3, 1)

    was_on = libprofile.enabled()
    per_off: list = []
    per_on: list = []
    samples_on = 0
    commits_on = 0
    tick_ns = 0.0
    nodes = _perfect_gossip_net("bench-profile")
    store = nodes[0][1]["block_store"]
    try:
        for cs, _ in nodes:
            cs.start()
        deadline = time.monotonic() + 240
        while (
            store.height() < warm_heights and time.monotonic() < deadline
        ):
            time.sleep(0.002)
        if store.height() < warm_heights:
            raise RuntimeError("burst never warmed")
        # alternating sampler-off/on windows over ONE live net (the
        # config-13 discipline: same threads, same warmed state)
        for rep in range(3):
            for on in (False, True):
                if on:
                    libprofile.reset()
                    libprofile.enable()
                else:
                    libprofile.disable()
                h0 = store.height()
                s0 = libprofile.status()["ring"]["recorded"]
                t0 = time.perf_counter()
                while (
                    store.height() < h0 + n_heights
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.002)
                dt = time.perf_counter() - t0
                commits = store.height() - h0
                if commits <= 0:
                    raise RuntimeError("burst stalled mid-measurement")
                (per_on if on else per_off).append(dt / commits)
                if on:
                    samples_on += (
                        libprofile.status()["ring"]["recorded"] - s0
                    )
                    commits_on += commits
        # direct walk cost against the live net's real thread count:
        # one _tick() is the entire per-period bill the sampler pays.
        # thread_time (not wall) — the net is still committing, and
        # wall per tick would double-count GIL waits the engine keeps.
        # min of 3 trials: the steady warm-cache cost is the mechanism
        # bound; churn ticks (novel stacks mid-commit) land in the max
        libprofile.disable()
        sampler = libprofile._SamplerThread(libprofile.DEFAULT_HZ)
        reps = _sz(200, 30)
        for _ in range(_sz(30, 10)):
            sampler._tick()
        trials = []
        for _ in range(3):
            t0 = time.thread_time_ns()
            for _ in range(reps):
                sampler._tick()
            trials.append((time.thread_time_ns() - t0) / reps)
        tick_ns = min(trials)
    finally:
        _stop_net(nodes)
        libprofile.reset()
        libprofile.enable() if was_on else libprofile.disable()

    off_s, on_s = min(per_off), min(per_on)
    samples_per_commit = samples_on / max(1, commits_on)
    # hz ticks/second x walk cost = the sampler's interpreter share;
    # through the GIL that share is the commit-latency tax
    derived_pct = 100.0 * libprofile.DEFAULT_HZ * tick_ns / 1e9
    noise_pct = 100.0 * (max(per_off) - min(per_off)) / min(per_off)

    # the profiled clean cell (seed 22: its cache key never collides
    # with the 16/17 grid) — wall shares + the silence contract
    libprofile.reset()
    libprofile.enable()
    before = libprofile.snapshot_agg()
    try:
        _cell, export = _run_fault_cell(
            "clean", LinkConfig(), None, _sz(6, 3), seed=22
        )
        shares = libprofile.module_shares(
            libprofile.delta_agg(before, libprofile.snapshot_agg())
        )
        _tl, rep = report_from_ring(export)
        clean_silent = rep.run.verdict is None and not any(
            f.cause == "cpu_saturated"
            for w in rep.slow_heights
            for f in w.findings
        )
    finally:
        libprofile.reset()
        libprofile.enable() if was_on else libprofile.disable()

    return {
        "heights_per_window": n_heights,
        "windows": len(per_off) + len(per_on),
        "validators": 4,
        "hz": libprofile.DEFAULT_HZ,
        "commit_ms_profiler_off": round(off_s * 1e3, 3),
        "commit_ms_profiler_on": round(on_s * 1e3, 3),
        "overhead_pct": round(derived_pct, 4),
        "measured_delta_pct": round(100.0 * (on_s - off_s) / off_s, 2),
        "ab_noise_floor_pct": round(noise_pct, 2),
        "tick_ns": round(tick_ns, 1),
        "samples_per_commit": round(samples_per_commit, 1),
        "clean_cell_profile": shares,
        "clean_cell_silent": clean_silent,
        "stat": "min_of_3_alternating_windows",
        "note": "one live 4-validator net, sampler toggled per window; "
        "overhead_pct = hz x measured stack-walk cost (live thread "
        "count) as the sampler's GIL share — the raw A/B delta "
        "(measured_delta_pct) is noise, floor ab_noise_floor_pct; "
        "clean_cell_profile = scheduler/verify/engine wall shares of "
        "a profiled healthy simnet cell (frame-module classification), "
        "which must stay verdict-silent (clean_cell_silent)",
    }


def bench_pipelined_commit(n_heights: int | None = None):
    """Config 23: serial vs pipelined commit chain on ONE live net.

    The pipelined-heights AFTER row: one in-process 4-validator burst
    over real FileDB stores and a real consensus WAL (so wal_fsync is
    actual fsync time), with the commit chain toggled serial (knob
    off) / pipelined (commit-writer + speculative execution) per
    window — the config-13 alternating-window discipline, so the two
    modes share threads, page cache and jit state and the delta
    isolates the chain itself.  Reports per-height commit p50/p99 per
    mode from the budget plane, the speculation hit rate, and the
    per-commit budget stage tiles, which must show wal_fsync/apply
    leaving the serial span (their serial-window milliseconds shrink
    toward zero in the pipelined windows while the same time reappears
    in the non-tiled ``overlapped`` credit).  ``bench.py --compare``
    against the PR 17 round diffs the occupancy drop via config 21,
    whose burst now runs this engine.
    """
    import shutil
    import tempfile

    from cometbft_tpu.libs import health as libhealth
    from cometbft_tpu.libs import metrics as libmetrics

    if n_heights is None:
        n_heights = _sz(10, 3)
    warm_heights = _sz(2, 1)

    health_was = libhealth.enabled()
    prev_ring = libhealth.recorder().capacity
    home_root = tempfile.mkdtemp(prefix="bench-pipelined-")
    m = libmetrics.node_metrics()

    def _spec_totals():
        return {
            k: m.spec_exec.labels(k).value()
            for k in ("hit", "miss", "abort")
        }

    lat = {"serial": [], "pipelined": []}  # per-height latency_s
    tiles = {"serial": {}, "pipelined": {}}  # stage -> summed seconds
    coverage = {"serial": [], "pipelined": []}
    overlapped_s = {"wal_fsync": 0.0, "spec_exec": 0.0}
    nodes = _perfect_gossip_net("bench-pipelined", home_root=home_root)
    pipes = [parts["pipe"] for _, parts in nodes]
    spec_support = [p.spec_enabled for p in pipes]
    store = nodes[0][1]["block_store"]
    try:
        libhealth.enable(ring=1 << 15)
        libhealth.reset()
        for cs, _ in nodes:
            cs.start()
        deadline = time.monotonic() + 300
        while (
            store.height() < warm_heights
            and time.monotonic() < deadline
        ):
            time.sleep(0.002)
        if store.height() < warm_heights:
            raise RuntimeError("pipelined burst never warmed")
        spec_pre = _spec_totals()
        for rep in range(3):
            for mode in ("serial", "pipelined"):
                on = mode == "pipelined"
                if not on:
                    # drain in-flight writer jobs before falling back
                    # to the serial chain, so no window straddles modes
                    for p in pipes:
                        p.wait_durable(store.height(), timeout_s=60)
                for p, sup in zip(pipes, spec_support):
                    p.enabled = on
                    p.spec_enabled = on and sup
                libhealth.reset()
                h0 = store.height()
                while (
                    store.height() < h0 + n_heights
                    and time.monotonic() < deadline
                ):
                    time.sleep(0.002)
                if store.height() - h0 <= 0:
                    raise RuntimeError(f"{mode} window stalled")
                bud = libhealth.budget()
                for hv in bud["heights"]:
                    lat[mode].append(hv["latency_s"])
                    for k, v in hv["stages"].items():
                        tiles[mode][k] = tiles[mode].get(k, 0.0) + v
                    ov = hv.get("overlapped")
                    if on and ov:
                        for k in overlapped_s:
                            overlapped_s[k] += ov.get(k, 0.0)
                if bud["coverage"] is not None:
                    coverage[mode].append(bud["coverage"])
        spec_post = _spec_totals()
    finally:
        _stop_net(nodes)
        libhealth.enable() if health_was else libhealth.disable()
        libhealth.set_ring_capacity(prev_ring)
        shutil.rmtree(home_root, ignore_errors=True)

    def _q(vals, frac):
        s = sorted(vals)
        return s[min(len(s) - 1, int(frac * (len(s) - 1) + 0.5))]

    p50 = {k: _q(v, 0.50) for k, v in lat.items()}
    p99 = {k: _q(v, 0.99) for k, v in lat.items()}
    spec = {
        k: spec_post[k] - spec_pre[k] for k in ("hit", "miss", "abort")
    }
    consumed = max(1, spec["hit"] + spec["miss"])
    # mean per-commit stage milliseconds per mode — THE tile evidence:
    # wal_fsync/apply milliseconds leave the serial span when pipelined
    stage_ms = {
        mode: {
            k: round(1e3 * v / max(1, len(lat[mode])), 3)
            for k, v in sorted(t.items())
        }
        for mode, t in tiles.items()
    }
    pipel_total = sum(lat["pipelined"]) or 1e-12
    return {
        "heights_per_window": n_heights,
        "windows": len(coverage["serial"]) + len(coverage["pipelined"]),
        "validators": 4,
        "commit_p50_ms_serial": round(p50["serial"] * 1e3, 2),
        "commit_p99_ms_serial": round(p99["serial"] * 1e3, 2),
        "commit_p50_ms_pipelined": round(p50["pipelined"] * 1e3, 2),
        "commit_p99_ms_pipelined": round(p99["pipelined"] * 1e3, 2),
        "pipelined_speedup_p50_vs_serial": round(
            p50["serial"] / (p50["pipelined"] or 1e-12), 2
        ),
        "spec_hit_rate": round(spec["hit"] / consumed, 3),
        "spec_outcomes": spec,
        "stage_ms_serial": stage_ms["serial"],
        "stage_ms_pipelined": stage_ms["pipelined"],
        # overlapped credit as a share of the pipelined windows' total
        # commit latency (the sidebar is NOT part of the stage tiling,
        # so this can't double-count)
        "overlapped_fsync_share": round(
            overlapped_s["wal_fsync"] / pipel_total, 3
        ),
        "overlapped_spec_share": round(
            overlapped_s["spec_exec"] / pipel_total, 3
        ),
        "budget_coverage_serial": round(
            min(coverage["serial"] or [0.0]), 3
        ),
        "budget_coverage_pipelined": round(
            min(coverage["pipelined"] or [0.0]), 3
        ),
        "stat": "3_alternating_window_pairs",
        "note": "one live 4-validator net over FileDB + real WAL, "
        "commit chain toggled serial/pipelined per window; p50/p99 "
        "from per-height budget latencies, stage_ms_* are mean "
        "per-commit budget tiles (wal_fsync/apply must shrink in the "
        "pipelined column; the same time reappears as overlapped_* "
        "credit, recorded outside the tiling sum), spec_hit_rate = "
        "hits/(hits+misses) across the pipelined windows",
    }


def bench_tx_lifecycle(
    seed: int | None = None, sample: int | None = None
):
    """Config 20: sampled end-to-end tx lifecycle under the mempool
    storm.

    Drives the PR 13 ``mempool_storm`` simnet scenario (4 real-reactor
    nodes, seeded 2000 tx/s load through commit churn) with the
    tx-lifecycle plane (libs/txtrace) enabled at 1/``sample``.
    Headlines: submit->commit p50/p99 of the sampled txs (virtual ms —
    the storm runs on the shared virtual clock, so the latencies are
    exact), per-stage residencies, the sampling-reconciliation check
    (sampled committed-tx records x rate vs the scenario ring's
    EV_COMMIT tx tallies — deterministic key-subset sampling, so the
    ratio lands within binomial expectation of 1.0), and the measured
    record-path overhead: a direct ns/record microbench on both the
    sampled and the not-sampled path, folded into the
    mechanism-level ``overhead_pct`` against the measured per-CheckTx
    key-hash cost (the config-13 methodology — the A/B wall delta of
    a storm run is noise-dominated on this shared container, the
    per-record cost is not).
    """
    import hashlib as _hashlib

    from cometbft_tpu.libs import health as libhealth
    from cometbft_tpu.libs import txtrace as libtxtrace
    from cometbft_tpu.simnet.scenarios import run_scenario

    if seed is None:
        seed = 23  # the tier-1 gray-smoke seed: known to commit storm txs
    if sample is None:
        sample = _sz(4, 2)
    storm_heights = _sz(6, 3)
    rate = 2000  # virtual tx/s — the PR 13 storm rate

    tx_was = libtxtrace.enabled()
    # restore BOTH the flag and the process-wide rate after each
    # section: enable() without a rate keeps the override, and a later
    # config must not sample 16x denser than the operator configured
    rate_was = libtxtrace.status()["sample_rate"]
    libtxtrace.reset()
    libtxtrace.enable(rate=sample)
    try:
        res = run_scenario(
            "mempool_storm", seed, rate=rate,
            storm_heights=storm_heights,
        )
        if not res.ok:
            raise RuntimeError(f"storm scenario failed: {res.failures}")
        lats = sorted(libtxtrace.commit_latencies_s())

        def q(vs, p):
            return (
                round(vs[min(len(vs) - 1, int(p * len(vs)))] * 1e3, 3)
                if vs
                else None
            )

        counts = libtxtrace.stage_counts()
        # reconciliation: sampled commit records x rate vs the ring's
        # EV_COMMIT tx tallies (both count each committed tx once per
        # NODE, so the node factor cancels). The sampled key subset is
        # a deterministic 1/rate draw over the storm's distinct keys —
        # binomial expectation, 5-sigma bound on the ratio.
        ring_events = (res.ring or {}).get("events", [])
        ev_commit_txs = sum(
            e.get("txs", 0)
            for e in ring_events
            if e.get("event") == "consensus.commit"
        )
        sampled_commits = counts["commit"]
        ratio = (
            sampled_commits * sample / ev_commit_txs
            if ev_commit_txs
            else None
        )
        # sigma of the ratio ~= sqrt(rate / distinct_sampled_txs)
        # (distinct sampled txs ~= sampled records / n_nodes = /4)
        distinct = max(1.0, sampled_commits / 4.0)
        bound = 5.0 * (sample / distinct) ** 0.5
        reconciled = (
            ratio is not None and abs(ratio - 1.0) <= bound
        )
        ev_tx_rows = sum(
            1 for e in ring_events if e.get("event") == "tx.stage"
        )
        # per-stage residencies of the completed sampled txs
        rows = libtxtrace.completed_rows()

        def stage_ms(field):
            vs = sorted(
                r[field] for r in rows if r.get(field) is not None
            )
            return {
                "p50_ms": q(vs, 0.50) if vs else None,
                "p99_ms": q(vs, 0.99) if vs else None,
            }

        stages = {
            "admit_to_proposal": stage_ms("admit_to_proposal_s"),
            "proposal_to_commit": stage_ms("proposal_to_commit_s"),
        }
    finally:
        libtxtrace.reset()
        libtxtrace.enable(rate=rate_was)
        if not tx_was:
            libtxtrace.disable()

    # -- record-path overhead: direct per-call microbench (plane ON,
    # flight ring ON — the sampled store includes its EV_TX ring
    # append) against a MEASURED live-CheckTx denominator ------------
    from cometbft_tpu import proxy
    from cometbft_tpu.abci.kvstore import KVStoreApplication
    from cometbft_tpu.config import MempoolConfig
    from cometbft_tpu.libs import db as dbm
    from cometbft_tpu.mempool.clist_mempool import CListMempool

    health_was = libhealth.enabled()
    prev_ring = libhealth.recorder().capacity
    libhealth.enable(ring=4096)
    libtxtrace.reset()
    libtxtrace.enable(rate=sample)
    conns = None
    try:
        # find one sampled and one not-sampled key deterministically
        # (the predicate is the key's first byte mod the rate)
        skey = nkey = None
        for i in range(4096):
            k = _hashlib.sha256(b"bench-tx-%d" % i).digest()
            if k[0] % sample == 0 and skey is None:
                skey = k
            elif k[0] % sample != 0 and nkey is None:
                nkey = k
            if skey is not None and nkey is not None:
                break
        reps = _sz(50_000, 5_000)

        def _per_call_ns(key):
            t0 = time.perf_counter()
            for _ in range(reps):
                libtxtrace.note_admit(key, 3)
            return (time.perf_counter() - t0) / reps * 1e9

        ns_sampled = min(_per_call_ns(skey) for _ in range(5))
        ns_fast = (
            min(_per_call_ns(nkey) for _ in range(5))
            if nkey is not None  # sample=1 traces every key
            else ns_sampled
        )
        # the commit side is BATCHED (one note_commit_many call per
        # block): per-key cost of the not-sampled loop body
        nkeys = [nkey or skey] * 256

        def _per_commit_key_ns():
            t0 = time.perf_counter()
            for _ in range(max(1, reps // 256)):
                libtxtrace.note_commit_many(nkeys, 0)
            return (
                (time.perf_counter() - t0)
                / (max(1, reps // 256) * 256)
                * 1e9
            )

        ns_commit_key = min(_per_commit_key_ns() for _ in range(5))
        # real per-tx denominator: the TWO instrumented seams — admit
        # txs through a live CListMempool + kvstore local client
        # (key hash + cache + ABCI round trip + clist insert), then
        # commit them through update() (batch re-key + cache + clist
        # removal) — what a tx actually costs this node
        from cometbft_tpu.abci.types import ExecTxResult

        n_txs = _sz(4000, 800)

        def _pipeline_ns() -> tuple[float, float]:
            app = KVStoreApplication(dbm.MemDB())
            c = proxy.AppConns(proxy.local_client_creator(app))
            c.start()
            try:
                mp = CListMempool(
                    MempoolConfig(
                        recheck=False, size=1 << 20,
                        cache_size=4 * n_txs, max_txs_bytes=1 << 40,
                    ),
                    c.mempool,
                )
                txs = [b"bench-life-%d=1" % i for i in range(n_txs)]
                t0 = time.perf_counter()
                for tx in txs:
                    mp.check_tx(tx)
                t_check = (time.perf_counter() - t0) / n_txs * 1e9
                results = [
                    ExecTxResult(code=0) for _ in txs
                ]
                mp.lock()
                try:
                    t0 = time.perf_counter()
                    mp.update(1, txs, results)
                    t_upd = (time.perf_counter() - t0) / n_txs * 1e9
                finally:
                    mp.unlock()
                return t_check, t_upd
            finally:
                c.stop()
        libtxtrace.disable()
        off = [_pipeline_ns() for _ in range(2)]
        checktx_off_ns = min(t for t, _ in off)
        update_off_ns = min(u for _, u in off)
        pipeline_off_ns = checktx_off_ns + update_off_ns
        libtxtrace.enable(rate=sample)
        on = [_pipeline_ns() for _ in range(2)]
        pipeline_on_ns = min(t for t, _ in on) + min(u for _, u in on)
        ab_delta_pct = (
            100.0 * (pipeline_on_ns - pipeline_off_ns) / pipeline_off_ns
        )

        # mechanism-level overhead (the config-13 posture: the A/B
        # wall delta above is noise-dominated on a shared container —
        # reported as evidence — while the per-record costs are
        # directly measurable): every tx pays one admit call + one
        # batched-commit loop pass; sampled txs add the two stores.
        def _per_tx_ns(rate: int) -> float:
            return ns_fast + ns_commit_key + 2 * max(
                0.0, ns_sampled - ns_fast
            ) / max(1, rate)

        overhead_pct = (
            100.0 * _per_tx_ns(sample) / max(1.0, pipeline_off_ns)
        )
        # the production default (COMETBFT_TPU_TX_SAMPLE=64) — the
        # bench pins a denser rate only to gather latency statistics
        overhead_pct_default = (
            100.0
            * _per_tx_ns(libtxtrace.DEFAULT_SAMPLE)
            / max(1.0, pipeline_off_ns)
        )
    finally:
        libtxtrace.reset()
        libtxtrace.enable(rate=rate_was)
        if not tx_was:
            libtxtrace.disable()
        libhealth.set_ring_capacity(prev_ring)
        libhealth.enable() if health_was else libhealth.disable()
        libhealth.reset()

    return {
        "seed": seed,
        "sample_rate": sample,
        "storm_rate_tx_s": rate,
        "storm_heights": storm_heights,
        "txs_sent": res.notes.get("txs_sent"),
        "txs_committed": res.notes.get("txs_committed"),
        "sampled_commit_records": sampled_commits,
        "sampled_counts": counts,
        "ev_commit_txs": ev_commit_txs,
        "ev_tx_ring_rows": ev_tx_rows,
        "tx_reconciliation_ratio": (
            round(ratio, 4) if ratio is not None else None
        ),
        "reconciliation_bound": round(bound, 4),
        "reconciled_within_expectation": reconciled,
        "submit_commit_p50_ms": q(lats, 0.50),
        "submit_commit_p99_ms": q(lats, 0.99),
        "stage_residency_ms": stages,
        "record_ns_not_sampled": round(ns_fast, 1),
        "record_ns_commit_key": round(ns_commit_key, 1),
        "record_ns_sampled": round(ns_sampled, 1),
        "checktx_ns": round(checktx_off_ns, 1),
        "update_ns_per_tx": round(update_off_ns, 1),
        "pipeline_ab_delta_pct": round(ab_delta_pct, 3),
        "overhead_pct_at_bench_rate": round(overhead_pct, 4),
        "overhead_pct": round(overhead_pct_default, 4),
        "note": "mempool_storm simnet scenario (virtual clock: "
        "latencies exact); overhead_pct is mechanism-level at the "
        "production default 1/64 rate — measured per-record cost vs "
        "a measured live CheckTx — the config-13 posture (the raw "
        "A/B delta is reported as evidence; its noise floor on this "
        "shared container exceeds the true cost)",
    }


# -------------------------------------------------- bench --compare


def _compare_load_rows(path: str) -> dict:
    """Rows-by-config from a BENCH_DETAILS*.json (list of config rows),
    a BENCH_r*.json capture (JSON lines embedded in its ``tail``), or a
    bare headline/config object."""
    with open(path) as f:
        obj = json.load(f)
    rows: dict[str, dict] = {}

    def _add(d) -> None:
        if not isinstance(d, dict):
            return
        key = d.get("config") or ("headline" if "metric" in d else None)
        if key is not None:
            rows.setdefault(key, d)

    if isinstance(obj, list):
        for d in obj:
            _add(d)
    elif isinstance(obj, dict) and "tail" in obj:
        # capture wrapper: best-effort recovery of the JSON objects the
        # bench printed (one per line; the tail may cut the first line)
        for line in str(obj["tail"]).splitlines():
            line = line.strip()
            if line.startswith("{") and line.endswith("}"):
                try:
                    _add(json.loads(line))
                except ValueError:
                    continue
    else:
        _add(obj)
    return rows


# metric-direction heuristics: which way is WORSE. Checked in order
# (higher-better first), so e.g. device_window_pct — more windows on
# the device path is the metric's goal — resolves higher-better before
# any lower-better fragment could claim it; bare "_pct" is deliberately
# NOT a lower-better fragment (overhead/noise/delta name their
# lower-better percentage metrics explicitly).
#
# Exception checked BEFORE both lists: lock-contention fragments.
# "lock_wait_share" would otherwise hit "share" (higher-better) — but
# a bigger share of time blocked on a mutex is always worse, which is
# the whole point of the 21_lock_contention before/after baseline
# ("occupancy" is the commit-chain serial fraction the pipelined-
# heights work exists to shrink).
_LOCK_LOWER_IS_BETTER = ("lock_wait", "contended", "occupancy", "acquires")
_HIGHER_IS_BETTER = (
    "per_sec", "vs_baseline", "vs_serial", "vs_batch_baseline", "rate",
    "hit", "coverage", "util", "value", "window_pct", "share",
)
_LOWER_IS_BETTER = (
    "_ms", "_s", "_ns", "latency", "seconds", "wait", "overhead",
    "noise", "delta", "bytes", "compile",
)


def _metric_direction(key: str) -> int:
    """+1 higher-better, -1 lower-better, 0 unknown (flag any move)."""
    for frag in _LOCK_LOWER_IS_BETTER:
        if frag in key:
            return -1
    for frag in _HIGHER_IS_BETTER:
        if frag in key:
            return 1
    for frag in _LOWER_IS_BETTER:
        if frag in key:
            return -1
    return 0


def bench_compare(path_a: str, path_b: str) -> dict:
    """Noise-aware headline delta table across two bench runs.

    Compares every numeric field of every config present in both runs;
    a delta is flagged as a REGRESSION only when it moves in the
    metric's worse direction by more than the measured noise floor —
    taken from 13_health_overhead's ``ab_noise_floor_pct`` (the
    off-window spread of one live burst, the config-13 methodology)
    when either run recorded it, with a 10% default floor otherwise
    and a 2% minimum (sub-noise jitter must never page).
    """
    a_rows = _compare_load_rows(path_a)
    b_rows = _compare_load_rows(path_b)
    floor = 10.0
    for rows in (a_rows, b_rows):
        h = rows.get("13_health_overhead")
        if h and isinstance(h.get("ab_noise_floor_pct"), (int, float)):
            floor = max(2.0, float(h["ab_noise_floor_pct"]))
            break
    deltas: list[dict] = []
    regressions: list[dict] = []
    for config in sorted(set(a_rows) & set(b_rows)):
        ra, rb = a_rows[config], b_rows[config]
        for key in sorted(set(ra) & set(rb)):
            va, vb = ra[key], rb[key]
            if (
                not isinstance(va, (int, float))
                or not isinstance(vb, (int, float))
                or isinstance(va, bool)
                or isinstance(vb, bool)
                or va == 0
            ):
                continue
            pct = 100.0 * (vb - va) / abs(va)
            row = {
                "config": config,
                "metric": key,
                "a": va,
                "b": vb,
                "delta_pct": round(pct, 2),
            }
            deltas.append(row)
            if abs(pct) <= floor:
                continue
            direction = _metric_direction(key)
            worse = (
                (direction > 0 and pct < 0)
                or (direction < 0 and pct > 0)
                or direction == 0
            )
            if worse:
                row["regression"] = True
                regressions.append(row)
    return {
        "a": path_a,
        "b": path_b,
        "noise_floor_pct": round(floor, 2),
        "compared": len(deltas),
        "regressions": regressions,
        "deltas": deltas,
    }


def compare_main(argv) -> int:
    if len(argv) < 2:
        print(
            "usage: bench.py --compare A.json B.json  "
            "(BENCH_DETAILS*.json / BENCH_r*.json / headline files)",
            file=sys.stderr,
        )
        return 2
    out = bench_compare(argv[0], argv[1])
    for row in out["regressions"]:
        print(
            f"REGRESSION {row['config']}.{row['metric']}: "
            f"{row['a']} -> {row['b']} ({row['delta_pct']:+.1f}% "
            f"> noise {out['noise_floor_pct']}%)",
            file=sys.stderr,
        )
    print(json.dumps(out))
    return 1 if out["regressions"] else 0


def main() -> None:
    _require_device()
    prov = _provenance()
    _eprint(prov)
    single, single_backend = _cpu_single_baseline()
    batch_baseline = _cpu_batch_baseline()
    _eprint(
        {
            "config": "cpu_baseline",
            "openssl_single_sigs_per_sec": round(single, 1),
            "single_backend": single_backend,
            "native_rlc_batch_sigs_per_sec": round(batch_baseline, 1),
            "note": "baseline MEASURED: native RLC multiscalar batch "
            "(the voi algorithm), crypto/host_batch.py; all rows and "
            "this baseline are min-of-reps since round 5",
        }
    )

    tput, dt = bench_flat_batch(_sz(64, 64))
    _eprint(
        {
            "config": "1_batch64",
            "sigs_per_sec": round(tput, 1),
            "latency_ms": round(dt * 1e3, 2),
            "vs_batch_baseline": round(tput / batch_baseline, 2),
            # statistic changed mean->min in round 5: recorded so
            # cross-round readers don't misread it as a perf delta
            "stat": "min_of_3",
        }
    )

    tput, dt = bench_commit_verify(_sz(150, 24), light=False)
    _eprint(
        {
            "config": "2_commit150_verify",
            "sigs_per_sec": round(tput, 1),
            "commit_latency_ms": round(dt * 1e3, 2),
            "vs_batch_baseline": round(tput / batch_baseline, 2),
        }
    )

    tput, dt = bench_vote_round(_sz(1000, 32))
    _eprint(
        {
            "config": "3_round1000_votes",
            "votes_per_sec": round(tput, 1),
            "round_latency_ms": round(dt * 1e3, 2),
            "vs_batch_baseline": round(tput / batch_baseline, 2),
        }
    )

    tput, dt = bench_commit_verify(_sz(10_000, 48), light=True)
    _eprint(
        {
            "config": "4_light10k_commit_verify",
            "sigs_per_sec": round(tput, 1),
            "commit_latency_ms": round(dt * 1e3, 2),
            "vs_batch_baseline": round(tput / batch_baseline, 2),
        }
    )

    tput, dt = bench_mixed(_sz(4096, 64))
    _eprint(
        {
            "config": "5_mixed4096_ed_sr",
            "sigs_per_sec": round(tput, 1),
            "latency_ms": round(dt * 1e3, 2),
            "vs_batch_baseline": round(tput / batch_baseline, 2),
        }
    )

    floor_row = None
    for name, fn in (
        ("6_wal_decode", bench_wal_decode),
        ("7_mempool", bench_mempool),
        ("8_valset_update", bench_valset_update),
        ("9_device_floor", bench_device_floor),
    ):
        try:
            row = fn()
            if name == "9_device_floor":
                # captured for the headline's crossover_lanes field
                floor_row = row
            _eprint({"config": name, **row})
        except Exception as e:  # micro extras must never sink the bench
            _eprint({"config": name, "error": repr(e)[:200]})

    coalesce_row = None
    try:
        # 128 concurrent callers, with min_device_lanes pinned low:
        # each storm thread blocks on its ticket before its next lane,
        # so a window never exceeds n_threads lanes — far below the
        # production crossover (seed 768, calibrated ~3000) — and
        # without the pin every window would route host and the row
        # would never measure the device micro-batch path it exists for
        coalesce_row = bench_coalesce_steady_state(
            n_threads=_sz(128, 8), min_device_lanes=8
        )
        _eprint({"config": "12_coalesce_steady_state", **coalesce_row})
    except Exception as e:
        _eprint(
            {"config": "12_coalesce_steady_state", "error": repr(e)[:200]}
        )

    health_row = None
    try:
        # host-side consensus burst: no device dependence, but recorded
        # in the chip round too so overhead regressions stay visible
        health_row = bench_health_overhead()
        _eprint({"config": "13_health_overhead", **health_row})
    except Exception as e:
        _eprint({"config": "13_health_overhead", "error": repr(e)[:200]})

    light_row = None
    try:
        # device=None probes the live backend: commits are 4-lane
        # groups, so windows route by the measured crossover (typically
        # host MSM) — the row reports which backend actually served
        light_row = bench_light_storm()
        _eprint({"config": "14_light_storm", **light_row})
    except Exception as e:
        _eprint({"config": "14_light_storm", "error": repr(e)[:200]})

    net_row = None
    try:
        # real-TCP 4-validator burst: per-phase gossip propagation
        # quantiles + peak send-queue depth (the large-N harness baseline)
        net_row = bench_net_propagation()
        _eprint({"config": "15_net_propagation", **net_row})
    except Exception as e:
        _eprint({"config": "15_net_propagation", "error": repr(e)[:200]})

    fault_row = None
    try:
        # deterministic simnet fault grid (host-only; same numbers with
        # or without a chip — recorded in the device round for the
        # round-over-round trend)
        fault_row = bench_fault_matrix()
        _eprint({"config": "16_fault_matrix", **fault_row})
    except Exception as e:
        _eprint({"config": "16_fault_matrix", "error": repr(e)[:200]})

    pm_row = None
    try:
        # cross-node postmortem attribution over the same grid (host-
        # only simnet workload; identical with or without a chip)
        pm_row = bench_postmortem_attribution()
        _eprint({"config": "17_postmortem_attribution", **pm_row})
    except Exception as e:
        _eprint({"config": "17_postmortem_attribution",
                 "error": repr(e)[:200]})

    hash_row = None
    try:
        # device probe decides routing; min_device_lanes is pinned low
        # inside (8) so storm windows — capped at n_threads lanes by
        # each CheckTx thread blocking on its key — actually exercise
        # the device path, mirroring 12's pin rationale
        hash_row = bench_hash_plane()
        _eprint({"config": "18_hash_plane", **hash_row})
    except Exception as e:
        _eprint({"config": "18_hash_plane", "error": repr(e)[:200]})

    ledger_row = None
    try:
        # mixed-tenant storm over the shared planes with the device
        # path live (min_device_lanes pinned low inside, the config-12
        # rationale); attribution + reconciliation are the headline
        ledger_row = bench_device_ledger(device=True)
        _eprint({"config": "19_device_ledger", **ledger_row})
    except Exception as e:
        _eprint({"config": "19_device_ledger", "error": repr(e)[:200]})

    txlife_row = None
    try:
        # sampled tx lifecycle under the mempool storm (host-only
        # simnet workload; identical with or without a chip)
        txlife_row = bench_tx_lifecycle()
        _eprint({"config": "20_tx_lifecycle", **txlife_row})
    except Exception as e:
        _eprint({"config": "20_tx_lifecycle", "error": repr(e)[:200]})

    lockprof_row = None
    try:
        # lock-contention burst with the device path live (the routed
        # storms' windows run real device rounds; contention accounting
        # itself is path-independent)
        lockprof_row = bench_lock_contention(device=True)
        _eprint({"config": "21_lock_contention", **lockprof_row})
    except Exception as e:
        _eprint({"config": "21_lock_contention", "error": repr(e)[:200]})

    profile_row = None
    try:
        # profiler overhead + profiled clean cell (the sampler walks
        # Python frames; whether verify dispatches to the device does
        # not change the walk cost, but the live-net thread population
        # under the device path is the production one)
        profile_row = bench_profile_overhead()
        _eprint({"config": "22_profile_overhead", **profile_row})
    except Exception as e:
        _eprint({"config": "22_profile_overhead", "error": repr(e)[:200]})

    pipeline_row = None
    try:
        # serial-vs-pipelined commit chain A/B: the engine work is
        # host-side (FileDB fsyncs + kvstore finalize) and identical
        # with or without a chip, but run it on the device round too so
        # the AFTER row rides the same provenance as the 21 baseline
        pipeline_row = bench_pipelined_commit()
        _eprint({"config": "23_pipelined_commit", **pipeline_row})
    except Exception as e:
        _eprint({"config": "23_pipelined_commit", "error": repr(e)[:200]})

    # Headline: 4096-lane flat ed25519 batch, min-of-5 (recorded in
    # the row).
    tput, dt = bench_flat_batch(_sz(4096, 256), reps=5)
    _eprint(
        {
            "config": "headline_flat4096",
            "sigs_per_sec": round(tput, 1),
            "latency_ms": round(dt * 1e3, 2),
            "stat": "min_of_5",
        }
    )
    print(
        json.dumps(
            {
                "metric": "ed25519_batch_verify_throughput",
                "value": round(tput, 1),
                "unit": "sigs/sec",
                "vs_baseline": round(tput / batch_baseline, 2),
                "provenance": _headline_provenance(prov),
                # the measured host/device crossover (config
                # 9_device_floor) — the device-floor work is measured
                # by this number dropping round-over-round
                **(
                    {"crossover_lanes": floor_row.get("crossover_lanes")}
                    if floor_row
                    else {}
                ),
                # steady-state vote-path headline: coalesced vs serial
                # single-verify (config 12_coalesce_steady_state), plus
                # the fraction of storm windows that actually took the
                # device path
                **(
                    {
                        "coalesce_vs_serial": coalesce_row[
                            "coalesced_vs_serial"
                        ],
                        "device_window_pct": coalesce_row[
                            "device_window_pct"
                        ],
                    }
                    if coalesce_row
                    else {}
                ),
                # always-on flight recorder's per-commit cost
                # (config 13_health_overhead; target <1%)
                **(
                    {"health_overhead_pct": health_row["overhead_pct"]}
                    if health_row
                    else {}
                ),
                # many-client proof-service storm vs per-client serial
                # verification (config 14_light_storm)
                **(
                    {"light_storm_vs_serial": light_row["storm_vs_serial"]}
                    if light_row
                    else {}
                ),
                # one-hop prevote gossip latency over real TCP
                # (config 15_net_propagation)
                **(
                    {
                        "net_prevote_prop_p50_ms": net_row[
                            "propagation_ms"
                        ]["prevote"]["p50_ms"]
                    }
                    if net_row
                    else {}
                ),
                # virtual-time commit latency under 5% message loss on
                # the deterministic simnet (config 16_fault_matrix)
                **(
                    {
                        "fault_drop05_commit_p50_ms": fault_row[
                            "grid"
                        ]["drop05"]["commit_ms_p50"]
                    }
                    if fault_row
                    else {}
                ),
                # fraction of faulty simnet cells whose postmortem
                # run verdict names the injected fault (config
                # 17_postmortem_attribution)
                **(
                    {
                        "postmortem_attribution_rate": pm_row[
                            "postmortem_attribution_rate"
                        ]
                    }
                    if pm_row
                    else {}
                ),
                # concurrent-CheckTx key hashing through the hash
                # plane vs serial hashlib (config 18_hash_plane)
                **(
                    {
                        "hash_storm_vs_serial": hash_row[
                            "hash_storm_vs_serial"
                        ]
                    }
                    if hash_row
                    else {}
                ),
                # consensus queue-wait p99 under a mixed-tenant storm
                # + the ledger reconciliation oracle (config
                # 19_device_ledger)
                **(
                    {
                        "ledger_consensus_wait_p99_ms": ledger_row[
                            "consensus_wait_p99_ms"
                        ],
                        "ledger_reconciled": ledger_row[
                            "reconciled_within_1pct"
                        ],
                    }
                    if ledger_row
                    else {}
                ),
                # sampled submit->commit p99 under the mempool storm
                # + measured tx-plane record overhead (config
                # 20_tx_lifecycle; target <1%)
                **(
                    {
                        "tx_commit_p99_ms": txlife_row[
                            "submit_commit_p99_ms"
                        ],
                        "tx_overhead_pct": txlife_row["overhead_pct"],
                    }
                    if txlife_row
                    else {}
                ),
                # commit-chain serial occupancy (the pipelined-heights
                # before baseline) + measured lock-profiler record
                # overhead (config 21_lock_contention; target <1%)
                **(
                    {
                        "commit_chain_occupancy_pct": lockprof_row[
                            "commit_chain_occupancy_pct"
                        ],
                        "lockprof_overhead_pct": lockprof_row[
                            "overhead_pct"
                        ],
                    }
                    if lockprof_row
                    else {}
                ),
                # sampling-profiler tax (config 22_profile_overhead;
                # mechanism-level hz x walk-cost bound, target <1%)
                **(
                    {
                        "profile_overhead_pct": profile_row[
                            "overhead_pct"
                        ],
                    }
                    if profile_row
                    else {}
                ),
                # serial vs pipelined commit chain on one live net
                # (config 23_pipelined_commit; p50 must drop, the hits
                # prove the speculative path carried it)
                **(
                    {
                        "pipelined_commit_p50_ms": pipeline_row[
                            "commit_p50_ms_pipelined"
                        ],
                        "serial_commit_p50_ms": pipeline_row[
                            "commit_p50_ms_serial"
                        ],
                        "pipelined_speedup_p50_vs_serial": (
                            pipeline_row[
                                "pipelined_speedup_p50_vs_serial"
                            ]
                        ),
                        "spec_hit_rate": pipeline_row["spec_hit_rate"],
                    }
                    if pipeline_row
                    else {}
                ),
            }
        )
    )


if __name__ == "__main__":
    if "--compare" in sys.argv:
        i = sys.argv.index("--compare")
        sys.exit(compare_main(sys.argv[i + 1 : i + 3]))
    main()
