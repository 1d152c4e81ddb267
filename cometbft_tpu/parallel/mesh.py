"""Device-mesh sharding for validator-scale signature batches.

The reference engine scales verification with CPU batch verification
(crypto/batch/batch.go:11, types/validation.go:153). The TPU-native analog
has two sharding axes that map onto a 2-D ``jax.sharding.Mesh``:

* ``commit`` — independent commits verified concurrently (light-client
  replay over many heights, blocksync catch-up windows). Embarrassingly
  parallel: no cross-shard traffic at all.
* ``sig``    — signatures *within* one commit (one lane per validator).
  The only cross-shard value is the commit-level verdict, a single bool;
  XLA lowers the ``jnp.all`` over the sharded axis to an ICI all-reduce of
  one byte per commit — the cheapest possible collective.

Everything is expressed as sharding annotations on a single ``jax.jit`` of
the plain batched kernel (ops/curve.py): XLA inserts the collectives; there
is no hand-written communication. This file is the ``pjit``-over-signature-
axis design called for by SURVEY.md §2.9/§5 (long-context analog: shard the
signature axis like a sequence axis, all-gather only the validity bitmap).
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import curve

AXIS_COMMIT = "commit"
AXIS_SIG = "sig"


def make_mesh(devices=None, commit_axis: int = 1) -> Mesh:
    """Build a (commit, sig) mesh over ``devices`` (default: all).

    ``commit_axis`` devices are assigned to the commit axis; the rest to the
    signature axis. With the default 1, the whole slice shards one commit's
    signature batch — the consensus hot-path layout (one commit per round).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if n % commit_axis != 0:
        raise ValueError(f"{n} devices not divisible by commit_axis={commit_axis}")
    arr = np.asarray(devices).reshape(commit_axis, n // commit_axis)
    return Mesh(arr, (AXIS_COMMIT, AXIS_SIG))


@lru_cache(maxsize=None)
def _sharded_verify(mesh: Mesh):
    """jit of the verify kernel over a (..., C, V) batch sharded on the mesh.

    Batch dims TRAIL (see ops/field.py): y limbs are (20, C, V), parity
    bits (C, V), scalar windows (64, C, V). Returns per-signature validity
    (C, V) sharded like the inputs plus the per-commit verdict (C,) — the
    latter forces the one collective (a commit-local all-reduce over the
    sig axis).
    """
    lead = NamedSharding(mesh, P(None, AXIS_COMMIT, AXIS_SIG))
    flat = NamedSharding(mesh, P(AXIS_COMMIT, AXIS_SIG))
    verdict = NamedSharding(mesh, P(AXIS_COMMIT))

    def step(y_a, sign_a, y_r, sign_r, s_nibs, kneg_nibs):
        ok = curve.verify_kernel(y_a, sign_a, y_r, sign_r, s_nibs, kneg_nibs)
        return ok, jnp.all(ok, axis=-1)

    return jax.jit(
        step,
        in_shardings=(lead, flat, lead, flat, lead, lead),
        out_shardings=(flat, verdict),
    )


# One synchronous pallas-under-shard_map failure retires the path for the
# process (per-mesh compile caches make retrying per call pointless).
_SHARDED_PALLAS_BROKEN = False


@lru_cache(maxsize=None)
def _sharded_verify_pallas(mesh: Mesh):
    """Sharded verify with the PALLAS kernel per shard (accelerators).

    Mosaic custom calls are not SPMD-auto-partitionable, so the kernel
    runs inside ``shard_map``: each device gets its (C_l, V_l) block,
    flattens the commit axis into lanes, pads to the kernel's 512-lane
    block constraint (static shapes — padding targets are computed at
    trace time), and runs the VMEM-resident ladder. The per-commit
    verdict's ``jnp.all`` stays OUTSIDE the shard_map, so XLA still
    lowers it to the one-byte-per-commit ICI all-reduce.
    """
    from ..ops import pallas_verify

    lead = P(None, AXIS_COMMIT, AXIS_SIG)
    flat = P(AXIS_COMMIT, AXIS_SIG)

    def local(y_a, sign_a, y_r, sign_r, s_nibs, kneg_nibs):
        c_l, v_l = y_a.shape[-2], y_a.shape[-1]
        n = c_l * v_l
        target = n if n <= 512 else pad_to(n, 512)

        def lanes(x):
            x = x.reshape(*x.shape[:-2], n)
            if target != n:
                pad = [(0, 0)] * (x.ndim - 1) + [(0, target - n)]
                x = jnp.pad(x, pad)
            return x

        ok = pallas_verify.verify_kernel(
            lanes(y_a), lanes(sign_a), lanes(y_r), lanes(sign_r),
            lanes(s_nibs), lanes(kneg_nibs), interpret=False,
        )
        return ok[:n].reshape(c_l, v_l)

    sm = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(lead, flat, lead, flat, lead, lead),
        out_specs=flat,
        check_vma=False,
    )

    def step(y_a, sign_a, y_r, sign_r, s_nibs, kneg_nibs):
        ok = sm(y_a, sign_a, y_r, sign_r, s_nibs, kneg_nibs)
        return ok, jnp.all(ok, axis=-1)

    return jax.jit(step)


def _dispatch_sharded(mesh: Mesh, args, lanes_per_shard: int):
    """Pallas-per-shard where ops/verify's one rule wants Pallas for a
    shard's lanes (an accelerator backend, a full block, no Pallas
    fault in this process), the portable XLA program otherwise (CPU
    virtual meshes: interpret mode is far too slow). Returns
    MATERIALIZED (ok, verdict) ndarrays: jit dispatch is asynchronous,
    so a Mosaic runtime fault only surfaces at np.asarray —
    materializing inside the try is what lets it retire the path and
    fall back (the multi-chip analog of ops/verify._materialize)."""
    global _SHARDED_PALLAS_BROKEN
    from ..ops import verify as ov

    if ov._pallas_wanted(lanes_per_shard) and not _SHARDED_PALLAS_BROKEN:
        try:
            ok, verdict = _sharded_verify_pallas(mesh)(*args)
            # cometlint: disable=CLNT002 -- sanctioned sharded readback:
            # materializing INSIDE the try is what lets a Mosaic runtime
            # fault retire the pallas path and fall through to XLA
            out = np.asarray(ok), np.asarray(verdict)
        except Exception as e:
            _SHARDED_PALLAS_BROKEN = True
            # counted with the single-chip Pallas faults
            # (ops.verify.dispatch_counters), never silent
            ov._note_fault("pallas", e, flavor="sharded")
        else:
            ov._served("verify_sharded.pallas")
            return out
    ok, verdict = _sharded_verify(mesh)(*args)
    ov._served("verify_sharded.xla")
    # cometlint: disable=CLNT002 -- sanctioned readback of the XLA
    # sharded launch (single sync point of the multi-chip path)
    return np.asarray(ok), np.asarray(verdict)


def pad_to(n: int, multiple: int) -> int:
    return (n + multiple - 1) // multiple * multiple


@lru_cache(maxsize=None)
def default_mesh() -> Mesh:
    """Process-wide (1, n_devices) mesh: one commit, all chips on the
    signature axis — the consensus hot-path layout. Cached so the
    production dispatch (ops/verify.verify_batch) builds it once."""
    return make_mesh(commit_axis=1)


def verify_sharded(
    arrays: dict,
    host_ok: np.ndarray,
    mesh: Mesh,
    n_commits: int,
    n_sigs: int,
):
    """Run the sharded verifier over host-packed arrays (see ops.verify).

    ``arrays``/``host_ok`` come from ops.verify.pack_inputs with trailing
    batch dim n_commits * n_sigs; arrays are padded so both mesh axes
    divide their dims, reshaped to (..., C, V), and dispatched. Padding
    lanes are sliced off the result. ``host_ok`` must be ANDed in: a lane
    the host rejected (malformed length, non-canonical S) is zeroed in
    ``arrays`` and the all-zero encoding decompresses to a small-order
    point that the cofactored check accepts — without the mask that is a
    consensus-critical false accept.

    Returns ok (n_commits, n_sigs) bool ndarray.
    """
    c_dev, v_dev = mesh.devices.shape
    cp = pad_to(n_commits, c_dev)
    vp = pad_to(n_sigs, v_dev)

    shaped = {}
    for k, v in arrays.items():
        v = v.reshape(*v.shape[:-1], n_commits, n_sigs)
        pad = [(0, 0)] * (v.ndim - 2) + [(0, cp - n_commits), (0, vp - n_sigs)]
        shaped[k] = np.pad(v, pad)
    # pjit with in_shardings requires positional args.
    ok, _ = _dispatch_sharded(
        mesh,
        (
            shaped["y_a"],
            shaped["sign_a"],
            shaped["y_r"],
            shaped["sign_r"],
            shaped["s_nibs"],
            shaped["kneg_nibs"],
        ),
        lanes_per_shard=(cp // c_dev) * (vp // v_dev),
    )
    device_ok = ok[:n_commits, :n_sigs]
    return device_ok & np.asarray(host_ok, bool).reshape(n_commits, n_sigs)
