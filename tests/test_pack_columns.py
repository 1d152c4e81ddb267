"""A batch's lanes travel as columns from the sign-bytes encoder to the
device wire buffer: a key column, a signature column, and the messages as
one blob with its offsets (``host_batch.MsgColumn``).

The pure-Python loop of ``ops/verify.pack_bytes`` (what runs without the
native engine) stays here as the reference: the columnar packer gives the
same ``(buf, host_ok)`` byte for byte, whatever the lanes came in. The
column itself is a ``list[bytes]`` to every reader but the ed25519 device
path, which never cuts it."""

import json
import os
import random
import subprocess
import sys
from array import array

import numpy as np
import pytest

from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.crypto import host_batch
from cometbft_tpu.crypto.keys import Ed25519PrivKey, Ed25519PubKey
from cometbft_tpu.crypto.sr25519 import Sr25519PrivKey
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import trace as libtrace
from cometbft_tpu.libs.metrics import NodeMetrics
from cometbft_tpu.ops import verify as ov
from cometbft_tpu.types import canonical
from cometbft_tpu.types.block import BlockID, PartSetHeader

from test_curve import make_batch

needs_engine = pytest.mark.skipif(
    not host_batch.available(), reason="native engine unavailable"
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAIN_ID = "pack-columns-chain"
BASE_NS = 1_700_000_000_000_000_000
BLOCK_ID = BlockID(
    hash=bytes(range(32)),
    part_set_header=PartSetHeader(total=3, hash=bytes(range(32, 64))),
)


def _lanes(n: int, seed: int = 7):
    """n well-formed lanes. The packer hashes and checks S < L; whether
    a signature verifies is the kernel's business, so random bytes do."""
    rng = random.Random(seed * 1_000_003 + n)
    pks = [rng.randbytes(32) for _ in range(n)]
    sigs = [rng.randbytes(63) + bytes([rng.randrange(16)]) for _ in range(n)]
    return pks, sigs


def _sign_bytes(n: int):
    """The encoder's column for n votes of one commit, and the same
    lanes encoded one by one."""
    stamps = [BASE_NS + 300_000_007 * i for i in range(n)]
    args = (CHAIN_ID, canonical.PRECOMMIT_TYPE, 77, 1, BLOCK_ID)
    column = canonical.vote_sign_bytes_many(*args, stamps)
    return column, [canonical.vote_sign_bytes(*args, t) for t in stamps]


def _no_engine(monkeypatch):
    monkeypatch.setattr(host_batch, "_lib", None)
    monkeypatch.setattr(host_batch, "_lib_failed", True)


def _python_pack(monkeypatch, pks, msgs, sigs, width=None):
    """pack_bytes with the native engine taken away: the per-lane loop."""
    with monkeypatch.context() as m:
        _no_engine(m)
        return ov.pack_bytes(pks, msgs, sigs, width)


MSG_KINDS = ("column", "list", "byteslikes")


def _msgs_as(kind: str, column, plain: list):
    if kind == "column":
        return column
    if kind == "list":
        return list(plain)
    return [
        (bytes, bytearray, memoryview)[i % 3](m) for i, m in enumerate(plain)
    ]


def _assert_same(got, want, n, width):
    buf, host_ok = got
    assert buf.dtype == np.uint8 and buf.shape == (128, width)
    assert buf.flags.c_contiguous
    assert host_ok.dtype == np.bool_ and host_ok.shape == (n,)
    assert np.array_equal(host_ok, want[1])
    assert np.array_equal(buf, want[0])


# --- parity with the per-lane loop ------------------------------------------


@needs_engine
@pytest.mark.parametrize("at_bucket", [False, True], ids=["n", "bucket"])
@pytest.mark.parametrize("kind", MSG_KINDS)
@pytest.mark.parametrize("n", [1, 59, 117, 293, 967, 6667])
def test_columnar_pack_equals_the_per_lane_loop(
    monkeypatch, n, kind, at_bucket
):
    pks, sigs = _lanes(n)
    column, plain = _sign_bytes(n)
    assert isinstance(column, host_batch.MsgColumn)
    width = ov.bucket_size(n) if at_bucket else n
    want = _python_pack(monkeypatch, pks, plain, sigs, width)
    assert want[1].all()
    got = ov.pack_bytes(pks, _msgs_as(kind, column, plain), sigs, width)
    _assert_same(got, want, n, width)
    assert not got[0][:, n:].any()  # the launch's padding: zero columns
    # the default width is the lane count
    assert ov.pack_bytes(pks, plain, sigs)[0].shape == (128, n)


@needs_engine
@pytest.mark.parametrize("kind", MSG_KINDS)
@pytest.mark.parametrize("width", [40, 64])
def test_malformed_lanes_keep_their_meaning(monkeypatch, kind, width):
    """A short key, a long signature, S >= L, an empty message and
    messages of every length in one batch: host_ok False and a zero
    column for the three bad lanes, every other lane as the loop has it."""
    n = 40
    pks, sigs = _lanes(n, seed=11)
    plain = [bytes([i]) * (i * 7 % 190) for i in range(n)]
    plain[9] = b""
    pks[3] = pks[3][:31]
    sigs[5] = sigs[5] + b"\x00"
    sigs[7] = sigs[7][:32] + (ov.L + 5).to_bytes(32, "little")
    pks[20] = bytearray(pks[20])
    sigs[21] = memoryview(sigs[21])
    column = host_batch.MsgColumn.joined(plain)
    want = _python_pack(monkeypatch, pks, plain, sigs, width)
    got = ov.pack_bytes(pks, _msgs_as(kind, column, plain), sigs, width)
    _assert_same(got, want, n, width)
    bad = [3, 5, 7]
    assert [i for i in range(n) if not got[1][i]] == bad
    assert not got[0][:, bad].any()
    assert got[0][:, 9].any() and got[0][:, 20].any() and got[0][:, 21].any()


@needs_engine
def test_s_at_and_around_the_group_order():
    """S < L is the native call's check: L - 1 passes, L and L + 1 fail
    with a zero column, and no per-lane pass runs for them."""
    pks, sigs = _lanes(3)
    for i, s in enumerate((ov.L - 1, ov.L, ov.L + 1)):
        sigs[i] = sigs[i][:32] + s.to_bytes(32, "little")
    buf, host_ok = ov.pack_bytes(pks, [b"m"] * 3, sigs)
    assert list(host_ok) == [True, False, False]
    assert buf[:, 0].any() and not buf[:, 1:].any()


@needs_engine
def test_empty_batch_and_uneven_columns():
    buf, host_ok = ov.pack_bytes([], [], [])
    assert buf.shape == (128, 0) and host_ok.shape == (0,)
    buf, host_ok = ov.pack_bytes([], host_batch.MsgColumn.joined([]), [], 8)
    assert buf.shape == (128, 8) and not buf.any()
    pks, sigs = _lanes(4)
    with pytest.raises(ValueError):
        ov.pack_bytes(pks, [b"m"] * 3, sigs)
    with pytest.raises(ValueError):
        ov.pack_bytes(pks, [b"m"] * 4, sigs[:3])
    with pytest.raises(ValueError):  # a buffer narrower than its lanes
        host_batch.pack_wire(
            b"".join(pks), b"".join(sigs),
            host_batch.MsgColumn.joined([b"m"] * 4),
            np.zeros((128, 3), np.uint8), np.empty(4, bool),
        )


@needs_engine
@pytest.mark.parametrize("offs_as", ["array", "list", "numpy"])
def test_pack_challenges_reads_offsets_of_any_kind(offs_as):
    """host_batch.pack_challenges (the mixed verifier's ed25519 lanes)
    reads an ``array('Q')`` where it lies and copies anything else."""
    n = 12
    pks, sigs = _lanes(n)
    plain = [bytes([i]) * (3 * i) for i in range(n)]
    column = host_batch.MsgColumn.joined(plain)
    offs = {
        "array": column.offs, "list": column.offs.tolist(),
        "numpy": np.array(column.offs.tolist(), np.uint64),
    }[offs_as]
    recs = b"".join(p + s for p, s in zip(pks, sigs))
    kneg, s_ok = host_batch.pack_challenges(recs, column.blob, offs, n)
    buf, host_ok = ov.pack_bytes(pks, plain, sigs)
    assert np.array_equal(s_ok, host_ok)
    assert kneg == np.ascontiguousarray(buf[96:].T).tobytes()
    with pytest.raises(ValueError):
        host_batch.pack_challenges(recs, column.blob, offs[:-1], n)


# --- the ed25519 path never cuts the column ---------------------------------


class _UncutColumn(host_batch.MsgColumn):
    """A column that refuses to be read lane by lane."""

    def __getitem__(self, i):
        raise AssertionError("the column was indexed")

    def __iter__(self):
        raise AssertionError("the column was iterated")


@needs_engine
def test_a_column_that_cannot_be_cut_still_packs(monkeypatch):
    n = 293
    pks, sigs = _lanes(n)
    column, plain = _sign_bytes(n)
    uncut = _UncutColumn(column.blob, column.offs)
    with pytest.raises(AssertionError):
        list(uncut)
    want = _python_pack(monkeypatch, pks, plain, sigs, 512)
    _assert_same(ov.pack_bytes(pks, uncut, sigs, 512), want, n, 512)


@needs_engine
def test_add_many_to_the_launch_never_cuts_the_column(monkeypatch):
    """Ed25519BatchVerifier.add_many -> verify -> ops/verify.verify_batch
    -> pack_bytes with a column that raises when read lane by lane: every
    signature verifies on the device path, so nothing cut it."""
    monkeypatch.setattr(
        ov, "_PUBKEY_CACHE", ov.PubkeyTableCache(capacity=64))
    monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", 2)
    monkeypatch.setenv("COMETBFT_TPU_SHARD", "0")
    pks, msgs, sigs = make_batch(12)
    joined = host_batch.MsgColumn.joined(msgs)
    bv = cbatch.Ed25519BatchVerifier()
    bv.add_many(
        [Ed25519PubKey(pk) for pk in pks],
        _UncutColumn(joined.blob, joined.offs), sigs,
    )
    assert len(bv) == 12
    ok, bits = bv.verify()
    assert ok and bits == [True] * 12
    # the same lanes with one message altered: that lane alone fails
    msgs[4] = msgs[4] + b"!"
    bv = cbatch.Ed25519BatchVerifier()
    bv.add_many(
        [Ed25519PubKey(pk) for pk in pks],
        host_batch.MsgColumn.joined(msgs), sigs,
    )
    ok, bits = bv.verify()
    assert not ok and bits == [i != 4 for i in range(12)]


# --- the column is a list[bytes] to its readers -----------------------------


@needs_engine
def test_column_reads_as_the_list_of_per_vote_encodings():
    column, plain = _sign_bytes(9)
    assert column == plain and plain == column
    assert not column != plain
    assert len(column) == 9
    assert [column[i] for i in range(9)] == plain
    assert [column[i] for i in range(-9, 0)] == plain
    assert all(type(m) is bytes for m in column)
    assert list(column) == plain and list(reversed(column)) == plain[::-1]
    assert column[2:7] == plain[2:7] and isinstance(
        column[2:7], host_batch.MsgColumn)
    assert column[2:7][1:3] == plain[3:5]
    assert column[:] == plain and column[7:2] == [] and column[20:] == []
    assert column[::2] == plain[::2] and column[::-1] == plain[::-1]
    assert column[-3:] == plain[-3:]
    assert plain[4] in column and b"nope" not in column
    assert column.index(plain[4]) == 4
    for i in (9, -10):
        with pytest.raises(IndexError):
            column[i]
    assert column != plain[:8] and column != plain[:8] + [b"other"]
    assert column != "a string" and column != 7
    assert column == host_batch.MsgColumn.joined(plain)
    assert column == tuple(plain)
    with pytest.raises(TypeError):
        hash(column)
    with pytest.raises(AttributeError):
        column.blob = b""
    assert "9 lanes" in repr(column)


@needs_engine
def test_a_slice_of_a_column_packs_as_its_lanes(monkeypatch):
    """A contiguous slice shares the blob (its offsets do not start at
    0): what verify_batch hands the packer for a chunk of a batch."""
    n = 117
    pks, sigs = _lanes(n)
    column, plain = _sign_bytes(n)
    part = column[10:70]
    assert part.blob is column.blob and part.offs[0] > 0
    want = _python_pack(monkeypatch, pks[10:70], plain[10:70], sigs[10:70])
    _assert_same(ov.pack_bytes(pks[10:70], part, sigs[10:70]), want, 60, 60)


def test_joined_column_of_plain_lanes():
    column = host_batch.MsgColumn.joined(
        [b"ab", b"", bytearray(b"cde"), memoryview(b"f")])
    assert column.blob == b"abcdef"
    assert column.offs == array("Q", [0, 2, 2, 5, 6])
    assert column == [b"ab", b"", b"cde", b"f"]
    assert len(host_batch.MsgColumn.joined([])) == 0


VERIFIERS = {
    "ed25519": (
        cbatch.Ed25519BatchVerifier,
        lambda: [Ed25519PrivKey.generate().pub_key() for _ in range(5)],
    ),
    "sr25519": (
        cbatch.Sr25519BatchVerifier,
        lambda: [Sr25519PrivKey.generate().pub_key() for _ in range(5)],
    ),
    "mixed": (
        cbatch.MixedBatchVerifier,
        lambda: [
            (Ed25519PrivKey, Sr25519PrivKey)[i % 2].generate().pub_key()
            for i in range(5)
        ],
    ),
}


@needs_engine
@pytest.mark.parametrize("kind", sorted(VERIFIERS))
def test_add_many_takes_the_column(kind):
    """Every backend takes the encoder's column where it took a list:
    the lanes it holds are those of repeated ``add``."""
    cls, keys = VERIFIERS[kind]
    keys = keys()
    column, plain = _sign_bytes(5)
    sigs = [bytes([i]) * 64 for i in range(5)]
    one_by_one, at_once, in_two = cls(), cls(), cls()
    for triple in zip(keys, plain, sigs):
        one_by_one.add(*triple)
    at_once.add_many(keys, column, sigs)
    in_two.add_many(keys[:2], column[:2], sigs[:2])
    in_two.add_many(keys[2:], column[2:], sigs[2:])
    for bv in (at_once, in_two):
        assert len(bv) == 5
        assert list(bv._pubkeys) == list(one_by_one._pubkeys)
        assert list(bv._msgs) == plain
        assert list(bv._sigs) == sigs
    assert one_by_one.verify() == at_once.verify() == in_two.verify()
    # add after add_many: the column gives way to a list that can grow
    at_once.add(keys[0], b"one more", sigs[0])
    assert len(at_once) == 6 and list(at_once._msgs) == plain + [b"one more"]
    if kind == "ed25519":
        kept = cls()
        kept.add_many(keys, column, sigs)
        assert kept._msgs is column  # kept as it came, not copied


@needs_engine
def test_commit_walk_hands_the_encoders_column_to_the_verifier(monkeypatch):
    """types/validation._verify_batch: what Commit.vote_sign_bytes_many
    returned is what add_many is given, uncut."""
    import helpers
    from cometbft_tpu.types import validation

    seen = []
    real = cbatch.Ed25519BatchVerifier.add_many

    def spy(self, pub_keys, msgs, signatures):
        seen.append(msgs)
        return real(self, pub_keys, msgs, signatures)

    monkeypatch.setattr(cbatch.Ed25519BatchVerifier, "add_many", spy)
    blocks = helpers.make_light_chain(2, n_vals=8)
    lb = blocks[2]
    validation.verify_commit_light(
        helpers.CHAIN_ID, lb.validator_set, lb.signed_header.commit.block_id,
        2, lb.signed_header.commit,
    )
    (msgs,) = seen
    assert isinstance(msgs, host_batch.MsgColumn) and len(msgs) == 6
    commit = lb.signed_header.commit
    assert msgs == [
        commit.vote_sign_bytes(helpers.CHAIN_ID, i) for i in range(6)
    ]


# --- the counters -----------------------------------------------------------


@pytest.fixture
def metrics():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    yield m
    libmetrics.pop_node_metrics(m)


def _packed(m) -> dict:
    return {
        path: m.verify_pack_lanes_total.labels(path).value()
        for path in ("columnar", "per_lane")
    }


@needs_engine
def test_pack_counter_reads_the_path_taken(monkeypatch, metrics):
    text = metrics.registry.render()
    for path in ("columnar", "per_lane"):  # both series from the start
        assert (
            'cometbft_tpu_crypto_verify_pack_lanes_total{path="%s"} 0' % path
        ) in text
    pks, sigs = _lanes(30)
    msgs = [b"m%d" % i for i in range(30)]
    ov.pack_bytes(pks, msgs, sigs)
    assert _packed(metrics) == {"columnar": 30, "per_lane": 0}
    # S >= L is the native call's own check: still columnar
    sigs[2] = sigs[2][:32] + ov.L.to_bytes(32, "little")
    ov.pack_bytes(pks, msgs, sigs, 32)
    assert _packed(metrics) == {"columnar": 60, "per_lane": 0}
    # a lane of the wrong length sends the batch through the per-lane pass
    pks[4] = pks[4] + b"\x00"
    ov.pack_bytes(pks, msgs, sigs)
    assert _packed(metrics) == {"columnar": 60, "per_lane": 30}
    # no engine: the pure-Python loop
    _python_pack(monkeypatch, pks[:7], msgs[:7], sigs[:7])
    assert _packed(metrics) == {"columnar": 60, "per_lane": 37}


def _looked_up(m) -> dict:
    return {
        result: m.pubkey_lookup_lanes_total.labels(result).value()
        for result in ("memo", "walked")
    }


def test_lookup_counter_reads_walked_then_memo(monkeypatch, metrics):
    cache = ov.PubkeyTableCache(capacity=64)
    monkeypatch.setattr(ov, "_PUBKEY_CACHE", cache)
    text = metrics.registry.render()
    for result in ("memo", "walked"):
        assert (
            'cometbft_tpu_ops_pubkey_lookup_lanes_total{result="%s"} 0'
            % result
        ) in text
    pks, _, _ = make_batch(12)
    first = cache.lookup(pks)
    assert _looked_up(metrics) == {"memo": 0, "walked": 12}
    second = cache.lookup(pks)
    assert _looked_up(metrics) == {"memo": 12, "walked": 12}
    assert np.array_equal(first[0], second[0])
    # a batch with a lane that is no key is walked every time
    cache.lookup(pks[:3] + [b"short"])
    cache.lookup(pks[:3] + [b"short"])
    assert _looked_up(metrics) == {"memo": 12, "walked": 20}


@needs_engine
def test_pack_span_carries_path_and_slots(monkeypatch, metrics):
    monkeypatch.setattr(
        ov, "_PUBKEY_CACHE", ov.PubkeyTableCache(capacity=64))
    monkeypatch.setenv("COMETBFT_TPU_SHARD", "0")
    pks, msgs, sigs = make_batch(12)
    libtrace.reset()
    libtrace.enable()
    try:
        for _ in range(2):
            ok, _bits = ov.verify_batch(pks, msgs, sigs)
            assert ok
        spans = [
            s for s in libtrace.ring_dump()
            if s.get("kind") == "span" and s["name"] == "verify.pack"
        ]
    finally:
        libtrace.disable()
        libtrace.reset()
    assert [(s["path"], s["slots"], s["arena"]) for s in spans] == [
        ("columnar", "walked", "miss"), ("columnar", "memo", "hit"),
    ]
    # outside a verify.pack span the packer names no span's fields
    with libtrace.span("something.else") as sp:
        ov.pack_bytes(pks, msgs, sigs)
    assert sp is libtrace.NOP_SPAN or "path" not in (sp.fields or {})


@needs_engine
def test_rehearsal_prints_the_pack_and_memo_metrics():
    """``benchmark/run.py --rehearse`` as the chip tool would start it:
    the cell's result line carries the two new per-layer metrics, every
    lane packed as a column and every header of the window answered from
    the memo (set-up's first header walked)."""
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", COMETBFT_TPU_HOST_THRESHOLD="2")
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "light10k-replay",
         "--rehearse", "--trace", "1", "--seed", "2147483999"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    # 3 is a rehearsal's exit code: it never passes for a result
    assert out.returncode == 3, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal_correct"] is True
    metrics = line["metrics"]
    assert metrics["pack_columnar_pct.replay"] == {"value": 100.0, "unit": "%"}
    assert metrics["arena_slot_memo_pct.replay"]["value"] >= 99.0
    assert metrics["device_lane_pct.replay"]["value"] == 100.0
