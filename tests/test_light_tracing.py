"""The light-client header's spans (libs/trace) and phase histograms
(light_verify_phase_seconds, crypto_verify_phase_seconds): one header
through ``light.Client`` and the device verifier on the CPU, small
validator counts. What the benchmark's per-layer metrics read is pinned
here, so a renamed family or a span that stops tiling fails in tier-1
and not on the chip."""

import glob
import json
import os
import threading
import types

import pytest

import helpers
from cometbft_tpu import light
from cometbft_tpu.crypto import batch as cbatch
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import trace as libtrace
from cometbft_tpu.libs.metrics import NodeMetrics
from cometbft_tpu.light.errors import InvalidHeaderError
from cometbft_tpu.light.store import MemStore
from cometbft_tpu.ops import verify as ov
from test_light import PERIOD, DictProvider, now_after

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_VALS = 8
CUT = 6  # lanes verify_commit_light counts of 8 equal validators

# phases that nest in no other: together they tile a header
TILING = (
    "light.fetch", "types.valset_hash", "light.header_basic",
    "commit.sign_bytes", "verify.pack", "verify.dispatch",
    "verify.readback",
)
# span name -> (histogram attribute of NodeMetrics, its labels)
PHASE_OF = {
    "light.verify_header": ("light_verify_phase_seconds", ("header",)),
    "light.fetch": ("light_verify_phase_seconds", ("fetch",)),
    "types.valset_hash": ("light_verify_phase_seconds", ("valset_hash",)),
    "light.header_basic": ("light_verify_phase_seconds", ("header_basic",)),
    "commit.sign_bytes": ("light_verify_phase_seconds", ("sign_bytes",)),
    "verify.pack": ("verify_phase_seconds", ("pack", "ed25519-tpu")),
    "verify.dispatch": ("verify_phase_seconds", ("dispatch", "ed25519-tpu")),
    "verify.readback": ("verify_phase_seconds", ("readback", "ed25519-tpu")),
    "verify.kernel_wait": (
        "verify_phase_seconds", ("kernel_wait", "ed25519-tpu")),
}


@pytest.fixture
def device_route(monkeypatch):
    """Commit checks of any size take ops/verify.verify_batch, as the
    device-path tests of test_observability.py route them on the CPU."""
    monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", 2)
    monkeypatch.setenv("COMETBFT_TPU_SHARD", "0")


@pytest.fixture
def tracer():
    libtrace.reset()
    libtrace.enable()
    yield libtrace
    libtrace.disable()
    libtrace.reset()


@pytest.fixture
def metrics():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    yield m
    libmetrics.pop_node_metrics(m)


def _client(blocks, provider=None):
    """A client that holds height 1 (its root-of-trust header)."""
    return light.Client(
        chain_id=helpers.CHAIN_ID,
        trust_options=light.TrustOptions(PERIOD, 1, blocks[1].hash()),
        primary=provider or DictProvider(blocks),
        trusted_store=MemStore(),
    )


def _spans():
    return [r for r in libtrace.ring_dump() if r["kind"] == "span"]


def _stack_empty() -> bool:
    return not getattr(libtrace._tls, "spans", None)


@pytest.fixture
def one_header(device_route, tracer, metrics):
    """Spans and metrics of exactly one adjacent header (height 2); the
    client's own root check (height 1) is dropped from both."""
    blocks = helpers.make_light_chain(3, n_vals=N_VALS)
    client = _client(blocks)
    libtrace.reset()
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        client.verify_light_block_at_height(2, now_after(blocks, 2))
    finally:
        libmetrics.pop_node_metrics(m)
    return _spans(), m


def test_one_header_is_one_tree_that_tiles(one_header):
    spans, _ = one_header
    roots = [s for s in spans if s["name"] == "light.verify_header"]
    assert len(roots) == 1
    root = roots[0]
    assert root["result"] == "accept" and root["height"] == 2
    assert "parent" not in root and root["root"] == root["span"]
    by_id = {s["span"]: s for s in spans}
    names = {s["name"] for s in spans}
    assert names == set(PHASE_OF), names ^ set(PHASE_OF)
    r0, r1 = root["start_ns"], root["start_ns"] + root["dur_ns"]
    for s in spans:
        assert s["root"] == root["span"], s
        assert s["ts"] == s["start_ns"] + s["dur_ns"]
        assert 0 <= s["cpu_ns"]
        assert r0 <= s["start_ns"] and s["start_ns"] + s["dur_ns"] <= r1, s
        hop = s
        while "parent" in hop:  # the parent chain reaches the root
            hop = by_id[hop["parent"]]
        assert hop is root
    (wait,) = [s for s in spans if s["name"] == "verify.kernel_wait"]
    assert by_id[wait["parent"]]["name"] == "verify.readback"
    assert wait["lanes"] == CUT and wait["backend"] == "ed25519-tpu"
    # the tiling phases never overlap on the thread, and leave little out
    tiles = sorted(
        (s["start_ns"], s["start_ns"] + s["dur_ns"], s["name"])
        for s in spans if s["name"] in TILING
    )
    for (_, end, a), (start, _, b) in zip(tiles, tiles[1:]):
        assert end <= start, (a, b)
    assert {t[2] for t in tiles} == set(TILING)
    per_name = {n: sum(1 for t in tiles if t[2] == n) for n in TILING}
    assert per_name["types.valset_hash"] == 3  # what a later PR may cut
    # each of the three is answered from the set's kept root: the chain's
    # builder hashed the set, and the provider serves that object
    hashes = [s for s in spans if s["name"] == "types.valset_hash"]
    assert [s["reused"] for s in hashes] == [1, 1, 1]
    assert all(s["validators"] == N_VALS for s in hashes)
    assert per_name["light.header_basic"] == 3
    assert per_name["commit.sign_bytes"] == 1
    (sb,) = [s for s in spans if s["name"] == "commit.sign_bytes"]
    assert sb["lanes"] == CUT


@pytest.mark.parametrize("span_name", sorted(PHASE_OF))
def test_histogram_sum_is_the_rings_duration(one_header, span_name):
    """One pair of clock readings feeds both sinks."""
    spans, m = one_header
    attr, labels = PHASE_OF[span_name]
    child = getattr(m, attr).labels(*labels)
    ring_s = sum(
        s["dur_ns"] for s in spans if s["name"] == span_name) / 1e9
    assert ring_s > 0
    assert child._sum == pytest.approx(ring_s, rel=0.01)
    # one observation per span, except the three phases verify_batch
    # sums over a batch's chunks (one chunk here)
    assert child._n == sum(1 for s in spans if s["name"] == span_name)


def test_valset_hash_counter_has_both_series(one_header):
    """What valset_hash_reuse_pct.replay divides: one count per span."""
    _, m = one_header
    assert m.valset_hash_total.labels("reused").value() == 3
    assert m.valset_hash_total.labels("computed").value() == 0
    text = m.registry.render()
    for result in ("computed", "reused"):
        assert (
            'cometbft_tpu_types_valset_hash_total{result="%s"} ' % result
        ) in text


def test_sign_bytes_counter_grows_by_the_spans_lanes(one_header):
    """What sign_bytes_batched_pct.* divides: one walk, one span, and the
    batched encoder's series grows by that span's ``lanes``."""
    spans, m = one_header
    (sb,) = [s for s in spans if s["name"] == "commit.sign_bytes"]
    assert sb["lanes"] == CUT and sb["encoder"] == "batched"
    lanes = m.commit_sign_bytes_lanes_total
    assert lanes.labels("batched").value() == sb["lanes"]
    assert lanes.labels("per_lane").value() == 0
    text = m.registry.render()
    for path in ("batched", "per_lane"):
        assert (
            'cometbft_tpu_types_commit_sign_bytes_lanes_total{path="%s"} '
            % path
        ) in text


def test_lanes_handed_back_count_per_lane(
    device_route, tracer, metrics, monkeypatch
):
    """Without the native engine the walk encodes lane by lane, and both
    the counter and the span say so."""
    from cometbft_tpu.crypto import host_batch

    monkeypatch.setattr(host_batch, "vote_sign_bytes", lambda *a: None)
    blocks = helpers.make_light_chain(3, n_vals=N_VALS)
    client = _client(blocks)
    libtrace.reset()
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        client.verify_light_block_at_height(2, now_after(blocks, 2))
    finally:
        libmetrics.pop_node_metrics(m)
    (sb,) = [s for s in _spans() if s["name"] == "commit.sign_bytes"]
    assert sb["lanes"] == CUT and sb["encoder"] == "per_lane"
    assert m.commit_sign_bytes_lanes_total.labels("per_lane").value() == CUT
    assert m.commit_sign_bytes_lanes_total.labels("batched").value() == 0


def test_single_verify_walk_counts_its_lanes_per_lane(metrics):
    """A commit of one signature is below the batch threshold: its lane
    goes through canonical.vote_sign_bytes and is counted so."""
    from cometbft_tpu.types import validation

    blocks = helpers.make_light_chain(2, n_vals=1)
    lb = blocks[2]
    commit = lb.signed_header.commit
    validation.verify_commit_light(
        helpers.CHAIN_ID, lb.validator_set, commit.block_id, 2, commit)
    lanes = metrics.commit_sign_bytes_lanes_total
    assert lanes.labels("per_lane").value() == 1
    assert lanes.labels("batched").value() == 0


class _FreshSetProvider(DictProvider):
    """A provider that decodes its reply: a set object of its own per
    light block, with no root kept."""

    def light_block(self, height):
        import dataclasses

        from cometbft_tpu.types import serialization

        lb = super().light_block(height)
        vals = serialization.loads(serialization.dumps(lb.validator_set))
        return dataclasses.replace(lb, validator_set=vals)


def test_a_decoded_set_is_hashed_once_a_header(device_route, tracer, metrics):
    blocks = helpers.make_light_chain(3, n_vals=N_VALS)
    client = _client(blocks, _FreshSetProvider(blocks))
    libtrace.reset()
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        client.verify_light_block_at_height(2, now_after(blocks, 2))
    finally:
        libmetrics.pop_node_metrics(m)
    hashes = [s for s in _spans() if s["name"] == "types.valset_hash"]
    assert [s["reused"] for s in hashes] == [0, 1, 1]
    assert m.valset_hash_total.labels("computed").value() == 1
    assert m.valset_hash_total.labels("reused").value() == 2


class _DownProvider(DictProvider):
    def light_block(self, height):
        if height == 2:
            raise ConnectionError("provider down")
        return super().light_block(height)


def _forged(blocks):
    """Height 2 with one counted signature altered: a refused commit."""
    import dataclasses

    lb = blocks[2]
    commit = lb.signed_header.commit
    sigs = list(commit.signatures)
    bad = bytearray(sigs[1].signature)
    bad[3] ^= 0x40
    sigs[1] = dataclasses.replace(sigs[1], signature=bytes(bad))
    commit = dataclasses.replace(commit, signatures=sigs)
    sh = dataclasses.replace(lb.signed_header, commit=commit)
    return {**blocks, 2: dataclasses.replace(lb, signed_header=sh)}


@pytest.mark.parametrize("how, result, raises", [
    ("forged", "refuse", InvalidHeaderError),
    ("down", "error", ConnectionError),
])
def test_failed_header_closes_every_span(
    device_route, tracer, metrics, how, result, raises
):
    blocks = helpers.make_light_chain(3, n_vals=N_VALS)
    if how == "forged":
        provider = DictProvider(_forged(blocks))
    else:
        provider = _DownProvider(blocks)
    client = _client(blocks, provider)
    libtrace.reset()
    with pytest.raises(raises):
        client.verify_light_block_at_height(2, now_after(blocks, 2))
    assert _stack_empty(), "a span outlived the exception on this thread"
    spans = _spans()
    (root,) = [s for s in spans if s["name"] == "light.verify_header"]
    assert root["result"] == result
    assert all(s["root"] == root["span"] for s in spans)
    if how == "forged":  # the verifier ran and every phase still closed
        assert {s["name"] for s in spans} == set(PHASE_OF)
    else:
        assert {s["name"] for s in spans} == {
            "light.verify_header", "light.fetch"}


@pytest.mark.parametrize("chunk, n, chunks", [
    (ov._CHUNK, 20, 1),
    (8, 20, 3),
])
def test_dispatch_lanes_add_up_once(
    device_route, tracer, metrics, monkeypatch, chunk, n, chunks
):
    """What verify_roofline_pct.* divides by: the lanes of a batch's
    verify.dispatch records are the batch's lanes, exactly once, and the
    histogram still gets one observation per batch."""
    from cometbft_tpu.crypto.keys import Ed25519PrivKey

    monkeypatch.setattr(ov, "_CHUNK", chunk)
    pvs = [Ed25519PrivKey.from_seed(bytes([7, i]) * 16) for i in range(n)]
    msgs = [b"lanes-%d" % i for i in range(n)]
    sigs = [pv.sign(m) for pv, m in zip(pvs, msgs)]
    ok, bits = ov.verify_batch([pv.pub_key().data for pv in pvs], msgs, sigs)
    assert ok and len(bits) == n
    records = [r for r in libtrace.ring_dump()
               if r["name"].startswith("verify.")]
    assert all(r["kind"] == "span" for r in records)  # no event beside it
    for phase in ("pack", "dispatch", "readback", "kernel_wait"):
        mine = [r for r in records if r["name"] == "verify." + phase]
        assert len(mine) == chunks, (phase, mine)
        assert sum(r["lanes"] for r in mine) == n
        per_batch = 1 if phase != "kernel_wait" else chunks
        child = metrics.verify_phase_seconds.labels(phase, "ed25519-tpu")
        assert child._n == per_batch
        assert child._sum == pytest.approx(
            sum(r["dur_ns"] for r in mine) / 1e9, rel=0.01)


class _CountingAnnotation:
    made = 0

    def __init__(self, name, **kwargs):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_tracing_off_builds_no_span_and_no_annotation(
    device_route, metrics, monkeypatch
):
    assert not libtrace.enabled()
    monkeypatch.setattr(libtrace, "_annotation", _CountingAnnotation)
    monkeypatch.setattr(_CountingAnnotation, "made", 0)
    libtrace.reset()
    blocks = helpers.make_light_chain(3, n_vals=N_VALS)
    client = _client(blocks)
    client.verify_light_block_at_height(2, now_after(blocks, 2))
    assert libtrace.ring_dump() == []
    assert _CountingAnnotation.made == 0
    assert _stack_empty()
    # the histograms are always on
    assert metrics.light_verify_phase_seconds.labels("header")._n == 2
    # and with tracing on the same header constructs one per with-span
    libtrace.enable()
    try:
        client.verify_light_block_at_height(3, now_after(blocks, 3))
    finally:
        libtrace.disable()
    assert _CountingAnnotation.made == len(_spans()) > 0
    libtrace.reset()


def test_enabling_trace_does_not_import_jax():
    """The mirror is taken only where jax is loaded already."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from cometbft_tpu.libs import trace as t\n"
        "t.enable()\n"
        "with t.span('light.verify_header', height=1):\n"
        "    pass\n"
        "assert t.ring_dump()[0]['name'] == 'light.verify_header'\n"
        "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO,
                   timeout=120)


def _host_events(trace_dir):
    import jax

    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out, start = [], None
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats)["profile_start_time"])
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bft."):
                    out.append((e.name, int(e.start_ns),
                                int(e.start_ns + e.duration_ns),
                                dict(e.stats)))
    return out, start


def test_spans_stand_on_the_profilers_host_plane(
    device_route, tracer, metrics, tmp_path
):
    """Under a profiler trace the with-spans are bft.* annotations on
    the host plane, nested as in the ring and on the ring's clock."""
    import jax

    blocks = helpers.make_light_chain(3, n_vals=N_VALS)
    client = _client(blocks)
    libtrace.reset()
    failure = []

    def traced():
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
            try:
                client.verify_light_block_at_height(
                    2, now_after(blocks, 2))
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # read on the test's thread
            failure.append(e)

    # the test's own time limit: a profiler that wedges fails, not hangs
    worker = threading.Thread(target=traced, daemon=True)
    worker.start()
    worker.join(timeout=180)
    assert not worker.is_alive(), "the profiler trace did not end"
    assert not failure, failure
    events, start = _host_events(str(tmp_path))
    (hdr,) = [e for e in events if e[0] == "bft.light.verify_header"]
    hashes = [e for e in events if e[0] == "bft.types.valset_hash"]
    assert len(hashes) == 3
    for _, s, e, stats in hashes:
        assert hdr[1] <= s and e <= hdr[2]
        assert stats.get("validators") == N_VALS
    assert hdr[3].get("height") == 2
    # one clock: the ring's start_ns is the annotation's start on the
    # profiler's epoch clock (the span reads it just before entering)
    (root,) = [s for s in _spans() if s["name"] == "light.verify_header"]
    assert abs(start + hdr[1] - root["start_ns"]) < 5_000_000
    assert {e[0][len("bft."):] for e in events} == set(PHASE_OF)


NEW_METRICS = (
    "provider_fetch_ms_per_header", "valset_hash_ms_per_header",
    "header_basic_ms_per_header", "sign_bytes_ms_per_header",
    "kernel_wait_ms_per_header", "light_span_coverage_pct.replay",
    "valset_hash_reuse_pct.replay", "sign_bytes_batched_pct.replay",
    "sign_bytes_batched_pct.backfill", "pack_columnar_pct.replay",
    "pack_columnar_pct.backfill", "arena_slot_memo_pct.replay",
)


@pytest.fixture
def rendered_after_a_header(device_route):
    """Series names of the registry the benchmark snapshots
    (node_metrics() with no node up), after one device-verified header."""
    blocks = helpers.make_light_chain(3, n_vals=N_VALS)
    _client(blocks).verify_light_block_at_height(2, now_after(blocks, 2))
    text = libmetrics.node_metrics().registry.render()
    return {
        line.rpartition(" ")[0] for line in text.splitlines()
        if line and not line.startswith("#")
    }


@pytest.mark.parametrize("name", NEW_METRICS)
def test_benchmark_metric_reads_a_series_that_exists(
    rendered_after_a_header, name
):
    with open(os.path.join(REPO, "benchmark", "metrics", name + ".json")) as f:
        metric = json.load(f)
    assert metric["reader"] == "counter_ratio"
    keys = metric["numerator"] + metric["denominator"]
    assert keys
    for key in keys:
        assert key.startswith("prom.")
        assert key[len("prom."):] in rendered_after_a_header, key
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"] if m["name"] == name]
    assert entry["moves"] == "sigs_per_s"
    assert entry["workloads"] == [
        "qa175-relayers-backfill" if name.endswith(".backfill")
        else "light10k-replay"
    ]


@pytest.mark.parametrize("cell", ["replay", "backfill"])
def test_sign_bytes_batched_pct_resolves_from_a_windows_counters(
    device_route, cell
):
    """The benchmark's own snapshot, delta and reader over a window of
    one header: every lane went through the batched encoder."""
    from benchmark.harness import counters
    from benchmark.readers import counter_ratio

    name = f"sign_bytes_batched_pct.{cell}"
    with open(os.path.join(REPO, "benchmark", "metrics", name + ".json")) as f:
        metric = json.load(f)
    blocks = helpers.make_light_chain(3, n_vals=N_VALS)
    client = _client(blocks)
    before = counters.snapshot()
    client.verify_light_block_at_height(2, now_after(blocks, 2))
    after = counters.snapshot()
    window = types.SimpleNamespace(counters=counters.delta(before, after))
    assert counter_ratio.read(metric, window) == 100.0
    # a window in which no commit was checked has nothing to read
    idle = types.SimpleNamespace(counters=counters.delta(after, after))
    assert counter_ratio.read(metric, idle) is None


def _metric_file(name):
    with open(os.path.join(REPO, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)


def test_pack_and_memo_metrics_resolve_from_a_windows_counters(
    device_route, monkeypatch
):
    """The benchmark's own snapshot, delta and reader: a header's lanes
    are packed as columns, and the second commit check of one validator
    set finds its slots in the memo (the first one, the client's check
    of its root of trust, walked)."""
    from benchmark.harness import counters, spec
    from benchmark.readers import counter_ratio

    monkeypatch.setattr(ov, "_PUBKEY_CACHE", ov.PubkeyTableCache(capacity=64))
    blocks = helpers.make_light_chain(3, n_vals=N_VALS)
    snaps = [counters.snapshot()]
    client = _client(blocks)
    snaps.append(counters.snapshot())
    client.verify_light_block_at_height(2, now_after(blocks, 2))
    snaps.append(counters.snapshot())
    first, second = (
        types.SimpleNamespace(counters=counters.delta(a, b))
        for a, b in zip(snaps, snaps[1:])
    )
    idle = types.SimpleNamespace(counters=counters.delta(snaps[2], snaps[2]))
    memo = _metric_file("arena_slot_memo_pct.replay")
    assert counter_ratio.read(memo, first) == 0.0
    assert counter_ratio.read(memo, second) == 100.0
    assert counter_ratio.read(memo, idle) is None
    for cell in ("replay", "backfill"):
        packed = _metric_file(f"pack_columnar_pct.{cell}")
        assert counter_ratio.read(packed, first) == 100.0
        assert counter_ratio.read(packed, second) == 100.0
        assert counter_ratio.read(packed, idle) is None
    # and a cell loaded as the harness loads it carries them, files and all
    for cell, names in (
        ("light10k-replay",
         {"pack_columnar_pct.replay", "arena_slot_memo_pct.replay"}),
        ("qa175-relayers-backfill", {"pack_columnar_pct.backfill"}),
    ):
        loaded = {m["name"]: m for m in spec.load_cell(cell).per_layer}
        assert names <= set(loaded)
        for name in names:
            assert loaded[name]["reader"] == "counter_ratio"
            assert loaded[name]["layer"] == "host pack"
