"""Who holds the interpreter lock: the kernel's CPU clock of every thread,
summed by role (``thread_cpu_seconds_total{role}``, libs/profile's sampler
thread reads it), the receive routine's own CPU inside its drains
(``consensus_drain_cpu_seconds_total``), and a sampler whose cost follows
the threads that moved: a parked thread costs no stack walk and no ring
row a tick, and the profile it yields is the one a full walk yields."""

from __future__ import annotations

import sys
import threading
import time

import pytest

import helpers
from cometbft_tpu.libs import health as libhealth
from cometbft_tpu.libs import metrics as libmetrics
from cometbft_tpu.libs import profile as libprofile
from cometbft_tpu.libs.metrics import NodeMetrics, audit_label_cardinality


@pytest.fixture
def metrics():
    m = NodeMetrics()
    libmetrics.push_node_metrics(m)
    yield m
    libmetrics.pop_node_metrics(m)


@pytest.fixture
def quiet_profiler(monkeypatch):
    """No sampler of the plane runs, the tables are fresh, and roles
    are counted in a table of this test's own (the cap is process-wide)."""
    libprofile.disable()
    assert not libprofile.enabled()
    monkeypatch.setattr(libprofile, "_roles", ["other"])
    monkeypatch.setattr(libprofile, "_role_ids", {"other": 0})
    monkeypatch.setattr(libprofile, "_name_roles", {})
    libprofile.reset(libprofile.DEFAULT_RING)
    yield
    libprofile.reset(libprofile.DEFAULT_RING)


def _park(ev: threading.Event) -> None:
    ev.wait()


def _left(ev: threading.Event) -> None:
    ev.wait()


def _right(ev: threading.Event) -> None:
    ev.wait()


def _stepper(evs) -> None:
    """Parks on each event in turn, alternately from two functions: a
    thread whose stack is another one at every tick."""
    for i, ev in enumerate(evs):
        (_left if i % 2 == 0 else _right)(ev)


def _wait_parked(tids, caller: str, timeout: float = 20.0) -> None:
    """Until every thread of ``tids`` sits in ``Condition.wait`` under
    ``Event.wait`` under ``caller``, at the same instruction over three
    looks: blocked in the lock's acquire, where nothing moves it."""
    seen: dict = {}
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        frames = sys._current_frames()
        steady = 0
        for tid in tids:
            f = frames.get(tid)
            names, g = [], f
            while g is not None and len(names) < 3:
                names.append(g.f_code.co_name)
                g = g.f_back
            if names != ["wait", "wait", caller]:
                seen.pop(tid, None)
                continue
            mark = (f, f.f_lasti)
            n = seen.get(tid, (None, 0))
            n = (mark, n[1] + 1) if n[0] == mark else (mark, 1)
            seen[tid] = n
            steady += n[1] >= 3
        if steady == len(tids):
            return
        time.sleep(0.005)
    raise AssertionError(f"threads never parked in {caller}")


def _frames_of(tids) -> dict:
    return {
        tid: f for tid, f in sys._current_frames().items() if tid in tids
    }


def _bridged(m: NodeMetrics, fam: str) -> dict:
    return {
        key: child.value()
        for key, child in getattr(m, fam)._children.items()
    }


# -------------------------------------------------------------- roles


@pytest.mark.parametrize("name, role", [
    ("gossip-data-1a2b3c4d", "gossip-data"),
    ("gossip-votes-0f9e8d7c", "gossip-votes"),
    ("maj23-deadbeef", "maj23"),
    ("mempool-bcast-12345678", "mempool-bcast"),
    ("evidence-bcast-abcdef01", "evidence-bcast"),
    ("pm-fetch-3", "pm-fetch"),
    ("Thread-7 (run)", "Thread"),
    ("ThreadPoolExecutor-0_1", "ThreadPoolExecutor"),
    ("cs-receive", "cs-receive"),
    ("prof-sampler", "prof-sampler"),
    ("node0-http", "node0-http"),
    ("bench-feeder", "bench-feeder"),
    ("odd name/with:chars", "odd_name_with_chars"),
    ("-42", "other"),
])
def test_role_strips_the_peer_id_and_ordinal_suffix(name, role):
    assert libprofile.role_of(name) == role
    assert libmetrics._LABEL_VALUE_RULES["role"].match(role)


def test_roles_cap_at_32_values_then_fold_into_other(quiet_profiler):
    ids = [libprofile._role_id(f"role{i:02d}x-{i:08x}") for i in range(40)]
    assert len(libprofile._roles) == libprofile._MAX_ROLES == 32
    assert ids[:31] == list(range(1, 32))
    assert set(ids[31:]) == {0} and libprofile._roles[0] == "other"
    # another peer's thread of a known role keeps its role
    assert libprofile._role_id("role05x-ffffffff") == ids[5]


def test_audit_holds_the_role_label_to_its_shape(metrics):
    metrics.thread_cpu_seconds.labels("gossip-data").inc(1.0)
    assert audit_label_cardinality(metrics.registry) == []
    metrics.thread_cpu_seconds.labels("gossip-data 1a2b3c4d").inc(1.0)
    bad = audit_label_cardinality(metrics.registry)
    assert len(bad) == 1 and "role" in bad[0], bad


# ------------------------------------------------------- thread CPU


def test_thread_cpu_counts_a_spinner_not_a_parked_thread_and_keeps_the_exited(
    quiet_profiler,
):
    t = libprofile._T
    s = libprofile._SamplerThread(libprofile.DEFAULT_HZ)
    stop = threading.Event()
    spun = threading.Event()

    def spin():
        deadline = time.thread_time() + 0.15
        while not stop.is_set():
            if time.thread_time() >= deadline:
                spun.set()

    parked = threading.Thread(
        target=_park, args=(stop,), name="cpu-parked-7", daemon=True)
    spinner = threading.Thread(
        target=spin, name="cpu-spin-1a2b3c4d", daemon=True)
    parked.start()
    s._read_cpu(t)  # the baseline
    spinner.start()
    assert spun.wait(timeout=30)
    s._read_cpu(t)  # the spinner, counted from its start
    spin = libprofile._role_ids["cpu-spin"]
    park = libprofile._role_ids["cpu-parked"]
    assert t.cpu_role[spin] >= 0.15e9, t.cpu_role[spin]
    assert t.cpu_role[park] < 0.01e9, t.cpu_role[park]
    # it exits: its last reading stays (what it used after that reading
    # is lost with its clock), and nothing is read of it any more
    stop.set()
    spinner.join(timeout=10)
    kept = t.cpu_role[spin]
    s._read_cpu(t)
    assert t.cpu_role[spin] == kept
    assert spinner not in s._clocks
    m = NodeMetrics()
    libprofile.sample(m)
    cpu = _bridged(m, "thread_cpu_seconds")
    assert cpu[("cpu-spin",)] == pytest.approx(kept / 1e9)
    assert cpu.get(("cpu-parked",), 0.0) < 0.01
    # a second bridge of the same columns adds nothing
    libprofile.sample(m)
    assert _bridged(m, "thread_cpu_seconds") == cpu
    parked.join(timeout=10)


def test_a_live_sampler_reads_its_own_price(quiet_profiler, metrics):
    """The sampler reads the clocks itself, at 4 Hz and at stop: a plane
    that runs half a second has bridged its own role's CPU."""
    libprofile.enable()
    try:
        time.sleep(0.5)
    finally:
        libprofile.disable()
    libprofile.sample(metrics)
    cpu = _bridged(metrics, "thread_cpu_seconds")
    assert cpu.get(("prof-sampler",), 0.0) > 0, cpu
    assert sum(cpu.values()) >= cpu[("prof-sampler",)]


# ------------------------------------------------------------ drains


def _solo():
    genesis, pvs = helpers.make_genesis(1)
    return helpers.make_consensus_node(genesis, pvs[0])


def test_a_drain_bridges_thread_cpu_into_the_registry(metrics):
    """The harness never scrapes: the receive routine bridges the
    profiler's CPU columns once a drain, beside the locks' ledger."""
    cs, parts = _solo()
    role = libprofile._role_id("cs-receive")
    label = (libprofile._roles[role],)
    libprofile.sample(metrics)  # what this process's threads used so far
    base = _bridged(metrics, "thread_cpu_seconds").get(label, 0.0)
    libprofile._T.cpu_role[role] += 1_500_000
    try:
        assert cs._process_batch([]) is False
        got = _bridged(metrics, "thread_cpu_seconds")[label] - base
        assert got == pytest.approx(0.0015)
        # a second drain of the same columns adds nothing
        assert cs._process_batch([]) is False
        got = _bridged(metrics, "thread_cpu_seconds")[label] - base
        assert got == pytest.approx(0.0015)
    finally:
        helpers.stop_node(cs, parts)


def test_a_drain_counts_its_own_cpu_within_its_wall(metrics):
    cs, parts = _solo()
    try:
        for _ in range(3):
            assert cs._process_batch([]) is False
    finally:
        helpers.stop_node(cs, parts)
    cpu = metrics.consensus_drain_cpu_seconds_total.value()
    wall = metrics.consensus_vote_phase_seconds.labels("drain")._sum
    assert 0 < cpu <= wall
    rendered = metrics.registry.render()
    assert "cometbft_tpu_consensus_drain_cpu_seconds_total " in rendered


def test_drain_cpu_stays_within_drain_wall_on_a_burst(metrics):
    """A real 4-validator burst to height 2: every drain of every node
    adds its routine's CPU, never more than the drain's wall time."""
    genesis, pvs = helpers.make_genesis(4)
    nodes = [helpers.make_consensus_node(genesis, pv) for pv in pvs]
    helpers.wire_perfect_gossip(nodes)
    try:
        for cs, _ in nodes:
            cs.start()
        assert helpers.wait_for_height(nodes[0][1], 2, timeout=120)
    finally:
        for cs, parts in nodes:
            helpers.stop_node(cs, parts)
    drains = metrics.consensus_vote_phase_seconds.labels("drain")
    cpu = metrics.consensus_drain_cpu_seconds_total.value()
    assert drains._n > 0
    assert 0 < cpu <= drains._sum, (cpu, drains._sum)


# -------------------------------------------------- memoised sampler


def _drive(memo: bool, parked: set, ticks: int, monkeypatch) -> dict:
    """``ticks`` samples of the parked threads plus a stepper that is
    somewhere else at every tick; everything the profile yields."""
    rows: list = []
    monkeypatch.setattr(libhealth, "enabled", lambda: True)
    monkeypatch.setattr(
        libhealth, "record",
        lambda code, h=0, r=0, a=0, b=0: rows.append((r, b))
        if code == libhealth.EV_PROF else None,
    )
    libprofile.reset(libprofile.DEFAULT_RING)
    s = libprofile._SamplerThread(libprofile.DEFAULT_HZ, memo=memo)
    evs = [threading.Event() for _ in range(ticks)]
    st = threading.Thread(
        target=_stepper, args=(evs,), name="load-stepper", daemon=True)
    st.start()
    mine = parked | {st.ident}
    for i, ev in enumerate(evs):
        _wait_parked([st.ident], "_left" if i % 2 == 0 else "_right")
        s._tick(frames=_frames_of(mine))
        ev.set()
    st.join(timeout=10)
    s.finish()
    m = NodeMetrics()
    libprofile.sample(m)
    ev_prof: dict = {}
    for sub, n in rows:
        ev_prof[sub] = ev_prof.get(sub, 0) + n
    return {
        "walks": s.walks,
        "profile_samples_total": _bridged(m, "profile_samples"),
        "collapsed": libprofile.collapsed(libprofile.snapshot_agg()),
        "ring": sorted(r[2:] for r in libprofile._T.rows(0)),
        "ev_prof": ev_prof,
    }


@pytest.fixture
def parked_threads():
    ev = threading.Event()
    ths = [
        threading.Thread(
            target=_park, args=(ev,), name=f"load-parked-{i}", daemon=True)
        for i in range(20)
    ]
    for th in ths:
        th.start()
    tids = {th.ident for th in ths}
    _wait_parked(tids, "_park")
    yield tids
    ev.set()
    for th in ths:
        th.join(timeout=10)


def test_memoised_walk_yields_what_a_full_walk_yields(
    quiet_profiler, parked_threads, monkeypatch,
):
    ticks = 40
    full = _drive(False, parked_threads, ticks, monkeypatch)
    memo = _drive(True, parked_threads, ticks, monkeypatch)
    for what in ("profile_samples_total", "collapsed", "ring", "ev_prof"):
        assert memo[what] == full[what], what
    n = ticks * (len(parked_threads) + 1)
    assert sum(full["profile_samples_total"].values()) == n
    assert sum(full["ev_prof"].values()) == n
    assert sum(r[-1] for r in full["ring"]) == n
    # the 20 parked threads are one run each; the stepper one a tick
    assert len(memo["ring"]) == len(parked_threads) + ticks


def test_a_parked_thread_costs_no_stack_walk(
    quiet_profiler, parked_threads, monkeypatch,
):
    ticks = 40
    memo = _drive(True, parked_threads, ticks, monkeypatch)
    full = _drive(False, parked_threads, ticks, monkeypatch)
    # a walk each at first sight, then only the stepper, which moved
    assert memo["walks"] == len(parked_threads) + ticks
    assert full["walks"] == ticks * (len(parked_threads) + 1)


def test_open_runs_count_before_they_are_folded(
    quiet_profiler, parked_threads,
):
    """Between two flushes a parked thread's samples are in no counter
    yet; snapshot_agg (the ?seconds=N endpoint's two readings) adds
    them, so a window counts them exactly."""
    s = libprofile._SamplerThread(libprofile.DEFAULT_HZ)
    for _ in range(10):
        s._tick(frames=_frames_of(parked_threads))
    assert sum(libprofile._agg.values()) == 0  # no flush yet
    assert sum(s.agg_now().values()) == 10 * len(parked_threads)
    s.finish()
    assert sum(libprofile._agg.values()) == 10 * len(parked_threads)
    assert sum(s.agg_now().values()) == 10 * len(parked_threads)


def test_ring_holds_30_s_of_ticks_at_260_parked_threads(quiet_profiler):
    ev = threading.Event()
    ths = [
        threading.Thread(
            target=_park, args=(ev,), name=f"gossip-data-{i:08x}",
            daemon=True)
        for i in range(260)
    ]
    for th in ths:
        th.start()
    try:
        tids = {th.ident for th in ths}
        _wait_parked(tids, "_park")
        s = libprofile._SamplerThread(libprofile.DEFAULT_HZ)
        ticks = int(30 * libprofile.DEFAULT_HZ) + 1
        for _ in range(ticks):
            s._tick(frames=_frames_of(tids))
        st = libprofile._T.status()
        assert st["capacity"] == libprofile.DEFAULT_RING
        assert st["span_ticks"] == ticks >= 30 * libprofile.DEFAULT_HZ
        assert st["recorded"] == 260
        assert sum(r[-1] for r in libprofile._T.rows(0)) == ticks * 260
        assert s.walks == 260
        # the recent-sample view a bundle takes counts every sample
        recent = libprofile.recent(3600.0)
        assert recent["samples"] == ticks * 260
        s.finish()
    finally:
        ev.set()
        for th in ths:
            th.join(timeout=10)


# --------------------------------------------------------- EV_PROF


def test_ev_prof_carries_the_kernel_cpu_not_samples_times_period(
    quiet_profiler, monkeypatch,
):
    """A thread asleep in C samples as on-CPU (its leaf is its caller's
    frame), one that spins really is: the EV_PROF row of the first says
    almost no CPU, of the second most of the window."""
    rows: list = []
    monkeypatch.setattr(libhealth, "enabled", lambda: True)
    monkeypatch.setattr(
        libhealth, "record",
        lambda code, h=0, r=0, a=0, b=0: rows.append((r, a, b))
        if code == libhealth.EV_PROF else None,
    )
    stop = threading.Event()

    def sleepy():
        while not stop.is_set():
            time.sleep(0.001)

    def spin():
        while not stop.is_set():
            pass

    ths = [
        threading.Thread(target=sleepy, name="mempool-sleepy", daemon=True),
        threading.Thread(target=spin, name="light-spin", daemon=True),
    ]
    for th in ths:
        th.start()
    s = libprofile._SamplerThread(libprofile.DEFAULT_HZ)
    s.start()
    try:
        time.sleep(1.6)
    finally:
        s.stop()
        s.join(timeout=10)
        stop.set()
        for th in ths:
            th.join(timeout=10)
    subs = libprofile._SUB_IDS
    sleepy_rows = [(a, b) for r, a, b in rows if r == subs["mempool"]]
    spin_rows = [(a, b) for r, a, b in rows if r == subs["light"]]
    assert sleepy_rows and spin_rows, rows
    # the sleeper: every sample on-CPU by its frame, a few ms of CPU
    slept_samples = sum(b for _, b in sleepy_rows)
    assert sum(a for a, _ in sleepy_rows) < 0.25 * slept_samples * s.period_ns
    spun_samples = sum(b for _, b in spin_rows)
    assert sum(a for a, _ in spin_rows) > 0.1 * spun_samples * s.period_ns
