"""``rot10k-bisect`` at test size on the CPU: a sound run is correct, each
control comes out not correct, each fault planted under a whole run (a
verifier that answers yes, an address index left stale by the rotation, a
script the provider runs out of, a target the reference holds no commit
for) comes out not correct, the client trusts the heights the plain
bisection trusts, and the series the cell's metric files read are the ones
the program's registry exports."""

import time

import pytest

from benchmark import run as bench_run
from benchmark.drivers import bisect_rotation
from benchmark.harness import counters, spec, tracing
from benchmark.reference import bisect_ref
from cellrun import CPU

CELL = "rot10k-bisect"


def run(seed: int, seconds: float = 2.0, control: str = ""):
    cell = spec.load_cell(CELL, rehearsal=True)
    return bench_run.execute(cell, seed, seconds, False, CPU,
                             control=control, t_process=time.monotonic())


def bad_checks(result: dict) -> set:
    return {k for k, c in result["checks"].items() if c["value"] > c["limit"]}


def test_sound_run_is_correct():
    res = run(2**31 + 4242)
    assert not bad_checks(res), res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 20
    assert res["metrics"]["sigs_per_s"]["value"] > 0


@pytest.mark.parametrize("control", ["stride8", "trust_all", "by_index"])
def test_control_is_not_correct(control):
    res = run(101, control=control)
    assert bad_checks(res) == {"verdict_mismatches"}, res["checks"]


def _yes_verifier(monkeypatch):
    from cometbft_tpu.crypto import batch as crypto_batch

    def yes(self):
        return True, [True] * len(self)

    monkeypatch.setattr(crypto_batch.Ed25519BatchVerifier, "verify", yes)


def _stale_index(monkeypatch):
    """The address map of the first set the walk reads, kept for every set
    after it: the rotation's new validators are unknown to it, and its
    positions are the old set's."""
    from cometbft_tpu.types.validator_set import ValidatorSet

    kept = {}
    own = ValidatorSet.address_index

    def stale(self):
        if "index" not in kept:
            kept["index"] = own(self)
        return kept["index"]

    monkeypatch.setattr(ValidatorSet, "address_index", stale)


def _short_script(monkeypatch):
    """The provider runs out of script before the pass ends."""
    from benchmark.drivers import bisect_rotation

    plan = bisect_rotation.Driver._plan

    def short(self, altered):
        script, events, done = plan(self, altered)
        return script[: len(script) - 3], events, done

    monkeypatch.setattr(bisect_rotation.Driver, "_plan", short)


def _unsigned_target(monkeypatch):
    """The reference holds no commit for the target: its pass ends at the
    first request (a control that leaves the script's path meets such a
    height), and the run is judged, not stopped."""
    from benchmark.drivers import bisect_rotation

    chain_of = bisect_rotation.Driver.reference_chain

    def unsigned(self):
        chain = chain_of(self)
        del chain.commits[(self.target, bisect_rotation.SOUND)]
        return chain

    monkeypatch.setattr(bisect_rotation.Driver, "reference_chain", unsigned)


@pytest.mark.parametrize("fault,caught", [
    (_yes_verifier, {"verdict_mismatches"}),
    (_stale_index, {"verdict_mismatches"}),
    (_short_script, {"fetches_off_script"}),
    (_unsigned_target, {"verdict_mismatches"}),
])
def test_planted_fault_is_caught(monkeypatch, fault, caught):
    fault(monkeypatch)
    res = run(102)
    assert caught <= bad_checks(res), res["checks"]


def _no_address_index(monkeypatch):
    from cometbft_tpu.types.validator_set import ValidatorSet

    monkeypatch.delattr(ValidatorSet, "address_index")


def _no_attempt_counter(monkeypatch):
    from cometbft_tpu.libs import metrics as libmetrics

    monkeypatch.delattr(libmetrics.node_metrics(),
                        "light_bisection_attempts_total")


@pytest.mark.parametrize("lack", [_no_address_index, _no_attempt_counter])
def test_program_without_the_cell_mechanisms_is_not_measured(
        monkeypatch, lack):
    """A program without the address map or the attempt counter exits 5
    before any set-up, with no result line, rather than running a window in
    which no verified step fits."""
    from benchmark.drivers import bisect_rotation

    lack(monkeypatch)
    t = time.monotonic()
    with pytest.raises(SystemExit) as refused:
        run(103)
    assert refused.value.code == bisect_rotation.EXIT_CANNOT_RUN
    assert time.monotonic() - t < 5


@pytest.mark.parametrize("seed", [7, 2**31 + 77, 2**32 + 1001])
def test_trusted_heights_are_the_references(seed):
    """One pass of the driver's client, answer by answer, against the plain
    bisection over the same script: the heights it came to trust, in order,
    and each accept or refusal."""
    cell = spec.load_cell(CELL, rehearsal=True)
    d = bisect_rotation.Driver(cell, seed, tracing.Tracer(False, "", 0.0))
    d.setup(1.0)
    steps, answers = [], []
    d._one_pass(bisect_rotation._ScriptProvider(d, float("inf")), steps,
                answers, d.mix["max_requests_per_pass"])
    want = bisect_ref.pass_answers(
        d.reference_chain(), d.script, d.root, d.target,
        d.mix["max_requests_per_pass"])
    assert [(trace, verdict) for _t, trace, verdict in answers] == [
        (trace, verdict) for trace, verdict, _lanes in want]
    assert sum(len(trace) for _t, trace, _v in answers) > 4  # it pivoted
    assert answers[-1][2][0] == "accept"


SERIES = (
    'light_bisection_attempts_total{outcome="verified"}',
    'light_bisection_attempts_total{outcome="cant_trust"}',
    'light_verify_phase_seconds_sum{phase="trusting"}',
    'crypto_verify_phase_seconds_sum{phase="table_build",backend="arena"}',
    "ops_pubkey_tables_built_total",
    'ops_pubkey_lookup_lanes_total{result="uncached"}',
)


def test_cell_series_reach_the_registry(monkeypatch):
    """A rehearsed run whose commit checks go to the device verifier (the
    host cut pinned low, as on the chip) moves the five series the cell
    adds, and every series a metric file of the cell names is one the
    registry exports."""
    from cometbft_tpu.crypto import batch as cbatch
    from cometbft_tpu.libs import metrics as libmetrics

    monkeypatch.setattr(cbatch, "HOST_BATCH_THRESHOLD", 2)
    m = libmetrics.NodeMetrics()
    libmetrics.push_node_metrics(m)
    try:
        before = counters._prom(m.registry)
        res = run(2**31 + 909, seconds=1.0)
        after = counters._prom(m.registry)
    finally:
        libmetrics.pop_node_metrics(m)
    assert not bad_checks(res), res["checks"]
    moved = {k for k in after if after[k] != before.get(k, 0.0)}
    for series in SERIES:
        assert "prom.cometbft_tpu_" + series in after, series
    for series in SERIES[:-1]:  # nothing gives up at this size
        assert "prom.cometbft_tpu_" + series in moved, series
    cell = spec.load_cell(CELL, rehearsal=True)
    for metric in cell.per_layer:
        for key in metric.get("numerator", []) + metric.get(
                "denominator", []) + metric.get("lanes", []):
            if not key.startswith("prom."):
                continue
            if key.endswith("*"):
                assert any(k.startswith(key[:-1]) for k in after), key
            else:
                assert key in after, (metric["name"], key)
