"""End-to-end dry run of the bench capture path (bench.py).

bench.py measures the chip or does not run: without a TPU it exits
non-zero. The one CPU run it makes is the dry run of the capture path
itself — ``COMETBFT_BENCH_TINY=1`` with ``JAX_PLATFORMS=cpu`` — which
proves the 5-config table, the extras (device floor + kernel A/B, both
in the parent process) and the details file execute without error, and
whose provenance row says ``cpu``.
"""

import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_env(**extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO
    env.pop("COMETBFT_BENCH_TINY", None)
    env.update(extra)
    return env


def test_bench_without_device_exits_nonzero(tmp_path):
    """No chip and no dry-run switch: non-zero, no headline, no rows."""
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        cwd=tmp_path,
        env=_bench_env(),
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr
    assert not (tmp_path / "BENCH_DETAILS.json").exists()


def test_bench_starts_no_child_process():
    """One process per chip: the process that holds the device must not
    start a child that needs it (the Pallas A/B used to)."""
    src = open(os.path.join(_REPO, "bench.py")).read()
    assert "subprocess" not in src and "Popen" not in src


@pytest.mark.slow
def test_bench_capture_path_end_to_end(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        cwd=tmp_path,
        env=_bench_env(COMETBFT_BENCH_TINY="1"),
        capture_output=True,
        text=True,
        timeout=560,
    )
    assert r.returncode == 0, r.stderr[-2000:]

    headline = json.loads(r.stdout.strip().splitlines()[-1])
    assert headline["metric"] == "ed25519_batch_verify_throughput"
    # a CPU dry run can never pass for a chip row
    assert headline["provenance"]["backend"] == "cpu"

    details = json.loads((tmp_path / "BENCH_DETAILS.json").read_text())
    configs = {d.get("config") for d in details if "config" in d}
    for required in (
        "cpu_baseline",
        "1_batch64",
        "2_commit150_verify",
        "3_round1000_votes",
        "4_light10k_commit_verify",
        "5_mixed4096_ed_sr",
        "9_device_floor",
        "headline_flat4096",
    ):
        assert required in configs, (required, configs)

    # provenance stamping: the 0_provenance row and the headline both
    # carry jax/jaxlib/backend, and name the device the rows ran on
    assert "0_provenance" in configs
    prov = next(d for d in details if d.get("config") == "0_provenance")
    for key in ("jax", "jaxlib", "backend", "python", "device_count"):
        assert prov.get(key), (key, prov)
    assert prov["backend"] == "cpu"
    assert headline["provenance"].get("jax") == prov["jax"]

    # the 9_device_floor compile-attribution fix: one-time XLA compile
    # is its own column, and the utilization estimate declares its
    # execute-only basis
    floor = next(d for d in details if d.get("config") == "9_device_floor")
    for row in floor["rows"]:
        assert "compile_ms" in row and "compiles" in row, row
        assert "est_vpu_util_basis" in row, row

    # no benchmark artifact that could steer production is written
    assert not (tmp_path / "BENCH_CHIP_TABLE.json").exists()


# ----------------------------------------------- bench --compare units
#
# Direct unit coverage for the regression comparator (it shipped with
# only review-hardening coverage): direction heuristics, noise-floor
# gating, file-shape loading, and the CLI exit codes.


def _bench_mod():
    import importlib.util

    spec = importlib.util.find_spec("bench")
    if spec is None:
        import sys as _sys

        _sys.path.insert(0, _REPO)
    import bench

    return bench


class TestMetricDirection:
    def test_higher_is_better_fragments(self):
        bench = _bench_mod()
        for key in (
            "sigs_per_sec",
            "coalesced_vs_serial",
            "storm_vs_serial",
            "vs_batch_baseline",
            "cache_hit_rate",
            "budget_coverage",
            "est_vpu_util",
            "device_window_pct",  # resolves higher-better FIRST
            "lane_share",
        ):
            assert bench._metric_direction(key) == 1, key

    def test_lower_is_better_fragments(self):
        bench = _bench_mod()
        for key in (
            "latency_ms",
            "commit_ms_p50",
            "burst_s",
            "consensus_wait_p99_ms",
            "overhead_pct",
            "ab_noise_floor_pct",
            "compile_ms",
            "h2d_bytes",
            "delta_pct",
        ):
            assert bench._metric_direction(key) == -1, key

    def test_unknown_direction_flags_any_move(self):
        bench = _bench_mod()
        assert bench._metric_direction("mystery_quantity") == 0

    def test_lock_contention_fragments_are_lower_is_better(self):
        """The contention pre-list must win before the generic
        fragments: "lock_wait_share_pct" contains "share" (a
        higher-better fragment) yet more lock waiting is never an
        improvement — the pipelined-heights PR's compare baseline
        depends on these classifying as regressions when they rise."""
        bench = _bench_mod()
        for key in (
            "lock_wait_total_s",
            "lock_wait_share_pct",  # "share" must NOT flip it
            "contended_acquires",
            "commit_chain_occupancy_pct",
            "lockprof_overhead_pct",
        ):
            assert bench._metric_direction(key) == -1, key


def _write(path, obj):
    path.write_text(json.dumps(obj))
    return str(path)


class TestBenchCompare:
    def _rows(self, **overrides):
        base = {
            "config": "1_batch64",
            "sigs_per_sec": 1000.0,
            "latency_ms": 10.0,
            "mystery_quantity": 5.0,
        }
        base.update(overrides)
        return [base]

    def test_regression_in_lower_better_metric_flags(self, tmp_path):
        bench = _bench_mod()
        a = _write(tmp_path / "a.json", self._rows())
        b = _write(tmp_path / "b.json", self._rows(latency_ms=15.0))
        out = bench.bench_compare(a, b)
        regs = {r["metric"] for r in out["regressions"]}
        assert "latency_ms" in regs
        # default floor without a 13_health_overhead row: 10%
        assert out["noise_floor_pct"] == 10.0

    def test_improvement_is_not_a_regression(self, tmp_path):
        bench = _bench_mod()
        a = _write(tmp_path / "a.json", self._rows())
        b = _write(
            tmp_path / "b.json",
            self._rows(latency_ms=5.0, sigs_per_sec=2000.0),
        )
        out = bench.bench_compare(a, b)
        assert out["regressions"] == []

    def test_throughput_drop_flags(self, tmp_path):
        bench = _bench_mod()
        a = _write(tmp_path / "a.json", self._rows())
        b = _write(tmp_path / "b.json", self._rows(sigs_per_sec=500.0))
        out = bench.bench_compare(a, b)
        assert [r["metric"] for r in out["regressions"]] == [
            "sigs_per_sec"
        ]

    def test_sub_noise_moves_never_flag(self, tmp_path):
        bench = _bench_mod()
        a = _write(tmp_path / "a.json", self._rows())
        b = _write(
            tmp_path / "b.json",
            self._rows(latency_ms=10.9, sigs_per_sec=950.0),
        )
        out = bench.bench_compare(a, b)  # 9%/5% < the 10% default floor
        assert out["regressions"] == []

    def test_unknown_direction_flags_both_ways(self, tmp_path):
        bench = _bench_mod()
        a = _write(tmp_path / "a.json", self._rows())
        up = _write(
            tmp_path / "up.json", self._rows(mystery_quantity=10.0)
        )
        down = _write(
            tmp_path / "dn.json", self._rows(mystery_quantity=1.0)
        )
        assert any(
            r["metric"] == "mystery_quantity"
            for r in bench.bench_compare(a, up)["regressions"]
        )
        assert any(
            r["metric"] == "mystery_quantity"
            for r in bench.bench_compare(a, down)["regressions"]
        )

    def test_noise_floor_from_health_row_with_2pct_min(self, tmp_path):
        bench = _bench_mod()
        rows_a = self._rows() + [
            {"config": "13_health_overhead", "ab_noise_floor_pct": 25.0}
        ]
        a = _write(tmp_path / "a.json", rows_a)
        b = _write(tmp_path / "b.json", self._rows(latency_ms=12.0))
        out = bench.bench_compare(a, b)
        assert out["noise_floor_pct"] == 25.0
        assert out["regressions"] == []  # +20% < the measured floor
        # the 2% minimum: a near-zero measured floor must not page on
        # sub-noise jitter
        rows_a[1]["ab_noise_floor_pct"] = 0.1
        a2 = _write(tmp_path / "a2.json", rows_a)
        b2 = _write(tmp_path / "b2.json", self._rows(latency_ms=10.15))
        out2 = bench.bench_compare(a2, b2)
        assert out2["noise_floor_pct"] == 2.0
        assert out2["regressions"] == []  # +1.5% < the 2% min

    def test_capture_tail_and_headline_shapes_load(self, tmp_path):
        bench = _bench_mod()
        lines = "\n".join([
            json.dumps({"config": "1_batch64", "sigs_per_sec": 1000.0}),
            json.dumps({"metric": "x", "value": 1.0}),
        ])
        cap = _write(
            tmp_path / "cap.json", {"tail": lines, "rc": 0}
        )
        rows = bench._compare_load_rows(cap)
        assert set(rows) == {"1_batch64", "headline"}
        head = _write(
            tmp_path / "head.json", {"metric": "x", "value": 2.0}
        )
        rows2 = bench._compare_load_rows(head)
        assert set(rows2) == {"headline"}

    def test_zero_and_non_numeric_fields_skipped(self, tmp_path):
        bench = _bench_mod()
        a = _write(tmp_path / "a.json", self._rows(
            zeroed_ms=0.0, note="text", flag=True,
        ))
        b = _write(tmp_path / "b.json", self._rows(
            zeroed_ms=99.0, note="other", flag=False,
            latency_ms=10.0, sigs_per_sec=1000.0, mystery_quantity=5.0,
        ))
        out = bench.bench_compare(a, b)
        compared = {d["metric"] for d in out["deltas"]}
        assert "zeroed_ms" not in compared  # a==0: pct undefined
        assert "note" not in compared and "flag" not in compared

    def test_compare_main_exit_codes(self, tmp_path, capsys):
        bench = _bench_mod()
        a = _write(tmp_path / "a.json", self._rows())
        ok = _write(tmp_path / "ok.json", self._rows())
        bad = _write(tmp_path / "bad.json", self._rows(latency_ms=20.0))
        assert bench.compare_main([a, ok]) == 0
        capsys.readouterr()
        assert bench.compare_main([a, bad]) == 1
        err = capsys.readouterr().err
        assert "REGRESSION" in err and "latency_ms" in err
        assert bench.compare_main([a]) == 2
