"""Bring-up invariants: what must hold for the program to run on a
directly attached chip and for a proof of that to mean something.

* the compile cache is placed from outside (``JAX_COMPILATION_CACHE_DIR``)
  or at a fixed path inside the checkout — never set in code when the
  environment names one;
* seeing several devices never reroutes a commit: sharding is opt-in;
* one process per chip: launchers pin every child but the named owner
  to the CPU;
* an operator who asked for the device gets an error, not a host-only
  node, when it cannot be opened;
* a cold kernel shape never sits inside a routed ticket wait.
"""

import os
import subprocess
import sys
import threading

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, **env_extra) -> str:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    r = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip()


_CACHE_PROBE = """
import jax
updates = []
_orig = jax.config.update
def _spy(name, value):
    updates.append(name)
    return _orig(name, value)
jax.config.update = _spy
from cometbft_tpu.ops import verify as ov
used = ov._enable_compilation_cache()
print(used)
print(jax.config.jax_compilation_cache_dir)
print("jax_compilation_cache_dir" in updates)
"""


class TestCompileCacheDirectory:
    def test_env_set_means_no_directory_set_in_code(self, tmp_path):
        want = str(tmp_path / "outside")
        used, effective, updated = _run_py(
            _CACHE_PROBE, JAX_COMPILATION_CACHE_DIR=want
        ).splitlines()
        assert used == want and effective == want
        assert updated == "False"

    def test_unset_means_fixed_path_inside_checkout(self):
        used, effective, updated = _run_py(_CACHE_PROBE).splitlines()
        assert used == os.path.join(_REPO, ".jax_cache") == effective
        assert updated == "True"

    def test_retired_knobs_are_gone(self):
        from cometbft_tpu import config

        for knob in (
            "COMETBFT_TPU_XLA_CACHE",
            "COMETBFT_TPU_SR_HOST",
            "COMETBFT_TPU_CHIP_TABLE",
        ):
            assert knob not in config.ENV_KNOBS


class TestShardingIsOptIn:
    def test_auto_never_shards(self, monkeypatch):
        """The suite runs on an 8-device virtual mesh: were sharding
        keyed on the device count, this would return them."""
        import jax

        from cometbft_tpu.ops import verify as ov

        assert len(jax.devices()) >= 2
        monkeypatch.delenv("COMETBFT_TPU_SHARD", raising=False)
        assert ov._shard_devices() is None
        monkeypatch.setenv("COMETBFT_TPU_SHARD", "auto")
        assert ov._shard_devices() is None
        # even on an accelerator backend, auto is single-device
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert ov._shard_devices() is None

    def test_opt_in_shards(self, monkeypatch):
        import jax

        from cometbft_tpu.ops import verify as ov

        monkeypatch.setenv("COMETBFT_TPU_SHARD", "1")
        assert ov._shard_devices() == jax.devices()


class TestOneProcessPerChip:
    def test_children_are_cpu_pinned_except_the_owner(
        self, tmp_path, monkeypatch
    ):
        from cometbft_tpu.e2e.runner import Testnet, child_env

        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        net = Testnet(str(tmp_path), 4, 31000)
        assert [n.env.get("JAX_PLATFORMS") for n in net.nodes] == ["cpu"] * 4
        net = Testnet(str(tmp_path), 4, 31000, chip_owner=2)
        pins = [n.env.get("JAX_PLATFORMS") for n in net.nodes]
        assert pins == ["cpu", "cpu", None, "cpu"]
        # the owner keeps the launcher's own setting
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        assert child_env(chip_owner=True)["JAX_PLATFORMS"] == "tpu"
        assert child_env()["JAX_PLATFORMS"] == "cpu"

    def test_no_jax_importing_child_started_outside_the_runner(self):
        """The only child processes the package starts are the g++
        build (no jax) and the runner's CPU-pinned nodes."""
        hits = []
        for root, _dirs, files in os.walk(
            os.path.join(_REPO, "cometbft_tpu")
        ):
            if os.sep + "devtools" in root:
                continue
            for f in files:
                if not f.endswith(".py"):
                    continue
                src = open(os.path.join(root, f)).read()
                if "subprocess.Popen(" in src or "subprocess.run(" in src:
                    hits.append(os.path.relpath(os.path.join(root, f), _REPO))
        assert sorted(hits) == [
            "cometbft_tpu/e2e/runner.py",
            "cometbft_tpu/libs/native_build.py",
        ]


class TestAcceleratorProbe:
    def _fresh(self, monkeypatch):
        from cometbft_tpu.libs import accel

        monkeypatch.setattr(accel, "_probe", None)
        return accel

    def test_host_pin_answers_without_probing(self, monkeypatch):
        accel = self._fresh(monkeypatch)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert accel.accelerator_backend(required=True) is False
        assert accel.accelerator_backend_live() is False
        assert accel.ACCELERATOR_BACKENDS == ("tpu",)

    def test_requested_device_that_fails_to_open_raises(self, monkeypatch):
        import jax

        accel = self._fresh(monkeypatch)

        def boom():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "default_backend", boom)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        with pytest.raises(RuntimeError):
            accel.accelerator_backend()
        # nothing named, plane forced on: still an error
        monkeypatch.delenv("JAX_PLATFORMS")
        monkeypatch.setattr(accel, "_probe", None)
        with pytest.raises(RuntimeError):
            accel.accelerator_backend(required=True)
        # nothing named, nothing forced: logged host-only answer
        monkeypatch.setattr(accel, "_probe", None)
        assert accel.accelerator_backend() is False


class TestWarmSet:
    def test_cold_key_answers_false_then_warms_in_background(self):
        from cometbft_tpu.ops.warm import WarmSet

        gate = threading.Event()
        compiled = []

        def compile_fn(key):
            gate.wait(10)
            compiled.append(key)

        ws = WarmSet("t", compile_fn)
        assert ws.ready("a") is False  # never blocks on the compile
        assert ws.ready("a") is False  # queued once, not twice
        gate.set()
        assert ws.wait_idle(10)
        assert ws.ready("a") is True
        assert compiled == ["a"]
        assert ws.cold == 2

    def test_failed_compile_stays_cold_and_is_counted(self):
        from cometbft_tpu.ops.warm import WarmSet

        calls = []

        def compile_fn(key):
            calls.append(key)
            raise ValueError("mosaic balked")

        ws = WarmSet("t", compile_fn)
        assert ws.ready("k") is False
        assert ws.wait_idle(10)
        assert ws.ready("k") is False
        assert ws.wait_idle(10)
        assert calls == ["k"]  # not retried in a loop
        assert "k" in ws.failed and "'k'" in ws.snapshot()["failed"]

    def test_auto_coalescer_keeps_cold_window_on_host(self, monkeypatch):
        """A routed (auto) coalescer never launches a shape that has no
        executable yet: the window's verdicts come from the host while
        the shape compiles, then the next window takes the device."""
        from cometbft_tpu.crypto import coalesce, fast25519
        from cometbft_tpu.ops import verify as ov

        launched = []
        monkeypatch.setattr(
            ov, "_warm_shape", lambda key: launched.append(key)
        )
        ws = ov.libwarm.WarmSet("t", lambda key: ov._warm_shape(key))
        monkeypatch.setattr(ov, "WARM", ws)
        seeds = [bytes([i + 1]) * 32 for i in range(4)]
        pks = [fast25519.pubkey_from_seed(s) for s in seeds]
        msgs = [b"m%d" % i for i in range(4)]
        sigs = [fast25519.sign_one(s, m) for s, m in zip(seeds, msgs)]
        sigs[2] = bytes(64)

        co = coalesce.VerifyCoalescer(
            window_us=1_000, max_lanes=8, min_device_lanes=1
        )
        monkeypatch.setattr(co, "_device_ok", lambda: True)
        co.start()
        try:
            bits = co.submit(pks, msgs, sigs).result(timeout=30)
            assert bits == [True, True, False, True]
            assert co.cold_windows == 1 and co.device_windows == 0
            assert ws.wait_idle(30)
            # window bucket and the builder for 4 unseen keys
            assert ("window", 8) in launched
        finally:
            co.stop()


class TestChipSmoke:
    """chip_smoke.py is the proof that the system starts on the chip;
    off the chip it must fail, and its CPU dry run must never be able
    to pass for it."""

    _SMOKE = os.path.join(_REPO, "chip_smoke.py")

    def test_without_a_chip_exits_nonzero_and_runs_no_leg(self, tmp_path):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, self._SMOKE, "--out", str(tmp_path)],
            env=env,
            capture_output=True,
            text=True,
            timeout=120,
        )
        assert r.returncode != 0
        assert r.stdout.strip() == ""  # no result, no leg output
        assert "no TPU" in r.stderr
        assert not os.listdir(tmp_path)

    @pytest.mark.slow  # leg A compiles interpret-mode Pallas (~1.5 min cold)
    def test_cpu_dry_run_passes_its_legs_but_never_the_chip_line(
        self, tmp_path
    ):
        import json

        env = dict(os.environ)
        env.pop("JAX_PLATFORMS", None)  # the flag pins the CPU itself
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [
                sys.executable, self._SMOKE, "--cpu-dry-run",
                "--out", str(tmp_path),
            ],
            env=env,
            capture_output=True,
            text=True,
            timeout=1500,
        )
        assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-2000:]
        assert "platform: cpu" in r.stdout
        last = json.loads(r.stdout.strip().splitlines()[-1])
        assert "ok" not in last  # the chip's pass line needs "ok": true
        assert last["dry_run"] is True and last["legs_ok"] is True
        assert last["device"]["platform"] == "cpu"
        assert last["legs"] == {"A": True, "B": True, "C": True}
        assert list(last)[-1] == "claim" and last["claim"] is None
        summary = json.loads((tmp_path / "summary.json").read_text())
        assert summary["ok"] is False  # a dry run is never a pass
        assert list(summary)[-1] == "claim" and summary["claim"] is None