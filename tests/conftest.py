"""Test configuration: force an 8-device virtual CPU mesh.

Real multi-chip hardware is not available in CI; all sharding tests run on a
virtual 8-device CPU platform (jax.sharding.Mesh over host devices). This
must run before jax is imported anywhere.
"""

import os

# Tests are CPU-only by design: they never open the chip (the chip
# proof is chip_smoke.py, run through the chip tool), and Pallas kernels
# run in interpret mode where a test asks for it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache for the WHOLE suite (the ops paths
# already opt in via ops/verify._enable_compilation_cache): kernel
# compiles are disk-cached across processes, so repeated tier runs and
# test-local jax.jit calls don't re-pay CPU XLA compile time. The
# directory is JAX_COMPILATION_CACHE_DIR when set, else <repo>/.jax_cache.
from cometbft_tpu.ops.verify import _enable_compilation_cache  # noqa: E402

_enable_compilation_cache()

import pytest  # noqa: E402

# The quick tier (`pytest -m quick`, < 60 s): suites with no JAX kernel
# compilation, no multi-node nets, no process spawning — the inner-loop
# answer to the full run's ~10 minutes. CI runs both tiers.
_QUICK_FILES = {
    "test_abci.py",
    "test_aead_armor.py",
    "test_cli_config.py",
    "test_cli_reindex_compact.py",
    "test_crypto_host.py",
    "test_db_native.py",
    "test_evidence.py",
    "test_host_batch.py",
    "test_indexer.py",
    "test_libs.py",
    "test_light.py",
    "test_observability.py",
    "test_p2p.py",
    "test_pex.py",
    "test_rpc.py",
    "test_sink.py",
    "test_state_exec.py",
    "test_types.py",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (
            item.fspath.basename in _QUICK_FILES
            and "slow" not in item.keywords
        ):
            item.add_marker(pytest.mark.quick)


@pytest.fixture(autouse=True)
def _thread_hygiene():
    """Every test must stop what it starts: a NON-daemon thread that
    outlives its test can wedge the whole pytest process at interpreter
    exit and silently serialize later tests behind its locks.  Engine
    routines are all daemon=True by design, so anything this catches is
    a missing Service.stop()/join in the test or a genuine engine leak.
    Named leakers, not just a count, so the culprit is greppable."""
    import helpers

    before = helpers.nondaemon_thread_snapshot()
    yield
    strays = helpers.stray_nondaemon_threads(before)
    assert not strays, (
        "test leaked non-daemon thread(s): "
        + ", ".join(sorted(t.name for t in strays))
    )
