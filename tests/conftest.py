"""Test configuration.

JAX-based tests run against a virtual 8-device CPU mesh (the sandbox has
no accelerator; chip runs go through ``chip_smoke.py``).  The platform is
pinned both in the environment (for the subprocesses tests spawn) and
through jax.config (for this process) before any backend initialization.
"""

import os
import time

_SESSION_START = time.monotonic()

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture
def shutdown_only():
    """Ensure the runtime is torn down after a test that calls init()."""
    yield None
    import ray_tpu

    ray_tpu.shutdown()


def _start_shared_cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)


@pytest.fixture(scope="module")
def ray_start_regular():
    """Single-node cluster shared by a test module (parity: reference
    conftest.py:266 ``ray_start_regular_shared``)."""
    import ray_tpu

    _start_shared_cluster()
    yield None
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _revive_shared_cluster(request):
    """A shared cluster that died (its head lost under load) takes ONE
    test with it, not the rest of the file: before each test of a module
    that shares one, a cluster with no live node is started again."""
    if "ray_start_regular" in request.fixturenames:
        import ray_tpu

        try:
            alive = ray_tpu.is_initialized() and any(
                n["alive"] for n in ray_tpu.nodes())
        except Exception:  # noqa: BLE001 — an unreachable GCS is a dead one
            alive = False
        if not alive:
            ray_tpu.shutdown()
            _start_shared_cluster()
    yield


@pytest.fixture
def chaos_cluster():
    """4 real raylets on this machine for kill-injection suites
    (parity: reference ``ray_start_cluster`` + NodeKillerActor)."""
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    for _ in range(3):
        c.add_node(num_cpus=2)
    c.connect()
    c.wait_for_nodes()
    yield c
    c.shutdown()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long regression runs (deselect with -m 'not slow')")
    config.addinivalue_line(
        "markers", "failpoints: deterministic fault-injection suite "
        "(run via `make chaos`)")


def pytest_collection_modifyitems(config, items):
    if config.getoption("-m"):
        return
    import pytest as _pytest

    skip_slow = _pytest.mark.skip(reason="slow regression; run -m slow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


def pytest_sessionfinish(session, exitstatus):
    """Tier-1 wall-clock budget gate: ``make test`` exports
    RTPU_TIER1_BUDGET_S (870), and a green run that still blew the
    budget fails here — time regressions surface as a red CI run with
    an actionable message instead of an eventual rc=124 timeout."""
    budget = os.environ.get("RTPU_TIER1_BUDGET_S")
    if not budget:
        return
    elapsed = time.monotonic() - _SESSION_START
    if elapsed > float(budget) and session.exitstatus == 0:
        tr = session.config.pluginmanager.get_plugin("terminalreporter")
        if tr is not None:
            tr.write_line(
                f"ERROR: tier-1 suite took {elapsed:.1f}s, over the "
                f"{budget}s budget — audit with --durations=25 and "
                f"slow-mark the offenders", red=True)
        session.exitstatus = 1
