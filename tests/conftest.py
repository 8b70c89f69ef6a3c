"""Test harness configuration.

Tests run JAX on a virtual 8-device CPU mesh (mirrors the reference's
InternalTestCluster strategy of booting multiple nodes in one JVM, ref:
test/framework/.../InternalTestCluster.java): sharding/collective code is
exercised without TPU hardware. The variables below must be set before the
first ``import jax``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--chaos-seed", action="store", default=None, type=int,
        help="override the fault-injection seed for @pytest.mark.chaos "
             "tests (replay a red chaos run from its logged seed)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "chaos(seed=N): seeded fault-injection test; the active seed is "
        "echoed on failure so any red run replays with --chaos-seed=N")


@pytest.fixture
def chaos_seed(request):
    """The fault-injection seed for this test: --chaos-seed wins,
    otherwise the @pytest.mark.chaos(seed=...) default. The chosen seed
    is stashed on the test item so a failure report echoes it."""
    override = request.config.getoption("--chaos-seed")
    marker = request.node.get_closest_marker("chaos")
    seed = override if override is not None else (
        marker.kwargs.get("seed", 0) if marker else 0)
    request.node._chaos_seed_used = seed
    return seed


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    outcome = yield
    rep = outcome.get_result()
    if rep.when == "call" and rep.failed and \
            item.get_closest_marker("chaos") is not None:
        seed = getattr(item, "_chaos_seed_used", "?")
        rep.sections.append(
            ("chaos fault injection",
             f"seeded chaos run failed; replay deterministically with: "
             f"pytest {item.nodeid} --chaos-seed={seed}"))
        if hasattr(rep.longrepr, "addsection"):
            rep.longrepr.addsection(
                "chaos seed", f"replay with --chaos-seed={seed}")


@pytest.fixture(autouse=True)
def _span_leak_guard():
    """Telemetry hygiene: fail any test that starts a trace span and
    never finishes it. Spans already open before the test (e.g. a
    background service of a long-lived node from another fixture) are
    excluded — only spans OPENED during this test count as leaks."""
    from elasticsearch_tpu.telemetry import tracing
    before = tracing.open_span_keys()
    yield
    leaked = tracing.open_span_keys() - before
    if leaked:
        # wall-clock transports may still be completing an RPC; give
        # in-flight handlers one beat before calling it a leak
        import time as _time
        _time.sleep(0.2)
        leaked = tracing.open_span_keys() - before
    assert not leaked, (
        "telemetry spans left open at teardown (started, never "
        f"finished): {sorted(k[3] for k in leaked)}")


@pytest.fixture(autouse=True)
def _task_leak_guard():
    """Task hygiene (mirror of the span-leak guard): fail any test that
    registers a task in a TaskManager and never unregisters it. Tasks
    already live before the test (e.g. a background service of a
    long-lived node from another fixture) are excluded — only tasks
    REGISTERED during this test count as leaks."""
    from elasticsearch_tpu.transport import tasks as _tasks
    before = _tasks.open_task_keys()
    yield
    leaked = _tasks.open_task_keys() - before
    if leaked:
        # wall-clock transports/threads may still be completing a
        # request; give in-flight handlers one beat before calling it
        import time as _time
        _time.sleep(0.2)
        leaked = _tasks.open_task_keys() - before
    assert not leaked, (
        "tasks left registered at teardown (registered, never "
        f"unregistered): {sorted((k[0], k[2]) for k in leaked)}")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    devices = jax.devices()
    assert devices[0].platform == "cpu" and len(devices) == 8, devices
