"""Test configuration.

Mirrors the reference's test strategy (reference: python/ray/tests/conftest.py
ray_start_regular :602 / ray_start_cluster :647): fixtures that start/stop the
runtime around each test, plus a virtual 8-device CPU mesh so every sharding/
collective test exercises real multi-device SPMD without TPU hardware.
"""

import os

# Tests run on an 8-virtual-device CPU mesh, whatever the host has. Both
# variables must be set before jax initialises a backend; subprocesses the
# tests start inherit them.
os.environ["JAX_PLATFORMS"] = "cpu"
# The program keeps a persistent compilation cache (utils/compile_cache.py).
# Tests switch it off: what they compile must not depend on what an earlier
# run left on disk.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """``multidevice`` tests need the 8-virtual-device mesh this conftest
    forces; when the env overrides XLA_FLAGS (or jax was initialized before
    us) skip them instead of failing on mesh construction. Registered in
    pyproject so `-m multidevice` can select them in isolation too."""
    try:
        n = len(jax.devices("cpu"))
    except Exception:
        n = 0
    if n >= 8:
        return
    skip = pytest.mark.skip(reason=f"needs 8 virtual cpu devices, have {n}")
    for item in items:
        if "multidevice" in item.keywords:
            item.add_marker(skip)


def poll_until(predicate, timeout: float = 15.0, interval: float = 0.05,
               desc: str = "condition"):
    """Event-polling helper: spin on ``predicate`` with short sleeps until
    it returns something truthy (returned) or the deadline passes
    (AssertionError). Keeps observability tests deterministic without
    sleep(>0.1) calls — poll fast, bound long."""
    import time as _time

    deadline = _time.monotonic() + timeout
    while True:
        value = predicate()
        if value:
            return value
        if _time.monotonic() >= deadline:
            raise AssertionError(f"timed out after {timeout}s waiting for {desc}")
        _time.sleep(interval)


@pytest.fixture
def wait_for():
    """Fixture handle for poll_until (conftest isn't importable as a module
    from test files under rootdir-relative invocation)."""
    return poll_until


@pytest.fixture
def rt_start():
    """In-process runtime with 8 fake CPUs and a fake 4-chip TPU host."""
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, resources={"TPU": 4.0})
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected >=8 virtual cpu devices, got {len(devs)}"
    return devs
