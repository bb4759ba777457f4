"""Test harness config: force CPU jax with 8 virtual devices so sharding /
collective tests run without TPU hardware (SURVEY.md §4 TPU note — the
reference fakes clusters with subprocesses+ports; we fake a pod with
xla_force_host_platform_device_count, which is simpler and faster)."""
import os

# must happen before jax backends initialize
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# the environment variable covers processes that import jax later; the
# config update covers a jax that was imported before this file
jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long soaks kept out of the tier-1 run")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection soaks (tools/chaos_check.py runs the "
        "full matrix); long ones are also marked slow")


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    np.random.seed(0)
    paddle.seed(0)
    yield


@pytest.fixture(autouse=True)
def _disarm_faults():
    """Fault injections must never leak across tests."""
    yield
    from paddle_tpu.testing import faults

    faults.reset()


@pytest.fixture(autouse=True)
def _disarm_tracing():
    """Tracer sessions / retrace sentinels / cost-accounting sessions
    must never leak across tests (a test may arm a standing sentinel
    without a with-block)."""
    yield
    from paddle_tpu.profiler import costs, trace

    costs.reset()
    trace.reset()
