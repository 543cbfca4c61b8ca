"""Test harness: fake 8-device CPU mesh.

The reference could only validate distributed behavior on a live cluster
(SURVEY.md §4). We do better: XLA's host-platform device-count flag gives an
8-device CPU mesh, so every psum/sharding code path is unit-testable with zero
TPU hardware. Must run before jax is first imported.
"""

import os

# The suite is a CPU suite wherever it runs: ASSIGN the platform (the sealed
# chip machine exports JAX_PLATFORMS=tpu,cpu, and a suite that claimed the
# chip would fail every test that spawns a jax child). The env var reaches
# spawned children; the config update below covers a jax that some plugin
# imported before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import signal
import threading

import numpy as np
import pytest

# Per-test watchdog (round-1 CI hung forever on a wedged jit dispatch; a
# hang must become a failing test, not an eternal run).
_DEFAULT_TIMEOUT = 300
_SLOW_TIMEOUT = 900


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running end-to-end test")
    config.addinivalue_line(
        "markers", "timeout(seconds): override the per-test SIGALRM watchdog"
    )


@pytest.fixture(autouse=True)
def _watchdog(request):
    marker = request.node.get_closest_marker("timeout")
    if marker:
        seconds = int(marker.args[0])
    elif request.node.get_closest_marker("slow"):
        seconds = _SLOW_TIMEOUT
    else:
        seconds = _DEFAULT_TIMEOUT
    if (
        not hasattr(signal, "SIGALRM")
        or threading.current_thread() is not threading.main_thread()
    ):
        yield
        return

    def _alarm(signum, frame):
        raise TimeoutError(
            f"{request.node.nodeid} exceeded the {seconds}s watchdog"
        )

    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
