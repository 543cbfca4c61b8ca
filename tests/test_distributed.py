"""Multi-host helpers, actor failure detection."""

import time

import jax
import numpy as np
import pytest

from distributed_ba3c_tpu.parallel.distributed import (
    initialize_from_flags,
    is_chief,
    local_batch_slice,
    make_global_mesh,
)


def test_initialize_single_host_noop():
    assert initialize_from_flags("", 0) is False
    assert initialize_from_flags("localhost:5000", 0) is False


def test_global_mesh_covers_all_devices():
    mesh = make_global_mesh()
    assert mesh.devices.size == len(jax.devices())
    assert mesh.axis_names == ("data", "model")


def test_chief_and_batch_slice_single_process():
    assert is_chief()
    assert local_batch_slice(64) == slice(0, 64)


def _prune_master(tmp_path):
    from distributed_ba3c_tpu.actors.master import BA3CSimulatorMaster

    class _P:
        def put_task(self, s, cb, **kw):
            pass

    m = BA3CSimulatorMaster(
        f"ipc://{tmp_path}/c2s",
        f"ipc://{tmp_path}/s2c",
        _P(),
    )
    m.actor_timeout = 0.1
    return m


def test_master_prunes_dead_actors(tmp_path):
    m = _prune_master(tmp_path)
    try:
        c = m.clients[b"sim-0"]
        c.last_seen = time.monotonic() - 10.0
        m._last_prune = 0.0
        m._prune_dead_actors()
        assert b"sim-0" not in m.clients
        # fresh client survives
        c2 = m.clients[b"sim-1"]
        c2.last_seen = time.monotonic()
        m._last_prune = 0.0
        m._prune_dead_actors()
        assert b"sim-1" in m.clients
    finally:
        m.close()


def test_prune_immune_to_wall_clock_jump(tmp_path, monkeypatch):
    """Regression for the ba3clint-A4 finding: heartbeat arithmetic used
    ``time.time()``, so an NTP step / suspend-resume would mass-expire every
    live actor at once. ``last_seen`` must be monotonic — a forward wall
    clock jump of a million seconds must not prune a fresh client."""
    m = _prune_master(tmp_path)
    try:
        m.clients[b"sim-0"]  # fresh heartbeat at creation
        m._last_prune = 0.0
        real_time = time.time
        monkeypatch.setattr(time, "time", lambda: real_time() + 1e6)
        m._prune_dead_actors()
        assert b"sim-0" in m.clients, (
            "wall-clock jump expired a live actor — heartbeats must use "
            "time.monotonic()"
        )
    finally:
        m.close()
