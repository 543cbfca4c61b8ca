"""TPU-claim mutex contract (utils/devicelock.py) — jax-free.

A chip belongs to one process; libtpu's own lockfile refuses a second
claimant without naming the first and cannot queue (OPERATIONS.md).
Contract: exclusion across processes, fail mode reports the holder, wait
mode queues, a SIGKILLed holder releases the lock via the kernel (no
stale-lock protocol to get wrong), and the claim is per process.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from distributed_ba3c_tpu.utils import devicelock
from distributed_ba3c_tpu.utils.devicelock import (
    TpuLock,
    TpuLockHeld,
    guard_tpu,
)

_HOLDER = r"""
import sys, time
from distributed_ba3c_tpu.utils.devicelock import TpuLock
lock = TpuLock("holder-run", path=sys.argv[1])
lock.acquire(mode="fail")
print("HELD", flush=True)
time.sleep(120)
"""


def _spawn_holder(path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    p = subprocess.Popen(
        [sys.executable, "-c", _HOLDER, str(path)],
        stdout=subprocess.PIPE, env=env, text=True,
    )
    assert p.stdout.readline().strip() == "HELD"
    return p


@pytest.fixture
def lock_file(tmp_path, monkeypatch):
    monkeypatch.setenv("BA3C_TPU_LOCK", str(tmp_path / "tpu.lock"))
    monkeypatch.setattr(devicelock, "_held", None)
    yield tmp_path / "tpu.lock"
    if devicelock._held is not None:
        devicelock._held.release()


def test_guard_skips_cpu_platform(monkeypatch, lock_file):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert guard_tpu("x") is None
    assert not lock_file.exists()


@pytest.mark.parametrize("platforms", ["tpu,cpu", "tpu", None])
def test_guard_locks_when_a_chip_may_be_claimed(
    monkeypatch, lock_file, platforms
):
    # "tpu,cpu" is what the sealed chip machine exports; unset lets jax
    # pick the best back-end present, which is the chip
    if platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", platforms)
    lock = guard_tpu("x", mode="fail")
    assert lock is not None and lock.held


def test_guard_is_idempotent_per_process(monkeypatch, lock_file):
    """Two entry-point calls in ONE process (chip_smoke's fused phase runs
    cli.main, then cli.main --load) share the claim; flock is per open file
    description, so a second acquire would queue behind the first forever."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    first = guard_tpu("run-a", mode="fail")
    assert guard_tpu("run-b", mode="fail") is first


def test_fail_mode_reports_holder(tmp_path):
    path = tmp_path / "tpu.lock"
    holder = _spawn_holder(path)
    try:
        with pytest.raises(TpuLockHeld) as exc:
            TpuLock("second", path=str(path)).acquire(mode="fail")
        msg = str(exc.value)
        assert str(holder.pid) in msg
        assert "holder-run" in msg
    finally:
        holder.kill()
        holder.wait()


def test_wait_mode_queues_until_release(tmp_path):
    path = tmp_path / "tpu.lock"
    first = TpuLock("first", path=str(path)).acquire(mode="fail")
    threading.Timer(0.5, first.release).start()
    t0 = time.monotonic()
    second = TpuLock("second", path=str(path)).acquire(
        mode="wait", poll_s=0.05, log=lambda _m: None
    )
    assert second.held
    assert time.monotonic() - t0 >= 0.4
    second.release()


def test_wait_mode_timeout(tmp_path):
    path = tmp_path / "tpu.lock"
    with TpuLock("first", path=str(path)).acquire(mode="fail"):
        with pytest.raises(TpuLockHeld, match="gave up"):
            TpuLock("second", path=str(path)).acquire(
                mode="wait", poll_s=0.05, timeout_s=0.3, log=lambda _m: None
            )


def test_sigkilled_holder_releases(tmp_path):
    """The whole point of flock over a pidfile: ANY death path frees the
    chip claim — no stale lock after a SIGKILLed training run."""
    path = tmp_path / "tpu.lock"
    holder = _spawn_holder(path)
    os.kill(holder.pid, signal.SIGKILL)
    holder.wait()
    lock = TpuLock("after", path=str(path)).acquire(
        mode="wait", poll_s=0.05, timeout_s=5.0, log=lambda _m: None
    )
    assert lock.held
    lock.release()


def test_holder_info_written_and_cleared(tmp_path):
    path = tmp_path / "tpu.lock"
    lock = TpuLock("myrun", path=str(path)).acquire(mode="fail")
    info = json.load(open(path))
    assert info["pid"] == os.getpid()
    assert info["run"] == "myrun"
    lock.release()
    assert open(path).read() == ""


_CHURN_WORKER = r"""
import os, sys, time
from distributed_ba3c_tpu.utils.devicelock import TpuLock
path, log_path, iters = sys.argv[1], sys.argv[2], int(sys.argv[3])
pid = os.getpid()
for seq in range(iters):
    lock = TpuLock(f"churn-{pid}", path=path).acquire(
        mode="wait", poll_s=0.01, log=lambda _m: None
    )
    with open(log_path, "a") as f:         # O_APPEND: atomic small writes
        f.write(f"S {pid} {seq}\n"); f.flush()
    time.sleep(0.05)
    with open(log_path, "a") as f:
        f.write(f"E {pid} {seq}\n"); f.flush()
    lock.release()
print("DONE", flush=True)
"""


def test_churn_many_claimants_one_holder(tmp_path):
    """6 processes fight over the lock; 2 get SIGKILLed mid-run. Invariants:
    the hold log shows NO overlapping holds (every S is closed by its E
    before the next S, except a killed holder's final S), and the lock is
    immediately acquirable after the dust settles."""
    path = str(tmp_path / "tpu.lock")
    log_path = str(tmp_path / "holds.log")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(__file__))
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHURN_WORKER, path, log_path, "5"],
            stdout=subprocess.PIPE, env=env, text=True,
        )
        for _ in range(6)
    ]
    time.sleep(0.4)
    os.kill(procs[0].pid, signal.SIGKILL)
    os.kill(procs[1].pid, signal.SIGKILL)
    for p in procs:
        p.wait(timeout=60)
    # a "killed" target may already have finished its 5 holds before the
    # 0.4s mark on a fast machine (the SIGKILL then hits a zombie and its
    # rc stays 0) — so derive the actually-killed set from the outcomes
    # rather than asserting an exact survivor count
    killed = {p.pid for p in procs if p.returncode != 0}
    assert len(killed) <= 2
    assert sum(p.returncode == 0 for p in procs) >= 4
    lines = [l.split() for l in open(log_path).read().splitlines()]
    open_holder = None
    for kind, pid_s, _seq in lines:
        pid = int(pid_s)
        if kind == "S":
            # a prior unclosed hold is legal ONLY if that holder was killed
            # mid-hold (the kernel released its flock with no E line)
            assert open_holder is None or open_holder in killed, lines
            open_holder = pid
        else:
            assert open_holder == pid, lines
            open_holder = None
    # and the lock is free now
    final = TpuLock("after-churn", path=path).acquire(
        mode="wait", poll_s=0.05, timeout_s=5.0, log=lambda _m: None
    )
    assert final.held
    final.release()


def test_off_mode_never_locks(tmp_path):
    path = tmp_path / "tpu.lock"
    with TpuLock("a", path=str(path)).acquire(mode="fail"):
        # off mode must not block even while another process holds it
        assert not TpuLock("b", path=str(path)).acquire(mode="off").held
