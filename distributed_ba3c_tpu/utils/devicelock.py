"""Local TPU-claim mutex: queue for the chip instead of failing on it.

A TPU chip belongs to ONE process. libtpu enforces that itself with
``/tmp/libtpu_lockfile``: measured on the sealed v5e machine (PR 21,
libtpu 0.0.34), a second process that initialises the TPU back-end while
the chip is held fails within ~3 s with ``ABORTED: Internal error when
accessing libtpu multi-process lockfile`` — prompt and safe, but it does
not say who holds the chip and it cannot wait. This guard sits in front of
that for entry points a person or a launcher may start while another run
holds the chip: it can QUEUE behind the holder (a bench behind a finishing
training run) and its refusal names the holder's pid and run. Whether that
is worth a module is ROADMAP D10.

Mechanics: ``flock(2)`` on a well-known path. The kernel releases the lock
when the holder dies — any exit path, including SIGKILL — so there is no
stale-lock protocol; the holder JSON written into the file (pid / run name /
since) is advisory context for log messages only, never trusted for
liveness. A process whose ``JAX_PLATFORMS`` names only ``cpu`` never claims
a chip and skips the lock, so CPU test suites and tooling coexist with a
live TPU run. The claim is per PROCESS: a second ``guard_tpu`` in a process
that already holds it gets the same lock back.

Modes (CLI ``--tpu_lock``, default ``wait``):
  - ``wait``: block until the chip frees, logging the holder once a minute.
  - ``fail``: exit immediately with the holder's pid/run in the message —
    for interactive use where queueing would surprise.
  - ``off``: no guard; libtpu's own lockfile is then the only arbiter.
"""

from __future__ import annotations

import errno
import fcntl
import json
import os
import sys
import time
from typing import Callable, Optional

from distributed_ba3c_tpu.utils.backend import cpu_only

LOCK_PATH_ENV = "BA3C_TPU_LOCK"
DEFAULT_LOCK_PATH = "/tmp/ba3c_tpu.lock"
MODES = ("wait", "fail", "off")

# diagnostics go to STDERR: the bench and eval scripts print exactly one
# JSON line on stdout for machine consumption — a "[tpu-lock] waiting" line
# there would corrupt the contract. sys.stderr is resolved at CALL time —
# a functools.partial bound the import-time stream and silently wrote to a
# stale object under any later redirection (pytest capture, daemonization).
# Public: the bench scripts share the stdout-JSON contract and import this
# (ba3clint A5 forbids cross-module imports of underscore names).
def stderr_print(*args, **kwargs) -> None:
    print(*args, file=sys.stderr, flush=True, **kwargs)


_stderr_print = stderr_print  # private alias kept for in-module history


def lock_path() -> str:
    return os.environ.get(LOCK_PATH_ENV) or DEFAULT_LOCK_PATH


#: the claim this process holds (guard_tpu is idempotent per process)
_held: Optional["TpuLock"] = None


class TpuLockHeld(SystemExit):
    """Raised in ``fail`` mode; SystemExit so entry points exit non-zero
    with the message and no traceback."""


class TpuLock:
    """Holds the host-local TPU claim for this process's lifetime.

    The fd stays open until ``release()`` or process death; flock identity
    is the open file description, so children sharing the fd after fork
    would also share the lock — entry points acquire before spawning
    workers, which is the intended containment.
    """

    def __init__(self, run_name: str, path: Optional[str] = None):
        self.run_name = run_name
        self.path = path or lock_path()
        self._fd: Optional[int] = None

    @property
    def held(self) -> bool:
        return self._fd is not None

    def _read_holder(self) -> str:
        try:
            with open(self.path, "r") as f:
                info = json.load(f)
            return "pid %s (run %r, since %s)" % (
                info.get("pid"), info.get("run"), info.get("since"),
            )
        except Exception:
            return "unknown holder"

    def _try_once(self) -> bool:
        fd = os.open(self.path, os.O_RDWR | os.O_CREAT, 0o666)
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError as e:
            os.close(fd)
            if e.errno in (errno.EAGAIN, errno.EACCES):
                return False
            raise
        # Holder info is advisory (for the *other* process's log message);
        # liveness is the flock itself.
        os.ftruncate(fd, 0)
        os.write(fd, json.dumps({
            "pid": os.getpid(),
            "run": self.run_name,
            "since": time.strftime("%Y-%m-%d %H:%M:%S"),
        }).encode())
        os.fsync(fd)
        self._fd = fd
        return True

    def acquire(
        self,
        mode: str = "wait",
        poll_s: float = 5.0,
        timeout_s: Optional[float] = None,
        log: Callable[[str], None] = stderr_print,
    ) -> "TpuLock":
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        if mode == "off" or self._try_once():
            return self
        holder = self._read_holder()
        if mode == "fail":
            raise TpuLockHeld(
                f"[tpu-lock] the TPU is held by {holder} ({self.path}). "
                "A chip belongs to one process (OPERATIONS.md); rerun "
                "with --tpu_lock wait to queue, or stop the holder."
            )
        t0 = time.monotonic()
        last_log = 0.0
        log(f"[tpu-lock] waiting for TPU held by {holder} ({self.path})")
        while not self._try_once():
            waited = time.monotonic() - t0
            if timeout_s is not None and waited >= timeout_s:
                raise TpuLockHeld(
                    f"[tpu-lock] gave up after {waited:.0f}s; TPU still "
                    f"held by {self._read_holder()} ({self.path})"
                )
            if waited - last_log >= 60.0:
                last_log = waited
                log(
                    f"[tpu-lock] still waiting ({waited:.0f}s) — holder: "
                    f"{self._read_holder()}"
                )
            time.sleep(poll_s)
        log(f"[tpu-lock] acquired after {time.monotonic() - t0:.0f}s")
        return self

    def release(self) -> None:
        if self._fd is not None:
            try:
                # Clear advisory holder info so a later reader doesn't see
                # our stale pid next to an unlocked file.
                os.ftruncate(self._fd, 0)
            except OSError:
                pass
            fcntl.flock(self._fd, fcntl.LOCK_UN)
            os.close(self._fd)
            self._fd = None

    def __enter__(self) -> "TpuLock":
        return self

    def __exit__(self, *exc) -> None:
        self.release()


def guard_tpu(
    run_name: str,
    mode: str = "wait",
    poll_s: float = 5.0,
    timeout_s: Optional[float] = None,
    log: Callable[[str], None] = stderr_print,
) -> Optional[TpuLock]:
    """Entry-point helper: acquire the host-local TPU claim unless this
    process is on the CPU platform (or mode='off'). Call BEFORE the first
    jax backend touch; held for process lifetime (the kernel releases on
    death). Returns the held lock — the SAME one on a repeated call, so
    two ``cli.main`` runs in one process do not queue behind themselves —
    or None when no lock is needed."""
    global _held
    if mode == "off" or cpu_only():
        return None
    if _held is None or not _held.held:
        _held = TpuLock(run_name).acquire(
            mode=mode, poll_s=poll_s, timeout_s=timeout_s, log=log
        )
    return _held
