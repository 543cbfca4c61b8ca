"""The start-up record (``distributed_ba3c_tpu/utils/backend.py``) and the
count of ``fused.step`` calls are the process's, and a pytest worker's process
has run other files' steps. Each test here starts from an empty record and a
count of 0, as a benchmark run does, so that a reader handed a recording of
another run finds no set-up of this process to report."""

import time

import pytest

from distributed_ba3c_tpu.utils import backend, profiling


@pytest.fixture(autouse=True)
def _a_process_that_has_run_no_step(monkeypatch):
    if backend.startup_record() is not None:  # else: nothing to read anyway
        monkeypatch.setattr(
            backend, "_record", backend.StartupRecord(None, time.monotonic()))
    monkeypatch.setattr(profiling, "_step_calls", 0)
