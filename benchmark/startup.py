"""The run's set-up, read from inside through the program's own record.

``distributed_ba3c_tpu/utils/backend.py`` keeps every trace, lowering and
compile-or-cache-read of the process as an interval on ``time.monotonic()``,
under its function's name and beside the count of fused ``step()`` calls at
that moment, and the step's first four calls as ``fused.step#<k>`` events on
the same clock. ``summary(ctx)`` is ``backend.startup_summary`` cut at the
step's FINAL call count: at read time the window is over, so an interval
recorded below that count came before the window's last dispatch, and a sound
window compiles nothing (``failed`` counts it); what ``memory_peak_bytes()``
lowers and what the reference compiles afterwards carry the final count and
fall out. Set-up precedes the capture, so nothing here needs the trace.

It yields ``None``, and every metric that reads through it leaves itself out
of the line, on a program without the record (this PR's parent) and where the
record heard no ``fused.step`` call (``BA3C_TELEMETRY=0``; a process that ran
no step). The record is the process's, so a test that hands a reader a
recording of another run starts from an empty one
(``tests/benchmark/conftest.py``).
"""

from __future__ import annotations

from typing import Optional

_KEY = "_startup_summary"


def _read() -> Optional[dict]:
    try:
        from distributed_ba3c_tpu.utils import backend, profiling
        summarise, calls = backend.startup_summary, profiling.step_calls
    except (ImportError, AttributeError):
        return None  # a program from before the record
    found = summarise(before_step_calls=calls())
    if found is None or not found["step_calls"]:
        return None
    return found


def summary(ctx) -> Optional[dict]:
    """The program's summary of this run's set-up (read once a run; kept in
    ``ctx``), or None."""
    if _KEY not in ctx:
        ctx[_KEY] = _read()
    return ctx[_KEY]


def costliest(found: dict, *stages: str) -> str:
    """The costliest functions' seconds in ``stages``, for a printed line."""
    from distributed_ba3c_tpu.utils.backend import costliest_line

    return costliest_line(found, stages)


def bounds(found: dict) -> str:
    """How many intervals the unions are over, and whether they are whole."""
    dropped = found["dropped"]
    return (f"{found['intervals']} intervals kept"
            + (f", {dropped} DROPPED: the unions are lower bounds"
               if dropped else ", none dropped"))


def call_line(call: dict) -> str:
    """``fused.step#2 1.190 s (hyper 0.001, enqueue 1.189): trace multi_step
    0.912, ...; 0.278 in none`` of one recorded call: with no interval inside
    it, the time is the jit's call cache and the dispatch itself."""
    parts = ", ".join(f"{k[:-2]} {v:.4f}" for k, v in call["parts"].items())
    inside = ", ".join(
        f"{stage} {fun_name} {s:.3f}" + (f" (cache {cache})" if cache != "none" else "")
        for stage, fun_name, s, cache in call["inside"])
    return (f"{call['name']} {call['step_s']:.4f} s ({parts}): "
            f"{inside or 'no trace, lowering, compile or cache read inside'}; "
            f"{call['step_s'] - call['inside_s']:.4f} in none")
