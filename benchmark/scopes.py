"""The run's capture, read through the program's own reader.

The per-layer metrics that sort device time by *what an op is for* (the
scopes and host spans of ``distributed_ba3c_tpu/utils/profiling.py``) need
the capture itself: ``benchmark/trace.py`` keeps an instruction's name and
kind and none of its metadata. ``capture(ctx)`` finds the traced run's
``.xplane.pb`` under ``.bench_trace/<cell>/`` and reads it with
``profiling.op_time_by_scope`` and ``profiling.host_spans``.

It yields ``None``, and every metric that reads through it leaves itself
out of the line, unless the capture's device events are the very events
``ctx["trace"]`` holds (the same number on each chip's ``XLA Ops`` line and
the same first start on the first chip): so a capture left by another run,
or a trace loaded from JSON, reads as nothing. A program without the reader
or without scopes (this PR's parent) reads as nothing too.
"""

from __future__ import annotations

import os
from typing import Optional

from benchmark import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_KEY = "_scopes_capture"


def _read(ctx) -> Optional[dict]:
    try:
        from distributed_ba3c_tpu.utils import profiling
        reader, spans = profiling.op_time_by_scope, profiling.host_spans
    except (ImportError, AttributeError):
        return None  # a program from before the scopes
    tr = ctx["trace"]
    try:
        path = trace_mod.find_xplane(
            os.path.join(ROOT, ".bench_trace", ctx["cell"]["name"])
        )
    except FileNotFoundError:
        return None
    by_scope = reader(path)
    if by_scope is None or not tr.ops:
        return None
    first = tr.chips[0]
    if sorted(by_scope["events"]) != tr.chips:
        return None
    for chip, (count, _start) in by_scope["events"].items():
        if count != len(tr.ops[chip]):
            return None
    if by_scope["events"][first][1] != min(r[1] for r in tr.ops[first]):
        return None
    return dict(by_scope, host_spans=spans(path), profiling=profiling)


def capture(ctx) -> Optional[dict]:
    """What the program's reader finds in this run's capture (read once a
    run; kept in ``ctx``), or None."""
    if _KEY not in ctx:
        ctx[_KEY] = _read(ctx)
    return ctx[_KEY]


def share(ctx, scope_attr: str) -> Optional[float]:
    """Percent of the device's op time under the scope that
    ``profiling.<scope_attr>`` names (by attribute, since a program from
    before the scopes has no such names to import)."""
    cap = capture(ctx)
    if cap is None:
        return None
    scope = getattr(cap["profiling"], scope_attr)
    return 100.0 * cap["seconds"][scope] / cap["total_s"]


def shares_line(ctx, *scope_attrs: str) -> str:
    """``scope 1.234 %`` of each named scope, for a metric's printed line."""
    prof = capture(ctx)["profiling"]
    return ", ".join(
        f"{getattr(prof, a)} {share(ctx, a):.3f} %" for a in scope_attrs
    )
