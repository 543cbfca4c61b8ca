"""From the profiler's trace to numbers: the one reduction every PR shares.

``load`` reads an ``.xplane.pb`` with nothing but JAX into a small plain
structure (``Trace``), and every per-layer metric is read from that. A trace
saved with ``Trace.to_json`` loads back with ``Trace.from_json``; the test
suite checks the reduction on such a recorded trace.

What the v5e's trace holds (looked at by hand, PR 23): one plane a chip,
``/device:TPU:<n>``. Its line ``XLA Ops`` has one event for each executed HLO
instruction, named by the instruction's whole text (``%fusion.240 = bf16[..]
fusion(..), kind=kOutput, calls=..``) and with no category or source stat;
a ``while`` is an event that spans its body's events. Its line ``XLA
Modules`` has one event for each execution of a compiled program
(``jit_multi_step(<hash>)``). The host's planes carry the
``TraceAnnotation`` spans of the benchmark's own threads. So an op's kind is
read from its text: a fusion of ``kind=kOutput`` is one whose root is a
convolution or a matrix product (the matrix unit's work, with the bias, relu
or reduction XLA fused onto it), ``kLoop``/``kInput`` are elementwise and
reduction loops.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]  # start_ns, end_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_SPAN_PREFIX = "bench_"

#: kinds whose event spans the events of its body: never summed as work
CONTAINER = "container"
MATMUL = "matmul"
_OPCODE = re.compile(r"(?<=\s)([a-z][a-z0-9\-]*)\(")


def op_kind(text: str) -> str:
    """The kind of an HLO instruction, from its text in the trace."""
    body = text.split(" = ", 1)[-1]
    found = _OPCODE.search(" " + body)
    opcode = found.group(1) if found else "unknown"
    if opcode in ("while", "conditional", "call"):
        return CONTAINER
    if opcode in ("convolution", "dot"):
        return MATMUL
    if opcode == "fusion":
        if "kind=kOutput" in body:
            return MATMUL
        return "loop fusion"
    if opcode.startswith("all-reduce"):
        return "all-reduce"
    return opcode


def union_ns(intervals: Iterable[Interval]) -> int:
    """Total length covered by the intervals, overlaps counted once."""
    total, end = 0, None
    for lo, hi in sorted(intervals):
        if end is None or lo > end:
            total += hi - lo
            end = hi
        elif hi > end:
            total += hi - end
            end = hi
    return total


def gaps(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of [lo, hi] that no interval covers."""
    out, cursor = [], lo
    for a, b in sorted(intervals):
        if a > cursor:
            out.append((cursor, min(a, hi)))
        cursor = max(cursor, b)
        if cursor >= hi:
            break
    if cursor < hi:
        out.append((cursor, hi))
    return [(a, b) for a, b in out if b > a]


class Trace:
    """Device op and module events by chip, and the host's spans.

    ``ops[chip]``: [name, start_ns, dur_ns, kind] rows (``op_kind``);
    ``modules[chip]``: [name, start_ns, dur_ns]; ``host``: [name, start_ns,
    dur_ns] of the benchmark's own annotations."""

    def __init__(self, ops: Dict[str, list], modules: Dict[str, list], host: list):
        self.ops, self.modules, self.host = ops, modules, host

    def to_json(self) -> dict:
        return {"ops": self.ops, "modules": self.modules, "host": self.host}

    @classmethod
    def from_json(cls, doc: dict) -> "Trace":
        return cls(doc["ops"], doc["modules"], doc["host"])

    @property
    def chips(self) -> List[str]:
        return sorted(self.ops)

    def window_ns(self) -> Interval:
        """From the first to the last device event of any chip."""
        starts = [r[1] for rows in self.ops.values() for r in rows]
        ends = [r[1] + r[2] for rows in self.ops.values() for r in rows]
        if not starts:
            raise ValueError("no operation ran on the device in the trace")
        return min(starts), max(ends)

    def work(self, rows: list) -> list:
        """The rows that are work themselves, not spans of other rows."""
        return [r for r in rows if r[3] != CONTAINER]

    def busy_s(self) -> float:
        """Seconds an op ran on the device, averaged over the chips."""
        per_chip = [
            union_ns((r[1], r[1] + r[2]) for r in rows)
            for rows in self.ops.values()
        ]
        return sum(per_chip) / len(per_chip) / 1e9

    def window_s(self) -> float:
        lo, hi = self.window_ns()
        return (hi - lo) / 1e9

    def op_seconds(self, want: Callable[[list], bool]) -> float:
        """Device seconds of the ops ``want`` accepts, averaged over chips."""
        per_chip = [
            sum(r[2] for r in self.work(rows) if want(r))
            for rows in self.ops.values()
        ]
        return sum(per_chip) / len(per_chip) / 1e9

    def kind_seconds(self, kind: str) -> float:
        return self.op_seconds(lambda r: r[3] == kind)

    def total_op_seconds(self) -> float:
        return self.op_seconds(lambda r: True)

    def module_ms(self, name_part: str) -> Optional[float]:
        """Median device span, in ms, of one execution of the program whose
        name holds ``name_part`` (the median leaves out the executions the
        window's edges cut)."""
        spans = sorted(
            r[2] for rows in self.modules.values() for r in rows
            if name_part in r[0]
        )
        return spans[len(spans) // 2] / 1e6 if spans else None

    def module_runs(self, name_part: str) -> float:
        """Executions of that program in the window, a chip: the traced
        time in it over the median execution, so cut ones count in part."""
        median = self.module_ms(name_part)
        if not median:
            return 0.0
        total = sum(
            r[2] for rows in self.modules.values() for r in rows
            if name_part in r[0]
        )
        return total / 1e6 / median / len(self.modules)

    def env_steps(self, update_module: str, work_per_update: float) -> float:
        """Env-steps one chip's traced updates trained on (``work_per_update``
        is the whole mesh's; a chip does its share of it)."""
        return self.module_runs(update_module) * work_per_update / len(self.chips)

    def exposed_seconds(self, want: Callable[[list], bool]) -> float:
        """Device seconds of the accepted ops during which no other op ran
        on the same chip, averaged over the chips."""
        per_chip = []
        for rows in self.ops.values():
            rows = self.work(rows)
            mine = [(r[1], r[1] + r[2]) for r in rows if want(r)]
            others = sorted((r[1], r[1] + r[2]) for r in rows if not want(r))
            starts = [a for a, _ in others]
            hidden = []
            for a, b in mine:
                # work ops of one chip run one after another, so those that
                # can overlap [a, b) sit together from just before a
                i = max(0, bisect.bisect_left(starts, a) - 1)
                while i < len(others) and others[i][0] < b:
                    if others[i][1] > a:
                        hidden.append((max(a, others[i][0]), min(b, others[i][1])))
                    i += 1
            per_chip.append(union_ns(mine) - union_ns(hidden))
        return sum(per_chip) / len(per_chip) / 1e9

    def top_ops(self, n: int = 10) -> List[list]:
        """[name, seconds] of the ops that took most device time (one chip's
        share: the mean over the chips)."""
        by_name: Dict[str, int] = {}
        for rows in self.ops.values():
            for r in self.work(rows):
                key = f"{r[0]} ({r[3]})"
                by_name[key] = by_name.get(key, 0) + r[2]
        ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / len(self.ops) / 1e9] for name, ns in ranked]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """[what the host was doing, seconds]: the first chip's idle time,
        charged to the benchmark's host span that covers most of each gap
        (``unattributed`` where none does), largest first."""
        chip = self.chips[0]
        lo, hi = self.window_ns()
        busy = [(r[1], r[1] + r[2]) for r in self.ops[chip]]
        charged: Dict[str, int] = {}
        for a, b in gaps(busy, lo, hi):
            best, cover = "unattributed", 0
            for name, start, dur in self.host:
                c = min(b, start + dur) - max(a, start)
                if c > cover:
                    best, cover = name, c
            charged[best] = charged.get(best, 0) + (b - a)
        ranked = sorted(charged.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in ranked]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or a saved ``.json``/``.json.gz``)."""
    if path.endswith(".json") or path.endswith(".json.gz"):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            return Trace.from_json(json.load(f))
    from jax.profiler import ProfileData

    ops: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host: list = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rows = ops.setdefault(plane.name, [])
                    for e in line.events:
                        rows.append([
                            e.name.split(" = ", 1)[0], int(e.start_ns),
                            int(e.duration_ns), op_kind(e.name),
                        ])
                elif line.name == MODULES_LINE:
                    rows = modules.setdefault(plane.name, [])
                    for e in line.events:
                        rows.append([e.name, int(e.start_ns), int(e.duration_ns)])
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_SPAN_PREFIX):
                        host.append([e.name, int(e.start_ns), int(e.duration_ns)])
    return Trace(ops, modules, host)
