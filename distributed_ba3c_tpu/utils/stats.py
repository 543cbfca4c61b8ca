"""Stat aggregation: per-step counters → per-epoch records → stat.json.

Reference equivalent: ``utils/stats.py`` ``StatCounter`` +
``callbacks/stats.py`` ``StatHolder``/``StatPrinter`` (SURVEY.md §2.7 #22):
scalar stats accumulate during an epoch, get flushed as one record appended
to ``stat.json`` in the log dir, and printed to the console with the same
metric names (score mean/max, losses, fps).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

import numpy as np


class StatCounter:
    """Accumulates scalars; exposes average/sum/max/count."""

    def __init__(self):
        self._values: List[float] = []

    def feed(self, v: float) -> None:
        self._values.append(float(v))

    def reset(self) -> None:
        self._values = []

    @property
    def count(self) -> int:
        return len(self._values)

    @property
    def average(self) -> float:
        assert self._values
        return float(np.mean(self._values))

    @property
    def sum(self) -> float:
        assert self._values
        return float(np.sum(self._values))

    @property
    def max(self) -> float:
        assert self._values
        return float(np.max(self._values))


class StatHolder:
    """Holds the current epoch's scalar stats; finalizes to stat.json.

    ``stat.json`` is a JSON list of per-epoch dicts — the format tensorpack
    tooling reads — so downstream plotting against the reference's logs works
    unchanged.
    """

    def __init__(
        self,
        log_dir: Optional[str] = None,
        tensorboard: bool = True,
        run_info: Optional[Dict[str, object]] = None,
    ):
        """``run_info`` (e.g. ``{"device": {...}}``) is written into the
        FIRST record this process finalizes — stat.json then names the
        device its numbers came from, per run and per resume. It is kept
        out of the returned record and of TensorBoard, which are scalars."""
        self.log_dir = log_dir
        self._run_info = dict(run_info or {})
        self.stat_now: Dict[str, float] = {}
        self.stat_history: List[Dict[str, float]] = []
        self._print_filter = None
        self._tb = None
        if log_dir is not None and tensorboard:
            from distributed_ba3c_tpu.utils.tb_writer import TBScalarWriter

            self._tb = TBScalarWriter(log_dir)
        if log_dir is not None:
            os.makedirs(log_dir, exist_ok=True)
            self._path = os.path.join(log_dir, "stat.json")
            if os.path.isfile(self._path):
                try:
                    with open(self._path) as f:
                        self.stat_history = json.load(f)
                except json.JSONDecodeError:
                    self.stat_history = []
        else:
            self._path = None

    def add_stat(self, name: str, value: float) -> None:
        self.stat_now[name] = float(value)

    def add_stats(self, values: Dict[str, float]) -> None:
        """Bulk :meth:`add_stat` — the telemetry bridge's entry point
        (StatPrinter folds ``telemetry.export_scalars()`` in per epoch, so
        stat.json/TB carry the same series the scrape endpoint serves)."""
        for name, v in values.items():
            self.stat_now[name] = float(v)

    def finalize(self) -> Dict[str, float]:
        """Close the epoch: append the record, write stat.json + TB events."""
        record = dict(self.stat_now)
        self.stat_history.append({**record, **self._run_info})
        self._run_info = {}
        if self._path is not None:
            tmp = self._path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(self.stat_history, f)
            os.replace(tmp, self._path)
        if self._tb is not None:
            step = int(record.get("global_step", record.get("epoch", 0)))
            self._tb.add_scalars(record, step)
            self._tb.flush()
        self.stat_now = {}
        return record

    def close(self) -> None:
        if self._tb is not None:
            self._tb.close()
