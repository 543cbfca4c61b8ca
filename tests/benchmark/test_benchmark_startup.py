"""The five start-up metrics (``benchmark/startup.py`` and its five thin
files) on a hand-made record of the program's (``utils/backend.py``), through
``Benchmark.layer_metric(...).read``; and that each reads nothing where there
is nothing to read: a program from before the record, a record that heard no
``fused.step`` call (``conftest.py`` starts every test here from such a one)."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import startup  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402
from distributed_ba3c_tpu.utils import backend, profiling  # noqa: E402
from distributed_ba3c_tpu.utils.backend import (  # noqa: E402
    Interval,
    StartupEvent,
    StartupRecord,
)

NEW_METRICS = {
    "setup_until_first_trace_s": ("program_span", "s"),
    "setup_trace_lower_s": ("program_span", "s"),
    "setup_compile_load_s": ("program_span", "s"),
    "setup_cache_misses": ("program_counter", "programs"),
    "step_first_call_s": ("program_span", "s"),
}
FINAL_CALLS = 140  # the step's call count when the window is over


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


def _a_warm_run():
    """What a warm run of a cell leaves: set-up's intervals below the final
    call count, the reference's compilation at it."""
    rec = StartupRecord(process_start=1000.0, installed=1002.5)
    rec.intervals += [
        Interval("trace", "build", 1008.0, 1009.0, 0),
        Interval("lower", "jit(build)", 1009.0, 1009.5, 0),
        Interval("compile_load", "jit(build)", 1009.5, 1010.0, 0, "hit", 0.3, 1.0),
        Interval("trace", "multi_step", 1012.0, 1015.0, 1),
        Interval("trace", "_sorted_rows", 1013.0, 1013.5, 1),  # inside the step's
        Interval("lower", "jit(multi_step)", 1015.0, 1017.0, 1),
        Interval("compile_load", "jit(multi_step)", 1017.0, 1020.5, 1, "hit", 3.4, 140.0),
        Interval("compile_load", "jit(multi_step)", 1300.0, 1301.0, FINAL_CALLS, "hit"),
        Interval("compile_load", "jit(follow_updates)", 1302.0, 1400.0, FINAL_CALLS, "miss"),
    ]
    rec.events += [
        StartupEvent("fused.step#1", 1011.9, 1020.7, {"hyper_s": 0.05, "enqueue_s": 8.7}),
        StartupEvent("fused.step#2", 1030.0, 1031.19, {"hyper_s": 0.0, "enqueue_s": 1.19}),
    ]
    return rec


@pytest.fixture
def chip_ctx(monkeypatch):
    """A process whose record is the hand-made one, the window over."""
    monkeypatch.setattr(backend, "_record", _a_warm_run())
    monkeypatch.setattr(profiling, "_step_calls", FINAL_CALLS)
    return {"counters": {"first_dispatch_s": 8.9}}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_start_up_metric_lists_every_cell_and_its_row_agrees(bench, name):
    entry = [m for m in bench.doc["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    assert entry[0]["workloads"] == [w["name"] for w in bench.doc["workloads"]]
    source, unit = NEW_METRICS[name]
    assert entry[0] == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": "entry and start-up", "moves": "setup_s",
        "workloads": entry[0]["workloads"]}
    module = bench.layer_metric(name)  # raises where ROW and entry differ
    assert callable(module.read)
    # the layer is one the benchmark already names, letter for letter
    assert entry[0]["layer"] == [
        m for m in bench.doc["per_layer"] if m["name"] == "first_dispatch_s"][0]["layer"]


def test_the_five_entries_are_appended_and_nothing_that_was_there_moved(bench):
    """Once each, in their order among themselves, after what PR 34 had put
    last; how many entries a later PR appends after them is not held."""
    names = [m["name"] for m in bench.doc["per_layer"]]
    at = [names.index(n) for n in NEW_METRICS]
    assert all(names.count(n) == 1 for n in NEW_METRICS)
    assert at == sorted(at) and at[0] > names.index("select_kept_share")
    first = bench.doc["per_layer"][0]
    assert first["name"] == "first_dispatch_s" and first["workloads"][:4] == [
        "fused-pong-256x20", "fused-pong-4096x20", "fused-pong-4chip-1024x20",
        "fused-lfm2moe-recall-128x256"]


@pytest.mark.parametrize("name,value", [
    ("setup_until_first_trace_s", 8.0),
    ("setup_trace_lower_s", 1.5 + 5.0),    # the nested trace counted once
    ("setup_compile_load_s", 0.5 + 3.5),   # what came after the window left out
    ("setup_cache_misses", 0),             # the reference's miss is not set-up's
    ("step_first_call_s", 8.8),
])
def test_a_start_up_metric_reads_the_hand_made_record(bench, chip_ctx, name, value):
    got = bench.layer_metric(name).read(chip_ctx)
    assert got == pytest.approx(value) and got is not None


def test_the_lines_say_what_the_numbers_are_made_of(bench, chip_ctx, capsys):
    for name in NEW_METRICS:
        bench.layer_metric(name).read(chip_ctx)
    said = capsys.readouterr().out
    assert "counted from the OS's stamp of the process's start; the installer ran 2.50 s in" in said
    assert "setup_trace_lower_s: 9 intervals kept, none dropped; multi_step trace 3.00 lower 2.00, build trace 1.00 lower 0.50" in said
    assert ("setup_compile_load_s: 2 cache hits, 0 misses, 3.70 s reading, "
            "141.00 s saved; multi_step compile_load 3.50, build compile_load 0.50") in said
    assert "follow_updates" not in said and "setup_cache_misses:" not in said
    assert ("step_first_call_s: fused.step#1 8.8000 s (hyper 0.0500, enqueue 8.7000): "
            "trace multi_step 3.000, lower jit(multi_step) 2.000, "
            "compile_load jit(multi_step) 3.500 (cache hit); 0.3000 in none") in said
    assert ("fused.step#2 1.1900 s (hyper 0.0000, enqueue 1.1900): no trace, "
            "lowering, compile or cache read inside; 1.1900 in none") in said


def test_a_cold_run_counts_its_misses_and_names_them(bench, chip_ctx, capsys):
    backend._record.intervals[6] = backend._record.intervals[6]._replace(
        cache="miss", end=1160.0, cache_read_s=0.0, time_saved_s=0.0)
    backend._record.dropped = 3
    assert bench.layer_metric("setup_cache_misses").read(chip_ctx) == 1
    bench.layer_metric("setup_trace_lower_s").read(chip_ctx)
    said = capsys.readouterr().out
    assert "setup_cache_misses: jit(multi_step) 143.00 s" in said
    assert "3 DROPPED: the unions are lower bounds" in said


def test_the_summary_is_read_once_a_run(bench, chip_ctx, monkeypatch):
    first = startup.summary(chip_ctx)
    monkeypatch.setattr(backend, "_record", StartupRecord(None, 0.0))
    assert startup.summary(chip_ctx) is first
    assert json.dumps(first)  # plain data


@pytest.mark.parametrize("name", NEW_METRICS)
@pytest.mark.parametrize("why", [
    "a program from before the record", "no record installed",
    "no fused.step call heard", "compilations and no fused.step call"])
def test_a_start_up_metric_reads_nothing_where_there_is_nothing_to_read(
        bench, chip_ctx, monkeypatch, name, why):
    if why == "a program from before the record":  # this PR's parent
        monkeypatch.delattr(backend, "startup_summary")
    elif why == "no record installed":
        monkeypatch.setattr(backend, "_record", None)
    elif why == "no fused.step call heard":  # BA3C_TELEMETRY=0
        monkeypatch.setattr(backend, "_record", StartupRecord(1000.0, 1002.5))
    else:  # an evaluation script: it compiled, and ran no step
        monkeypatch.setattr(backend._record, "events", [])
    assert bench.layer_metric(name).read(chip_ctx) is None


def test_a_test_here_starts_from_a_process_that_has_run_no_step(bench):
    # conftest.py: whatever this worker ran before, a reader that is handed
    # another run's recording finds no set-up of this process beside it
    assert profiling.step_calls() == 0
    assert startup.summary({"counters": {"first_dispatch_s": 19.9}}) is None
