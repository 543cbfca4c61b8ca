"""The door stays open: a later PR adds a cell by files and by appending,
and may edit no file that is here. On a copy of the real ``BENCHMARK.json``
with a stand-in configuration, a stand-in cell (appended to ``workloads``, to
the five start-up lists and to the shared lists a fused cell's capture
feeds) and a stand-in per-layer entry appended at the END of ``per_layer``,
every check of this directory that reads the document still holds: each test
function of the five files below whose code reads ``.doc``, called as the
plain function of a ``Benchmark`` it is, once for each of its own
``parametrize`` cases."""

import inspect
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import test_benchmark_keyevl2  # noqa: E402
import test_benchmark_lm  # noqa: E402
import test_benchmark_seq  # noqa: E402
import test_benchmark_spec  # noqa: E402
import test_benchmark_startup  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

FILES = (test_benchmark_spec, test_benchmark_startup, test_benchmark_lm,
         test_benchmark_seq, test_benchmark_keyevl2)
CONFIG, CELL, METRIC = "stand-in-config", "stand-in-cell", "stand_in_train_mfu"
ROW = {"name": METRIC, "unit": "%", "better": "higher", "source": "device_trace",
       "layer": "fused trainer", "moves": "env_steps_per_s_per_chip"}


def _cases(function):
    """The argument sets the function's own ``parametrize`` mark gives."""
    marks = [m for m in getattr(function, "pytestmark", [])
             if m.name == "parametrize"]
    if not marks:
        return [{}]
    (mark,) = marks  # a check of the document has one axis, of one name
    return [{mark.args[0]: value} for value in mark.args[1]]


def _checks_of_the_document():
    for module in FILES:
        for name, function in inspect.getmembers(module, inspect.isfunction):
            if name.startswith("test_") and "doc" in function.__code__.co_names:
                for case in _cases(function):
                    tag = "-".join(str(v) for v in case.values())
                    yield pytest.param(module, function, case, id=(
                        f"{module.__name__[len('test_benchmark_'):]}::"
                        f"{name[len('test_'):]}" + (f"[{tag}]" if tag else "")))


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    """A checkout's benchmark with one of each thing appended by files and
    entries alone, as ``benchmark/README.md`` says a cell is added."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    added = [root / f"benchmark/configs/{CONFIG}.json",
             root / f"benchmark/workloads/{CELL}.json",
             root / f"benchmark/layer_metrics/{METRIC}.py"]
    assert not any(p.exists() for p in added)  # no file that is there is edited
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    old_config, old_cell = doc["configs"][0], doc["workloads"][0]
    shutil.copy(root / old_config["file"], added[0])
    doc["configs"].append(dict(
        old_config, name=CONFIG, file=f"benchmark/configs/{CONFIG}.json"))
    with open(root / f"benchmark/workloads/{old_cell['name']}.json") as f:
        params = json.load(f)
    added[1].write_text(json.dumps(dict(params, config=CONFIG)))
    doc["workloads"].append(dict(
        old_cell, name=CELL, config=CONFIG, traffic="a2c-stand-in", why="test"))
    for entry in doc["per_layer"]:
        if (entry["name"] in test_benchmark_startup.NEW_METRICS
                or entry["name"] in test_benchmark_seq.SHARED_METRICS):
            entry["workloads"].append(CELL)
    added[2].write_text(f"ROW = {ROW!r}\n\n\ndef read(ctx):\n    return 12.5\n")
    doc["per_layer"].append(dict(ROW, workloads=[CELL]))
    (root / "BENCHMARK.json").write_text(json.dumps(doc, indent=1))
    return Benchmark(root=str(root))


def test_the_stand_ins_are_found_and_stand_last(appended):
    assert appended.doc["per_layer"][-1]["name"] == METRIC
    assert appended.doc["workloads"][-1]["name"] == CELL
    listed = [m["name"] for m in appended.per_layer(CELL)]
    assert listed[-1] == METRIC and len(listed) == 1 + 5 + 11


@pytest.mark.parametrize("module,check,case", _checks_of_the_document())
def test_a_check_of_the_document_holds_with_a_cell_appended(
        appended, module, check, case):
    wanted = inspect.signature(check).parameters
    given = dict(case, bench=appended)
    if "config" in wanted:  # the files' own fixture: their configuration
        given["config"] = appended.config(module.CONFIG)
    assert set(wanted) == set(given), "a check of the document takes the document"
    check(**given)
