"""The benchmark's data files: the contract's rules, and that a cell, a
configuration and a per-layer metric are each added by files and entries."""

import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import opcount, stats  # noqa: E402
from benchmark.spec import Benchmark, SpecError  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


def _names(doc):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in doc[group]:
            yield group, entry["name"]


def test_top_level_keys_and_command(bench):
    doc = bench.doc
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["command"] == ["python3", "benchmark/run.py"]
    assert doc["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(doc["run_seconds"], int) and 1 <= doc["run_seconds"] <= 51
    assert len(json.dumps(doc)) < 64 * 1024


@pytest.mark.parametrize("group", ["configs", "workloads", "end_to_end", "per_layer"])
def test_names_are_allowed_and_unique(bench, group):
    names = [e["name"] for e in bench.doc[group]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    if group in ("end_to_end", "per_layer"):
        both = [e["name"] for g in ("end_to_end", "per_layer") for e in bench.doc[g]]
        assert len(both) == len(set(both))


def test_entries_have_just_the_contract_keys(bench):
    doc = bench.doc
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and os.path.isfile(
            os.path.join(bench.root, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0 < m["bound"] <= 0.1 and m["source"] in {"host_clock", "device_trace"}
    for m in doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
    for m in doc["end_to_end"] + doc["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    pairs = [(w["config"], w["traffic"]) for w in doc["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in doc["workloads"])
    assert four <= max(1, len(doc["workloads"]) // 4)
    assert "setup_s" in [m["name"] for m in doc["end_to_end"]]


def test_every_cell_reports_setup_another_metric_and_a_layer(bench):
    used = set()
    for w in bench.doc["workloads"]:
        e2e = [m["name"] for m in bench.end_to_end()]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert bench.per_layer(w["name"])
        used.add(w["config"])
    assert used == {c["name"] for c in bench.doc["configs"]}


def test_each_layer_metric_moves_a_metric_all_its_cells_report(bench):
    cells = [w["name"] for w in bench.doc["workloads"]]
    for m in bench.doc["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= set(cells), m
        assert m["moves"] in [e["name"] for e in bench.end_to_end()], m


def test_harness_finds_everything_by_name(bench):
    for w in bench.doc["workloads"]:
        cell = bench.cell(w["name"])
        config = bench.config(cell["config"])
        assert hasattr(bench.driver(config["driver"]), "setup")
        assert set(cell["limits"]) == {
            "loss_gap", "first_grad_norm_gap", "param_delta_norm_gap",
            "state_mismatch_share", "action_flip_share"}
    for m in bench.doc["per_layer"]:
        assert callable(bench.layer_metric(m["name"]).read)
    layers = {}
    for m in bench.doc["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers
    with pytest.raises(SpecError):
        bench.cell("no-such-cell")
    with pytest.raises(SpecError):
        bench.peaks("TPU v9 imaginary")
    assert bench.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_a_cell_a_config_and_a_layer_metric_are_added_by_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: (root / "benchmark" / p).read_bytes()
              for p in ("run.py", "spec.py", "drivers/fused.py")}
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    old = doc["configs"][0]
    cfg = json.load(open(os.path.join(ROOT, old["file"])))
    cfg["argv"] = cfg["argv"] + ["--env", "jax:seaquest"]
    (root / "benchmark/configs/new-config.json").write_text(json.dumps(cfg))
    doc["configs"].append(dict(old, name="new-config",
                               file="benchmark/configs/new-config.json"))
    (root / "benchmark/workloads/new-cell.json").write_text(json.dumps({
        "config": "new-config", "argv": ["--batch_size", "640"],
        "follow_updates": 3, "trace_seconds": 2,
        "limits": {"loss_gap": 1, "first_grad_norm_gap": 1, "param_delta_norm_gap": 1},
    }))
    doc["workloads"].append({"name": "new-cell", "config": "new-config",
                             "traffic": "a2c-32x20", "chips": 1, "why": "test"})
    row = {"name": "new.metric-1", "unit": "ms", "better": "lower",
           "source": "host_clock", "layer": "entry and start-up", "moves": "setup_s"}
    (root / "benchmark/layer_metrics/new.metric-1.py").write_text(
        f"ROW = {row!r}\n\ndef read(ctx):\n    return 1.5\n")
    doc["per_layer"].append(dict(row, workloads=["new-cell"]))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    b = Benchmark(root=str(root))
    cell = b.cell("new-cell")
    assert cell["argv"] == ["--batch_size", "640"]
    assert b.config(cell["config"])["argv"][-1] == "jax:seaquest"
    assert "new.metric-1" in [m["name"] for m in b.per_layer("new-cell")]
    assert b.layer_metric("new.metric-1").read({}) == 1.5
    assert "new.metric-1" not in [m["name"] for m in b.per_layer(doc["workloads"][0]["name"])]
    for p, data in before.items():
        assert (root / "benchmark" / p).read_bytes() == data


def test_a_layer_metric_file_must_agree_with_its_entry(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    doc["per_layer"][0]["moves"] = "env_steps_per_s_per_chip" \
        if doc["per_layer"][0]["moves"] == "setup_s" else "setup_s"
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(SpecError):
        Benchmark(root=str(root)).layer_metric(doc["per_layer"][0]["name"])


def test_a_layer_metric_entry_must_list_its_cells(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    del doc["per_layer"][0]["workloads"]
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(SpecError):
        Benchmark(root=str(root)).per_layer(doc["workloads"][0]["name"])


def test_shape_counts_of_one_forward(bench):
    cfg = bench.config("ba3cnet-pong-fused-a2c")
    convs = [l["macs"] for l in opcount.conv_layers(cfg)]
    assert convs == [22_579_200, 45_158_400, 14_450_688, 3_686_400]
    assert opcount.dense_layers(cfg)[0]["macs"] == 3_276_800
    assert opcount.forward_macs(cfg) == 89_155_072  # 89.2 M
    # rollout fwd + learner fwd + dW + dx (no dx into the frames)
    assert opcount.flops_per_env_step(cfg) == 668_082_176
    assert opcount.conv_flops_per_env_step(cfg) == 2 * (
        3 * sum(convs) + sum(convs[1:]))
    assert opcount.conv_bytes_per_env_step(cfg) > 0


def test_rate_stops_the_clock_at_the_last_completed_update():
    # 4 updates of 1000 env-steps, the last seen complete 2.0 s after the
    # start, on 2 chips; time after the last completion is not counted
    rate, seconds = stats.completed_rate(10.0, [10.5, 11.0, 11.5, 12.0], 1000, 2)
    assert seconds == 2.0 and rate == 4 * 1000 / 2.0 / 2
    with pytest.raises(ValueError):
        stats.completed_rate(0.0, [], 1000, 1)
    with pytest.raises(ValueError):  # under 250 ms the host clock cannot time it
        stats.completed_rate(0.0, [0.1], 1000, 1)
