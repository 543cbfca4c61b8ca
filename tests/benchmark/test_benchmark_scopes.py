"""The by-scope reduction on a capture recorded on the v5e with the program's
scopes in it (three whole updates, 234 ms, of fused-pong-256x20, PR 24; cut
from a traced run's ``.xplane.pb`` by ``tests/benchmark/cut_capture.py``), the eight per-layer
metrics that read it, and that each of them reads nothing where there is no
scope to read: PR 23's recording, another run's capture, an older program."""

import gzip
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import scopes, trace  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCOPED = os.path.join(DATA, "fused-256x20-v5e-scoped.xplane.pb.gz")
UNSCOPED = os.path.join(DATA, "fused-256x20-v5e.trace.json.gz")
CELL = "fused-pong-256x20"
NEW_METRICS = [
    "rollout_time_share", "env_time_share", "learner_fwd_time_share",
    "learner_bwd_time_share", "optimizer_time_share", "unscoped_time_share",
    "dispatch_host_ms", "interstep_gap_ms",
]


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A directory laid out as a traced run of the cell leaves it."""
    root = tmp_path_factory.mktemp("checkout")
    run = root / ".bench_trace" / CELL / "plugins" / "profile" / "recorded"
    run.mkdir(parents=True)
    with gzip.open(SCOPED, "rb") as src, open(run / "v5e.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(root)


@pytest.fixture(scope="module")
def xplane(checkout):
    return trace.find_xplane(os.path.join(checkout, ".bench_trace", CELL))


@pytest.fixture(scope="module")
def by_scope(xplane):
    return profiling.op_time_by_scope(xplane)


def _ctx(tr):
    bench = Benchmark()
    cell = bench.cell(CELL)
    return {
        "trace": tr, "cell": cell, "config": bench.config(cell["config"]),
        "peaks": bench.peaks("TPU v5 lite"),
        "counters": {"first_dispatch_s": 19.9, "work_per_update": 5120},
    }


@pytest.fixture()
def scoped_ctx(checkout, xplane, monkeypatch):
    monkeypatch.setattr(scopes, "ROOT", checkout)
    return _ctx(trace.load(xplane))


# the chip run the recording was cut from read, over its 3 s: rollout 14.3 %
# of op time, policy 10.4 %, env 1.2 %, learner forward 24.3 % and backward
# 58.4 %, unscoped 2.4 %
@pytest.mark.parametrize("scope,low,high", [
    (profiling.ROLLOUT, 13.0, 16.0),
    (profiling.ROLLOUT_POLICY, 9.0, 12.0),
    (profiling.ROLLOUT_SAMPLE, 0.02, 0.3),
    (profiling.ROLLOUT_ENV_STEP, 0.8, 2.0),
    (profiling.ROLLOUT_RENDER, 0.7, 1.8),
    (profiling.ROLLOUT_STACK, 0.8, 2.0),
    (profiling.RETURNS, 0.3, 1.0),
    (profiling.LEARNER, 76.0, 84.0),
    (profiling.LEARNER_LOSS, 0.01, 0.2),
    (profiling.LEARNER_FWD, 20.0, 27.0),
    (profiling.LEARNER_BWD, 50.0, 60.0),
    (profiling.GRAD_REDUCE, 0.0, 0.05),
    (profiling.OPTIMIZER, 0.01, 0.2),
    (profiling.METRICS, 0.001, 0.05),
    (profiling.UNSCOPED, 1.0, 5.0),
])
def test_a_scopes_share_of_the_recorded_op_time(by_scope, scope, low, high):
    share = 100.0 * by_scope["seconds"][scope] / by_scope["total_s"]
    assert low <= share <= high, share


def test_phases_and_the_unscoped_rest_sum_to_all_op_time(by_scope):
    s = by_scope["seconds"]
    phases = sum(s[p] for p in profiling.PHASES)
    assert phases + s[profiling.UNSCOPED] == pytest.approx(by_scope["total_s"], rel=1e-9)
    assert s[profiling.LEARNER_FWD] + s[profiling.LEARNER_BWD] == pytest.approx(
        s[profiling.LEARNER], rel=1e-9)
    nested = [profiling.ROLLOUT_POLICY, profiling.ROLLOUT_SAMPLE,
              profiling.ROLLOUT_ENV_STEP, profiling.ROLLOUT_STACK]
    assert sum(s[n] for n in nested) <= s[profiling.ROLLOUT] * (1 + 1e-9)
    assert s[profiling.ROLLOUT_RENDER] <= s[profiling.ROLLOUT_ENV_STEP]
    assert by_scope["unscoped_share"] == pytest.approx(
        s[profiling.UNSCOPED] / by_scope["total_s"])
    top = by_scope["unscoped_ops"]
    assert 0 < len(top) <= 10 and all(a[1] >= b[1] for a, b in zip(top, top[1:]))


def test_containers_are_never_summed(by_scope, xplane):
    tr = trace.load(xplane)
    # the same events, the same rule: the benchmark's own reduction agrees
    assert by_scope["total_s"] == pytest.approx(tr.total_op_seconds(), rel=1e-9)
    assert by_scope["total_s"] <= tr.busy_s() * 1.001
    chip = tr.chips[0]
    whiles = [r for r in tr.ops[chip] if r[3] == trace.CONTAINER]
    assert whiles and sum(r[2] for r in whiles) / 1e9 > 0.1 * by_scope["total_s"]
    assert by_scope["events"] == {
        chip: [len(tr.ops[chip]), min(r[1] for r in tr.ops[chip])]}


def test_the_v5e_keeps_the_op_name_of_every_convolution(xplane):
    names = profiling.event_op_names(xplane)["/device:TPU:0"]
    matmuls = {e: n for e, n in names.items() if trace.op_kind(e) == trace.MATMUL}
    homes = {profiling.scope_of(n) for n in matmuls.values()}
    assert len(matmuls) > 30
    assert homes == {profiling.ROLLOUT_POLICY, profiling.RETURNS, profiling.LEARNER}
    backward = [n for n in matmuls.values() if profiling.is_backward(n)]
    assert 0 < len(backward) < len(matmuls)
    pools = [n for e, n in names.items() if trace.op_kind(e) == "select-and-scatter"]
    assert pools and all(
        profiling.scope_of(n) == profiling.LEARNER and profiling.is_backward(n)
        for n in pools)


def test_host_spans_of_the_recording_nest_and_sit_among_the_device_events(
        xplane, by_scope):
    spans = profiling.host_spans(xplane)
    steps = [r for r in spans if r[0] == profiling.SPAN_STEP]
    assert len(steps) >= 3 and len(spans) == 3 * len(steps)
    for part in (profiling.SPAN_STEP_HYPER, profiling.SPAN_STEP_ENQUEUE):
        for (_, lo, dur), (_, a, d) in zip(
                steps, [r for r in spans if r[0] == part]):
            assert lo <= a and a + d <= lo + dur
    first = by_scope["events"]["/device:TPU:0"][1]
    assert all(first - 1e9 < r[1] < first + 1e9 for r in spans)  # one clock
    assert all(1e5 < r[2] < 1e7 for r in steps)  # 0.1-10 ms a dispatch


@pytest.mark.parametrize("name,low,high", [
    ("rollout_time_share", 13.0, 16.0),
    ("env_time_share", 0.8, 2.0),
    ("learner_fwd_time_share", 20.0, 27.0),
    ("learner_bwd_time_share", 50.0, 60.0),
    ("optimizer_time_share", 0.01, 0.2),
    ("unscoped_time_share", 1.0, 5.0),
    ("dispatch_host_ms", 1.0, 5.0),
    ("interstep_gap_ms", 0.005, 0.1),
])
def test_a_new_metric_reads_the_scoped_recording(scoped_ctx, name, low, high, capsys):
    value = Benchmark().layer_metric(name).read(scoped_ctx)
    assert low <= value <= high, value
    said = capsys.readouterr().out
    if name == "dispatch_host_ms":
        assert profiling.SPAN_STEP_HYPER in said and profiling.SPAN_STEP_ENQUEUE in said
    if name == "interstep_gap_ms":
        assert "jit_convert_element_type x2.00" in said
    if name == "env_time_share":
        assert profiling.ROLLOUT_RENDER in said


def test_the_old_metrics_read_the_scoped_recording_as_they_read_the_old_one(
        scoped_ctx):
    got = {}
    bench = Benchmark()
    for entry in bench.per_layer(CELL):
        value = bench.layer_metric(entry["name"]).read(scoped_ctx)
        if value is not None:
            got[entry["name"]] = value
    assert set(got) == set(NEW_METRICS) | {
        "first_dispatch_s", "update_device_ms", "train_mfu", "conv_time_share",
        "pool_bwd_time_share", "conv_roofline"}
    assert got["update_device_ms"] == pytest.approx(77.99, abs=0.02)
    assert got["conv_time_share"] == pytest.approx(67.5, abs=1.0)
    assert got["pool_bwd_time_share"] == pytest.approx(15.0, abs=0.5)
    # the pool's backward is all inside the learner's backward
    assert got["pool_bwd_time_share"] < got["learner_bwd_time_share"]


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_nothing_from_the_unscoped_recording(
        checkout, monkeypatch, name):
    # PR 23's recording, loaded from JSON, beside another run's capture
    monkeypatch.setattr(scopes, "ROOT", checkout)
    assert Benchmark().layer_metric(name).read(_ctx(trace.load(UNSCOPED))) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_nothing_without_a_capture(tmp_path, monkeypatch, name):
    monkeypatch.setattr(scopes, "ROOT", str(tmp_path))
    assert Benchmark().layer_metric(name).read(_ctx(trace.load(UNSCOPED))) is None


@pytest.mark.parametrize("missing", ["op_time_by_scope", "host_spans"])
def test_a_program_from_before_the_scopes_reads_as_nothing(
        scoped_ctx, monkeypatch, missing):
    monkeypatch.delattr(profiling, missing)
    assert scopes.capture(scoped_ctx) is None
    for name in NEW_METRICS:
        assert Benchmark().layer_metric(name).read(scoped_ctx) is None


def test_a_capture_whose_events_are_not_the_traces_reads_as_nothing(scoped_ctx):
    chip = scoped_ctx["trace"].chips[0]
    scoped_ctx["trace"].ops[chip].pop()  # one event fewer than the capture
    assert scopes.capture(scoped_ctx) is None


def test_a_capture_that_starts_elsewhere_reads_as_nothing(scoped_ctx):
    chip = scoped_ctx["trace"].chips[0]
    for row in scoped_ctx["trace"].ops[chip]:
        row[1] += 1000
    assert scopes.capture(scoped_ctx) is None


def test_the_capture_is_read_once_a_run(scoped_ctx, monkeypatch):
    first = scopes.capture(scoped_ctx)
    monkeypatch.setattr(profiling, "op_time_by_scope", None)
    assert first is not None and scopes.capture(scoped_ctx) is first
