"""The reduction from the profiler's trace to per-layer numbers, on a small
trace recorded on the v5e (250 ms of fused-pong-256x20, PR 23) and on
synthetic events."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

RECORDED = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "data",
    "fused-256x20-v5e.trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    return trace.load(RECORDED)


@pytest.mark.parametrize("text,kind", [
    ("%fusion.240 = bf16[5,5,32,32]{3,2,1,0:T(8,128)(2,1)S(1)} fusion(bf16[2560,42,42,32]{0,3,2,1:T(8,128)(2,1)} %fusion.246), kind=kOutput, calls=%fused_computation.646", "matmul"),
    ("%fusion.99 = bf16[32]{0:T(256)(128)(2,1)} fusion(bf16[2560,84,84,32]{0,3,2,1:T(8,128)(2,1)} %x), kind=kLoop, calls=%f", "loop fusion"),
    ("%abs_reduce_fusion.1 = (f32[]{:T(128)}, bf16[5,5,32,32]{3,2,1,0:T(8,128)(2,1)}) fusion(bf16[5,5,32,32]{3,2,1,0} %a), kind=kOutput, calls=%c", "matmul"),
    ("%select-and-scatter.8 = bf16[2560,84,84,32]{0,3,2,1:T(8,128)(2,1)} select-and-scatter(bf16[2560,84,84,32]{0,3,2,1} %g), window={size=1x2x2x1}", "select-and-scatter"),
    ("%while.139 = (s32[]{:T(128)}, /*index=5*/s32[256]{0:T(256)}) while((s32[]{:T(128)}) %tuple.395), condition=%c, body=%b", "container"),
    ("%all-reduce.3 = f32[6400,512]{1,0:T(8,128)} all-reduce(f32[6400,512]{1,0} %p), replica_groups={}", "all-reduce"),
    ("%all-reduce-start.1 = f32[32]{0} all-reduce-start(f32[32]{0} %p)", "all-reduce"),
    ("%convolution.4 = bf16[8,84,84,32]{0,3,2,1} convolution(bf16[8,84,84,4]{0,3,2,1} %a, bf16[5,5,4,32]{3,2,1,0} %b), window={size=5x5}", "matmul"),
    ("%copy.40 = u8[1,2560,84,84,4]{1,4,3,2,0:T(4,128)(4,1)} copy(u8[1,2560,84,84,4]{1,4,3,2,0} %x)", "copy"),
])
def test_an_ops_kind_is_read_from_its_text(text, kind):
    assert trace.op_kind(text) == kind


def test_union_and_gaps():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.union_ns([]) == 0
    assert trace.gaps([(5, 10), (8, 12), (20, 25)], 0, 30) == [
        (0, 5), (12, 20), (25, 30)]
    assert trace.gaps([], 3, 9) == [(3, 9)]


def test_synthetic_two_chips():
    ops = {
        "/device:TPU:0": [["%while.1", 0, 100, "container"],
                          ["%fusion.1", 0, 40, "matmul"],
                          ["%fusion.2", 40, 20, "loop fusion"],
                          ["%all-reduce.1", 60, 40, "all-reduce"]],
        "/device:TPU:1": [["%fusion.1", 0, 40, "matmul"],
                          ["%all-reduce.1", 50, 50, "all-reduce"],
                          ["%fusion.9", 90, 20, "loop fusion"]],
    }
    modules = {c: [["jit_step(1)", 0, 100], ["jit_step(1)", 100, 100],
                   ["jit_step(1)", 200, 30]] for c in ops}
    host = [["bench_wait_slot", 35, 20]]
    tr = trace.Trace(ops, modules, host)
    assert tr.window_ns() == (0, 110)
    assert tr.busy_s() == pytest.approx((100 + 100) / 2 / 1e9)
    # the while spans its body and is never summed as work
    assert tr.total_op_seconds() == pytest.approx((100 + 110) / 2 / 1e9)
    assert tr.kind_seconds("matmul") == pytest.approx(40 / 1e9)
    # chip 1's all-reduce overlaps %fusion.9 for 10 ns
    assert tr.exposed_seconds(lambda r: r[3] == "all-reduce") == pytest.approx(
        (40 + 40) / 2 / 1e9)
    assert tr.module_ms("jit_step") == pytest.approx(100 / 1e6)
    assert tr.module_runs("jit_step") == pytest.approx(2.3)  # a chip
    assert tr.module_ms("nothing") is None and tr.module_runs("nothing") == 0
    # chip 0 is idle nowhere (a while counts as running from its first op to
    # its last); without it, a hole is charged to the host span over it
    ops["/device:TPU:0"] = [["%fusion.1", 0, 40, "matmul"],
                            ["%fusion.2", 50, 60, "loop fusion"]]
    assert trace.Trace(ops, modules, host).idle_gaps() == [["bench_wait_slot", 1e-8]]
    with pytest.raises(ValueError):
        trace.Trace({"/device:TPU:0": []}, {}, []).window_ns()


def test_recorded_trace_reduces_to_the_numbers_read_by_hand(recorded):
    assert recorded.chips == ["/device:TPU:0"]
    # three executions of the step (one cut by the window) of 78 ms each
    assert recorded.module_ms("jit_multi_step") == pytest.approx(77.99, abs=0.01)
    assert 2.0 < recorded.module_runs("jit_multi_step") < 3.0
    busy, window = recorded.busy_s(), recorded.window_s()
    assert 0.99 < busy / window <= 1.0
    share = recorded.kind_seconds(trace.MATMUL) / recorded.total_op_seconds()
    assert 0.6 < share < 0.75
    pool = recorded.kind_seconds("select-and-scatter") / recorded.total_op_seconds()
    assert 0.12 < pool < 0.18
    assert recorded.total_op_seconds() <= busy * 1.001  # nothing counted twice
    top = recorded.top_ops(3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]
    assert all("(" in name for name, _ in top)
    assert {name for name, _ in recorded.idle_gaps()} <= {
        "bench_wait_slot", "bench_dispatch", "bench_wait_update", "unattributed"}


def test_layer_metrics_read_the_recorded_trace(recorded):
    bench = Benchmark()
    cell = bench.cell("fused-pong-256x20")
    ctx = {
        "trace": recorded, "cell": cell, "config": bench.config(cell["config"]),
        "peaks": bench.peaks("TPU v5 lite"),
        "counters": {"first_dispatch_s": 19.9, "work_per_update": 5120},
    }
    got = {}
    for entry in bench.per_layer(cell["name"]):
        value = bench.layer_metric(entry["name"]).read(ctx)
        if value is not None:
            got[entry["name"]] = value
    assert set(got) == {"first_dispatch_s", "update_device_ms", "train_mfu",
                        "conv_time_share", "pool_bwd_time_share", "conv_roofline"}
    assert got["update_device_ms"] == pytest.approx(77.99, abs=0.01)
    # 5,120 env-steps x 668 MFLOP in 78 ms of a 197 TFLOP/s chip
    assert got["train_mfu"] == pytest.approx(
        100 * 5120 * 668.08e6 / 0.07799 / 197e12, rel=0.05)
    for name in ("train_mfu", "conv_roofline", "conv_time_share"):
        assert 0 < got[name] < 100
    # one chip has no all-reduce to read: the metric is left out of the line
    assert bench.layer_metric("allreduce_exposed_ms").read(ctx) is None
