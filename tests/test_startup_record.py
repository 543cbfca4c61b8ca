"""The start-up record (utils/backend.py): what the listeners on
``jax.monitoring`` keep, the step's first four calls beside them, the summary
both readers share, and the operator's line, gauges and after-warm-up watch.

Everything here runs on the CPU and measures nothing: times are asserted to
be ordered and to nest, never to be small.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import monitoring  # the public module shows no getters

from distributed_ba3c_tpu import audit, telemetry
from distributed_ba3c_tpu.telemetry import metrics
from distributed_ba3c_tpu.utils import backend, profiling
from distributed_ba3c_tpu.utils.backend import Interval, StartupEvent, StartupRecord


@pytest.fixture
def record(monkeypatch):
    """A fresh record behind the process's (one) set of listeners, the step's
    call count at 0, telemetry on: whatever ran before in this worker."""
    backend.install_startup_record()
    now = time.monotonic()
    fresh = StartupRecord(process_start=now - 1.0, installed=now)
    monkeypatch.setattr(backend, "_record", fresh)
    monkeypatch.setattr(profiling, "_step_calls", 0)
    monkeypatch.setattr(metrics, "_enabled", True)
    return fresh


def _ours(listeners):
    return [f for f in listeners if getattr(f, "__module__", "") == backend.__name__]


def test_the_installer_registers_one_listener_of_each_kind_however_often_called(
        monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # a CPU-only process gets no cache (returns early), and the record all the same
    assert backend.configure_compile_cache() is None
    first = backend.startup_record()
    assert first is not None and backend.install_startup_record() is first
    assert backend.configure_compile_cache() is None
    assert backend.startup_record() is first
    assert _ours(monitoring.get_event_time_span_listeners()) == [backend._on_time_span]
    assert _ours(monitoring.get_event_listeners()) == [backend._on_event]
    assert _ours(monitoring.get_event_duration_listeners()) == [backend._on_duration]
    assert first.installed <= time.monotonic()
    if first.process_start is not None:  # Linux: the OS's stamp lies before ours
        assert first.process_start <= first.installed


def _by_stage(record, bare_name):
    return {
        stage: [i for i in record.intervals
                if i.stage == stage and backend._bare(i.fun_name) == bare_name]
        for stage in backend.STAGE_OF_EVENT.values()
    }


def test_a_jitted_function_leaves_an_interval_of_each_stage_under_its_name(record):
    def startup_probe(x):
        return jnp.sin(x) * 2.0

    before = time.monotonic()
    jax.block_until_ready(jax.jit(startup_probe)(jnp.ones(3)))
    after = time.monotonic()
    found = _by_stage(record, "startup_probe")
    assert {stage: len(v) for stage, v in found.items()} == {
        "trace": 1, "lower": 1, "compile_load": 1}
    trace, lower, load = (found[s][0] for s in ("trace", "lower", "compile_load"))
    assert trace.fun_name == "startup_probe"  # the later stages name the module
    for i in (trace, lower, load):
        assert before <= i.start <= i.end <= after  # time.monotonic(), not time.time()
        assert i.step_calls == 0 and i.cache == "none"  # no cache in a CPU process
    assert trace.end <= lower.end <= load.end
    reg = telemetry.registry("learner")
    assert reg.counter("jit_traces_total").value() >= 1
    assert reg.counter("backend_compiles_total").value() >= 1


def test_a_jit_traced_inside_another_adds_nothing_to_the_union(record):
    @jax.jit
    def startup_inner(x):
        return jnp.cos(x) + 1.0

    def startup_outer(x):
        return startup_inner(x) * startup_inner(x + 1.0)

    x = jnp.ones(5)
    record.intervals.clear()  # whatever making the argument traced
    jax.block_until_ready(jax.jit(startup_outer)(x))
    outer = _by_stage(record, "startup_outer")["trace"][0]
    inner = _by_stage(record, "startup_inner")["trace"]
    assert inner and all(outer.start <= i.start and i.end <= outer.end for i in inner)
    traces = [i for i in record.intervals if i.stage == "trace"]
    assert backend._union_s(traces) == pytest.approx(outer.end - outer.start)
    assert sum(i.end - i.start for i in traces) > backend._union_s(traces)
    assert backend._outermost(traces) == [outer]


def test_the_cut_by_step_calls_leaves_out_what_came_after_the_last_call(
        record, monkeypatch):
    monkeypatch.setattr(profiling, "_step_calls", 3)
    jax.block_until_ready(jax.jit(lambda x: x * 3.0 + 1.0)(jnp.ones(3)))
    during = len(record.intervals)
    monkeypatch.setattr(profiling, "_step_calls", 5)  # the window's last dispatch
    jax.block_until_ready(jax.jit(lambda x: x * 5.0 - 1.0)(jnp.ones(3)))
    assert {i.step_calls for i in record.intervals[:during]} == {3}
    assert {i.step_calls for i in record.intervals[during:]} == {5}
    whole = backend.startup_summary()
    cut = backend.startup_summary(before_step_calls=profiling.step_calls())
    kept = record.intervals[:during]
    assert cut["compile_load_s"] == pytest.approx(
        backend._union_s(i for i in kept if i.stage == "compile_load"))
    assert cut["compile_load_s"] < whole["compile_load_s"]
    assert cut["trace_lower_s"] < whole["trace_lower_s"]
    assert backend.startup_summary(before_step_calls=3)["compile_load_s"] == 0.0


def _small_step():
    """fused.step at the audit's canonical small shapes, and what makes its
    state on the devices: the same one each time."""
    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import create_fused_state, make_fused_step

    cfg, model, opt = audit._canonical_parts()
    n = audit.CANONICAL_MESH_DEVICES
    step = make_fused_step(
        model, opt, cfg, audit.canonical_mesh(), pong, rollout_len=4,
        grad_chunk_samples=4)
    return step, lambda: step.put(create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong, 2 * n, n_shards=n))


class _CountedClock:
    def __init__(self):
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return time.perf_counter()


def test_step_records_its_first_four_calls_and_then_reads_no_clock(
        record, monkeypatch):
    step, fresh_state = _small_step()
    state = fresh_state()
    clock = _CountedClock()
    reads = []
    with monkeypatch.context() as patched:
        patched.setattr(time, "monotonic", clock)
        for _ in range(6):
            before = clock.reads
            state, m = step(state, 0.01, 1e-3)
            reads.append(clock.reads - before)
    jax.block_until_ready(m)
    # calls #1-#4 read the clock (theirs, and the listeners' during call #1);
    # call #5 and #6: one integer more than the parent's step(), nothing else
    assert all(r >= 4 for r in reads[:4]) and reads[4:] == [0, 0]
    assert profiling.step_calls() == 6
    names = [ev.name for ev in record.events]
    assert names == [f"fused.step#{k}" for k in (1, 2, 3, 4)]
    for ev in record.events:
        assert ev.start <= ev.end and set(ev.parts) == {"hyper_s", "enqueue_s"}
        assert 0 <= ev.parts["hyper_s"] + ev.parts["enqueue_s"] <= ev.end - ev.start
    calls = backend.startup_summary()["step_calls"]
    assert [c["name"] for c in calls] == names
    # the first call traced, lowered and compiled the step; the second none
    assert [row[:2] for row in calls[0]["inside"]] == [
        ["trace", "multi_step"], ["lower", "jit(multi_step)"],
        ["compile_load", "jit(multi_step)"]]
    assert 0 < calls[0]["inside_s"] <= calls[0]["step_s"]
    assert calls[1]["inside"] == [] and calls[1]["inside_s"] == 0.0
    # every interval of the first call carries its count: it is inside the cut
    first = [i for i in record.intervals if backend._bare(i.fun_name) == "multi_step"]
    assert {i.step_calls for i in first} == {1}
    kinds = [e["kind"] for e in telemetry.flight_recorder().snapshot()]
    assert kinds.count("startup") >= 4 and "compile" in kinds


def test_the_first_calls_hand_the_jit_what_the_later_ones_do(record, monkeypatch):
    """``first_calls`` is a second copy of ``step()``'s body (fused/loop.py):
    the default learning rate and an explicit one through both."""
    step, fresh_state = _small_step()

    def two_updates(calls_before):
        monkeypatch.setattr(profiling, "_step_calls", calls_before)
        state, _ = step(fresh_state(), 0.01)  # the configuration's rate
        state, m = step(state, 0.02, 3e-4)
        return jax.device_get((state.train.params, state.train.opt_state, m))

    start = jax.device_get(fresh_state().train.params)
    timed = two_updates(0)
    assert [ev.name for ev in record.events] == ["fused.step#1", "fused.step#2"]
    plain = two_updates(profiling.RECORDED_STEP_CALLS)
    assert len(record.events) == 2  # the later calls recorded nothing
    jax.tree_util.tree_map(np.testing.assert_array_equal, timed, plain)
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(np.any(a != b)), start, timed[0])
    assert any(jax.tree_util.tree_leaves(moved))  # the comparison has teeth


def test_with_telemetry_off_nothing_is_recorded(record, monkeypatch):
    monkeypatch.setattr(metrics, "_enabled", False)
    ring = len(telemetry.flight_recorder().snapshot())
    jax.block_until_ready(jax.jit(lambda x: x * 7.0 + 2.0)(jnp.ones(3)))
    backend.startup_event("fused.step#1", 0.0, 1.0, hyper_s=0.1, enqueue_s=0.9)
    with backend.startup_phase("state_init"):
        pass  # neither its beginning nor its end
    assert record.intervals == [] and record.events == [] and record.dropped == 0
    assert len(telemetry.flight_recorder().snapshot()) == ring
    assert backend.report_startup(time.monotonic()) is None and not record.warm


def _hand_made(process_start=100.0):
    """Two threads' worth of intervals with overlaps, a nested trace, a hit
    and a miss, one interval after the last call, and two recorded calls."""
    rec = StartupRecord(process_start=process_start, installed=103.0)
    rec.intervals += [
        Interval("trace", "build", 105.0, 106.0, 0),
        Interval("trace", "multi_step", 110.0, 114.0, 1),
        Interval("trace", "inner", 111.0, 112.0, 1),         # inside multi_step's
        Interval("trace", "other_thread", 113.0, 115.0, 1),  # overlaps its end
        Interval("lower", "jit(multi_step)", 115.0, 117.0, 1),
        Interval("compile_load", "jit(multi_step)", 117.0, 127.0, 1, "miss"),
        Interval("compile_load", "jit(build)", 106.0, 106.5, 0, "hit", 0.4, 2.5),
        Interval("compile_load", "jit_rollout", 130.0, 131.0, 2, "hit", 0.9, 5.0),
        Interval("compile_load", "jit(reference)", 200.0, 260.0, 9, "miss"),
    ]
    rec.events += [
        StartupEvent("state_init", 104.0, 104.5, {}),
        StartupEvent("fused.step#1", 109.9, 127.2, {"hyper_s": 0.1, "enqueue_s": 17.1}),
        StartupEvent("fused.step#2", 140.0, 141.2, {"hyper_s": 0.0, "enqueue_s": 1.2}),
    ]
    rec.dropped = 2
    return rec


def test_the_summary_of_a_hand_made_record():
    s = backend.startup_summary(before_step_calls=9, record=_hand_made())
    assert s["process_start_from"] == "os" and s["installed_s"] == 3.0
    assert s["until_first_trace_s"] == 5.0
    # build 1 + (110..115 as one stretch) 5 + the lowering 2: never 4 + 1 + 2
    assert s["trace_lower_s"] == pytest.approx(8.0)
    assert s["compile_load_s"] == pytest.approx(11.5)  # the reference's 60 s fell out
    assert (s["cache_hits"], s["cache_misses"]) == (2, 1)
    assert s["cache_read_s"] == pytest.approx(1.3) and s["time_saved_s"] == 7.5
    assert s["missed"] == [["jit(multi_step)", 10.0]]
    assert s["dropped"] == 2 and s["intervals"] == 9
    assert s["costliest"][0] == (
        "multi_step", {"trace": 4.0, "lower": 2.0, "compile_load": 10.0})
    assert dict(s["costliest"])["build"] == {"trace": 1.0, "compile_load": 0.5}
    assert dict(s["costliest"])["rollout"] == {"compile_load": 1.0}
    assert "reference" not in dict(s["costliest"])
    assert s["phases"] == [["state_init", 0.5]]
    first, second = s["step_calls"]
    assert [row[:2] for row in first["inside"]] == [
        ["trace", "multi_step"], ["trace", "other_thread"],
        ["lower", "jit(multi_step)"], ["compile_load", "jit(multi_step)"]]
    assert first["inside_s"] == pytest.approx(17.0)
    assert first["step_s"] == pytest.approx(17.3)
    assert second["inside"] == [] and second["parts"]["enqueue_s"] == 1.2
    line = backend.costliest_line(s, ("trace", "lower"))
    assert line.startswith("multi_step trace 4.00 lower 2.00, other_thread trace 2.00")
    assert "compile_load" not in line and "rollout" not in line
    assert backend.startup_summary(record=_hand_made())["compile_load_s"] == 71.5


def test_a_record_without_the_oss_stamp_counts_from_the_installer_and_says_so():
    s = backend.startup_summary(record=_hand_made(process_start=None))
    assert s["process_start_from"] == "installer" and s["installed_s"] == 0.0
    assert s["until_first_trace_s"] == 2.0
    empty = backend.startup_summary(record=StartupRecord(None, 5.0))
    assert empty["until_first_trace_s"] is None and empty["step_calls"] == []
    assert empty["trace_lower_s"] == empty["compile_load_s"] == 0.0


def test_past_the_bound_an_interval_is_counted_and_not_kept(record, monkeypatch):
    monkeypatch.setattr(backend, "MAX_INTERVALS", 2)
    jax.block_until_ready(jax.jit(lambda x: x * 11.0 + 3.0)(jnp.ones(3)))
    assert len(record.intervals) == 2 and record.dropped >= 1
    assert backend.startup_summary()["dropped"] == record.dropped


@pytest.mark.parametrize("name,bare", [
    ("jit(multi_step)", "multi_step"), ("jit_multi_step", "multi_step"),
    ("multi_step", "multi_step"), ("pmap(f)", "f"), ("jitter", "jitter"),
    ("jit(_sorted_rows)", "_sorted_rows"),
])
def test_the_stages_of_one_function_land_under_one_name(name, bare):
    assert backend._bare(name) == bare


def test_the_os_stamp_of_the_process_start_lies_before_now():
    before = time.monotonic()
    start = backend._os_process_start()
    if not os.path.exists("/proc/self/stat"):
        assert start is None
    else:
        assert start is not None and before - 7 * 86400 < start <= before + 0.02


def test_a_phase_tells_the_flight_recorder_when_it_begins_and_when_it_ends(record):
    mark = time.monotonic()

    def heard():
        return [e for e in telemetry.flight_recorder().snapshot()
                if e["kind"] == "startup" and e["t_monotonic"] >= mark]

    with pytest.raises(RuntimeError):
        with backend.startup_phase("restore"):
            # a dump taken now, of a restore that hangs, names it
            assert [(e["name"], e.get("at")) for e in heard()] == [
                ("restore", "begin")]
            raise RuntimeError("the checkpoint is not there")
    assert [(e["name"], "dur_s" in e) for e in heard()] == [
        ("restore", False), ("restore", True)]
    assert [ev.name for ev in record.events] == ["restore"]  # one event, the whole


@pytest.mark.timeout(600)
def test_a_tiny_fused_run_logs_the_line_sets_the_gauges_and_counts_a_later_recompile(
        record, monkeypatch, tmp_path):
    """``run_fused_training`` on one CPU device: the evaluator's program is
    compiled at the end of the first epoch, after the first update, and is
    that function's first: a healthy run raises no alarm. A function that
    compiles a second program after the line does."""
    from distributed_ba3c_tpu import cli
    from distributed_ba3c_tpu.fused import loop
    from distributed_ba3c_tpu.models.a3c import BA3CNet
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel import mesh as mesh_mod

    real = mesh_mod.make_mesh
    monkeypatch.setattr(
        mesh_mod, "make_mesh",
        lambda num_data, num_model: real(1, num_model, devices=jax.devices()[:1]))
    args = cli.make_parser().parse_args([
        "--trainer", "tpu_fused_ba3c", "--env", "jax:pong",
        "--batch_size", "4", "--rollout_len", "2", "--fc_units", "16",
        "--steps_per_epoch", "1", "--max_epoch", "1", "--nr_eval", "1",
        "--eval_max_steps", "2", "--logdir", str(tmp_path), "--tpu_lock", "off",
    ])
    cfg = cli.build_config(args)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    reg = telemetry.registry("learner")
    later_before = reg.counter("compiles_after_warmup_total").value()
    assert loop.run_fused_training(args, cfg, model, opt) == 0

    log = (tmp_path / "log.log").read_text()
    line = [l for l in log.splitlines() if "start-up: " in l]
    assert len(line) == 1, log
    for said in ("s to the first update", "until the first trace", "trace+lower",
                 "compile/load (0 cache hits, 0 misses)", "first execution",
                 "other host work; by phase: state_init ", ", build_step ",
                 ", put ", ", first_update ", " in none"):
        assert said in line[0], line[0]
    assert record.warm
    gauges = {name: reg.gauge(name).value() for name in (
        "startup_s", "startup_until_first_trace_s", "startup_trace_lower_s",
        "startup_compile_load_s")}
    assert all(v > 0 for v in gauges.values()), gauges
    assert gauges["startup_s"] >= (
        gauges["startup_until_first_trace_s"] + gauges["startup_compile_load_s"])
    stat = json.loads((tmp_path / "stat.json").read_text())[-1]
    assert stat["tele/learner/startup_s"] == pytest.approx(gauges["startup_s"])
    assert stat["tele/learner/compiles_after_warmup_total"] == later_before
    assert [name for name, _ in backend.startup_summary()["phases"]] == [
        "state_init", "build_step", "put", "first_update"]
    events = telemetry.flight_recorder().snapshot()
    assert any(e["kind"] == "startup" and e.get("name") == "first_update"
               for e in events)
    # the evaluator compiled after the line, for the first time: no alarm
    evaluator = [i for i in record.intervals if i.stage == "compile_load"
                 and "local_eval" in i.fun_name]
    assert len(evaluator) == 1 and evaluator[0].start > [
        ev for ev in record.events if ev.name == "first_update"][0].end
    assert reg.counter("compiles_after_warmup_total").value() == later_before
    assert "compiled again after warm-up" not in log
    assert not any(e["kind"] == "retrace" and e["t_monotonic"] >= record.installed
                   for e in events)

    # a steady-state recompile: a function that has a program compiles another
    def startup_forced(x):
        return jnp.sin(x) * 3.0

    monkeypatch.setattr(backend, "EVENT_FROM_S", 0.0)  # however fast the CPU is
    forced = jax.jit(startup_forced)
    jax.block_until_ready(forced(np.ones(3, np.float32)))  # its first: none
    assert reg.counter("compiles_after_warmup_total").value() == later_before
    # a new shape: its second (numpy's: making it on the device would compile
    # jnp's own broadcast again, and be counted too)
    jax.block_until_ready(forced(np.ones((2, 3), np.float32)))
    assert reg.counter("compiles_after_warmup_total").value() == later_before + 1
    again = [e for e in telemetry.flight_recorder().snapshot()
             if e["kind"] == "retrace" and e["t_monotonic"] >= record.installed]
    assert [e["entry"] for e in again] == ["jit(startup_forced)"]
    assert "compiled again after warm-up: jit(startup_forced)" in (
        tmp_path / "log.log").read_text()
