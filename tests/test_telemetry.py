"""Telemetry plane: metrics core, flight recorder, piggyback wire,
exporters, and the live scrape e2e (docs/observability.md).

The slow test is the CI telemetry job's teeth: a real `train.py --env fake
--telemetry_port` run must expose master+predictor+learner+fleet series on
the scrape endpoint, every /json series must appear in /metrics, and every
/metrics line must parse as Prometheus text exposition.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.telemetry import metrics as tmetrics

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_registries():
    telemetry.reset_all()
    yield
    telemetry.reset_all()


# -- metrics core -----------------------------------------------------------


def test_counter_sums_across_threads():
    c = tmetrics.Counter("x_total")

    def work():
        for _ in range(10_000):
            c.inc()

    ts = [threading.Thread(target=work, daemon=True) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    # no locks anywhere, yet the per-thread shards make the total exact
    assert c.value() == 40_000


def test_gauge_set_and_fn():
    g = tmetrics.Gauge("depth")
    g.set(3)
    assert g.value() == 3.0
    g.set_fn(lambda: 7)
    assert g.value() == 7.0
    g.set_fn(lambda: 1 / 0)  # a dead fn reads 0, never raises
    assert g.value() == 0.0


def test_histogram_log2_buckets():
    h = tmetrics.Histogram("wait_s", unit=1e-6)
    h.observe(0.0)        # below unit -> bucket 0
    h.observe(3e-6)       # ~2 us -> bucket 2 ([2us, 4us))
    h.observe(1.0)        # 1e6 us -> high bucket
    assert h.count == 3
    assert h.sum == pytest.approx(1.000003)
    b = h.buckets()
    assert b[0] == 1 and sum(b) == 3
    assert b[2] == 1  # int(3e-6/1e-6)=3 -> bit_length 2


def test_registry_get_or_create_and_scalars():
    r = telemetry.registry("master")
    assert r.counter("a_total") is r.counter("a_total")
    r.counter("a_total").inc(5)
    r.gauge("g", fn=lambda: 2)
    r.histogram("h_s").observe(0.5)
    s = r.scalars()
    assert s["a_total"] == 5 and s["g"] == 2
    assert s["h_s_count"] == 1 and s["h_s_sum"] == pytest.approx(0.5)


def test_set_enabled_gates_writes():
    r = telemetry.registry("master")
    c = r.counter("gated_total")
    try:
        telemetry.set_enabled(False)
        c.inc(10)
        r.histogram("gated_s").observe(1)
        assert c.value() == 0
    finally:
        telemetry.set_enabled(True)
    c.inc(2)
    assert c.value() == 2


# -- attribution: the dead stage, named from the counters -------------------


@pytest.mark.parametrize("counters, disabled, says", [
    ({}, True, "telemetry disabled, no attribution"),
    ({("predictor", "batches_total"): 3}, False, "no wire traffic"),
    # fleet roles: the sums over master.f<k> are what is read
    ({("master.f0", "block_msgs_total"): 2,
      ("master.f1", "block_shm_msgs_total"): 1},
     False, "wire traffic but predictor never served"),
    ({("master", "per_env_msgs_total"): 9, ("predictor", "batches_total"): 4},
     False, "predictor serving but no datapoints"),
    ({("master", "block_msgs_total"): 9, ("predictor", "batches_total"): 4,
      ("master", "datapoints_total"): 17},
     False, "plane went quiet after progress"),
], ids=["disabled", "no_wire", "never_served", "no_datapoints", "quiet"])
def test_stall_attribution_names_the_dead_stage(counters, disabled, says):
    from distributed_ba3c_tpu.telemetry.attribution import stall_attribution

    for (role, name), v in counters.items():
        telemetry.registry(role).counter(name).inc(v)
    try:
        telemetry.set_enabled(not disabled)
        why = stall_attribution()
    finally:
        telemetry.set_enabled(True)
    assert why.startswith(says), why
    msgs = sum(v for (_, name), v in counters.items() if "msgs_total" in name)
    assert f"wire_msgs={msgs} " in why
    assert f"datapoints={counters.get(('master', 'datapoints_total'), 0)} " in why


# -- flight recorder --------------------------------------------------------


def test_flight_recorder_ring_and_dump(tmp_path):
    rec = telemetry.FlightRecorder(capacity=4)
    for i in range(7):
        rec.record("evt", i=i)
    snap = rec.snapshot()
    assert [e["i"] for e in snap] == [3, 4, 5, 6]  # ring keeps the newest
    path = rec.dump("test", path=str(tmp_path / "flight.json"))
    doc = json.load(open(path))
    assert doc["reason"] == "test" and len(doc["events"]) == 4
    assert {"anchor_monotonic", "anchor_wall"} <= set(doc)


def test_flight_dump_never_raises(tmp_path):
    rec = telemetry.FlightRecorder()
    rec.record("evt")
    # unwritable target: dump must swallow, not mask the original failure
    assert rec.dump("x", path="/proc/nope/flight.json") is None


# -- piggyback wire ---------------------------------------------------------


def test_delta_tracker_emits_deltas_once():
    r = telemetry.registry("simulator")
    c = r.counter("env_steps_total")
    t = telemetry.DeltaTracker(r)
    c.inc(100)
    assert t.deltas() == {"env_steps_total": 100}
    assert t.deltas() == {}  # nothing moved since
    c.inc(5)
    assert t.deltas() == {"env_steps_total": 5}


def test_apply_fleet_deltas_aggregates_and_rejects_garbage():
    telemetry.apply_fleet_deltas(b"a", {"env_steps_total": 10})
    telemetry.apply_fleet_deltas(b"b", {"env_steps_total": 7, 42: 1, "x": "no"})
    telemetry.apply_fleet_deltas(b"c", "not-a-dict")
    telemetry.apply_fleet_deltas(b"d", [1, 2])
    s = telemetry.registry("fleet").scalars()
    assert s["env_steps_total"] == 17
    assert s["reporting_clients"] >= 2


# -- exporters --------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^ba3c_[A-Za-z0-9_]+(\{[A-Za-z0-9_]+=\"[^\"]*\"(,[A-Za-z0-9_]+=\"[^\"]*\")*\})? "
    r"[-+]?[0-9.eE+naninf-]+$"  # trailing '-' admits negative exponents (5e-05)
)


def _assert_prom_parses(text: str) -> set:
    names = set()
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert line.startswith("# TYPE ba3c_"), line
            continue
        assert _PROM_LINE.match(line), f"unparseable metrics line: {line!r}"
        names.add(line.split("{")[0].split(" ")[0])
    return names


def test_prometheus_text_covers_every_registered_series():
    telemetry.registry("master").counter("a_total").inc()
    telemetry.registry("predictor").gauge("depth", fn=lambda: 1)
    telemetry.registry("learner").histogram("step_s").observe(0.01)
    names = _assert_prom_parses(telemetry.prometheus_text())
    assert {"ba3c_a_total", "ba3c_depth"} <= names
    # histograms expand to the full prometheus triplet
    assert {"ba3c_step_s_bucket", "ba3c_step_s_sum", "ba3c_step_s_count"} <= names


def test_prometheus_text_one_type_line_per_family():
    """The same metric name in two roles (episodes_total lives in learner,
    simulator AND fleet by design) must share ONE # TYPE line — the
    Prometheus text parser rejects a whole scrape with duplicate TYPEs."""
    telemetry.registry("learner").counter("episodes_total").inc(3)
    telemetry.registry("fleet").counter("episodes_total").inc(7)
    text = telemetry.prometheus_text()
    _assert_prom_parses(text)
    assert text.count("# TYPE ba3c_episodes_total ") == 1
    assert 'ba3c_episodes_total{role="learner"} 3' in text
    assert 'ba3c_episodes_total{role="fleet"} 7' in text


def test_prometheus_text_small_values_parse():
    """Negative-exponent renderings (5e-05) must pass the parse gate."""
    telemetry.registry("master").histogram("tiny_s").observe(5e-5)
    _assert_prom_parses(telemetry.prometheus_text())


def test_telemetry_server_endpoints():
    telemetry.registry("master").counter("served_total").inc(3)
    telemetry.record("evt", note="x")
    srv = telemetry.TelemetryServer(0)  # ephemeral port
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics", timeout=10).read().decode()
        _assert_prom_parses(text)
        assert 'ba3c_served_total{role="master"} 3' in text
        snap = json.loads(
            urllib.request.urlopen(f"{base}/json", timeout=10).read()
        )
        assert snap["master"]["served_total"]["value"] == 3
        ring = json.loads(
            urllib.request.urlopen(f"{base}/flight", timeout=10).read()
        )
        assert any(e["kind"] == "evt" for e in ring)
    finally:
        srv.stop()
        srv.join(timeout=5)
        srv.close()


def test_export_scalars_prefixes_roles():
    telemetry.registry("learner").counter("train_steps_total").inc(4)
    out = telemetry.export_scalars()
    assert out["tele/learner/train_steps_total"] == 4


# -- live e2e: scrape a real training run -----------------------------------


def _get_json(url, timeout=5):
    return json.loads(urllib.request.urlopen(url, timeout=timeout).read())


@pytest.mark.slow
@pytest.mark.timeout(600)
def test_live_e2e_scrape_endpoint(tmp_path):
    """A real `train.py --env fake --telemetry_port` run exposes
    master+predictor+learner+fleet series; /metrics covers every /json
    series and parses as Prometheus text (the CI telemetry job)."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    logdir = str(tmp_path / "log")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO_ROOT
    proc = subprocess.Popen(
        [
            sys.executable, os.path.join(REPO_ROOT, "train.py"),
            "--env", "fake", "--simulator_procs", "4",
            "--batch_size", "32", "--image_size", "16", "--fc_units", "16",
            "--steps_per_epoch", "80", "--max_epoch", "2", "--nr_eval", "2",
            "--telemetry_port", str(port), "--logdir", logdir,
        ],
        env=env, cwd=REPO_ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    base = f"http://127.0.0.1:{port}"
    try:
        # wait for the endpoint (it starts with the actor plane, after the
        # train-step compile), then for all four roles to report
        deadline = time.monotonic() + 420
        snap = None
        while time.monotonic() < deadline and proc.poll() is None:
            try:
                snap = _get_json(f"{base}/json")
                if all(
                    snap[role][series]["value"] > 0
                    for role, series in (
                        ("master", "per_env_msgs_total"),
                        ("predictor", "batches_total"),
                        ("learner", "train_steps_total"),
                        ("fleet", "env_steps_total"),
                    )
                ):
                    break
            except (OSError, KeyError):
                pass
            time.sleep(1.0)
        assert snap is not None, "scrape endpoint never came up"
        assert {"master", "predictor", "learner", "fleet"} <= set(snap), snap.keys()
        # the fleet aggregation actually flowed (piggybacked sim deltas)
        assert snap["fleet"]["env_steps_total"]["value"] > 0
        assert snap["master"]["per_env_msgs_total"]["value"] > 0
        assert snap["learner"]["train_steps_total"]["value"] > 0
        assert snap["predictor"]["batches_total"]["value"] > 0

        # every registered series is present in /metrics and parseable
        text = urllib.request.urlopen(f"{base}/metrics", timeout=10).read().decode()
        prom_names = _assert_prom_parses(text)
        for role, series in snap.items():
            for name, m in series.items():
                safe = "ba3c_" + re.sub(r"[^A-Za-z0-9_]", "_", name)
                want = {safe} if m["type"] != "histogram" else {
                    f"{safe}_bucket", f"{safe}_sum", f"{safe}_count"
                }
                missing = want - prom_names
                assert not missing, f"{role}/{name}: missing {missing}"
    finally:
        try:
            out, _ = proc.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, _ = proc.communicate()
            pytest.fail("training run did not finish")
    assert proc.returncode == 0, out[-3000:]
    # the stat.json/TB bridge carried the same series
    stats = json.load(open(os.path.join(logdir, "stat.json")))
    assert any(k.startswith("tele/") for k in stats[-1]), stats[-1].keys()
