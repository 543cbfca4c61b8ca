#!/usr/bin/env python
"""Latency-vs-throughput frontier for the SLO-aware serving plane.

Drives the real ``BatchedPredictor`` scheduler — continuous batching,
deadline admission, load shedding (docs/serving.md) — with OPEN-LOOP
Poisson arrivals at a sweep of offered rates, and publishes per-rate
p50/p90/p99 serve latency, shed rate and batch occupancy: the frontier the
way ``plane_bench_r6/r7`` publish throughput.

Open-loop matters: a closed-loop driver slows down with the server and
hides the overload region entirely; here arrivals keep coming at the
offered rate no matter what, so past saturation the plane must SHED (fast
typed rejects) while the p99 of what it does serve stays under the SLO —
that is the acceptance shape, load shedding rather than latency collapse.

Device-free by default: the device is the plane-bench null predictor with
a SIMULATED per-call service time (``--service_us``, slept at fetch like a
real serialized device queue), so the frontier's service-time axis is real
while no accelerator is in the loop — ``device_free_proxy: true`` in the
JSON, and ``platform`` names what jax ran on.

Prints ONE JSON line on stdout (the repo's bench-tooling contract), with
the per-rate evidence BEFORE any gate verdict; diagnostics go to stderr.

``--dtype f32,bf16,int8`` sweeps the rollout-precision LADDER: one
frontier per dtype with the null device's service time scaled by the
MXU-throughput model (bf16 2x f32, int8 2x bf16 — the relative-rate
claim the audit entries' byte censuses back), per-dtype param-table
bytes measured on the REAL quantized tables (quantize/), the int8 spec
calibrated from real jax-Pong rollouts (its hash stamped in every int8
row), a Pong parity section holding the int8 forward inside the bf16
bands, and the rows/s-per-replica gate (int8 >= 1.05x bf16 at equal p99
inside the SLO). Every JSON row carries ``rollout_dtype``.

Usage:
  python scripts/serving_bench.py                       # default sweep + gate
  python scripts/serving_bench.py --rates 1000,4000 --seconds 2   # CI smoke
  python scripts/serving_bench.py --dtype f32,bf16,int8 # the quant frontier
  python scripts/plane_bench.py --serving               # embedded in the
                                                        # plane instrument
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


#: the MXU-throughput model the --dtype sweep scales the null device's
#: service time by: bf16 doubles f32's matmul rate, int8 doubles bf16's
#: (the relative-rate shape the audit entries' byte censuses back); the
#: absolute numbers stay a device-free proxy — on-chip re-capture is the
#: ROADMAP item, the RATIO at equal p99 is what this instrument pins
_DTYPE_SERVICE_FACTOR = {"float32": 1.0, "bfloat16": 0.5, "int8": 0.25}

_DTYPE_ALIASES = {
    "f32": "float32", "float32": "float32",
    "bf16": "bfloat16", "bfloat16": "bfloat16",
    "int8": "int8",
}


def _percentiles_ms(lats):
    import numpy as np

    if not lats:
        return None, None, None
    arr = np.asarray(lats) * 1000.0
    return (
        round(float(np.percentile(arr, 50)), 3),
        round(float(np.percentile(arr, 90)), 3),
        round(float(np.percentile(arr, 99)), 3),
    )


def _make_replica(opts, tele_role: str):
    """One null-device replica (a complete BatchedPredictor serving plane
    with simulated service time — predict.null.make_null_predictor) under its
    own telemetry role, started."""
    from distributed_ba3c_tpu.predict.null import make_null_predictor

    # a stub model is enough: the null predictor never traces the forward,
    # and the scheduler only reads num_actions for the fallback contract
    model = SimpleNamespace(num_actions=opts.num_actions, apply=None)
    pred = make_null_predictor(
        model, {}, opts.num_actions,
        service_s=opts.service_us / 1e6,
        batch_size=opts.batch_size,
        coalesce_ms=0.0,
        slo_ms=opts.slo_ms,
        queue_depth=opts.queue_depth,
        tele_role=tele_role,
    )
    pred.start()
    return pred


def _make_plane(opts, replicas: int):
    """Build the measurand: a single predictor (``replicas == 1``, the
    PR-9 plane, byte-identical behavior) or R replicas behind the REAL
    ServingRouter. Returns ``(target, roles, teardown)`` where ``roles``
    are the telemetry registries the point's evidence reads."""
    from distributed_ba3c_tpu import telemetry

    telemetry.reset_all()
    if replicas == 1:
        pred = _make_replica(opts, "predictor")
        return pred, ["predictor"], lambda: (pred.stop(), pred.join(5))

    from distributed_ba3c_tpu.predict.router import (
        ServingRouter,
        replica_role,
    )

    router = ServingRouter(health_interval_s=0.1)
    preds = []
    roles = []
    for i in range(replicas):
        role = replica_role("predictor", i)
        pred = _make_replica(opts, role)
        router.add_replica(f"r{i}", pred)
        preds.append(pred)
        roles.append(role)
    router.start()

    def teardown():
        router.stop()
        router.join(timeout=5)
        for p in preds:
            p.stop()
            p.join(timeout=5)

    target = SimpleNamespace(
        put_block_task=router.put_block_task, router=router, preds=preds
    )
    return target, roles, teardown


def _replica_sub_rows(roles) -> list:
    """Per-replica occupancy/shed/p99 evidence rows — a dead replica must
    not hide behind a healthy aggregate (ISSUE 15 house style)."""
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.predict.router import signals_from_snapshot

    rows = []
    for role in roles:
        snap = telemetry.registry(role).collect()
        s = signals_from_snapshot(snap)
        batches = float(snap.get("batches_total", {}).get("value", 0.0))
        served_rows = s["rows_total"]
        rows.append({
            "role": role,
            "rows": served_rows,
            "batches": batches,
            "mean_batch_rows": (
                round(served_rows / batches, 2) if batches else None
            ),
            "sheds": s["sheds_total"],
            "serve_p99_ms": s["serve_p99_ms"],
            "deadline_misses": float(
                snap.get("deadline_misses_total", {}).get("value", 0.0)
            ),
        })
    return rows


def _drive_point(target, rate_rows_per_s: float, opts) -> tuple:
    """The open-loop Poisson submit/drain loop against ``target`` (a
    predictor or the routed facade). Returns (lats, sheds, submit_elapsed,
    total_elapsed, n_tasks)."""
    import numpy as np

    lats: list = []    # served: admit -> callback, seconds
    sheds: list = []   # ShedReject.reason per shed task
    state = np.zeros((opts.block_rows, 1), np.uint8)  # content is irrelevant
    rng = np.random.default_rng(opts.seed)
    n_tasks = max(1, int(opts.seconds * rate_rows_per_s / opts.block_rows))
    mean_gap = opts.block_rows / rate_rows_per_s
    gaps = rng.exponential(mean_gap, n_tasks)
    clock = time.monotonic
    t_start = clock()
    next_t = t_start
    for i in range(n_tasks):
        next_t += gaps[i]
        now = clock()
        if next_t > now:
            time.sleep(next_t - now)
        t0 = clock()

        def cb(a, v, lp, t0=t0):
            lats.append(clock() - t0)

        def shed_cb(rej):
            sheds.append(rej.reason)

        target.put_block_task(state, cb, shed_callback=shed_cb)
    submit_elapsed = clock() - t_start
    # drain: every deadline'd task resolves (served, or shed at pop)
    deadline = clock() + opts.slo_ms / 1000.0 * 4 + 10.0
    while len(lats) + len(sheds) < n_tasks and clock() < deadline:
        time.sleep(0.01)
    # served throughput is measured over the WHOLE service window
    # (submission + drain): dividing drain-phase completions by the
    # submission window alone would overstate capacity exactly at the
    # knee, where the backlog drains after arrivals stop
    total_elapsed = clock() - t_start
    return lats, sheds, submit_elapsed, total_elapsed, n_tasks


def run_point(rate_rows_per_s: float, opts, replicas: int = 1) -> dict:
    """One open-loop rate point: fresh plane, Poisson arrivals of
    ``block_rows``-row block tasks for ``seconds``, drained to
    completion. ``replicas > 1`` drives the routed plane and embeds
    per-replica sub-rows."""
    from distributed_ba3c_tpu import telemetry

    target, roles, teardown = _make_plane(opts, replicas)
    try:
        lats, sheds, submit_elapsed, total_elapsed, n_tasks = _drive_point(
            target, rate_rows_per_s, opts
        )
    finally:
        teardown()
    batches = rows = misses = 0.0
    for role in roles:
        scal = telemetry.registry(role).scalars()
        batches += scal.get("batches_total", 0)
        rows += scal.get("rows_total", 0)
        misses += scal.get("deadline_misses_total", 0)
    p50, p90, p99 = _percentiles_ms(lats)
    served = len(lats)
    shed = len(sheds)
    point = {
        "offered_rows_per_s": round(
            n_tasks * opts.block_rows / max(submit_elapsed, 1e-9), 1
        ),
        "target_rows_per_s": rate_rows_per_s,
        "submitted_tasks": n_tasks,
        "served_tasks": served,
        "shed_tasks": shed,
        "unresolved_tasks": n_tasks - served - shed,
        "shed_rate": round(shed / n_tasks, 4),
        "sheds_by_reason": {
            r: sheds.count(r) for r in sorted(set(sheds))
        },
        "p50_ms": p50,
        "p90_ms": p90,
        "p99_ms": p99,
        "served_rows_per_s": round(
            served * opts.block_rows / max(total_elapsed, 1e-9), 1
        ),
        "mean_batch_rows": round(rows / batches, 2) if batches else None,
        "deadline_misses": misses,
    }
    if replicas > 1:
        point["replica_rows"] = _replica_sub_rows(roles)
    return point


def run_frontier(opts, replicas: int = 1, rates=None) -> tuple:
    """The full sweep + gate. Returns (json_row, gate_failure_messages)."""
    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    points = []
    for rate in (opts.rates if rates is None else rates):
        p = run_point(rate, opts, replicas=replicas)
        points.append(p)
        stderr_print(
            f"serving x{replicas} {rate:>8.0f} rows/s offered: "
            f"p99={p['p99_ms']} ms shed={p['shed_rate']:.1%} "
            f"occupancy={p['mean_batch_rows']}"
        )

    slo = opts.slo_ms
    failures = []
    ok = [
        p for p in points
        if p["shed_rate"] < 0.01 and p["p99_ms"] is not None
        and p["p99_ms"] <= slo
    ]
    best = max(ok, key=lambda p: p["offered_rows_per_s"]) if ok else None
    if best is None:
        failures.append(
            f"serving gate FAILED: no rate point met the SLO "
            f"(p99 <= {slo} ms with shed < 1%)"
        )
        overload = None
    else:
        over = [
            p for p in points
            if p["offered_rows_per_s"] >= 2 * best["offered_rows_per_s"]
        ]
        overload = max(over, key=lambda p: p["offered_rows_per_s"]) \
            if over else None
        if overload is None:
            failures.append(
                "serving gate FAILED: sweep never reached 2x the best "
                f"SLO-meeting rate ({best['offered_rows_per_s']} rows/s) — "
                "extend --rates to cover overload"
            )
        else:
            if not overload["shed_rate"] > best["shed_rate"]:
                failures.append(
                    "serving gate FAILED: 2x overload did not raise the "
                    f"shed rate ({overload['shed_rate']} vs "
                    f"{best['shed_rate']} at the SLO point)"
                )
            if overload["p99_ms"] is not None and overload["p99_ms"] > slo:
                failures.append(
                    "serving gate FAILED: served-task p99 "
                    f"{overload['p99_ms']} ms exceeded the {slo} ms SLO "
                    "under overload — latency collapse, not load shedding"
                )
    out = {
        "metric": "serving_frontier_rows_per_s_vs_latency",
        "unit": "rows/sec vs ms",
        "rollout_dtype": getattr(opts, "rollout_dtype", "float32"),
        "replicas": replicas,
        "slo_ms": slo,
        "block_rows": opts.block_rows,
        "batch_size": opts.batch_size,
        "service_us": opts.service_us,
        "queue_depth": opts.queue_depth,
        "seconds": opts.seconds,
        "seed": opts.seed,
        # no accelerator in the loop; the service-time axis is simulated
        # at the null device's fetch
        "device_free_proxy": True,
        "rate_points": points,
        "gate": {
            "criterion": (
                f"exists rate point with p99 <= {slo} ms and shed < 1%; at "
                ">= 2x that rate, shed rises while served p99 stays <= SLO"
            ),
            "best_slo_point_rows_per_s": (
                best["offered_rows_per_s"] if best else None
            ),
            "overload_point_rows_per_s": (
                overload["offered_rows_per_s"] if overload else None
            ),
            "passed": not failures,
        },
    }
    if getattr(opts, "quant_spec_hash", None):
        out["quant_spec_hash"] = opts.quant_spec_hash
    if getattr(opts, "param_table_bytes", None):
        out["param_table_bytes"] = opts.param_table_bytes
    return out, failures


def run_chaos_rep(opts, replicas: int, rate_rows_per_s: float) -> dict:
    """Replica-kill chaos: open-loop load on the routed plane, one
    replica's scheduler killed mid-submission (the SIGKILL analogue for
    an in-process replica: its queue survives, nobody serves it). The
    acceptance shape: every task RESOLVES (served, or a typed shed the
    masters answer with the uniform fallback — zero lockstep wedges),
    served p99 stays inside the SLO, and the router's flight record
    carries the replica_dead verdict."""
    import numpy as np

    from distributed_ba3c_tpu import telemetry

    target, roles, teardown = _make_plane(opts, replicas)
    router = target.router
    victim = target.preds[0]
    lats: list = []
    sheds: list = []
    state = np.zeros((opts.block_rows, 1), np.uint8)
    rng = np.random.default_rng(opts.seed + 1)
    n_tasks = max(2, int(opts.seconds * rate_rows_per_s / opts.block_rows))
    kill_at = n_tasks // 2
    gaps = rng.exponential(opts.block_rows / rate_rows_per_s, n_tasks)
    clock = time.monotonic
    killed_t = None
    try:
        t_start = clock()
        next_t = t_start
        for i in range(n_tasks):
            if i == kill_at:
                # the kill: the victim's next dispatch raises, its
                # scheduler thread dies with the queue intact — exactly
                # what a SIGKILL leaves behind
                def _die(params, batch):
                    raise RuntimeError("chaos: replica killed")

                victim._dispatch = _die
                killed_t = clock() - t_start
            next_t += gaps[i]
            now = clock()
            if next_t > now:
                time.sleep(next_t - now)
            t0 = clock()

            def cb(a, v, lp, t0=t0):
                lats.append(clock() - t0)

            def shed_cb(rej):
                sheds.append(rej.reason)

            target.put_block_task(state, cb, shed_callback=shed_cb)
        deadline = clock() + opts.slo_ms / 1000.0 * 4 + 10.0
        while len(lats) + len(sheds) < n_tasks and clock() < deadline:
            time.sleep(0.01)
    finally:
        teardown()
    _, _, p99 = _percentiles_ms(lats)
    dead_events = [
        ev for ev in telemetry.flight_recorder().snapshot()
        if ev.get("kind") == "replica_dead"
    ]
    router_scal = telemetry.registry(router.tele_role).scalars()
    return {
        "rate_rows_per_s": rate_rows_per_s,
        "submitted_tasks": n_tasks,
        "killed_after_s": round(killed_t, 3) if killed_t else None,
        "served_tasks": len(lats),
        "shed_tasks": len(sheds),
        "unresolved_tasks": n_tasks - len(lats) - len(sheds),
        "sheds_by_reason": {
            r: sheds.count(r) for r in sorted(set(sheds))
        },
        "served_p99_ms": p99,
        "replica_dead_flight_events": len(dead_events),
        "replica_lost_sheds": router_scal.get("replica_lost_sheds_total", 0),
        "replica_rows": _replica_sub_rows(roles),
    }


def run_canary_rep(opts, replicas: int, rate_rows_per_s: float) -> dict:
    """The canary loop e2e on the routed plane: a WINNING canary is
    auto-promoted to default (statistical reward win inside the SLO),
    then a second, OVERLOADED canary is auto-rolled-back on its SLO
    breach — both decisions land in the flight record WITH their input
    snapshots (the committed evidence)."""
    import numpy as np

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.orchestrate.serving import PromotionController

    target, roles, teardown = _make_plane(opts, replicas)
    router = target.router
    rng = np.random.default_rng(opts.seed + 2)
    state = np.zeros((opts.block_rows, 1), np.uint8)
    clock = time.monotonic

    def drive(n_tasks: int, rate: float):
        gaps = rng.exponential(opts.block_rows / rate, n_tasks)
        next_t = clock()
        for i in range(n_tasks):
            next_t += gaps[i]
            now = clock()
            if next_t > now:
                time.sleep(next_t - now)
            target.put_block_task(
                state, lambda a, v, lp: None,
                shed_callback=lambda rej: None,
            )
        deadline = clock() + opts.slo_ms / 1000.0 * 4 + 5.0
        while router.outstanding_rows() > 0 and clock() < deadline:
            time.sleep(0.01)

    out = {}
    try:
        n = max(20, int(opts.seconds * rate_rows_per_s / opts.block_rows))
        # phase 1: a healthy candidate that WINS on reward
        ctrl = PromotionController(
            router, fraction=0.3, slo_ms=opts.slo_ms,
            min_samples=16, min_decide_tasks=8, interval_s=3600.0,
        )
        ctrl.start_canary({"w": np.float32(1.0)})
        drive(n, rate_rows_per_s)
        for i in range(20):
            ctrl.observe_reward("canary", float(rng.normal(10.0, 0.5)))
            ctrl.observe_reward("default", float(rng.normal(1.0, 0.5)))
        ctrl.tick()
        out["promoted"] = ctrl.state == PromotionController.PROMOTED
        # phase 2: a candidate whose traffic BREACHES the SLO (offered at
        # many times capacity, its share sheds) — auto-rollback
        ctrl2 = PromotionController(
            router, fraction=0.3, slo_ms=opts.slo_ms,
            min_samples=10_000,  # reward evidence can never promote it
            min_decide_tasks=8, breach_shed_rate=0.02, interval_s=3600.0,
        )
        ctrl2.start_canary({"w": np.float32(2.0)})
        drive(4 * n, 8 * rate_rows_per_s)
        ctrl2.tick()
        out["rolled_back"] = ctrl2.state == PromotionController.ROLLED_BACK
    finally:
        teardown()
    flights = telemetry.flight_recorder().snapshot()
    promote_ev = [e for e in flights if e.get("kind") == "canary_promote"]
    rollback_ev = [e for e in flights if e.get("kind") == "canary_rollback"]
    out["promote_flight_event"] = promote_ev[-1] if promote_ev else None
    out["rollback_flight_event"] = rollback_ev[-1] if rollback_ev else None
    return out


def run_replicated(opts) -> tuple:
    """The ISSUE-15 instrument: single-replica frontier and R-replica
    routed frontier in ONE session (same host, same nulls — same-session
    ratios are the honest unit, PERF.md convention), the near-linear
    scaling gate, the replica-kill chaos rep, and the canary
    promote/rollback e2e. Returns (json_row, failures)."""
    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    R = opts.replicas
    single_row, single_failures = run_frontier(opts, replicas=1)
    routed_rates = [r * R for r in opts.rates]
    routed_row, routed_failures = run_frontier(
        opts, replicas=R, rates=routed_rates
    )
    failures = [f"single-replica {m}" for m in single_failures]
    failures += [f"routed x{R} {m}" for m in routed_failures]

    slo = opts.slo_ms

    def best(row):
        ok = [
            p for p in row["rate_points"]
            if p["shed_rate"] < 0.01 and p["p99_ms"] is not None
            and p["p99_ms"] <= slo
        ]
        return max(ok, key=lambda p: p["served_rows_per_s"]) if ok else None

    b1, bR = best(single_row), best(routed_row)
    required = opts.gate_frac * R
    ratio = None
    if b1 is None or bR is None:
        failures.append(
            "scaling gate FAILED: no SLO-meeting rate point on "
            f"{'the single plane' if b1 is None else 'the routed plane'}"
        )
    else:
        ratio = bR["served_rows_per_s"] / max(b1["served_rows_per_s"], 1e-9)
        if ratio < required:
            failures.append(
                f"scaling gate FAILED: x{R} routed served "
                f"{bR['served_rows_per_s']} rows/s = {ratio:.2f}x the "
                f"single plane's {b1['served_rows_per_s']} at equal p99 "
                f"(need >= {required:.2f}x)"
            )
        dead = [
            sub for p in routed_row["rate_points"]
            for sub in p.get("replica_rows", ())
            if sub["rows"] == 0
        ]
        if dead:
            failures.append(
                f"scaling gate FAILED: {len(dead)} per-replica sub-rows "
                "served ZERO rows — a dead replica is hiding in the "
                "aggregate"
            )
    stderr_print(
        f"scaling: single best {b1['served_rows_per_s'] if b1 else None} "
        f"rows/s, x{R} routed best "
        f"{bR['served_rows_per_s'] if bR else None} rows/s "
        f"(ratio {f'{ratio:.2f}' if ratio else 'n/a'}, "
        f"gate >= {required:.2f})"
    )

    chaos_rate = (
        0.5 * bR["served_rows_per_s"] if bR is not None
        else 0.5 * routed_rates[0]
    )
    chaos = run_chaos_rep(opts, R, chaos_rate)
    if chaos["unresolved_tasks"] != 0:
        failures.append(
            f"chaos gate FAILED: {chaos['unresolved_tasks']} tasks never "
            "resolved after the replica kill — a lockstep caller would "
            "have wedged"
        )
    if chaos["served_p99_ms"] is not None and chaos["served_p99_ms"] > slo:
        failures.append(
            f"chaos gate FAILED: served p99 {chaos['served_p99_ms']} ms "
            f"breached the {slo} ms SLO during the replica kill"
        )
    if chaos["replica_dead_flight_events"] == 0:
        failures.append(
            "chaos gate FAILED: the kill left no replica_dead flight "
            "event — the router never noticed"
        )

    canary = run_canary_rep(
        opts, R, chaos_rate if bR is None else 0.3 * bR["served_rows_per_s"]
    )
    if not canary["promoted"] or canary["promote_flight_event"] is None:
        failures.append(
            "canary gate FAILED: the winning candidate was not promoted "
            "(or its decision left no flight event)"
        )
    if not canary["rolled_back"] or canary["rollback_flight_event"] is None:
        failures.append(
            "canary gate FAILED: the SLO-breaching candidate was not "
            "rolled back (or its decision left no flight event)"
        )

    out = {
        "metric": "replicated_serving_rows_per_s_vs_latency",
        "unit": "rows/sec vs ms",
        "rollout_dtype": getattr(opts, "rollout_dtype", "float32"),
        "replicas": R,
        "slo_ms": slo,
        "block_rows": opts.block_rows,
        "batch_size": opts.batch_size,
        "service_us": opts.service_us,
        "queue_depth": opts.queue_depth,
        "seconds": opts.seconds,
        "seed": opts.seed,
        "device_free_proxy": True,
        "single": single_row,
        "routed": routed_row,
        "scaling_gate": {
            "criterion": (
                f"x{R} routed served rows/s >= {required:.2f}x the "
                f"same-session single plane at equal p99 inside the "
                f"{slo} ms SLO; every per-replica sub-row served > 0"
            ),
            "single_best_rows_per_s": (
                b1["served_rows_per_s"] if b1 else None
            ),
            "routed_best_rows_per_s": (
                bR["served_rows_per_s"] if bR else None
            ),
            "ratio": round(ratio, 3) if ratio is not None else None,
            "required": round(required, 3),
        },
        "chaos": chaos,
        "canary": canary,
        "gate": {"passed": not failures},
    }
    return out, failures


def _quant_artifacts(opts) -> dict:
    """The REAL int8 artifacts the dtype sweep's evidence is measured on:
    canonical BA3CNet params, a QuantSpec calibrated from real jax-Pong
    rollout frames (calibrate_from_env — the same path ``--rollout_dtype
    int8 --quant_calibrate N`` takes), per-dtype param-table bytes summed
    over the actual table leaves, and the Pong parity section holding the
    int8 forward inside the bf16 bands (tests/test_staging.py: |d log mu|
    < 0.1, |dV| < 0.05)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import make_rollout_body
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.quantize import (
        calibrate_from_env,
        make_quant_apply,
        quantize_params,
    )

    cfg = BA3CConfig(num_actions=pong.num_actions)
    model = build_model(DEFAULT_MODEL, cfg)
    key = jax.random.PRNGKey(opts.seed)
    dummy = jnp.zeros((1, *cfg.state_shape), jnp.uint8)
    params = model.init(key, dummy)["params"]
    spec = calibrate_from_env(
        model, cfg, pong, params, jax.random.fold_in(key, 1),
        n_envs=8, batches=2, rollout_len=16,
    )
    qparams = jax.device_get(
        jax.jit(lambda p: quantize_params(p, spec))(params)
    )

    def table_bytes(tree):
        return int(sum(
            np.asarray(leaf).nbytes
            for leaf in jax.tree_util.tree_leaves(tree)
        ))

    bf16 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        params,
    )
    # parity frames: a FRESH rollout window (distinct key) through the
    # actor's own scan body — real game pixels, not the calibration set
    keys = jax.random.split(jax.random.fold_in(key, 2), 8)
    env_state = jax.vmap(pong.reset)(keys)
    obs = jax.vmap(pong.render)(env_state)
    stack = jnp.zeros(
        (8, *obs.shape[1:], cfg.frame_history), jnp.uint8
    ).at[..., -1].set(obs)
    body = make_rollout_body(model, cfg, pong, params)
    carry = (
        env_state, stack, jax.random.fold_in(key, 3),
        jnp.zeros(8, jnp.float32), jnp.zeros(8, jnp.int32),
        jnp.zeros(8, jnp.float32),
    )
    _, traj = jax.jit(
        lambda c: lax.scan(body, c, None, length=16)
    )(carry)
    frames = jnp.asarray(traj[0]).reshape(-1, *cfg.state_shape)
    out32 = model.apply({"params": params}, frames)
    outq = make_quant_apply(model)(qparams, frames)
    lp32 = jax.nn.log_softmax(out32.logits, axis=-1)
    lpq = jax.nn.log_softmax(outq.logits, axis=-1)
    d_logmu = float(jnp.max(jnp.abs(lp32 - lpq)))
    d_value = float(jnp.max(jnp.abs(out32.value - outq.value)))
    return {
        "spec": spec,
        "param_table_bytes": {
            "float32": table_bytes(params),
            "bfloat16": table_bytes(jax.device_get(bf16)),
            "int8": table_bytes(qparams),
        },
        "parity": {
            "env": "jax:pong",
            "frames": int(frames.shape[0]),
            "calibration_batches": spec.calibration_batches,
            "calibration_rows": spec.calibration_rows,
            "max_abs_d_log_mu": round(d_logmu, 6),
            "max_abs_d_value": round(d_value, 6),
            # the acceptance bands are the bf16 rung's own
            # (tests/test_staging.py) — int8 must not be a WORSE serving
            # numerics rung than the one below it on the ladder
            "band_log_mu": 0.1,
            "band_value": 0.05,
            "inside_bf16_bands": d_logmu < 0.1 and d_value < 0.05,
        },
    }


def run_dtype_sweep(opts) -> tuple:
    """The rollout-precision ladder frontier (``--dtype f32,bf16,int8``):
    one single-replica frontier per dtype, service time and offered rates
    scaled by the MXU-throughput model so each sweep covers ITS OWN knee,
    plus the Pong parity section and the rows/s-per-replica gate (int8
    best >= ``--quant_gate_ratio`` x bf16 best at equal p99 inside the
    SLO). Returns (json_row, failures)."""
    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    artifacts = _quant_artifacts(opts) if "int8" in opts.dtypes else None
    failures = []
    frontiers = {}
    for dtype in opts.dtypes:
        factor = _DTYPE_SERVICE_FACTOR[dtype]
        sub = SimpleNamespace(**vars(opts))
        sub.rollout_dtype = dtype
        sub.service_us = opts.service_us * factor
        # faster service moves the knee up — scale the offered rates so
        # every dtype's sweep covers both sides of ITS knee (otherwise
        # the rate ceiling, not the device, caps the faster rungs and the
        # ratio gate reads 1.0x)
        sub.rates = [r / factor for r in opts.rates]
        if artifacts is not None:
            sub.param_table_bytes = artifacts["param_table_bytes"][dtype]
            if dtype == "int8":
                sub.quant_spec_hash = artifacts["spec"].sha256()
        stderr_print(
            f"dtype {dtype}: service_us={sub.service_us:.0f} "
            f"(factor {factor})"
        )
        row, fr = run_frontier(sub, replicas=1)
        frontiers[dtype] = row
        failures += [f"{dtype} {m}" for m in fr]

    def best(row):
        # a dtype's capacity claim is its best SERVED rows/s among points
        # whose served p99 holds the SLO — shedding is the admission
        # control protecting that latency, so an overloaded point still
        # counts (its served rate IS the sustainable capacity). Requiring
        # shed < 1% here would collapse every dtype onto the same
        # pre-knee rate on a loaded CI host and read the ratio as 1.0x
        slo = opts.slo_ms
        ok = [
            p for p in row["rate_points"]
            if p["p99_ms"] is not None and p["p99_ms"] <= slo
        ]
        return max(ok, key=lambda p: p["served_rows_per_s"]) if ok else None

    gate = None
    if "int8" in frontiers and "bfloat16" in frontiers:
        b8, bbf = best(frontiers["int8"]), best(frontiers["bfloat16"])
        required = opts.quant_gate_ratio
        ratio = None
        if b8 is None or bbf is None:
            failures.append(
                "quant gate FAILED: no SLO-meeting rate point on the "
                f"{'int8' if b8 is None else 'bf16'} frontier"
            )
        else:
            ratio = b8["served_rows_per_s"] / max(
                bbf["served_rows_per_s"], 1e-9
            )
            if ratio < required:
                failures.append(
                    f"quant gate FAILED: int8 served "
                    f"{b8['served_rows_per_s']} rows/s/replica = "
                    f"{ratio:.2f}x bf16's {bbf['served_rows_per_s']} with "
                    f"served p99 inside the {opts.slo_ms} ms SLO "
                    f"(need >= {required:.2f}x)"
                )
        gate = {
            "criterion": (
                f"int8 best served rows/s-per-replica >= "
                f"{opts.quant_gate_ratio:.2f}x bf16's, both at served "
                f"p99 inside the {opts.slo_ms} ms SLO; int8 Pong parity "
                "inside the bf16 bands"
            ),
            "int8_best_rows_per_s": (
                b8["served_rows_per_s"] if b8 else None
            ),
            "bf16_best_rows_per_s": (
                bbf["served_rows_per_s"] if bbf else None
            ),
            "ratio": round(ratio, 3) if ratio is not None else None,
            "required": opts.quant_gate_ratio,
        }
    if artifacts is not None and not artifacts["parity"]["inside_bf16_bands"]:
        failures.append(
            "quant gate FAILED: int8 Pong parity outside the bf16 bands "
            f"(d_log_mu={artifacts['parity']['max_abs_d_log_mu']}, "
            f"d_value={artifacts['parity']['max_abs_d_value']})"
        )
    out = {
        "metric": "quantized_serving_frontier_rows_per_s_vs_latency",
        "unit": "rows/sec vs ms",
        "rollout_dtype": ",".join(opts.dtypes),
        "replicas": 1,
        "slo_ms": opts.slo_ms,
        "block_rows": opts.block_rows,
        "batch_size": opts.batch_size,
        "service_us": opts.service_us,
        "service_factor_model": {
            d: _DTYPE_SERVICE_FACTOR[d] for d in opts.dtypes
        },
        "seconds": opts.seconds,
        "seed": opts.seed,
        # the frontier's service-time axis is the MXU-throughput MODEL on
        # the null device; the parity section and table bytes are real.
        # On-chip re-capture of the absolute rows/s is tracked in ROADMAP
        # item 1 — the RATIO at equal p99 is the pinned claim
        "device_free_proxy": True,
        "frontiers": frontiers,
        "gate": dict(gate or {}, passed=not failures),
    }
    if artifacts is not None:
        out["quant_spec_hash"] = artifacts["spec"].sha256()
        out["param_table_bytes"] = artifacts["param_table_bytes"]
        out["pong_parity"] = artifacts["parity"]
    return out, failures


def parse_opts(argv=None) -> SimpleNamespace:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--rates", default="1000,2000,4000,8000,16000",
        help="comma list of offered rates in ROWS/s (each request is a "
        "--block_rows block). The default tops out at ~2x the default "
        "service capacity so the sweep covers both sides of the knee",
    )
    ap.add_argument(
        "--block_rows", type=int, default=8,
        help="rows per request (the block wire's natural request unit)",
    )
    ap.add_argument(
        "--batch_size", type=int, default=32,
        help="predictor coalesce target; the bucket cap is the next pow-2 "
        "(capacity = cap rows per --service_us device call)",
    )
    ap.add_argument(
        "--service_us", type=float, default=4000.0,
        help="simulated device time per call (slept at fetch) — the "
        "frontier's service-time axis on a device-free host",
    )
    ap.add_argument("--slo_ms", type=float, default=50.0)
    ap.add_argument(
        "--queue_depth", type=int, default=64,
        help="admission-queue bound in TASKS (overload beyond it is fast "
        "queue_full rejection)",
    )
    ap.add_argument("--seconds", type=float, default=4.0, help="per rate point")
    ap.add_argument("--num_actions", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--replicas", type=int, default=1,
        help="R > 1 = the ISSUE-15 replicated instrument: single AND "
        "R-replica routed frontiers same-session (routed rates = --rates "
        "x R), the near-linear scaling gate, a replica-kill chaos rep, "
        "and the canary promote/rollback e2e",
    )
    ap.add_argument(
        "--gate_frac", type=float, default=0.8,
        help="scaling gate: routed served rows/s must be >= gate_frac * R "
        "x the same-session single plane (0.8 * 4 = the 3.2x acceptance "
        "bar)",
    )
    ap.add_argument(
        "--dtype", default="float32",
        help="comma list from {f32,bf16,int8}: one entry = stamp every "
        "row with that rollout_dtype; several = the rollout-precision "
        "ladder sweep (one frontier per dtype under the MXU-throughput "
        "service model, int8 calibrated from real jax-Pong rollouts, "
        "Pong parity section, rows/s-per-replica gate)",
    )
    ap.add_argument(
        "--quant_gate_ratio", type=float, default=1.05,
        help="dtype sweep gate: int8 best served rows/s-per-replica must "
        "be >= this x bf16's at equal p99 inside the SLO",
    )
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",") if r.strip()]
    if not rates:
        raise SystemExit("--rates must name at least one rate")
    if args.replicas < 1:
        raise SystemExit(f"--replicas must be >= 1, got {args.replicas}")
    dtypes = []
    for d in args.dtype.split(","):
        d = d.strip()
        if not d:
            continue
        if d not in _DTYPE_ALIASES:
            raise SystemExit(
                f"--dtype {d!r} is not on the ladder "
                f"(choose from {sorted(set(_DTYPE_ALIASES))})"
            )
        dtypes.append(_DTYPE_ALIASES[d])
    if not dtypes:
        raise SystemExit("--dtype must name at least one dtype")
    if args.replicas > 1 and len(dtypes) > 1:
        raise SystemExit(
            "--dtype sweeps and --replicas > 1 are separate instruments — "
            "run them as two invocations"
        )
    return SimpleNamespace(rates=rates, dtypes=dtypes, **{
        k: getattr(args, k)
        for k in ("block_rows", "batch_size", "service_us", "slo_ms",
                  "queue_depth", "seconds", "num_actions", "seed",
                  "replicas", "gate_frac", "quant_gate_ratio")
    })


def main(argv=None) -> int:
    # no accelerator in the loop: pin cpu BEFORE jax imports (a no-op where
    # JAX_PLATFORMS is already exported, e.g. on the chip machine — the
    # JSON names the platform that was actually used)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    opts = parse_opts(argv)
    if len(opts.dtypes) > 1:
        out, failures = run_dtype_sweep(opts)
    elif opts.replicas > 1:
        opts.rollout_dtype = opts.dtypes[0]
        out, failures = run_replicated(opts)
    else:
        opts.rollout_dtype = opts.dtypes[0]
        out, failures = run_frontier(opts)
    # the JSON (per-point evidence) prints BEFORE any gate verdict — the
    # evidence is most valuable exactly when the gate fails
    import jax

    # a CPU instrument by design: name the platform its rates came from
    out["platform"] = jax.default_backend()
    print(json.dumps(out))
    if failures:
        from distributed_ba3c_tpu.utils.devicelock import stderr_print

        for msg in failures:
            stderr_print(msg)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
