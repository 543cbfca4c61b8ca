#!/usr/bin/env python
"""Actor-plane throughput instrument: the plane finally gets a PINNED number.

Measures the full ZMQ experience plane — C++ batched env servers → ZMQ →
master routing → batched predictor → n-step assembly → train queue — in two
predictor modes and (by default) both wire protocols:

- **device-free** (null predictor, host-side random actions): the plane's
  OWN ceiling, no device round trip in the loop. This is the number
  that pinned the per-env wire at 2,128 env-steps/s/host (PERF.md round 4)
  and the one the block wire's ≥40k acceptance bar is defined on.
- **device-in-loop** (``--device``): the same plane serving through the real
  batched predictor on whatever device jax finds (named in the JSON's
  ``platform``) — measured so the gap between the two modes stays
  attributed, not asserted. Not re-measured on the installed stack.

Prints ONE JSON line on stdout (the repo's bench-tooling contract); per-mode
diagnostics go to stderr. Device-free runs default ``JAX_PLATFORMS`` to
``cpu`` and never take the TPU-claim mutex — a plane bench must not queue
behind a training run when no device is in its loop.

Usage:
  python scripts/plane_bench.py                        # device-free, both wires
  python scripts/plane_bench.py --wires block          # device-free, block only
  python scripts/plane_bench.py --device --tpu_lock wait   # add device-in-loop
  python scripts/plane_bench.py --telemetry both       # telemetry overhead gate
                                                       # (same-session alternating
                                                       # off/on reps; fails if the
                                                       # median on-rate drops >2%
                                                       # below the median off-rate)
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
# sibling-script import surface (serving_bench rides along under --serving)
sys.path.insert(0, str(Path(__file__).resolve().parent))


def run_trace_capture(
    game: str = "pong",
    sample: int = 32,
    n_envs: int = 64,
    unroll_len: int = 5,
    feed_batch: int = 4,
    min_traces: int = 3,
    timeout_s: float = 120.0,
):
    """One traced block-shm plane through a REAL (CPU) V-trace learner.

    C++ env server (block-shm, trace contexts stamped 1-in-``sample``) →
    master → null predictor → unroll flush → RolloutFeed → device staging
    → the actual jitted ``parallel.vtrace_step`` — the full causal chain
    the trace plane exists to attribute, run until ``min_traces``
    complete env-step→learner-step traces are buffered. Returns
    ``(capture_dict, gate_failures)``; the capture embeds the raw
    ``/trace`` document plus a per-hop summary of one complete trace.
    """
    import queue
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.telemetry import tracing
    from distributed_ba3c_tpu.actors.vtrace_master import VTraceSimulatorMaster
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.data.dataflow import RolloutFeed
    from distributed_ba3c_tpu.envs import native
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh
    from distributed_ba3c_tpu.parallel.train_step import create_train_state
    from distributed_ba3c_tpu.parallel.vtrace_step import make_vtrace_train_step

    from distributed_ba3c_tpu.predict.null import make_null_predictor
    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    telemetry.reset_all()
    telemetry.set_enabled(True)
    os.environ["BA3C_TELEMETRY"] = "1"
    tracing.set_sampling(sample)
    os.environ["BA3C_TRACE"] = str(sample)

    n_actions = native.CppBatchedEnv(game, 1).num_actions
    cfg = BA3CConfig(num_actions=n_actions, predict_batch_size=max(64, n_envs))
    model = build_model(DEFAULT_MODEL, cfg)
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, *cfg.state_shape), np.uint8)
    )["params"]
    mesh = make_mesh(num_model=1)
    step_fn = make_vtrace_train_step(
        model, make_optimizer(cfg.learning_rate, cfg.adam_epsilon,
                              cfg.grad_clip_norm), cfg, mesh,
    )
    state = jax.device_put(
        create_train_state(
            jax.random.PRNGKey(0), model, cfg,
            make_optimizer(cfg.learning_rate, cfg.adam_epsilon,
                           cfg.grad_clip_norm),
        ),
        step_fn.state_sharding,
    )

    tmp = tempfile.mkdtemp(prefix="ba3c-trace-cap-")
    c2s, s2c = f"ipc://{tmp}/c2s", f"ipc://{tmp}/s2c"
    predictor = make_null_predictor(
        model, params, n_actions, batch_size=max(64, n_envs), coalesce_ms=0.0,
    )
    master = VTraceSimulatorMaster(
        c2s, s2c, predictor, unroll_len=unroll_len,
        train_queue=queue.Queue(maxsize=256),
    )
    master.feed_batch = feed_batch
    feed = RolloutFeed(master.queue, batch_size=feed_batch)
    proc = native.CppEnvServerProcess(  # ba3clint: disable=A8 — raw plane is the measurand, like bench_zmq_plane
        0, c2s, s2c, game=game, n_envs=n_envs, wire="block-shm",
    )
    completed = 0
    steps = 0
    failures = []
    try:
        predictor.start()
        master.start()
        feed.start()
        proc.start()
        deadline = _time.monotonic() + timeout_s
        while completed < min_traces and _time.monotonic() < deadline:
            try:
                batch = feed.next_batch(timeout=10)
            except queue.Empty:
                continue
            ref = batch.pop("_trace", None)
            staged = {
                k: jax.device_put(v, step_fn.batch_sharding[k])
                for k, v in batch.items()
            }
            if ref is not None:
                ref = ref.hop("ingest", "learner")
            state, _metrics = step_fn(
                state, staged, cfg.entropy_beta, cfg.learning_rate
            )
            steps += 1
            if ref is not None:
                ref.hop("learner_step", "learner")
                completed += 1
    finally:
        proc.terminate()
        feed.stop()
        master.close()
        predictor.stop()
        predictor.join(timeout=5)
        feed.join(timeout=2)

    doc = tracing.tracer().document()
    # pick ONE complete trace (env_step AND learner_step present) and
    # summarize its named hops in causal order
    by_trace = {}
    for s in doc["spans"]:
        by_trace.setdefault(s["trace_id"], []).append(s)
    chain = None
    for spans in by_trace.values():
        names = {s["name"] for s in spans}
        if "env_step" in names and "learner_step" in names:
            chain = sorted(spans, key=lambda s: s["ts_us"])
            break
    hop_hists = {
        f"{role}/{name}": m
        for role, series in telemetry.all_snapshots().items()
        for name, m in series.items()
        if name.startswith("hop_")
    }
    capture = {
        "game": game, "n_envs": n_envs, "wire": "block-shm",
        "sample_n": sample, "learner_steps": steps,
        "completed_traces": completed,
        "one_block_chain": [
            {"name": s["name"], "role": s["role"], "dur_us": s["dur_us"]}
            for s in (chain or [])
        ],
        "hop_histograms": hop_hists,
        "document": doc,
    }
    if chain is None:
        failures.append(
            "trace capture FAILED: no complete env-step->learner-step "
            f"trace after {steps} learner steps (completed={completed})"
        )
    elif len({s["name"] for s in chain}) < 6:
        failures.append(
            "trace capture FAILED: complete trace has fewer than 6 named "
            f"hops: {[s['name'] for s in chain]}"
        )
    else:
        stderr_print(
            "trace capture: one block-shm chain = "
            + " -> ".join(
                f"{s['name']}({s['dur_us']}us)" for s in chain
            )
        )
    return capture, failures


def run_ingest_phase(
    game: str = "pong",
    n_envs: int = 64,
    unroll_len: int = 5,
    feed_batch: int = 4,
    steps_per_arm: int = 40,
    sample: int = 4,
    timeout_s: float = 240.0,
):
    """The ingest before/after: legacy materialize→collate→device_put vs
    the staged pipeline (data/staging.py), SAME SESSION, device-free.

    Both arms run the full block-shm plane (C++ env server → master →
    null predictor → unroll flush → RolloutFeed) into the REAL jitted
    CPU V-trace learner; what differs is ONLY the ingest chain:

    - ``legacy``: plain RolloutFeed (compat collate: coerce + stack +
      time-major copy = 3 obs passes/batch) + per-key ``device_put`` at
      the head of the step — the measured ingest hop is that put chain.
    - ``staged``: RolloutFeed writing into a HostStagingRing (ONE obs
      pass/batch) wrapped in DeviceIngest — the H2D for batch k+1 is
      dispatched right after step k (prefetch), so the measured ingest
      hop is just the claim of already-dispatched device arrays.

    Gates (ISSUE 14 acceptance): staged copies-per-block == exactly 1.0
    (``ingest_copies_total / ingest_blocks_total``), and the staged
    median ingest hop ≥ 20% below the legacy median. Returns
    ``(row, gate_failures)``; the row embeds both arms' per-hop
    histograms and the master's e2e series as evidence.
    """
    import queue
    import statistics as _stats
    import tempfile
    import time as _time

    import jax
    import numpy as np

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.telemetry import tracing
    from distributed_ba3c_tpu.actors.vtrace_master import VTraceSimulatorMaster
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.data.dataflow import RolloutFeed
    from distributed_ba3c_tpu.data.staging import DeviceIngest, HostStagingRing
    from distributed_ba3c_tpu.envs import native
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh
    from distributed_ba3c_tpu.parallel.train_step import create_train_state
    from distributed_ba3c_tpu.parallel.vtrace_step import make_vtrace_train_step

    from distributed_ba3c_tpu.predict.null import make_null_predictor
    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    n_actions = native.CppBatchedEnv(game, 1).num_actions
    cfg = BA3CConfig(num_actions=n_actions, predict_batch_size=max(64, n_envs))
    model = build_model(DEFAULT_MODEL, cfg)
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, *cfg.state_shape), np.uint8)
    )["params"]
    mesh = make_mesh(num_model=1)
    opt = make_optimizer(
        cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm
    )
    step_fn = make_vtrace_train_step(model, opt, cfg, mesh)

    def scalars():
        return telemetry.registry("learner").scalars()

    def arm(staged: bool) -> dict:
        telemetry.reset_all()
        telemetry.set_enabled(True)
        tracing.set_sampling(sample)
        os.environ["BA3C_TRACE"] = str(sample)
        state = jax.device_put(
            create_train_state(jax.random.PRNGKey(0), model, cfg, opt),
            step_fn.state_sharding,
        )
        tmp = tempfile.mkdtemp(prefix="ba3c-ingest-")
        c2s, s2c = f"ipc://{tmp}/c2s", f"ipc://{tmp}/s2c"
        predictor = make_null_predictor(
            model, params, n_actions,
            batch_size=max(64, n_envs), coalesce_ms=0.0,
        )
        master = VTraceSimulatorMaster(
            c2s, s2c, predictor, unroll_len=unroll_len,
            train_queue=queue.Queue(maxsize=256),
        )
        master.feed_batch = feed_batch
        ring = HostStagingRing() if staged else None
        feed = RolloutFeed(master.queue, batch_size=feed_batch, staging=ring)
        ingest = (
            DeviceIngest(feed, step_fn.batch_sharding) if staged else None
        )
        proc = native.CppEnvServerProcess(  # ba3clint: disable=A8 — raw plane is the measurand, like run_trace_capture
            0, c2s, s2c, game=game, n_envs=n_envs, wire="block-shm",
        )
        ingest_s = []
        steps = 0
        try:
            predictor.start()
            master.start()
            feed.start()
            proc.start()
            deadline = _time.monotonic() + timeout_s
            while steps < steps_per_arm and _time.monotonic() < deadline:
                if staged:
                    # wait for work WITHOUT timing the actor plane: the
                    # measurand is the step-path ingest hop, not feed wait
                    while (
                        not ingest.prefetch()
                        and _time.monotonic() < deadline
                    ):
                        _time.sleep(0.002)
                    t0 = _time.perf_counter()
                    try:
                        batch = ingest.next_batch(timeout=10)
                    except queue.Empty:
                        continue  # starved: the steps gate reports it
                    ingest_s.append(_time.perf_counter() - t0)
                    ref = batch.pop("_trace", None)
                else:
                    try:
                        batch = feed.next_batch(timeout=10)
                    except queue.Empty:
                        continue
                    ref = batch.pop("_trace", None)
                    t0 = _time.perf_counter()
                    batch = {
                        k: jax.device_put(v, step_fn.batch_sharding[k])
                        for k, v in batch.items()
                    }
                    ingest_s.append(_time.perf_counter() - t0)
                    if ref is not None:
                        ref = ref.hop("ingest", "learner")
                state, _m = step_fn(
                    state, batch, cfg.entropy_beta, cfg.learning_rate
                )
                steps += 1
                if ref is not None:
                    ref.hop("learner_step", "learner")
        finally:
            proc.terminate()
            if ingest is not None:
                ingest.stop()
            else:
                feed.stop()
            master.close()
            predictor.stop()
            predictor.join(timeout=5)
            feed.join(timeout=2)
        learner = scalars()
        hop_hists = {
            f"{role}/{name}": m
            for role, series in telemetry.all_snapshots().items()
            for name, m in series.items()
            if name.startswith(("hop_", "e2e_ingest", "staging_wait"))
        }
        copies = learner.get("ingest_copies_total", 0.0)
        blocks = learner.get("ingest_blocks_total", 0.0)
        row = {
            "staged": staged,
            "learner_steps": steps,
            "median_ingest_s": (
                _stats.median(ingest_s) if ingest_s else None
            ),
            "p90_ingest_s": (
                sorted(ingest_s)[int(0.9 * (len(ingest_s) - 1))]
                if ingest_s else None
            ),
            "ingest_copies_total": copies,
            "ingest_blocks_total": blocks,
            "copies_per_block": (
                round(copies / blocks, 4) if blocks else None
            ),
            "prefetched": learner.get("ingest_prefetched_total", 0.0),
            "dispatch_now": learner.get("ingest_dispatch_now_total", 0.0),
            "staging_waits": learner.get("staging_waits_total", 0.0),
            "hop_histograms": hop_hists,
        }
        stderr_print(
            f"ingest arm {'staged' if staged else 'legacy'}: "
            f"{steps} steps, median ingest "
            f"{(row['median_ingest_s'] or 0) * 1e6:.0f} us, "
            f"copies/block {row['copies_per_block']}"
        )
        return row

    failures = []
    legacy = arm(staged=False)
    staged = arm(staged=True)
    telemetry.reset_all()
    row = {
        "game": game, "n_envs": n_envs, "unroll_len": unroll_len,
        "feed_batch": feed_batch, "wire": "block-shm",
        "trace_sample": sample,
        # this container has no reachable accelerator: the H2D here is
        # the CPU PJRT transfer (de-aliased, data/staging.py) — the
        # on-chip re-capture stays on ROADMAP item 1's list
        "device_free_proxy": True,
        "legacy": legacy,
        "staged": staged,
    }
    if staged["learner_steps"] < steps_per_arm // 2 or legacy[
        "learner_steps"
    ] < steps_per_arm // 2:
        failures.append(
            "ingest phase FAILED: an arm starved before half its steps "
            f"(legacy {legacy['learner_steps']}, staged "
            f"{staged['learner_steps']} of {steps_per_arm})"
        )
        return row, failures
    if staged["copies_per_block"] != 1.0:
        failures.append(
            "ingest copy gate FAILED: staged copies-per-block = "
            f"{staged['copies_per_block']} (must be exactly 1.0 — "
            "shm bytes -> staging write, nothing else)"
        )
    if legacy["copies_per_block"] is None or legacy["copies_per_block"] <= 1.0:
        failures.append(
            "ingest foil broken: legacy copies-per-block = "
            f"{legacy['copies_per_block']} (expected > 1 — the before "
            "arm no longer measures the chain the staging replaced)"
        )
    ratio = (
        staged["median_ingest_s"] / legacy["median_ingest_s"]
        if legacy["median_ingest_s"] else None
    )
    row["staged_over_legacy_ingest"] = (
        round(ratio, 4) if ratio is not None else None
    )
    if ratio is None or ratio > 0.8:
        failures.append(
            "ingest latency gate FAILED: staged median ingest is "
            f"{ratio if ratio is None else round(ratio, 3)}x the legacy "
            "median (gate: <= 0.8x, i.e. >= 20% improvement same-session)"
        )
    return row, failures


def bench_zmq_plane(
    game: str = "pong", n_envs: int = 256, seconds: float = 20.0,
    null_device: bool = False, wire: str = "per-env",
    envs_per_proc: int = 32, warmup_datapoints: int = 512,
    windows: int = 1, telemetry_on: bool = True, fleets: int = 1,
    trace_sample: int = 0,
) -> dict:
    """Actor-plane throughput (BASELINE configs #1/#2): C++ batched env
    servers -> ZMQ -> master -> batched TPU predictor, counting n-step
    datapoints entering the train queue.

    ``null_device=True`` (every mode but ``--device``) swaps the device
    forward for host-side random actions while keeping EVERY other stage —
    C++ envs, serialization, ZMQ transport, master routing,
    batching/coalesce, n-step assembly. That measures the plane's own ceiling with no device
    in the loop: the number that separates "the plane is slow" from "the
    device round trip is slow".

    ``wire`` selects the env-server protocol: ``per-env`` (the reference's
    B-messages-per-step shape, the historical 2,128/s ceiling) or ``block``
    (one zero-copy multipart message per server per step,
    docs/actor_plane.md).

    ``fleets`` > 1 stands up K INDEPENDENT planes at the SAME per-fleet
    shape — per-fleet pipes/masters/predictors/telemetry roles, fleet-
    tagged idents (actors/fleet.py addressing) — and counts the AGGREGATE
    datapoint rate across their train queues: the device-free proof of the
    multi-fleet macro-batching scaling claim (``plane_bench --fleets``;
    ``n_envs``/``envs_per_proc`` stay per-fleet quantities)."""
    import queue
    import tempfile
    import time

    import jax
    import numpy as np

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.actors.fleet import fleet_pipes
    from distributed_ba3c_tpu.actors.master import BA3CSimulatorMaster
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.envs import native
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.predict.null import make_null_predictor
    from distributed_ba3c_tpu.predict.server import BatchedPredictor
    from distributed_ba3c_tpu.telemetry.attribution import (
        master_progress,
        stall_attribution,
        tele_snapshot,
    )

    # per-run telemetry accounting: fresh registries, and the A/B switch
    # for the overhead gate (--telemetry both). Children inherit the env
    # var through spawn.
    telemetry.reset_all()
    telemetry.set_enabled(telemetry_on)
    os.environ["BA3C_TELEMETRY"] = "1" if telemetry_on else "0"
    # the trace plane's A/B lever rides the same pattern (plane_bench
    # --trace both): sampling armed here for the master/predictor side,
    # via the env var for the spawned env servers
    trace_n = trace_sample if telemetry_on else 0
    telemetry.tracing.set_sampling(trace_n)
    os.environ["BA3C_TRACE"] = str(trace_n)

    n_actions = native.CppBatchedEnv(game, 1).num_actions
    cfg = BA3CConfig(num_actions=n_actions, predict_batch_size=256)
    model = build_model(DEFAULT_MODEL, cfg)
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, *cfg.state_shape), np.uint8)
    )["params"]
    # Coalescing exists to multiply TINY per-env tasks per device call; a
    # block already IS a full batch, so block wires serve greedily (waiting
    # would only add latency to the lockstep round trip).
    coalesce_ms = 5.0 if wire == "per-env" else 0.0
    predict_bs = max(cfg.predict_batch_size, envs_per_proc)
    tmp = tempfile.mkdtemp(prefix="ba3c-bench-")
    base_c2s, base_s2c = f"ipc://{tmp}/c2s", f"ipc://{tmp}/s2c"
    per = envs_per_proc
    predictors, masters, procs = [], [], []
    for k in range(max(1, fleets)):
        tag = k if fleets > 1 else None
        c2s, s2c = fleet_pipes(base_c2s, base_s2c, k)
        if null_device:
            predictor = make_null_predictor(
                model, params, n_actions,
                batch_size=predict_bs, num_threads=2,
                coalesce_ms=coalesce_ms,
                tele_role=telemetry.fleet_role("predictor", tag),
            )
        else:
            predictor = BatchedPredictor(  # ba3clint: disable=A14 — the RAW single plane is the measurand here (the routed plane has its own instrument, serving_bench --replicas)
                model, params, batch_size=predict_bs, num_threads=2,
                coalesce_ms=coalesce_ms,
                tele_role=telemetry.fleet_role("predictor", tag),
            )
            predictor.warmup(cfg.state_shape)
        master = BA3CSimulatorMaster(
            c2s, s2c, predictor,
            gamma=cfg.gamma, local_time_max=cfg.local_time_max,
            score_queue=queue.Queue(maxsize=100_000),
            tele_role=telemetry.fleet_role("master", tag),
        )
        predictors.append(predictor)
        masters.append(master)
        procs += [
            # the RAW unsupervised plane is the measurand here (no respawn
            # machinery in the loop); the supervised path has its own
            # instrument, scripts/chaos_bench.py
            native.CppEnvServerProcess(  # ba3clint: disable=A8
                i, c2s, s2c, game=game, n_envs=min(per, n_envs - i * per),
                wire=wire,
                ident_prefix=(
                    f"f{k}-cppsim-{i}" if fleets > 1 else None
                ),
            )
            for i in range((n_envs + per - 1) // per)
        ]
    for predictor in predictors:
        predictor.start()
    for master in masters:
        master.start()
    for p in procs:
        p.start()
    try:
        # warmup until the pipeline flows, then count datapoints over
        # best-of-N windows (the sandbox scheduler intermittently starves
        # a window — a slow window is scheduler noise, not plane rate).
        # First-datapoint
        # timeout is generous: spawning the server fleet re-imports
        # numpy/zmq per process and takes minutes under load
        # (tests/test_native_env.py saw the same)
        try:
            # EVERY fleet must produce before the clock starts (an
            # aggregate-only warmup would let a dead fleet hide behind a
            # healthy one and publish a fake per-fleet scaling number)
            for master in masters:
                master.queue.get(timeout=300)
            for _ in range(warmup_datapoints - len(masters)):
                masters[_ % len(masters)].queue.get(timeout=60)
        except queue.Empty:
            # a bare Empty says "timeout"; the counters say WHICH stage
            # never moved (fleet spawn, predictor serve, flush) — the
            # difference between a mystery and a diagnosis when a fleet
            # shape fails to come up (docs/observability.md)
            raise RuntimeError(
                f"plane produced no warmup data — {stall_attribution()}"
            ) from None
        window_rates = []
        qs = [m.queue for m in masters]
        for _ in range(max(1, windows)):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            n = 0
            empty_since = None
            # drain in BURSTS (get_nowait + short sleeps) rather than
            # blocking get() per item: a consumer parked in the queue's
            # condition variable makes every producer put() pay a futex
            # wake — tens of us of syscall on sandboxed kernels, which at
            # 40k datapoints/s would dominate the measurement. A real
            # learner feed drains in batch-sized gulps for the same reason.
            while True:
                now = time.perf_counter()
                if now >= deadline:
                    break
                drained = 0
                for q in qs:
                    # round-robin burst drain across fleets, same fairness
                    # shape as the FleetMergeFeed collator
                    try:
                        while True:
                            q.get_nowait()
                            drained += 1
                    except queue.Empty:
                        pass
                if drained:
                    n += drained
                    empty_since = None
                else:
                    if empty_since is None:
                        empty_since = now
                        stall_mark = master_progress()[1]
                    elif now - empty_since > min(5.0, seconds / 2):
                        # the quiet threshold only OPENS the investigation
                        # (it must be reachable inside one window, else the
                        # deadline expires first and a wedged wire silently
                        # publishes a near-zero rate); the VERDICT comes
                        # from the real counters — a master that provably
                        # emitted DATAPOINTS during the quiet spell is
                        # draining elsewhere, not stalled. Datapoints ONLY:
                        # wire messages still ticking while the flush path
                        # is dead is the "flush path stalled" wedge itself
                        # and must keep counting toward the raise
                        if master_progress()[1] != stall_mark:
                            empty_since = None
                            continue
                        raise RuntimeError(
                            "plane stalled: "
                            f"{min(5.0, seconds / 2):.1f}s without data "
                            f"post-warmup — {stall_attribution()}"
                        )
                    time.sleep(0.002)
            window_rates.append(n / (time.perf_counter() - t0))
    finally:
        for p in procs:
            p.terminate()
        for master in masters:
            master.close()
        for predictor in predictors:
            predictor.stop()
        for predictor in predictors:
            predictor.join(timeout=5)
        for p in procs:
            p.join(timeout=5)
    rate = max(window_rates)
    kind = "nodevice" if null_device else "tpu"
    return {
        "telemetry_enabled": telemetry_on,
        "telemetry": tele_snapshot(),
        # the null-predictor ceiling must be UNMISTAKABLE from a real plane
        # measurement: distinct metric name + an explicit predictor field
        "metric": f"zmq_plane_{kind}_{game}_env_steps_per_sec_per_host",
        "value": round(rate, 1),
        "unit": "env-steps/sec/host",
        "predictor": "null-host-random" if null_device else "batched-tpu",
        "wire": wire,
        "fleets": max(1, fleets),
        # per-fleet shape (the unit the --fleets scaling gate compares at)
        "n_envs": n_envs,
        "envs_per_proc": per,
        "seconds": seconds,
        "window_rates": [round(r, 1) for r in window_rates],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--game", default="pong")
    ap.add_argument("--n_envs", type=int, default=512)
    ap.add_argument(
        "--envs_per_proc", type=int, default=512,
        help="block size B: envs per server process (= envs per wire "
        "message). Fewer, bigger blocks win on few-core hosts: the "
        "committed capture's 1x512 beat 2x256 by ~40%% (scheduler "
        "contention; see docs/actor_plane.md)",
    )
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument(
        "--wires", default="block-shm,block,per-env",
        help="comma list of wire modes to measure "
        "(block-shm | block | per-env)",
    )
    ap.add_argument(
        "--windows", type=int, default=3,
        help="timed windows per mode; best window wins (scheduler-noise "
        "filter, same policy as bench_fused)",
    )
    ap.add_argument(
        "--device", action="store_true",
        help="ALSO measure device-in-loop (real predictor on whatever "
        "device jax finds; takes the TPU-claim mutex)",
    )
    ap.add_argument("--tpu_lock", default="wait", choices=["wait", "fail", "off"])
    ap.add_argument(
        "--foil_shape", default="256/32",
        help="per-env foil fleet shape as N_ENVS/ENVS_PER_PROC. The "
        "historical 256/32 (the shape PERF.md's 2,128 baseline was pinned "
        "at) no longer comes up on this container (PERF.md round 7) — "
        "pass a feasible shape (e.g. 64/16) to re-measure the foil; the "
        "shape is recorded in the JSON row either way",
    )
    ap.add_argument(
        "--fleets", type=int, default=1,
        help="ALSO measure N independent fleets at the SAME per-fleet "
        "shape (per-fleet pipes/masters/predictors/telemetry roles, "
        "fleet-tagged idents) and gate the aggregate device-free rate at "
        ">= --fleet_gate x the single-fleet rate — the multi-fleet "
        "macro-batching scaling proof (docs/actor_plane.md)",
    )
    ap.add_argument(
        "--fleet_gate", type=float, default=1.6,
        help="minimum aggregate/single-fleet ratio for the --fleets gate",
    )
    ap.add_argument(
        "--serving", action="store_true",
        help="ALSO run the SLO-serving latency-vs-throughput frontier "
        "(scripts/serving_bench.py default sweep) and embed it under "
        "'serving' in the JSON; its SLO gate failures fail this run",
    )
    ap.add_argument(
        "--telemetry", default="on", choices=["on", "off", "both"],
        help="telemetry plane A/B: on = production default (instrumented "
        "masters/servers, fleet piggyback), off = BA3C_TELEMETRY=0 "
        "everywhere (pre-telemetry wire format), both = alternate off/on "
        "runs per wire in one session and FAIL unless the MEDIAN "
        "telemetry-on rate stays within 2%% of the median off rate (the "
        "overhead gate — runs/plane_bench_r7.json, PERF.md)",
    )
    ap.add_argument(
        "--pair_reps", type=int, default=3,
        help="(--telemetry both) off/on run pairs per wire, order "
        "alternating between reps; the gate compares medians — one pair "
        "is a coin flip against this container's run-to-run scheduler "
        "variance (PERF.md round 7)",
    )
    ap.add_argument(
        "--trace", default="off", choices=["on", "off", "both"],
        help="distributed trace plane A/B (telemetry/tracing.py): on = "
        "run with 1-in---trace_sample block sampling armed, off = "
        "tracing disarmed (the default), both = alternate off/on reps "
        "per wire in one session and FAIL unless the MEDIAN traced rate "
        "stays within 2%% of the median untraced rate (same methodology "
        "as --telemetry both; telemetry stays ON in both arms so the "
        "gate measures tracing's own marginal cost). on/both also run a "
        "block-shm capture through a REAL CPU V-trace learner and embed "
        "one complete env-step->learner-step trace under 'trace' in the "
        "JSON (runs/trace_bench_r13.json)",
    )
    ap.add_argument(
        "--trace_sample", type=int, default=64,
        help="1-in-N block sampling rate for the --trace arms",
    )
    ap.add_argument(
        "--ingest", action="store_true",
        help="ALSO run the staged-ingest before/after (data/staging.py): "
        "legacy materialize->collate->device_put vs the pinned staging "
        "ring + async H2D pipeline, same session through a REAL CPU "
        "V-trace learner. Gates: staged host copies-per-block == 1 "
        "exactly (ingest_copies_total) and staged median ingest hop "
        ">= 20%% below legacy (docs/ingest.md)",
    )
    ap.add_argument(
        "--ingest_steps", type=int, default=40,
        help="learner steps per --ingest arm",
    )
    args = ap.parse_args()

    wires = [w.strip() for w in args.wires.split(",") if w.strip()]
    for w in wires:
        if w not in ("block-shm", "block", "per-env"):
            raise SystemExit(f"unknown wire mode {w!r}")
    try:
        foil_envs, foil_per = (
            int(x) for x in args.foil_shape.replace("x", "/").split("/")
        )
        if foil_envs <= 0 or foil_per <= 0:
            raise ValueError
    except ValueError:
        raise SystemExit(
            f"--foil_shape {args.foil_shape!r} must be N_ENVS/ENVS_PER_PROC "
            "with both positive (e.g. 256/32)"
        )

    if not args.device:
        # device-free: no accelerator in the loop, so no TPU claim — pin
        # the platform BEFORE jax imports (bench_zmq_plane builds params;
        # on cpu that is milliseconds)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    else:
        from distributed_ba3c_tpu.utils.devicelock import guard_tpu

        _lock = guard_tpu(  # noqa: F841 — held for process lifetime
            "plane_bench",
            mode=args.tpu_lock,
            timeout_s=float(os.environ.get("BA3C_TPU_LOCK_TIMEOUT", "1800")),
        )

    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    runs = {}
    overhead = {}
    trace_overhead = {}
    fleet_scaling = {}
    gate_failures = []
    for wire in wires:
        if wire == "per-env":
            # the compat foil is measured at ITS OWN fleet shape —
            # historically 256/32 (the shape PERF.md's 2,128 baseline was
            # pinned at), --foil_shape when that doesn't come up on the
            # host (PERF.md round 7); hundreds of DEALER sockets per
            # process is not a shape the per-env wire ever ran at
            n_envs, per = min(foil_envs, args.n_envs), foil_per
        else:
            n_envs, per = args.n_envs, args.envs_per_proc
        if args.telemetry == "both":
            # SAME-SESSION, ALTERNATING off/on reps: this container's
            # run-to-run variance is enormous (observed back-to-back
            # block-shm pairs at 0.90x AND 1.68x with zero code change —
            # the 1-core scheduler, not the plane), so one pair is a coin
            # flip against a 2% budget. Alternation + median-of-reps is
            # the honest comparison: slow host drift hits both arms
            # equally, and the median drops the starved-run outliers the
            # same way best-of-windows drops starved windows.
            off_vals, on_vals = [], []
            for rep in range(max(1, args.pair_reps)):
                for tele_on in (False, True) if rep % 2 == 0 else (True, False):
                    r = bench_zmq_plane(
                        game=args.game, n_envs=n_envs, seconds=args.seconds,
                        null_device=True, wire=wire, envs_per_proc=per,
                        windows=args.windows, telemetry_on=tele_on,
                    )
                    tag = "on" if tele_on else "off"
                    if wire == "per-env":
                        r["foil_shape"] = f"{n_envs}/{per}"
                    (on_vals if tele_on else off_vals).append(r["value"])
                    runs[f"nodevice_{wire}_telemetry_{tag}_rep{rep}"] = r
                    if tele_on:
                        runs[f"nodevice_{wire}"] = max(
                            runs.get(f"nodevice_{wire}", r), r,
                            key=lambda x: x["value"],
                        )
                    stderr_print(
                        f"device-free {wire:8s} (tele {tag:3s}, rep {rep}): "
                        f"{r['value']:>10.1f} env-steps/s/host"
                    )
            med_off = statistics.median(off_vals)
            med_on = statistics.median(on_vals)
            ratio = med_on / max(med_off, 1e-9)
            overhead[wire] = {
                "median_off": med_off, "median_on": med_on,
                "on_over_off": round(ratio, 4),
                "off_reps": off_vals, "on_reps": on_vals,
            }
            stderr_print(
                f"telemetry overhead {wire}: median on/off = "
                f"{med_on:.1f}/{med_off:.1f} = {ratio:.4f}"
            )
            if ratio < 0.98:
                # verdict is deferred to AFTER the JSON prints: the
                # per-rep evidence is most valuable exactly when the
                # gate fails
                gate_failures.append(
                    f"telemetry overhead gate FAILED on {wire}: median "
                    f"on-rate {med_on:.1f} is {100 * (1 - ratio):.1f}% "
                    f"below the median off-rate {med_off:.1f} (budget: 2%)"
                )
        else:
            r = bench_zmq_plane(
                game=args.game, n_envs=n_envs, seconds=args.seconds,
                null_device=True, wire=wire, envs_per_proc=per,
                windows=args.windows, telemetry_on=args.telemetry != "off",
                trace_sample=(
                    args.trace_sample if args.trace == "on" else 0
                ),
            )
            if wire == "per-env":
                # the foil's fleet shape is part of the number — rows are
                # not comparable across shapes (PERF.md rounds 4/7)
                r["foil_shape"] = f"{n_envs}/{per}"
            runs[f"nodevice_{wire}"] = r
            stderr_print(
                f"device-free {wire:8s}: {r['value']:>10.1f} env-steps/s/host"
            )
        if args.trace == "both":
            # tracing overhead gate: SAME alternating-medians methodology
            # as the telemetry gate above (and the same honest reason —
            # this container's scheduler variance dwarfs a 2% budget on
            # any single pair). Telemetry stays ON in both arms: the gate
            # measures the TRACE plane's marginal cost over the already-
            # gated telemetry baseline, not the sum of both planes.
            off_vals, on_vals = [], []
            for rep in range(max(1, args.pair_reps)):
                for tr_on in (False, True) if rep % 2 == 0 else (True, False):
                    r = bench_zmq_plane(
                        game=args.game, n_envs=n_envs, seconds=args.seconds,
                        null_device=True, wire=wire, envs_per_proc=per,
                        windows=args.windows, telemetry_on=True,
                        trace_sample=args.trace_sample if tr_on else 0,
                    )
                    tag = "on" if tr_on else "off"
                    (on_vals if tr_on else off_vals).append(r["value"])
                    runs[f"nodevice_{wire}_trace_{tag}_rep{rep}"] = r
                    stderr_print(
                        f"device-free {wire:8s} (trace {tag:3s}, rep {rep}): "
                        f"{r['value']:>10.1f} env-steps/s/host"
                    )
            med_off = statistics.median(off_vals)
            med_on = statistics.median(on_vals)
            ratio = med_on / max(med_off, 1e-9)
            trace_overhead[wire] = {
                "sample_n": args.trace_sample,
                "median_off": med_off, "median_on": med_on,
                "on_over_off": round(ratio, 4),
                "off_reps": off_vals, "on_reps": on_vals,
            }
            stderr_print(
                f"trace overhead {wire}: median on/off = "
                f"{med_on:.1f}/{med_off:.1f} = {ratio:.4f}"
            )
            if ratio < 0.98:
                gate_failures.append(
                    f"trace overhead gate FAILED on {wire}: median "
                    f"traced rate {med_on:.1f} is {100 * (1 - ratio):.1f}% "
                    f"below the median untraced rate {med_off:.1f} "
                    "(budget: 2%)"
                )
        if args.fleets > 1:
            # the multi-fleet arm at the SAME per-fleet shape, same
            # session (this container's run-to-run scheduler drift makes
            # cross-session ratios dishonest — PERF.md round 7); the
            # single-fleet arm is the nodevice_{wire} row just measured
            rf = bench_zmq_plane(
                game=args.game, n_envs=n_envs, seconds=args.seconds,
                null_device=True, wire=wire, envs_per_proc=per,
                windows=args.windows,
                telemetry_on=args.telemetry != "off",
                fleets=args.fleets,
            )
            runs[f"nodevice_{wire}_fleets{args.fleets}"] = rf
            single = runs[f"nodevice_{wire}"]["value"]
            ratio = rf["value"] / max(single, 1e-9)
            fleet_scaling[wire] = {
                "fleets": args.fleets,
                "single_fleet": single,
                "aggregate": rf["value"],
                "aggregate_over_single": round(ratio, 4),
                "gate": args.fleet_gate,
            }
            stderr_print(
                f"device-free {wire:8s} x{args.fleets} fleets: "
                f"{rf['value']:>10.1f} aggregate = {ratio:.2f}x single"
            )
            if ratio < args.fleet_gate:
                # verdict deferred to AFTER the JSON prints (evidence
                # first), per the plane_bench convention
                gate_failures.append(
                    f"fleet scaling gate FAILED on {wire}: "
                    f"{args.fleets}-fleet aggregate {rf['value']:.1f} is "
                    f"{ratio:.2f}x the single-fleet {single:.1f} "
                    f"(gate: >= {args.fleet_gate}x at equal per-fleet "
                    "shape)"
                )
        if args.device:
            r = bench_zmq_plane(
                game=args.game, n_envs=n_envs, seconds=args.seconds,
                null_device=False, wire=wire,
                envs_per_proc=per, windows=args.windows,
                telemetry_on=args.telemetry != "off",
            )
            runs[f"device_{wire}"] = r
            stderr_print(
                f"device     {wire:8s}: {r['value']:>10.1f} env-steps/s/host"
            )

    headline = (runs.get("nodevice_block-shm")
        or runs.get("nodevice_block") or next(iter(runs.values())))
    out = {
        "metric": "zmq_plane_env_steps_per_sec_per_host",
        # the headline is the best same-host block wire's device-free
        # rate: the plane's own ceiling here (the ISSUE-4 acceptance
        # number)
        "value": headline["value"],
        "unit": "env-steps/sec/host",
        "game": args.game,
        "n_envs": args.n_envs,
        "envs_per_proc": args.envs_per_proc,
        "seconds": args.seconds,
        "telemetry": args.telemetry,
        # the plane instrument drives f32 masters end to end — stamped so
        # every bench row names its rung of the rollout-precision ladder
        # (serving_bench --dtype covers the quantized rungs)
        "rollout_dtype": "float32",
        "runs": runs,
    }
    if overhead:
        # the overhead gate's evidence: per-rep off/on rates + median
        # ratio per wire, all measured alternating in THIS session
        # (PERF.md round 7 cites it)
        out["telemetry_overhead_on_over_off"] = overhead
    if trace_overhead:
        out["trace_overhead_on_over_off"] = trace_overhead
    if args.trace in ("on", "both"):
        # one REAL traced block-shm run through a CPU V-trace learner:
        # the committed evidence that a sampled block's causal chain is
        # complete env-step -> learner-step (runs/trace_bench_r13.json)
        capture, cap_failures = run_trace_capture(
            game=args.game, sample=args.trace_sample,
        )
        out["trace"] = capture.pop("document")
        out["trace_capture"] = capture
        gate_failures.extend(cap_failures)
    if args.ingest:
        # the staged-ingest before/after: copies-per-block + the ingest
        # hop collapse, measured same-session (ISSUE-14 acceptance;
        # committed as runs/plane_bench_r15.json)
        ingest_row, ingest_failures = run_ingest_phase(
            game=args.game,
            # one env server drives the rig: its block B is the smaller
            # of the fleet flags (the same flags every other phase obeys)
            n_envs=min(args.n_envs, args.envs_per_proc),
            steps_per_arm=args.ingest_steps,
        )
        out["ingest"] = ingest_row
        gate_failures.extend(ingest_failures)
    if fleet_scaling:
        # the multi-fleet scaling gate's evidence: single vs aggregate at
        # equal per-fleet shape, same session (ISSUE-10 acceptance)
        out["fleet_scaling"] = fleet_scaling
    if args.serving:
        # the SLO-serving frontier rides along (scripts/serving_bench.py
        # owns the sweep + gate; its default shape is device-free)
        import serving_bench

        serving_row, serving_failures = serving_bench.run_frontier(
            serving_bench.parse_opts([])
        )
        out["serving"] = serving_row
        gate_failures.extend(serving_failures)
    import jax

    # device-free unless --device: name the platform its rates came from
    out["platform"] = jax.default_backend()
    print(json.dumps(out))
    if gate_failures:
        for msg in gate_failures:
            stderr_print(msg)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
