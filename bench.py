"""Benchmark: Atari env-steps/sec/chip (BASELINE.json metric).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

What is measured: the fused on-device actor+learner loop (envs, rendering,
policy forward, sampling, n-step returns, loss, grads, Adam — one jitted
program, distributed_ba3c_tpu/fused/) on pure-JAX Pong, counting AGENT steps
(each = 4 physics substeps, ALE frameskip parity). This is the path that
replaces the reference's 64-node CPU cluster: its whole pipeline (ALE procs →
ZMQ → predictor → FIFOQueue → PS updates, SURVEY.md §3) collapses into this
one computation.

Baseline denominator: BASELINE.json's north-star is "matching the original
64-node CPU cluster's env-steps/sec on one host". The reference published no
verifiable throughput number (mount empty; BASELINE.json `published` == {});
BASELINE.md records the recalled-UNVERIFIED figure of ~80k agent-steps/sec
across the 64-node cluster for the 21-minute runs. vs_baseline uses that
80_000 until a verified figure exists. (The secondary metric — wall-clock to
Pong >= 18 — is tracked separately in full training runs' stat.json, not in
this number.)

``python bench.py`` measures a chip and exits nonzero on any other platform
(a CPU run under a device metric's name is the one thing it must never
print); the JSON names the device. The device-free plane instrument is
``scripts/plane_bench.py``. The benchmark itself — cells, medians, a ledger —
is ROADMAP S0; this file is the pre-S0 single-metric line.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax

BASELINE_ENV_STEPS_PER_SEC = 80_000.0  # recalled 64-node cluster rate, UNVERIFIED

# bf16 peak FLOP/s by device kind — the MFU denominator. Only kinds this
# project has actually run on; unknown kinds report mfu=null rather than a
# made-up denominator.
_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e datasheet bf16
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
}


def _mfu(per_chip_rate: float, entries: tuple = ("fused.step",)) -> dict:
    """Model FLOPs utilization of the measured program(s) at the given rate.

    Numerator: the audit manifest's PINNED per-sample FLOPs for the given
    entry point(s) (tools/ba3caudit T5 — canonical shape 4 envs x 4 rollout
    = 16 samples/step; conv/matmul cost scales linearly in samples, and the
    per-update fixed terms (Adam, bookkeeping) are <0.01 us/sample at real
    shapes, PERF.md round 3). Keeping the numerator manifest-pinned means
    MFU moves only when the measured RATE moves — a program change that
    alters FLOPs shows up as a T5 audit finding first.

    Overlap mode passes BOTH registered programs — ``("fused.actor",
    "fused.learner")`` — and their FLOPs are SUMMED: a single-manifest
    lookup would undercount the actor program's rollout forwards, inflating
    the reported MFU exactly when the split is being judged.
    """
    from distributed_ba3c_tpu.audit import CANONICAL_MESH_DEVICES

    # a missing manifest or entry is an error, not "mfu: null": the number
    # this returns is only as good as its numerator
    with open(
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "audit_manifest.json")
    ) as fh:
        manifest = json.load(fh)
    flops = sum(float(manifest[e]["flops"]) for e in entries)

    canonical_samples = (2 * CANONICAL_MESH_DEVICES) * 4  # n_envs x rollout
    per_sample = flops / canonical_samples
    kind = jax.devices()[0].device_kind
    peak = _PEAK_FLOPS.get(kind)
    out = {
        "flops_per_sample": round(per_sample, 1),
        "device_kind": kind,
    }
    if peak is None:
        out["mfu"] = None  # unknown silicon: no honest denominator
    else:
        out["mfu"] = round(per_chip_rate * per_sample / peak, 4)
    return out


def bench_fused(
    n_envs: int = 128,
    rollout_len: int = 20,
    iters: int = 200,
    steps_per_dispatch: int | None = None,
) -> dict:
    """Measures the FLAGSHIP TRAINING SHAPE (128 envs x 20 rollout — the
    batch the round-3 sample-efficiency ladder settled on; RESULTS.md).

    By default each window is ONE scanned program of `iters` updates
    (--steps_per_dispatch mechanics), so the measured rate is device
    throughput with no dependence on host dispatch pipelining
    (scan-vs-sequential parity is tested). Passing steps_per_dispatch=K <
    iters instead runs iters/K pipelined host dispatches of a K-step program
    per window (the K-sweep, scripts/ksweep_bench.py) — at K=1 that is
    deliberately the pipelined methodology, host dispatch and all.
    Best-of-3 windows is a stall filter inherited from an earlier link to
    the chip, not a statistic; ROADMAP S0 replaces it with a median and
    quartiles. The shape grid lives in scripts/profile_fused.py."""
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import create_fused_state, make_fused_step
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh

    n_chips = len(jax.devices())
    cfg = BA3CConfig(num_actions=pong.num_actions)
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh()
    # default: ONE dispatch per window (iters updates in a single scanned
    # program). steps_per_dispatch=K overrides for the K-sweep
    # (scripts/ksweep_bench.py): iters/K dispatches per window, same sync
    # and best-of-N policy either way.
    K = iters if steps_per_dispatch is None else steps_per_dispatch
    if K < 1 or iters % K != 0:
        raise ValueError(
            f"steps_per_dispatch={K} must be >= 1 and divide iters={iters}"
        )
    step = make_fused_step(
        model, opt, cfg, mesh, pong, rollout_len=rollout_len,
        steps_per_dispatch=K,
    )
    state = create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong,
        n_envs * n_chips, n_shards=n_chips,
    )
    state = step.put(state)

    # warmup / compile; the host read of a value waits for the program
    state, metrics = step(state, cfg.entropy_beta)
    float(metrics["loss"])

    # best of 3 windows (see docstring); each window fully syncs via the
    # loss fetch
    window_dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters // K):
            state, metrics = step(state, cfg.entropy_beta)
        float(metrics["loss"])  # full sync on the whole scanned window
        window_dts.append(time.perf_counter() - t0)
    best_dt = min(window_dts)

    env_steps = iters * n_envs * n_chips * rollout_len
    host_rate = env_steps / best_dt
    per_chip = host_rate / n_chips
    # account the measured work in the learner registry so the embedded
    # telemetry snapshot below reflects this run (docs/observability.md)
    from distributed_ba3c_tpu import telemetry

    # 1 warmup step + 3 timed windows of `iters` updates
    telemetry.registry("learner").counter("train_steps_total").inc(
        3 * iters + 1
    )
    telemetry.registry("learner").counter("train_samples_total").inc(
        (3 * iters + 1) * n_envs * n_chips * rollout_len
    )
    return {
        "metric": "fused_pong_env_steps_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "env-steps/sec/chip",
        # north-star compares the HOST-aggregate rate to the 64-node cluster
        "vs_baseline": round(host_rate / BASELINE_ENV_STEPS_PER_SEC, 3),
        # MFU pins the 0.8x plateau to silicon utilization (VERDICT r5 #3):
        # manifest-pinned FLOPs/sample x measured rate / bf16 peak
        **_mfu(per_chip),
        # methodology (ADVICE r3): shape + best-of-N policy are part of the
        # number — without them BENCH_r{N}.json files are not comparable
        "n_envs": n_envs,
        "rollout_len": rollout_len,
        "iters": iters,
        "steps_per_dispatch": K,
        "policy": f"best_of_3_windows, {iters // K} scanned dispatch(es) per window",
        "window_rates": [round(env_steps / dt, 1) for dt in window_dts],
        "telemetry": _tele_snapshot(),
    }


def bench_overlap(
    n_envs: int = 128,
    rollout_len: int = 20,
    iters: int = 200,
    rollout_dtype: str = "float32",
    probe_reps: int = 5,
) -> dict:
    """Overlapped two-program mode (--overlap): rollout k+1 dispatched
    concurrently with learner k, lag-1 V-trace (fused/overlap.py,
    docs/overlap.md). Same flagship shape, window policy and sync contract
    as ``bench_fused``; each window is ``iters`` async actor/learner
    dispatch pairs with one metrics fetch at the end.

    Extra first-class fields vs the fused row (ISSUE 8 satellite):

    - ``mfu`` sums the manifest FLOPs of BOTH registered programs
      (``fused.actor`` + ``fused.learner``) — the actor's rollout forwards
      are real work the chip does; a fused.step-only lookup would
      undercount it.
    - ``program_latency``: per-program wall-time MEDIANS from the overlap
      probe (the same numbers published as tele/learner/actor_program_ms,
      learner_program_ms, overlap_pair_ms gauges), plus
      ``overlap_efficiency`` — the measured learner-hidden fraction of the
      actor program, (t_actor + t_learner - t_pair) / t_actor — and
      ``learner_window_coverage`` — min(1, t_learner/t_actor), the
      device-free proxy gate quantity (how much of the actor's wall time
      the learner window is LONG enough to hide; realized hiding requires
      an execution backend with concurrent queues, PERF.md round 9).
    """
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import create_fused_state
    from distributed_ba3c_tpu.fused.overlap import make_overlap_step
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh

    n_chips = len(jax.devices())
    cfg = BA3CConfig(num_actions=pong.num_actions)
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh()
    step = make_overlap_step(
        model, opt, cfg, mesh, pong, rollout_len=rollout_len,
        steps_per_dispatch=iters, rollout_dtype=rollout_dtype,
    )
    state = step.put(create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong,
        n_envs * n_chips, n_shards=n_chips,
    ))

    # warmup / compile all programs (same sync contract as bench_fused).
    # One facade call = `iters` pairs; acceptable as warmup since the
    # windows below re-measure.
    state, metrics = step(state, cfg.entropy_beta)
    float(metrics["loss"])

    # per-program latencies + overlap efficiency: the ONE sanctioned
    # sync-between-dispatches site (fused/overlap.py probe_overlap) —
    # medians over probe_reps, published as telemetry gauges too
    state, probe = step.probe_overlap(
        state, cfg.entropy_beta, reps=probe_reps
    )

    window_dts = []
    for _ in range(3):
        t0 = time.perf_counter()
        state, metrics = step(state, cfg.entropy_beta)
        float(metrics["loss"])  # full sync on the whole window
        window_dts.append(time.perf_counter() - t0)
    best_dt = min(window_dts)

    env_steps = iters * n_envs * n_chips * rollout_len
    host_rate = env_steps / best_dt
    per_chip = host_rate / n_chips
    from distributed_ba3c_tpu import telemetry

    telemetry.registry("learner").counter("train_steps_total").inc(4 * iters)
    telemetry.registry("learner").counter("train_samples_total").inc(
        4 * iters * n_envs * n_chips * rollout_len
    )
    return {
        "metric": "overlap_pong_env_steps_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "env-steps/sec/chip",
        "vs_baseline": round(host_rate / BASELINE_ENV_STEPS_PER_SEC, 3),
        # BOTH programs' manifest FLOPs — see docstring
        **_mfu(per_chip, entries=("fused.actor", "fused.learner")),
        "program_latency": probe,
        # computed by probe_overlap itself so every consumer reports the
        # same gate number (fused/overlap.py)
        "learner_window_coverage": probe["learner_window_coverage"],
        "rollout_dtype": rollout_dtype,
        "lag": step.lag,
        "n_envs": n_envs,
        "rollout_len": rollout_len,
        "iters": iters,
        "policy": "best_of_3_windows, "
                  f"{iters} async actor/learner pairs per window",
        "window_rates": [round(env_steps / dt, 1) for dt in window_dts],
        "telemetry": _tele_snapshot(),
    }


def _tele_snapshot() -> dict:
    """Compact final telemetry snapshot embedded in every bench JSON:
    counters/gauges as scalars per role (histograms as _count/_sum)."""
    from distributed_ba3c_tpu import telemetry

    return {
        role: reg.scalars()
        for role, reg in sorted(telemetry.all_registries().items())
        if reg.scalars()
    }


def make_null_predictor(model, params, n_actions: int, service_s: float = 0.0,
                        **kw):
    """A BatchedPredictor whose 'device' is host numpy: identical queueing,
    continuous-batching scheduler, deadline/shed machinery and callbacks —
    only the dispatch/fetch pair is replaced by thread-safe host-side
    random actions. The plane's own ceiling measurement
    (scripts/plane_bench.py) uses this to take the device out of the loop.

    ``service_s`` > 0 simulates a device that takes that long PER CALL
    (slept at fetch time, like a real serialized device queue) — the knob
    ``scripts/serving_bench.py`` uses to give the latency frontier a real
    service-time axis on a device-free host."""
    import threading
    import time as _time

    import numpy as np

    from distributed_ba3c_tpu.predict.server import BatchedPredictor

    class _NullDevicePredictor(BatchedPredictor):
        """Identical scheduler machinery; the 'device' is host numpy."""

        def __init__(self, *a, **kws):
            super().__init__(*a, **kws)
            self._null_rng = np.random.default_rng(0)
            # numpy Generators are not thread-safe and the sync
            # predict_batch path can race the scheduler thread here (the
            # real predictor guards its PRNG key with a lock — keep the
            # invariant)
            self._null_lock = threading.Lock()

        def _dispatch(self, params, batch):
            # 'dispatch' computes eagerly on host; 'fetch' pays the
            # simulated device time, so the depth-2 pipeline sees the
            # same serialized-device timing a real backend gives it
            k = np.asarray(batch).shape[0]
            with self._null_lock:
                acts = self._null_rng.integers(0, n_actions, k).astype(
                    np.int32
                )
            vals = np.zeros(k, np.float32)
            logp = np.full(k, -np.log(n_actions), np.float32)
            return k, (acts, vals, logp, acts)

        def _collect(self, handle):
            if service_s > 0:
                _time.sleep(service_s)
            return handle[1]

    return _NullDevicePredictor(model, params, **kw)


def _role_scalars(base: str) -> dict:
    """Summed counters/gauges over ``base`` AND its dotted sub-roles
    (``master`` + ``master.f0``/``master.f1``/... — telemetry.fleet_role;
    ``pod`` + ``pod.host0``/``pod.host1``/... — pod/wire.py pod_role):
    the bench's progress/attribution reads must see the WHOLE plane, not
    one fleet (or one actor host) of it."""
    from distributed_ba3c_tpu import telemetry

    out: dict = {}
    for role, reg in telemetry.all_registries().items():
        if role != base and not role.startswith(f"{base}."):
            continue
        for name, v in reg.scalars().items():
            out[name] = out.get(name, 0.0) + v
    return out


def _master_progress() -> tuple:
    """(wire messages, datapoints) from the master registries — the plane's
    provable forward motion, read lock-free off the live counters."""
    s = _role_scalars("master")
    msgs = (
        s.get("per_env_msgs_total", 0)
        + s.get("block_msgs_total", 0)
        + s.get("block_shm_msgs_total", 0)
    )
    return msgs, s.get("datapoints_total", 0)


def stall_attribution() -> str:
    """Name the dead stage from the real counters (the bare time threshold
    used to be the whole diagnosis; now it only opens the case). Public:
    scripts/chaos_bench.py attributes its own warmup failures with it."""
    from distributed_ba3c_tpu import telemetry

    m = _role_scalars("master")
    p = _role_scalars("predictor")
    msgs, dps = _master_progress()
    depth = m.get("train_queue_depth", 0)
    parts = (
        f"wire_msgs={msgs:.0f} datapoints={dps:.0f} "
        f"train_queue_depth={depth:.0f} "
        f"predictor_batches={p.get('batches_total', 0):.0f} "
        f"blocked_puts={m.get('queue_blocked_puts_total', 0):.0f}"
    )
    if not telemetry.enabled():
        return f"telemetry disabled, no attribution ({parts})"
    if msgs == 0:
        return f"no wire traffic: env servers never connected or died ({parts})"
    if p.get("batches_total", 0) == 0:
        return f"wire traffic but predictor never served ({parts})"
    if dps == 0:
        return f"predictor serving but no datapoints: flush path stalled ({parts})"
    return f"plane went quiet after progress ({parts})"


#: private alias kept so staged callers keep working (same
#: convention as devicelock.stderr_print)
_stall_attribution = stall_attribution


def bench_zmq_plane(
    game: str = "pong", n_envs: int = 256, seconds: float = 20.0,
    null_device: bool = False, wire: str = "per-env",
    envs_per_proc: int = 32, warmup_datapoints: int = 512,
    windows: int = 1, telemetry_on: bool = True, fleets: int = 1,
    trace_sample: int = 0,
) -> dict:
    """Actor-plane throughput (BASELINE configs #1/#2): C++ batched env
    servers -> ZMQ -> master -> batched TPU predictor, counting n-step
    datapoints entering the train queue. Run via `python bench.py --plane zmq`
    (the driver's default invocation stays the fused line); the dedicated
    plane instrument with both wires and both predictors in one JSON is
    ``scripts/plane_bench.py``.

    ``null_device=True`` (``--plane zmq-null``) swaps the device forward for
    host-side random actions while keeping EVERY other stage — C++ envs,
    serialization, ZMQ transport, master routing, batching/coalesce,
    n-step assembly. That measures the plane's own ceiling with no device
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

    import numpy as np

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.actors.fleet import fleet_pipes
    from distributed_ba3c_tpu.actors.master import BA3CSimulatorMaster
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.envs import native
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.predict.server import BatchedPredictor

    # per-run telemetry accounting: fresh registries, and the A/B switch
    # for the overhead gate (scripts/plane_bench.py --telemetry both).
    # Children inherit the env var through spawn.
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
                        stall_mark = _master_progress()[1]
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
                        if _master_progress()[1] != stall_mark:
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
        "telemetry": _tele_snapshot(),
        # the null-predictor ceiling must be UNMISTAKABLE from a real plane
        # measurement: distinct metric name + an explicit predictor field
        "metric": f"zmq_plane_{kind}_{game}_env_steps_per_sec_per_host",
        "value": round(rate, 1),
        "unit": "env-steps/sec/host",
        "vs_baseline": round(rate / BASELINE_ENV_STEPS_PER_SEC, 3),
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
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--plane",
        choices=["fused", "zmq", "zmq-null"],
        default="fused",
        help="fused = on-device actor+learner (the driver metric); "
        "zmq = host actor plane via C++ env servers; "
        "zmq-null = same plane with a no-device null predictor (the "
        "serialization+transport+batching ceiling, PERF.md)",
    )
    ap.add_argument(
        "--wire",
        default="auto",
        choices=["auto", "block-shm", "block", "per-env"],
        help="env-server wire protocol for the zmq planes (the fused plane "
        "has no wire): block-shm = control over zmq + obs through a "
        "/dev/shm ring (the README headline wire), block = all-zmq "
        "zero-copy multipart, per-env = the pre-block compat baseline; "
        "auto = block-shm when /dev/shm is available, else block (same "
        "resolution as cli.py --wire)",
    )
    ap.add_argument(
        "--tpu_lock",
        default="wait",
        choices=["wait", "fail", "off"],
        help="host-local TPU-claim mutex (utils/devicelock.py). Default "
        "wait: a bench launched while training holds the chip QUEUES "
        "instead of failing on libtpu's own lockfile.",
    )
    ap.add_argument(
        "--overlap", action="store_true",
        help="fused plane only: measure the overlapped two-program mode "
        "(rollout k+1 concurrent with learner k, lag-1 V-trace — "
        "docs/overlap.md) instead of the single fused program; MFU sums "
        "the manifest FLOPs of both registered programs",
    )
    ap.add_argument(
        "--n_envs", type=int, default=128,
        help="fused/overlap planes: envs per chip (the flagship bench "
        "shape; shrink for device-free proxy captures)",
    )
    ap.add_argument(
        "--rollout_len", type=int, default=20,
        help="fused/overlap planes: rollout length per update",
    )
    ap.add_argument(
        "--iters", type=int, default=200,
        help="fused/overlap planes: updates per timed window",
    )
    ap.add_argument(
        "--rollout_dtype", default="float32",
        choices=["float32", "bfloat16"],
        help="--overlap only: actor-side params-snapshot dtype",
    )
    args = ap.parse_args()

    from distributed_ba3c_tpu.utils.backend import (
        configure_compile_cache,
        device_info,
    )
    from distributed_ba3c_tpu.utils.devicelock import guard_tpu

    # bounded wait: the driver invokes bench.py unattended at round end —
    # queueing briefly behind a finishing run is right, hanging forever
    # behind a stuck one is not (exit nonzero with the holder identity)
    _lock = guard_tpu(  # noqa: F841 — held for process lifetime
        "bench.py",
        mode=args.tpu_lock,
        timeout_s=float(os.environ.get("BA3C_TPU_LOCK_TIMEOUT", "1800")),
    )
    configure_compile_cache()
    device = device_info()
    if device["platform"] != "tpu":
        print(
            f"bench.py measures a TPU; jax found {device} — no result "
            "(the device-free instrument is scripts/plane_bench.py)",
            file=sys.stderr,
        )
        return 1
    if args.wire == "auto":
        from distributed_ba3c_tpu.utils import shm

        args.wire = "block-shm" if shm.available() else "block"
    if args.overlap and args.plane != "fused":
        # same convention as cli.py: contradictory flags are a usage
        # error, never a silently-ignored modifier
        raise SystemExit(
            f"--overlap measures the fused plane's two-program schedule; "
            f"it does not combine with --plane {args.plane}"
        )
    if args.plane == "zmq":
        result = bench_zmq_plane(wire=args.wire)
    elif args.plane == "zmq-null":
        result = bench_zmq_plane(null_device=True, wire=args.wire)
    elif args.overlap:
        result = bench_overlap(
            n_envs=args.n_envs, rollout_len=args.rollout_len,
            iters=args.iters, rollout_dtype=args.rollout_dtype,
        )
    else:
        result = bench_fused(
            n_envs=args.n_envs, rollout_len=args.rollout_len,
            iters=args.iters,
        )
    print(json.dumps({**result, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
