#!/usr/bin/env python
"""Chaos acceptance gate: the orchestrated plane under random SIGKILLs.

Exercises the full orchestration stack (docs/orchestration.md) device-free
in one process tree and prints ONE JSON line (the repo's bench-tooling
contract, like plane_bench_r6/r7):

1. **control**: a supervised C++ env-server fleet -> ZMQ -> master -> null
   predictor -> n-step assembly, measured with NO chaos — the steady-state
   baseline.
2. **chaos**: the same plane while a seeded :class:`ChaosMonkey` SIGKILLs
   ``--kills`` (default 3) servers mid-measurement and the
   :class:`FleetSupervisor` respawns them. GATE: the chaos rate must hold
   >= ``--gate`` (default 0.90) of control. Control/chaos reps alternate
   in one session and the gate compares MEDIANS (scheduler drift hits
   both arms equally — the plane_bench_r7 lesson).
3. **autoscale**: a fleet launched at ``fleet_min`` grows to ``fleet_max``
   purely from the starvation signal (queue fill below the low watermark)
   — scale decisions land as flight events + ``tele/orchestrator/*``.
4. **failover**: a real ``train.py`` run under :class:`LearnerSupervisor`
   is SIGKILLed after its first FINALIZED checkpoint and must resume from
   it without operator action, completing its full epoch budget.

The JSON carries the per-rep rates, the orchestrator registry snapshot and
the orchestration flight events — the postmortem evidence IS the bench
artifact (committed as ``runs/chaos_bench_r8.json``). Exit 1 if the
throughput gate or the failover fails. Device-free: forces
``JAX_PLATFORMS=cpu``, never touches the TPU pool.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import signal
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: flight-event kinds that belong to the orchestration story — the JSON
#: embeds exactly these so the committed artifact shows scale / respawn /
#: failover evidence without a 4096-event dump
_ORCH_KINDS = (
    "server_spawn", "server_respawn", "server_death", "chaos_kill",
    "scale_up", "scale_down", "scale_decision", "circuit_open",
    "circuit_close", "wedged_kill", "learner_failover", "learner_giveup",
    "incarnation_reset", "prune",
)


def _drain_warmup(master, n: int, first_timeout: float = 300.0) -> None:
    from distributed_ba3c_tpu.telemetry.attribution import stall_attribution

    try:
        master.queue.get(timeout=first_timeout)
        for _ in range(n - 1):
            master.queue.get(timeout=60)
    except queue.Empty:
        raise RuntimeError(
            f"plane produced no warmup data — {stall_attribution()}"
        ) from None


def _measure(master, seconds: float, windows: int) -> list:
    """Datapoints/s entering the train queue, per window, drained in
    bursts (a blocking consumer would make every producer put pay a futex
    wake — bench.py's measured lesson). No stall-raise here: brief dips
    are exactly what a chaos window produces. Returns the per-window
    rates; the caller takes the BEST window (the repo's scheduler-noise
    filter) — under chaos every window still contains kills, because the
    kill interval is shorter than a window."""
    q = master.queue
    rates = []
    for _ in range(max(1, windows)):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        n = 0
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            try:
                q.get_nowait()
                n += 1
            except queue.Empty:
                time.sleep(0.002)
        rates.append(round(n / (time.perf_counter() - t0), 1))
    return rates


class _Plane:
    """One supervised device-free plane (fleet + master + null predictor)."""

    def __init__(
        self, game: str, n_servers: int, per: int, wire: str,
        fleet_min=None, fleet_max=None, backoff_base_s: float = 0.25,
    ):
        import jax
        import numpy as np

        from distributed_ba3c_tpu.actors.master import BA3CSimulatorMaster
        from distributed_ba3c_tpu.config import BA3CConfig
        from distributed_ba3c_tpu.envs import native
        from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
        from distributed_ba3c_tpu.orchestrate import FleetSpec, FleetSupervisor
        from distributed_ba3c_tpu.predict.null import make_null_predictor

        n_actions = native.CppBatchedEnv(game, 1).num_actions
        cfg = BA3CConfig(
            num_actions=n_actions, predict_batch_size=max(256, per)
        )
        model = build_model(DEFAULT_MODEL, cfg)
        params = model.init(
            jax.random.PRNGKey(0), np.zeros((1, *cfg.state_shape), np.uint8)
        )["params"]
        self.predictor = make_null_predictor(
            model, params, n_actions,
            batch_size=max(cfg.predict_batch_size, per), num_threads=2,
            coalesce_ms=0.0,
        )
        tmp = tempfile.mkdtemp(prefix="ba3c-chaos-")
        c2s, s2c = f"ipc://{tmp}/c2s", f"ipc://{tmp}/s2c"
        # actor_timeout None: respawns land inside the master's patience,
        # so a respawned slot re-enters as an INCARNATION RESET (same
        # ident, step going backwards) — the PR-4 machinery under test
        self.master = BA3CSimulatorMaster(
            c2s, s2c, self.predictor,
            gamma=cfg.gamma, local_time_max=cfg.local_time_max,
            score_queue=queue.Queue(maxsize=100_000),
        )
        self.spec = FleetSpec(
            pipe_c2s=c2s, pipe_s2c=s2c, game=game, envs_per_server=per,
            wire=wire, fleet_size=n_servers,
            fleet_min=fleet_min if fleet_min is not None else n_servers,
            fleet_max=fleet_max if fleet_max is not None else n_servers,
            backoff_base_s=backoff_base_s, backoff_max_s=5.0,
            stable_after_s=5.0, restart_budget=64, budget_window_s=120.0,
        )
        self.supervisor = FleetSupervisor(self.spec, poll_interval_s=0.1)

    def start(self) -> None:
        self.predictor.start()
        self.master.start()
        self.supervisor.start()

    def settle(self, timeout_s: float = 60.0) -> bool:
        """Wait until every target slot is live again (post-chaos)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.supervisor.live_count() >= self.supervisor.target:
                return True
            time.sleep(0.2)
        return False

    def close(self) -> None:
        self.supervisor.stop()
        self.supervisor.join(timeout=5)
        self.supervisor.close()
        self.master.close()
        self.predictor.stop()
        self.predictor.join(timeout=5)


def _phase_rate(args, chaos_kills: int, seed: int) -> dict:
    """One rep: bring a plane up, (optionally) unleash the monkey inside
    the measurement window, return the rate + orchestration evidence."""
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.orchestrate import ChaosMonkey

    telemetry.reset_all()
    plane = _Plane(args.game, args.n_servers, args.envs_per_proc, args.wire)
    monkey = None
    try:
        plane.start()
        _drain_warmup(plane.master, args.warmup_datapoints)
        if chaos_kills:
            # the monkey kills CONTINUOUSLY at an interval shorter than
            # one window, so the fleet is in some phase of dying or
            # respawning inside EVERY window — best-of-windows then
            # filters scheduler starvation, never a kill-free window
            interval = args.seconds / (chaos_kills + 1)
            monkey = ChaosMonkey(
                plane.supervisor,
                interval_s=interval,
                jitter_s=min(0.2, interval / 4),
                max_kills=None,
                seed=seed,
                initial_delay_s=interval / 2,
            )
            monkey.start()
        window_rates = _measure(plane.master, args.seconds, args.windows)
        out = {"rate": max(window_rates), "window_rates": window_rates}
        if chaos_kills:
            monkey.stop()
            monkey.join(timeout=5)
            out["kills"] = monkey.kills
            out["settled"] = plane.settle()
            reg = telemetry.registry("orchestrator")
            out["respawns"] = reg.counter("server_respawns_total").value()
            out["fleet_live_size"] = reg.gauge("fleet_live_size").value()
            out["fleet_target_size"] = reg.gauge("fleet_target_size").value()
            out["incarnation_resets"] = (
                telemetry.registry("master")
                .counter("incarnation_resets_total").value()
            )
            out["orchestrator_series"] = reg.scalars()
        return out
    finally:
        if monkey is not None:
            monkey.stop()
        plane.close()


def _phase_autoscale(args) -> dict:
    """fleet_min -> fleet_max on the starvation signal alone."""
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.orchestrate import (
        Autoscaler,
        AutoscalerPolicy,
        master_signals,
    )

    telemetry.reset_all()
    fleet_max = min(3, args.n_servers)
    plane = _Plane(
        args.game, 1, args.envs_per_proc, args.wire,
        fleet_min=1, fleet_max=fleet_max,
    )
    scaler = Autoscaler(
        plane.supervisor,
        master_signals(plane.master),
        policy=AutoscalerPolicy(patience=2, cooldown_ticks=1),
        interval_s=0.5,
    )
    from distributed_ba3c_tpu.utils.concurrency import LoopThread

    def drain_once():  # a hungry learner: keeps the queue at the low watermark
        try:
            plane.master.queue.get(timeout=0.2)
        except queue.Empty:
            pass

    drainer = LoopThread(drain_once)
    try:
        plane.start()
        drainer.start()
        scaler.start()
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if plane.supervisor.live_count() >= fleet_max:
                break
            time.sleep(0.5)
        reg = telemetry.registry("orchestrator")
        return {
            "fleet_min": 1,
            "fleet_max": fleet_max,
            "reached_live": plane.supervisor.live_count(),
            "scale_up_events": reg.counter("scale_up_total").value(),
            "autoscale_ticks": reg.counter("autoscale_ticks_total").value(),
        }
    finally:
        scaler.stop()
        scaler.join(timeout=5)
        drainer.stop()
        drainer.join(timeout=5)
        plane.close()


def _phase_failover(args) -> dict:
    """SIGKILL a real learner after its first finalized checkpoint; the
    supervisor must resume it from that checkpoint to a clean finish."""
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.orchestrate import LearnerSupervisor, finalized_step

    logdir = os.path.join(
        tempfile.mkdtemp(prefix="ba3c-chaos-failover-"), "run"
    )
    ckpt_dir = os.path.join(logdir, "checkpoints")
    train_args = [
        "--env", "fake",
        "--simulator_procs", "2",
        "--batch_size", "16",
        "--image_size", "16",
        "--fc_units", "16",
        "--steps_per_epoch", str(args.failover_steps_per_epoch),
        "--max_epoch", "3",
        "--nr_eval", "0",
        "--logdir", logdir,
    ]
    sup = LearnerSupervisor(
        logdir, train_args, max_restarts=3, poll_s=0.2
    )
    killed = {}

    def killer():
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            step = finalized_step(ckpt_dir)
            pid = sup.child_pid
            if step is not None and pid is not None:
                killed["at_step"] = step
                try:
                    os.killpg(pid, signal.SIGKILL)  # the whole process group
                except (OSError, ProcessLookupError):
                    pass
                return
            time.sleep(0.3)

    from distributed_ba3c_tpu.utils.concurrency import StoppableThread

    kt = StoppableThread(target=killer, daemon=True)
    kt.start()
    rc = sup.run()
    kt.join(timeout=5)
    reg = telemetry.registry("orchestrator")
    stats_path = os.path.join(logdir, "stat.json")
    epochs = None
    if os.path.isfile(stats_path):
        with open(stats_path) as fh:
            epochs = len(json.load(fh))
    final = finalized_step(ckpt_dir)
    return {
        "rc": rc,
        "killed_at_step": killed.get("at_step"),
        "resumes": reg.counter("learner_resumes_total").value(),
        "restarts": reg.counter("learner_restarts_total").value(),
        "final_step": final,
        "epochs_in_stat_json": epochs,
        # resume proof is STEP CONTINUITY: the relaunched learner restored
        # the killed attempt's finalized step and trained PAST it (the ZMQ
        # trainer's --max_epoch budget is per-attempt, so stat.json may
        # carry the pre-kill epochs plus the resumed run's — epoch count
        # alone cannot distinguish resume from restart; steps can)
        "ok": rc == 0
        and killed.get("at_step") is not None
        and reg.counter("learner_resumes_total").value() >= 1
        and final is not None
        and final > killed.get("at_step", 0)
        and (epochs or 0) >= 3,
    }


def _phase_network(args) -> dict:
    """The netchaos phase (docs/netchaos.md): the pod's DCN-shaped links
    under emulated 50 ms RTT + 1% loss must hold >= --net_gate of the
    clean-proxy control, a timed full partition must heal restart-free
    with only typed counters, and every rep must replay from its seed."""
    from distributed_ba3c_tpu.netchaos.bench import (
        NetShape,
        dcn_schedule,
        quiet_schedule,
        run_partition_rep,
        run_throughput_rep,
    )

    shape = NetShape(
        hosts=1,
        sims_per_host=args.net_sims,
        segments_per_block=8,
        warmup_timeout=args.warmup_timeout_net,
    )
    clean = run_throughput_rep(
        shape, quiet_schedule(args.seed), args.net_seconds, args.net_windows
    )
    dcn = run_throughput_rep(
        shape,
        dcn_schedule(args.net_rtt_ms, args.net_loss, seed=args.seed),
        args.net_seconds,
        args.net_windows,
    )
    ratio = round(dcn["rate"] / max(clean["rate"], 1e-9), 4)
    partition = run_partition_rep(shape, args.seed, partition_s=10.0)
    return {
        "rtt_ms": args.net_rtt_ms,
        "loss": args.net_loss,
        "clean": clean,
        "dcn": dcn,
        "dcn_over_clean": ratio,
        "gate": args.net_gate,
        "gate_passed": ratio >= args.net_gate,
        "partition": partition,
        "replay_ok": bool(
            clean["replay"]["match"]
            and dcn["replay"]["match"]
            and partition["replay"]["match"]
        ),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--game", default="pong")
    ap.add_argument(
        "--n_servers", type=int, default=8,
        help="fleet size in server processes — each kill idles 1/K of "
        "the fleet for the respawn latency, so K sizes the gate headroom",
    )
    ap.add_argument("--envs_per_proc", type=int, default=16)
    ap.add_argument("--wire", default="block", choices=["block-shm", "block", "per-env"])
    ap.add_argument("--seconds", type=float, default=12.0, help="seconds per measurement window")
    ap.add_argument(
        "--windows", type=int, default=3,
        help="windows per rep; the BEST window is the rep's rate (the "
        "repo's scheduler-noise filter, bench.py policy). Chaos kills "
        "run through ALL windows, so no window is kill-free",
    )
    ap.add_argument(
        "--kills", type=int, default=3,
        help="kill pacing: the monkey SIGKILLs every seconds/(kills+1) "
        "continuously through the rep — >= this many land inside every "
        "window (acceptance: >=3 mid-run)",
    )
    ap.add_argument(
        "--pair_reps", type=int, default=3,
        help="alternating control/chaos rep pairs; the gate compares "
        "MEDIANS — with 3+ pairs one scheduler-starved rep cannot decide "
        "the verdict (the plane_bench_r7 lesson: this container swings "
        "2x run-to-run with zero code change)",
    )
    ap.add_argument("--gate", type=float, default=0.90)
    ap.add_argument("--warmup_datapoints", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip_failover", action="store_true")
    ap.add_argument("--skip_autoscale", action="store_true")
    ap.add_argument(
        "--net", action="store_true",
        help="add the netchaos network phase: pod-link throughput under "
        "--net_rtt_ms/--net_loss vs a quiet-proxy control, the "
        "partition-and-heal rep, and the seed-replay verdict "
        "(docs/netchaos.md)",
    )
    ap.add_argument(
        "--net_only", action="store_true",
        help="run ONLY the network phase (no native env core needed — "
        "the pod rig runs fake env hosts); the CI netchaos job's mode",
    )
    ap.add_argument("--net_rtt_ms", type=float, default=50.0)
    ap.add_argument("--net_loss", type=float, default=0.01)
    ap.add_argument("--net_gate", type=float, default=0.85, help="degraded pod throughput must hold >= this x the quiet-proxy control")
    ap.add_argument("--net_seconds", type=float, default=6.0)
    ap.add_argument("--net_windows", type=int, default=2)
    ap.add_argument("--net_sims", type=int, default=2, help="fake sims per pod host in the network phase")
    ap.add_argument("--warmup_timeout_net", type=float, default=240.0)
    ap.add_argument(
        "--failover_steps_per_epoch", type=int, default=60,
        help="failover phase train.py epoch length (checkpoint cadence)",
    )
    args = ap.parse_args()

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.envs import native
    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    if args.net_only:
        # the network phase is self-contained (fake-env pod hosts): its
        # own JSON, its own gates, evidence before verdict
        net = _phase_network(args)
        stderr_print(
            f"network: clean {net['clean']['rate']:.1f} vs "
            f"{net['rtt_ms']:.0f}ms/{100 * net['loss']:.1f}% "
            f"{net['dcn']['rate']:.1f} env-steps/s "
            f"({net['dcn_over_clean']:.3f}x, gate {net['gate']}), "
            f"partition recovered={net['partition']['recovered']}, "
            f"replay={net['replay_ok']}"
        )
        out = {
            "metric": "netchaos_pod_dcn_over_clean",
            "value": net["dcn_over_clean"],
            "unit": "ratio (degraded/clean ingest env-steps/s)",
            "network": net,
        }
        # evidence prints BEFORE the verdict (the repo's bench contract)
        import jax

        # a CPU instrument by design: name the platform its rates came from
        out["platform"] = jax.default_backend()
        print(json.dumps(out))
        ok = (
            net["gate_passed"]
            and net["partition"]["recovered"]
            and net["replay_ok"]
        )
        if not ok:
            stderr_print(f"network phase gates FAILED: {json.dumps(net)[:500]}")
        return 0 if ok else 1

    if not native.available():
        stderr_print("native env core not built: run `make -C cpp`")
        return 2

    failures = []
    control_rates, chaos_rates = [], []
    reps = {}
    chaos_evidence = {}
    for rep in range(max(1, args.pair_reps)):
        # alternate which arm goes first: slow host drift (the scheduler,
        # page cache) must hit both arms equally over the session
        order = (0, args.kills) if rep % 2 == 0 else (args.kills, 0)
        for kills in order:
            r = _phase_rate(args, kills, seed=args.seed + rep)
            tag = "chaos" if kills else "control"
            reps[f"{tag}_rep{rep}"] = r
            (chaos_rates if kills else control_rates).append(r["rate"])
            if kills:
                chaos_evidence = r
            stderr_print(
                f"{tag:8s} rep {rep}: {r['rate']:>9.1f} env-steps/s"
                + (f" ({r.get('kills')} kills, {r.get('respawns'):.0f} respawns)" if kills else "")
            )

    med_control = statistics.median(control_rates)
    med_chaos = statistics.median(chaos_rates)
    ratio = med_chaos / max(med_control, 1e-9)
    if ratio < args.gate:
        failures.append(
            f"chaos throughput gate FAILED: median chaos rate {med_chaos:.1f} "
            f"is {100 * (1 - ratio):.1f}% below median control "
            f"{med_control:.1f} (gate: hold >={args.gate:.0%})"
        )
    if chaos_evidence.get("kills", 0) < min(3, args.kills):
        failures.append(
            f"chaos rep killed only {chaos_evidence.get('kills', 0)} servers "
            f"(need >= {min(3, args.kills)} for the acceptance scenario)"
        )
    if chaos_evidence.get("respawns", 0) < chaos_evidence.get("kills", 0):
        failures.append(
            "supervisor respawned fewer servers than chaos killed "
            f"({chaos_evidence.get('respawns')} < {chaos_evidence.get('kills')})"
        )

    autoscale = None
    if not args.skip_autoscale:
        autoscale = _phase_autoscale(args)
        stderr_print(
            f"autoscale: 1 -> {autoscale['reached_live']} servers "
            f"({autoscale['scale_up_events']:.0f} scale-up decisions)"
        )
        if autoscale["reached_live"] < autoscale["fleet_max"]:
            failures.append(
                f"autoscaler never reached fleet_max: live "
                f"{autoscale['reached_live']} < {autoscale['fleet_max']}"
            )

    failover = None
    if not args.skip_failover:
        failover = _phase_failover(args)
        stderr_print(
            f"failover: killed at step {failover['killed_at_step']}, "
            f"resumes {failover['resumes']:.0f}, rc {failover['rc']}, "
            f"final step {failover['final_step']}"
        )
        if not failover["ok"]:
            failures.append(f"learner checkpoint-failover FAILED: {failover}")

    network = None
    if args.net:
        network = _phase_network(args)
        stderr_print(
            f"network: clean {network['clean']['rate']:.1f} vs degraded "
            f"{network['dcn']['rate']:.1f} env-steps/s "
            f"({network['dcn_over_clean']:.3f}x, gate {network['gate']})"
        )
        if not network["gate_passed"]:
            failures.append(
                f"netchaos throughput gate FAILED: degraded pod held only "
                f"{network['dcn_over_clean']:.3f}x clean (gate "
                f">={network['gate']})"
            )
        if not network["partition"]["recovered"]:
            failures.append(
                f"partition-and-heal rep FAILED: {network['partition']}"
            )
        if not network["replay_ok"]:
            failures.append("netchaos seed-replay mismatch (rep not reproducible)")

    # the orchestration flight events ARE the acceptance evidence: dump the
    # ring (postmortem form) and embed the relevant kinds in the artifact
    flight = telemetry.flight_recorder()
    dump_path = flight.dump("chaos bench complete")
    events = [
        {"kind": k, **f}
        for _, k, f in flight.events_since(0)
        if k in _ORCH_KINDS
    ]
    kinds = sorted({e["kind"] for e in events})

    out = {
        "metric": "chaos_plane_env_steps_per_sec_per_host",
        "value": round(med_chaos, 1),
        "unit": "env-steps/sec/host",
        "control_value": round(med_control, 1),
        "chaos_over_control": round(ratio, 4),
        "gate": args.gate,
        "gate_passed": ratio >= args.gate,
        "game": args.game,
        "wire": args.wire,
        "n_servers": args.n_servers,
        "envs_per_proc": args.envs_per_proc,
        "seconds": args.seconds,
        "kills_per_rep": args.kills,
        "pair_reps": args.pair_reps,
        "control_reps": control_rates,
        "chaos_reps": chaos_rates,
        "reps": reps,
        "autoscale": autoscale,
        "failover": failover,
        "network": network,
        "flight_dump": dump_path,
        "flight_event_kinds": kinds,
        "flight_events": events[-200:],
    }
    # evidence prints BEFORE the verdict: per-rep rates and events are most
    # valuable exactly when a gate fails (plane_bench precedent)
    import jax

    # a CPU instrument by design: name the platform its rates came from
    out["platform"] = jax.default_backend()
    print(json.dumps(out))
    if failures:
        for msg in failures:
            stderr_print(msg)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
