#!/usr/bin/env python
"""Reconcile-loop chaos certification: every resource class killed, healed.

The acceptance contract for the declarative control plane
(docs/topology.md): ONE seeded run SIGKILLs a member of EVERY resource
class the :class:`Reconciler` drives, and gates on the loop healing each
back to spec with zero manual intervention:

1. **fleet**: a supervised fake-env simulator fleet (per-env wire ->
   master -> null predictor) with the reconciler owning the tick (the
   supervisor thread is never started); one env-server slot is SIGKILLed
   mid-stream and must respawn through a flight-recorded
   ``reconcile_action``, the plane producing datapoints again afterwards.
   The env flavor is irrelevant here — the C++ fleet's own chaos story is
   scripts/chaos_bench.py; the measurand is the LOOP.
2. **pod**: a 2-host fake-env pod against a real :class:`PodLearnerPlane`,
   the hosts under :class:`PodSupervisor` ridden as a ``kind="pod"``
   resource; one WHOLE host process group is SIGKILLed and must rejoin,
   the learner taking updates again post-heal with zero learner restarts.
3. **netchaos partition**: the pod links under a timed full partition
   (10 s at the committed shape) from the seeded netchaos plane — heal
   restart-free, typed counters only, and the rep must replay from its
   seed (docs/netchaos.md: spec'd chaos is part of the document).
4. **learner**: a real ``train.py`` fake-env run driven through
   :class:`LearnerResource` (the reconciler's re-arm path, NOT
   ``LearnerSupervisor.run``); SIGKILLed after its first FINALIZED
   checkpoint, it must resume from that checkpoint to rc 0 — zero
   state loss proven by step continuity (final step > kill step).
5. **serving**: two null-predictor replicas behind the REAL
   ServingRouter in a :class:`ReplicaSet` whose sweeper thread is OFF
   (the reconciler owns the sweep); one replica's scheduler is killed
   mid-traffic (the in-process SIGKILL analogue, serving_bench
   precedent) and the set must heal back to target with a fresh
   incarnation, every submitted task resolving.

Prints ONE JSON line (the repo's bench-tooling contract) embedding the
flight-recorded decision trail (``reconcile_action`` and friends) — the
committed artifact is ``runs/reconcile_bench_r17.json``. Exit 1 if any
gate fails. ``--short`` is the CI schedule (same gates, smaller shapes
— the ``reconcile`` job). Device-free: forces ``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import queue
import random
import signal
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: flight-event kinds that belong to the reconcile story — each phase
#: embeds exactly these (captured per phase: the netchaos rig resets
#: telemetry, so the trail is accumulated, not re-read at the end)
_TRAIL_KINDS = (
    "reconcile_action", "reconcile_act_error", "reconcile_circuit_open",
    "reconcile_circuit_close", "server_spawn", "server_respawn",
    "server_death", "learner_failover", "learner_giveup",
    "serving_replica_spawn", "serving_replica_replace", "replica_dead",
)


def _policy(poll_s: float = 0.1):
    from distributed_ba3c_tpu.orchestrate.topology import ReconcilePolicy

    return ReconcilePolicy(
        poll_interval_s=poll_s, backoff_base_s=0.25, backoff_max_s=5.0,
        restart_budget=32, budget_window_s=120.0,
    )


def _heal_count(kind: str) -> float:
    from distributed_ba3c_tpu import telemetry

    return telemetry.registry("reconciler").counter(
        f"reconcile_heal_{kind}_total"
    ).value()


def _trail(since_t: float, cap: int = 80) -> list:
    from distributed_ba3c_tpu import telemetry

    return [
        {"kind": k, **f}
        for _, k, f in telemetry.flight_recorder().events_since(since_t)
        if k in _TRAIL_KINDS
    ][-cap:]


def _drain(master, n: int, first_timeout: float = 240.0) -> int:
    """Pull ``n`` datapoints off the master's train queue (liveness
    proof: the plane is actually streaming, not just process-alive)."""
    got = 0
    try:
        master.queue.get(timeout=first_timeout)
        got += 1
        while got < n:
            master.queue.get(timeout=60)
            got += 1
    except queue.Empty:
        pass
    return got


# ---------------------------------------------------------------------------
# phase 1: env-server slot
# ---------------------------------------------------------------------------

def _phase_fleet(args, rng: random.Random) -> dict:
    """SIGKILL one supervised fake-env simulator slot; the reconciler's
    FleetResource must respawn it and the plane must stream again."""
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.actors.master import BA3CSimulatorMaster
    from distributed_ba3c_tpu.actors.simulator import SimulatorProcess
    from distributed_ba3c_tpu.envs.fake import build_fake_player
    from distributed_ba3c_tpu.orchestrate import FleetSpec, FleetSupervisor
    from distributed_ba3c_tpu.orchestrate.reconcile import (
        FleetResource,
        Reconciler,
    )
    from distributed_ba3c_tpu.predict.null import make_null_predictor

    t0 = time.monotonic()
    model = SimpleNamespace(num_actions=4, apply=None)
    predictor = make_null_predictor(
        model, {}, 4, batch_size=64, num_threads=2, coalesce_ms=0.0
    )
    tmp = tempfile.mkdtemp(prefix="ba3c-reconcile-fleet-")
    c2s, s2c = f"ipc://{tmp}/c2s", f"ipc://{tmp}/s2c"
    master = BA3CSimulatorMaster(
        c2s, s2c, predictor, gamma=0.99, local_time_max=5,
        score_queue=queue.Queue(maxsize=100_000),
    )
    build_player = functools.partial(
        build_fake_player, image_size=(16, 16), frame_history=4,
        num_actions=4,
    )
    sup = FleetSupervisor(
        FleetSpec(
            pipe_c2s=c2s, pipe_s2c=s2c, envs_per_server=1, wire="per-env",
            fleet_size=args.fleet_sims, fleet_min=args.fleet_sims,
            fleet_max=args.fleet_sims, backoff_base_s=0.25,
            backoff_max_s=5.0, stable_after_s=5.0,
        ),
        # construction only parameterizes the slot — the reconciler-driven
        # supervisor this factory is handed to owns the spawn
        factory=lambda i: SimulatorProcess(  # ba3clint: disable=A8
            i, c2s, s2c, build_player
        ),
        ident_prefix=lambda i: f"simulator-{i}",
    )
    rec = Reconciler(policy=_policy())  # ba3cflow: disable=F5 — the finally's rec.close() stops AND joins the loop thread (Reconciler.close)
    rec.add(FleetResource("fleet0", sup))
    heal_before = _heal_count("fleet")
    out: dict = {"ok": False, "fleet_size": args.fleet_sims}
    try:
        predictor.start()
        master.start()
        rec.start()  # prepare() spawns the initial fleet; the loop ticks
        out["warmup_datapoints"] = _drain(master, args.warmup_datapoints)
        if out["warmup_datapoints"] < args.warmup_datapoints:
            out["error"] = "plane produced no warmup stream"
            return out
        victim = rng.choice([idx for idx, _ in sup.live_slots()])
        out["killed_slot"] = victim
        sup.sigkill_slot(victim)
        deadline = time.monotonic() + args.settle_timeout
        while time.monotonic() < deadline:
            if (
                sup.live_count() >= sup.target
                and _heal_count("fleet") > heal_before
            ):
                break
            time.sleep(0.1)
        out["settled"] = sup.live_count() >= sup.target
        out["heal_actions"] = _heal_count("fleet") - heal_before
        # the respawned slot must STREAM, not just sit in the process
        # table — drain fresh datapoints through the healed fleet
        out["post_heal_datapoints"] = _drain(
            master, args.post_heal_datapoints, first_timeout=60.0
        )
        reg = telemetry.registry("orchestrator")
        out["respawns"] = reg.counter("server_respawns_total").value()
        out["ok"] = bool(
            out["settled"]
            and out["heal_actions"] >= 1
            and out["respawns"] >= 1
            and out["post_heal_datapoints"] >= args.post_heal_datapoints
        )
        return out
    finally:
        out["decisions"] = _trail(t0)
        rec.close()  # retires the resource -> supervisor.close()
        master.close()
        predictor.stop()
        predictor.join(timeout=5)


# ---------------------------------------------------------------------------
# phase 2: whole pod host group
# ---------------------------------------------------------------------------

def _phase_pod(args, rng: random.Random) -> dict:
    """SIGKILL one WHOLE pod host process group mid-training; the
    reconciler must respawn it and the learner must take updates again
    — with zero learner restarts (host loss is not a learner event)."""
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.orchestrate.pod import (
        PodLearnerPlane,
        PodSupervisor,
        host_argv,
    )
    from distributed_ba3c_tpu.orchestrate.reconcile import (
        FleetResource,
        Reconciler,
    )

    t0 = time.monotonic()
    cfg = BA3CConfig(
        image_size=(16, 16), frame_history=4, num_actions=4, fc_units=16,
        local_time_max=5, predict_batch_size=16,
    )
    tmp = tempfile.mkdtemp(prefix="ba3c-reconcile-pod-")
    c2s, s2c = f"ipc://{tmp}/c2s", f"ipc://{tmp}/s2c"
    plane = PodLearnerPlane(cfg, c2s, s2c, max_staleness=8)
    sup = PodSupervisor(
        2,
        lambda i: host_argv(
            i, c2s, s2c, env="fake", n_sims=2, unroll_len=5,
            segments_per_block=4, max_staleness=8, image_size=16,
            frame_history=4, num_actions=4, fc_units=16,
            predict_batch_size=16,
        ),
        backoff_base_s=0.25,
    )
    rec = Reconciler(policy=_policy())  # ba3cflow: disable=F5 — the finally's rec.close() stops AND joins the loop thread (Reconciler.close)
    rec.add(FleetResource("pod-hosts", sup, kind="pod"))
    heal_before = _heal_count("pod")
    # delta, not absolute: the fleet phase's respawn counter carries over
    respawns_before = telemetry.registry("orchestrator").counter(
        "server_respawns_total"
    ).value()
    out: dict = {"ok": False, "hosts": 2}
    try:
        plane.start()
        rec.start()
        updates = 0
        deadline = time.monotonic() + args.warmup_timeout_net
        while updates < args.pod_warmup_updates:
            if time.monotonic() > deadline:
                out["error"] = "pod produced no warmup updates"
                return out
            if plane.step_once(timeout=1.0) is not None:
                updates += 1
        out["warmup_updates"] = updates
        victim = rng.choice([idx for idx, _ in sup.live_slots()])
        out["killed_host"] = victim
        sup.sigkill_slot(victim)  # the whole host process group
        post_kill_updates = 0
        deadline = time.monotonic() + max(120.0, args.settle_timeout)
        while time.monotonic() < deadline:
            if plane.step_once(timeout=0.2) is not None:
                post_kill_updates += 1
            if (
                sup.live_count() >= sup.target
                and _heal_count("pod") > heal_before
                and post_kill_updates >= args.pod_heal_updates
            ):
                break
        out["settled"] = sup.live_count() >= sup.target
        out["heal_actions"] = _heal_count("pod") - heal_before
        out["post_kill_updates"] = post_kill_updates
        orch = telemetry.registry("orchestrator").scalars()
        out["host_respawns"] = int(
            orch.get("server_respawns_total", 0) - respawns_before
        )
        out["learner_restarts"] = int(orch.get("learner_restarts_total", 0))
        out["ok"] = bool(
            out["settled"]
            and out["heal_actions"] >= 1
            and out["host_respawns"] >= 1
            and post_kill_updates >= args.pod_heal_updates
            and out["learner_restarts"] == 0
        )
        return out
    finally:
        out["decisions"] = _trail(t0)
        rec.close()  # retires the resource -> supervisor.close()
        plane.close()


# ---------------------------------------------------------------------------
# phase 3: netchaos partition across the pod links
# ---------------------------------------------------------------------------

def _phase_partition(args) -> dict:
    """A timed FULL partition of every pod link from the seeded netchaos
    plane; heal must be restart-free and the rep must replay."""
    from distributed_ba3c_tpu.netchaos.bench import NetShape, run_partition_rep

    shape = NetShape(
        hosts=1, sims_per_host=args.net_sims, segments_per_block=8,
        warmup_timeout=args.warmup_timeout_net,
    )
    part = run_partition_rep(shape, args.seed, partition_s=args.partition_s)
    return {
        "partition_s": args.partition_s,
        "recovered": part.get("recovered", False),
        "replay_ok": bool(part.get("replay", {}).get("match")),
        "detail": part,
        "ok": bool(
            part.get("recovered") and part.get("replay", {}).get("match")
        ),
    }


# ---------------------------------------------------------------------------
# phase 4: learner, post-checkpoint
# ---------------------------------------------------------------------------

def _phase_learner(args) -> dict:
    """SIGKILL a real train.py run's whole process group after its first
    FINALIZED checkpoint; the reconciler's re-arm path must resume it
    from that checkpoint to rc 0 (step continuity = zero state loss)."""
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.orchestrate import LearnerSupervisor, finalized_step
    from distributed_ba3c_tpu.orchestrate.reconcile import (
        LearnerResource,
        Reconciler,
    )
    from distributed_ba3c_tpu.utils.concurrency import StoppableThread

    t0 = time.monotonic()
    logdir = os.path.join(
        tempfile.mkdtemp(prefix="ba3c-reconcile-learner-"), "run"
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
    sup = LearnerSupervisor(logdir, train_args, max_restarts=3, poll_s=0.2)
    rec = Reconciler(policy=_policy(poll_s=0.2))  # ba3cflow: disable=F5 — the finally's rec.close() stops AND joins the loop thread (Reconciler.close)
    lres = rec.add(LearnerResource("learner", sup))
    heal_before = _heal_count("learner")
    killed = {}

    def killer():
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            step = finalized_step(ckpt_dir)
            pid = sup.child_pid
            if step is not None and pid is not None:
                killed["at_step"] = step
                try:
                    os.killpg(pid, signal.SIGKILL)  # the whole group
                except (OSError, ProcessLookupError):
                    pass
                return
            time.sleep(0.3)

    kt = StoppableThread(target=killer, daemon=True)
    out: dict = {"ok": False}
    try:
        rec.start()  # the first tick re-arms: start from scratch
        kt.start()
        deadline = time.monotonic() + 900
        while lres.final_rc is None and time.monotonic() < deadline:
            time.sleep(0.2)
        kt.join(timeout=5)
        reg = telemetry.registry("orchestrator")
        final = finalized_step(ckpt_dir)
        out.update({
            "rc": lres.final_rc,
            "killed_at_step": killed.get("at_step"),
            "resumes": reg.counter("learner_resumes_total").value(),
            "restarts": reg.counter("learner_restarts_total").value(),
            "final_step": final,
            "heal_actions": _heal_count("learner") - heal_before,
        })
        # resume proof is STEP CONTINUITY (the chaos_bench lesson: epoch
        # counts cannot distinguish resume from restart; steps can)
        out["ok"] = bool(
            lres.final_rc == 0
            and killed.get("at_step") is not None
            and out["resumes"] >= 1
            and final is not None
            and final > killed.get("at_step", 0)
            # >= 2 re-arms: the scratch start AND the post-kill resume
            # both went through the reconciler, not a side channel
            and out["heal_actions"] >= 2
        )
        return out
    finally:
        out["decisions"] = _trail(t0)
        kt.stop()
        rec.close()


# ---------------------------------------------------------------------------
# phase 5: serving replica
# ---------------------------------------------------------------------------

def _phase_serving(args, rng: random.Random) -> dict:
    """Kill one routed replica's scheduler mid-traffic (the in-process
    SIGKILL analogue); the reconciler's ServingResource must sweep the
    corpse and heal the set back to target with a fresh incarnation."""
    import numpy as np

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.orchestrate.reconcile import (
        Reconciler,
        ServingResource,
    )
    from distributed_ba3c_tpu.orchestrate.serving import ReplicaSet
    from distributed_ba3c_tpu.predict.null import make_null_predictor
    from distributed_ba3c_tpu.predict.router import ServingRouter, replica_role

    t0 = time.monotonic()
    model = SimpleNamespace(num_actions=4, apply=None)
    spawned: dict = {}

    def factory(idx: int):
        pred = make_null_predictor(
            model, {}, 4, service_s=0.002, batch_size=16, coalesce_ms=0.0,
            tele_role=replica_role("predictor", idx),
        )
        spawned[idx] = pred
        return pred

    router = ServingRouter(health_interval_s=0.1)
    rs = ReplicaSet(
        router, factory, min_replicas=2, max_replicas=4, retire_grace_s=1.0
    )
    rec = Reconciler(policy=_policy())  # ba3cflow: disable=F5 — the finally's rec.close() stops AND joins the loop thread (Reconciler.close)
    rec.add(ServingResource("serving", rs))
    heal_before = _heal_count("serving")
    served: list = []
    sheds: list = []
    out: dict = {"ok": False, "replicas": 2}
    try:
        router.start()
        rs.start(2, reconcile_thread=False)  # the reconciler owns the sweep
        rec.start()
        victim = rng.choice(rs.replica_ids())
        out["killed_replica"] = victim
        vpred = spawned[int(victim[1:])]

        def _die(params, batch):
            raise RuntimeError("chaos: replica killed")

        # the kill: the victim's next dispatch raises and its scheduler
        # thread dies with the queue intact — what a SIGKILL leaves behind
        vpred._dispatch = _die

        def saw_dead() -> bool:
            # the router's OWN verdict, read from its flight record: the
            # reconciler sweeps the corpse out of replica_states() within
            # one tick, so polling the live table races the heal
            return any(
                e["kind"] == "replica_dead" and e.get("replica") == victim
                for e in _trail(t0)
            )

        state = np.zeros((16, 1), np.uint8)
        submitted = 0
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline and not saw_dead():
            for _ in range(8):  # keep both replicas fed until the verdict
                router.put_block_task(
                    state,
                    lambda *a: served.append(1),
                    shed_callback=lambda rej: sheds.append(
                        getattr(rej, "reason", "?")
                    ),
                )
                submitted += 1
            time.sleep(0.2)
        out["replica_dead_verdict"] = saw_dead()
        healed = False
        deadline = time.monotonic() + args.settle_timeout
        while time.monotonic() < deadline:
            ids = rs.replica_ids()
            states = router.replica_states()
            if (
                victim not in ids
                and len(ids) >= 2
                and all(states.get(r) == "up" for r in ids)
            ):
                healed = True
                break
            time.sleep(0.1)
        # drain: every submitted task must RESOLVE (served or typed shed)
        deadline = time.monotonic() + 10.0
        while (
            len(served) + len(sheds) < submitted
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        out.update({
            "healed_to_target": healed,
            "final_replicas": rs.replica_ids(),
            "heal_actions": _heal_count("serving") - heal_before,
            "submitted_tasks": submitted,
            "served_tasks": len(served),
            "shed_tasks": len(sheds),
            "unresolved_tasks": submitted - len(served) - len(sheds),
            "sheds_by_reason": {
                r: sheds.count(r) for r in sorted(set(sheds))
            },
        })
        out["ok"] = bool(
            out["replica_dead_verdict"]
            and healed
            and out["heal_actions"] >= 1
            and out["unresolved_tasks"] == 0
        )
        return out
    finally:
        out["decisions"] = _trail(t0)
        rec.close()
        rs.close()  # the bench owns the set (ServingResource.retire defers)
        router.stop()
        router.join(timeout=5)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--seed", type=int, default=17)
    ap.add_argument(
        "--short", action="store_true",
        help="CI schedule: identical gates, smaller shapes (fewer warmup "
        "datapoints, 4 s partition, shorter learner epochs)",
    )
    ap.add_argument("--fleet_sims", type=int, default=4)
    ap.add_argument("--warmup_datapoints", type=int, default=128)
    ap.add_argument("--post_heal_datapoints", type=int, default=64)
    ap.add_argument("--pod_warmup_updates", type=int, default=3)
    ap.add_argument("--pod_heal_updates", type=int, default=2)
    ap.add_argument(
        "--partition_s", type=float, default=10.0,
        help="netchaos full-partition length (the committed capture's 10 s)",
    )
    ap.add_argument("--net_sims", type=int, default=2)
    ap.add_argument("--warmup_timeout_net", type=float, default=240.0)
    ap.add_argument("--failover_steps_per_epoch", type=int, default=60)
    ap.add_argument("--settle_timeout", type=float, default=90.0)
    args = ap.parse_args()
    if args.short:
        args.fleet_sims = 3
        args.warmup_datapoints = 48
        args.post_heal_datapoints = 24
        args.partition_s = 4.0
        args.failover_steps_per_epoch = 40

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    telemetry.reset_all()
    rng = random.Random(args.seed)
    failures: list = []

    fleet = _phase_fleet(args, rng)
    stderr_print(
        f"fleet:     killed slot {fleet.get('killed_slot')}, settled="
        f"{fleet.get('settled')}, {fleet.get('heal_actions', 0):.0f} heal "
        f"actions, {fleet.get('post_heal_datapoints', 0)} post-heal "
        f"datapoints"
    )
    if not fleet["ok"]:
        failures.append(f"fleet phase FAILED: {json.dumps(fleet)[:500]}")

    pod = _phase_pod(args, rng)
    stderr_print(
        f"pod:       killed host {pod.get('killed_host')} (whole group), "
        f"settled={pod.get('settled')}, {pod.get('host_respawns', 0)} host "
        f"respawns, {pod.get('post_kill_updates', 0)} post-kill updates, "
        f"{pod.get('learner_restarts', 0)} learner restarts"
    )
    if not pod["ok"]:
        failures.append(f"pod phase FAILED: {json.dumps(pod)[:500]}")

    partition = _phase_partition(args)
    stderr_print(
        f"partition: {args.partition_s:.0f}s full partition, recovered="
        f"{partition['recovered']}, replay={partition['replay_ok']}"
    )
    if not partition["ok"]:
        failures.append(
            "netchaos partition phase FAILED: "
            f"{json.dumps(partition['detail'])[:500]}"
        )

    learner = _phase_learner(args)
    stderr_print(
        f"learner:   killed at step {learner.get('killed_at_step')}, "
        f"resumes {learner.get('resumes', 0):.0f}, rc {learner.get('rc')}, "
        f"final step {learner.get('final_step')}"
    )
    if not learner["ok"]:
        failures.append(f"learner phase FAILED: {json.dumps(learner)[:800]}")

    serving = _phase_serving(args, rng)
    stderr_print(
        f"serving:   killed {serving.get('killed_replica')}, dead verdict="
        f"{serving.get('replica_dead_verdict')}, healed="
        f"{serving.get('healed_to_target')}, unresolved "
        f"{serving.get('unresolved_tasks')}"
    )
    if not serving["ok"]:
        failures.append(f"serving phase FAILED: {json.dumps(serving)[:500]}")

    flight = telemetry.flight_recorder()
    dump_path = flight.dump("reconcile bench complete")
    # the accumulated per-phase trails ARE the decision record (the
    # netchaos rig resets telemetry mid-run, so a single events_since(0)
    # at the end would only cover the tail phases)
    trail = (
        fleet.get("decisions", []) + pod.get("decisions", [])
        + learner.get("decisions", []) + serving.get("decisions", [])
    )
    healed_classes = sum(
        1 for p in (fleet, pod, learner, serving) if p["ok"]
    )
    out = {
        "metric": "reconcile_chaos_classes_healed",
        "value": healed_classes,
        "unit": "resource classes SIGKILLed and healed to spec (of 4)",
        "seed": args.seed,
        "short": bool(args.short),
        "partition_recovered": partition["recovered"],
        "partition_replay_ok": partition["replay_ok"],
        "fleet": fleet,
        "pod": pod,
        "partition": partition,
        "learner": learner,
        "serving": serving,
        "reconciler_series": telemetry.registry("reconciler").scalars(),
        "flight_dump": dump_path,
        "flight_event_kinds": sorted({e["kind"] for e in trail}),
        "decision_trail": trail[-200:],
    }
    # evidence prints BEFORE the verdict (the repo's bench contract): the
    # per-phase detail and the decision trail matter most on a failure
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
