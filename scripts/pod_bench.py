#!/usr/bin/env python
"""Pod acceptance gate: aggregate scaling, the staleness curve, host loss.

Exercises the whole pod parameter plane (docs/pod.md) device-free on
localhost tcp and prints ONE JSON line (the repo's bench-tooling
contract, like chaos_bench/plane_bench):

1. **aggregate**: for each host count in ``--hosts``, a real pod — N
   supervised ``pod.host`` processes (fake envs, equal per-host shape)
   against one bounded-staleness learner — measured as env-steps/s
   ARRIVING at the learner's ingest. GATE: 2 hosts must aggregate
   >= ``--gate`` (default 1.6x) the single-host rate measured in the
   same session. This is the scaling story the reference paper's 64-node
   PS cluster hand-tended, run by the orchestrator.
2. **staleness curve**: the measurement the paper never published —
   LaggedBlockDriver rollouts at measured lag k (jax pong, device-free)
   for each ``--lags`` entry, reporting mean ``value_lag_mae``, mean
   rho, and the ``params_lag`` histogram; plus a ``--max_staleness``
   rejection demo showing the typed counter engage while the consuming
   loop keeps draining.
3. **host-kill chaos rep**: with 2 hosts live, SIGKILL one host's whole
   process GROUP mid-run. The learner must keep training on the
   survivor (no learner restart — ``learner_restarts_total`` stays 0),
   the supervisor must respawn the host, and its rejoined cache must
   catch back up to the current params version.

Evidence prints BEFORE the verdict; exit 1 if any gate fails. The
committed full-shape capture is ``runs/pod_bench_r12.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _free_port_base() -> str:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"tcp://127.0.0.1:{port}", f"tcp://127.0.0.1:{port + 1}"


def _cfg(args):
    from distributed_ba3c_tpu.config import BA3CConfig

    return BA3CConfig(
        image_size=(args.image_size, args.image_size),
        frame_history=4,
        num_actions=4,
        fc_units=args.fc_units,
        local_time_max=args.unroll_len,
        predict_batch_size=16,
    )


def _phase_aggregate(args, n_hosts: int) -> dict:
    """One pod at ``n_hosts`` actor hosts; env-steps/s at the ingest."""
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.orchestrate.pod import (
        PodLearnerPlane,
        PodSupervisor,
        host_argv,
    )
    from distributed_ba3c_tpu.telemetry.attribution import stall_attribution

    telemetry.reset_all()
    c2s, s2c = _free_port_base()
    plane = PodLearnerPlane(
        _cfg(args), c2s, s2c, max_staleness=args.max_staleness or None
    )
    plane.start()
    sup = PodSupervisor(
        n_hosts,
        lambda i: host_argv(
            i, c2s, s2c, env="fake", n_sims=args.sims_per_host,
            unroll_len=args.unroll_len,
            segments_per_block=args.segments_per_block,
            image_size=args.image_size, frame_history=4, num_actions=4,
            fc_units=args.fc_units,
        ),
        backoff_base_s=0.25,
    )
    sup.start()
    reg = telemetry.registry("learner")
    c_steps = reg.counter("pod_ingest_env_steps_total")
    c_blocks = reg.counter("pod_ingest_blocks_total")
    try:
        # warmup: every host reported at least one block (startup includes
        # a jax import + predictor bucket warmup per host)
        deadline = time.monotonic() + args.warmup_timeout
        while time.monotonic() < deadline:
            plane.step_once(timeout=0.2)
            if c_blocks.value() >= 2 * n_hosts and len(
                [r for r in telemetry.all_registries()
                 if r.startswith("pod.host")]
            ) >= n_hosts:
                break
        else:
            raise RuntimeError(
                f"pod produced no warmup blocks from {n_hosts} hosts — "
                f"{stall_attribution()}"
            )
        window_rates = []
        for _ in range(max(1, args.windows)):
            n0, t0 = c_steps.value(), time.perf_counter()
            wdeadline = t0 + args.seconds
            while time.perf_counter() < wdeadline:
                plane.step_once(timeout=0.05)
            dt = time.perf_counter() - t0
            window_rates.append(round((c_steps.value() - n0) / dt, 1))
        hosts_reporting = sorted(
            r for r in telemetry.all_registries() if r.startswith("pod.host")
        )
        return {
            "hosts": n_hosts,
            "rate": max(window_rates),  # best window: scheduler-noise filter
            "window_rates": window_rates,
            "updates": int(plane.learner.version),
            "ingest_blocks": int(c_blocks.value()),
            "ingest_dropped": int(
                reg.counter("pod_ingest_dropped_total").value()
            ),
            "stale_rejected": int(
                reg.counter("stale_blocks_rejected_total").value()
            ),
            "hosts_reporting": hosts_reporting,
        }
    finally:
        sup.stop()
        sup.join(timeout=5)
        sup.close()
        plane.close()


def _phase_staleness_curve(args) -> dict:
    """value_lag_mae / params_lag at measured lag k, device-free (pong)."""
    import jax

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import create_fused_state
    from distributed_ba3c_tpu.fused.overlap import make_overlap_step
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh
    from distributed_ba3c_tpu.parallel.train_step import create_train_state
    from distributed_ba3c_tpu.pod.learner import (
        LaggedBlockDriver,
        PodLearner,
        make_pod_learner_step,
    )

    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=args.fc_units)
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(
        cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm
    )
    mesh = make_mesh(num_data=1, devices=jax.devices()[:1])
    ostep = make_overlap_step(
        model, opt, cfg, mesh, pong, rollout_len=args.unroll_len
    )
    pstep = make_pod_learner_step(model, opt, cfg, mesh)
    n_envs = 2

    curve = []
    for lag in args.lags:
        telemetry.reset_all()
        learner = PodLearner(
            pstep, create_train_state(jax.random.PRNGKey(0), model, cfg, opt),
            cfg,
        )
        learner.learning_rate = args.curve_lr
        drv = LaggedBlockDriver(ostep, learner, lag=lag)
        drv.prime(
            ostep.put(
                create_fused_state(
                    jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs,
                    n_shards=1,
                )
            )
        )
        maes, rhos = [], []
        for _ in range(args.lag_iters):
            m = drv.iterate()
            maes.append(float(m["value_lag_mae"]))
            rhos.append(float(m["mean_rho"]))
        post_ramp = maes[lag:] or maes
        hist = telemetry.registry("learner").histogram(
            "params_lag", unit=1
        ).collect()
        curve.append({
            "lag": lag,
            "value_lag_mae_mean": round(sum(post_ramp) / len(post_ramp), 6),
            "mean_rho": round(sum(rhos) / len(rhos), 6),
            "params_lag_hist": {
                "count": hist["count"],
                "sum": hist["sum"],
                "buckets": hist["buckets"][:8],
            },
            "iters": args.lag_iters,
        })

    # the bound engaging: lag 2x the bound, rejections counted, loop drains
    telemetry.reset_all()
    bound = max(1, args.max_staleness or 2)
    learner = PodLearner(
        pstep, create_train_state(jax.random.PRNGKey(0), model, cfg, opt),
        cfg, max_staleness=bound,
    )
    drv = LaggedBlockDriver(ostep, learner, lag=2 * bound)
    drv.prime(
        ostep.put(
            create_fused_state(
                jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs,
                n_shards=1,
            )
        )
    )
    consumed = rejected = 0
    # the driver's snapshot ring takes 2*bound iterations to ramp past
    # the bound — size the demo to ITS lag, not the curve's iter count,
    # or a small --lag_iters never reaches a rejectable staleness
    for _ in range(max(args.lag_iters, 2 * bound + 6)):
        if drv.iterate() is None:
            rejected += 1
        else:
            consumed += 1
    return {
        "curve": curve,
        "rejection_demo": {
            "bound": bound,
            "driver_lag": 2 * bound,
            "consumed": consumed,
            "rejected": rejected,
            "stale_blocks_rejected_total": int(
                telemetry.registry("learner")
                .counter("stale_blocks_rejected_total").value()
            ),
        },
    }


def _phase_host_kill(args) -> dict:
    """SIGKILL one of two hosts mid-run; recovery without learner restart."""
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.orchestrate.pod import (
        PodLearnerPlane,
        PodSupervisor,
        host_argv,
    )

    telemetry.reset_all()
    c2s, s2c = _free_port_base()
    plane = PodLearnerPlane(_cfg(args), c2s, s2c, max_staleness=None)
    plane.start()
    sup = PodSupervisor(
        2,
        lambda i: host_argv(
            i, c2s, s2c, env="fake", n_sims=args.sims_per_host,
            unroll_len=args.unroll_len,
            segments_per_block=args.segments_per_block,
            image_size=args.image_size, frame_history=4, num_actions=4,
            fc_units=args.fc_units,
        ),
        backoff_base_s=0.25,
    )
    sup.start()
    out = {"recovered": False}
    try:
        def train_until(n, timeout):
            deadline = time.monotonic() + timeout
            while plane.learner.version < n and time.monotonic() < deadline:
                plane.step_once(timeout=0.5)
            return plane.learner.version >= n

        if not train_until(5, args.warmup_timeout):
            out["error"] = "pod never reached 5 updates before the kill"
            return out
        v_kill = plane.learner.version
        out["killed_at_version"] = v_kill
        assert sup.sigkill_slot(0)
        out["survivor_progress"] = train_until(v_kill + 5, 120)
        # respawn + rejoin: the killed host's mirrored params_version must
        # catch up to the post-kill publish frontier
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            plane.step_once(timeout=0.5)
            g = telemetry.registry("pod.host0").scalars()
            if g.get("params_version", -1) >= v_kill:
                out["rejoined_at_version"] = g["params_version"]
                break
        out["respawns"] = int(
            telemetry.registry("orchestrator")
            .counter("server_respawns_total").value()
        )
        out["learner_restarts"] = int(
            telemetry.registry("orchestrator")
            .counter("learner_restarts_total").value()
        )
        out["final_version"] = int(plane.learner.version)
        out["recovered"] = bool(
            out.get("survivor_progress")
            and "rejoined_at_version" in out
            and out["respawns"] >= 1
            and out["learner_restarts"] == 0
        )
        return out
    finally:
        sup.stop()
        sup.join(timeout=5)
        sup.close()
        plane.close()


def _phase_net(args) -> dict:
    """The emulated-DCN rows (ISSUE 13 / ROADMAP item 2a): the same pod,
    measured through netchaos proxies — a quiet-proxy control, one row
    per (RTT, loss) point, the partition-and-heal rep, the live
    corruption rep against CRC-armed codecs, and a seed-replay verdict
    on every rep (docs/netchaos.md). Committed capture:
    ``runs/netchaos_bench_r14.json``."""
    from distributed_ba3c_tpu.netchaos.bench import (
        NetShape,
        dcn_schedule,
        quiet_schedule,
        run_corrupt_rep,
        run_partition_rep,
        run_throughput_rep,
    )
    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    shape = NetShape(
        hosts=args.net_hosts,
        sims_per_host=args.sims_per_host,
        segments_per_block=args.segments_per_block,
        unroll_len=args.unroll_len,
        image_size=args.image_size,
        fc_units=args.fc_units,
        max_staleness=args.max_staleness,
        warmup_timeout=args.warmup_timeout,
    )
    clean = run_throughput_rep(
        shape, quiet_schedule(args.net_seed), args.seconds, args.windows
    )
    stderr_print(f"net clean (quiet proxies): {clean['rate']:>9.1f} env-steps/s")
    rows = []
    for spec in str(args.net_points).split(","):
        if not spec:
            continue
        rtt_s, loss_s = spec.split(":")
        rtt, loss = float(rtt_s), float(loss_s)
        r = run_throughput_rep(
            shape, dcn_schedule(rtt, loss, seed=args.net_seed),
            args.seconds, args.windows,
        )
        row = {
            "rtt_ms": rtt,
            "loss": loss,
            "rate": r["rate"],
            "window_rates": r["window_rates"],
            "over_clean": round(r["rate"] / max(clean["rate"], 1e-9), 4),
            "updates": r["updates"],
            "injected": r["injected"],
            "replay_match": r["replay"]["match"],
            "schedule": r["schedule"],
        }
        rows.append(row)
        stderr_print(
            f"net DCN {rtt:>5.0f}ms RTT / {100 * loss:4.1f}% loss: "
            f"{r['rate']:>9.1f} env-steps/s ({row['over_clean']:.3f}x clean, "
            f"replay {'ok' if row['replay_match'] else 'MISMATCH'})"
        )
    # a 10 s window outlasts the emulated wire's + the kernel's buffering
    # at this block rate, so the host's OWN bounds (SNDHWM -> spill ->
    # ship_backpressure_total) are what the artifact shows engaging
    partition = run_partition_rep(shape, args.net_seed, partition_s=10.0)
    stderr_print(
        f"net partition-and-heal: pre {partition['pre']['rate']:.1f} -> "
        f"partition {partition['partition']['rate']:.1f} -> heal "
        f"{partition['heal']['rate']:.1f} env-steps/s, rejoined at "
        f"v{partition['rejoined_at_version']}, learner restarts "
        f"{partition['learner_restarts']}, backpressure "
        f"{partition['ship_backpressure']}, recovered "
        f"{partition['recovered']}"
    )
    corrupt = run_corrupt_rep(shape, args.net_seed)
    stderr_print(
        f"net corruption: {corrupt['injected_mangled']} frames mangled -> "
        f"{corrupt['typed_rejects']} typed rejects, training continued "
        f"({corrupt['blocks']} blocks)"
    )
    gate_row = next(
        (
            r for r in rows
            if r["rtt_ms"] == args.net_rtt_ms and r["loss"] == args.net_loss
        ),
        None,
    )
    return {
        "clean": clean,
        "rows": rows,
        "gate_point": {"rtt_ms": args.net_rtt_ms, "loss": args.net_loss},
        "gate": args.net_gate,
        "gate_row_over_clean": gate_row["over_clean"] if gate_row else None,
        # the gate applies to the NAMED point only — verdicting a milder
        # row while the artifact claims 50ms/1% would be a silent lie, so
        # a sweep that omits the gate point FAILS with the reason named
        "gate_error": (
            None if gate_row else
            f"gate point {args.net_rtt_ms}:{args.net_loss} not in "
            f"--net_points {args.net_points!r}"
        ),
        "gate_passed": bool(
            gate_row and gate_row["over_clean"] >= args.net_gate
        ),
        "partition": partition,
        "corrupt": corrupt,
        "replay_ok": bool(
            clean["replay"]["match"]
            and all(r["replay_match"] for r in rows)
            and partition["replay"]["match"]
            and corrupt["replay"]["match"]
        ),
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hosts", default="1,2", help="comma-separated host counts for the aggregate phase (equal per-host shape)")
    ap.add_argument("--sims_per_host", type=int, default=4)
    ap.add_argument("--segments_per_block", type=int, default=16)
    ap.add_argument("--unroll_len", type=int, default=5)
    ap.add_argument("--image_size", type=int, default=16)
    ap.add_argument("--fc_units", type=int, default=16)
    ap.add_argument("--seconds", type=float, default=10.0, help="seconds per measurement window")
    ap.add_argument("--windows", type=int, default=3, help="windows per host count; best window is the rate (scheduler-noise filter)")
    ap.add_argument("--gate", type=float, default=1.6, help="2-host aggregate must be >= gate x single-host")
    ap.add_argument("--max_staleness", type=int, default=8)
    ap.add_argument("--lags", default="0,1,2,4,8", help="measured-lag points of the staleness curve")
    ap.add_argument("--lag_iters", type=int, default=24)
    ap.add_argument("--curve_lr", type=float, default=1e-2, help="curve-phase LR (large enough that lag shows in value drift)")
    ap.add_argument("--warmup_timeout", type=float, default=240.0)
    ap.add_argument("--skip_curve", action="store_true")
    ap.add_argument("--skip_chaos", action="store_true")
    ap.add_argument(
        "--net", action="store_true",
        help="add the netchaos emulated-DCN phase (docs/netchaos.md): "
        "per-(RTT, loss) throughput rows through real proxy pumps, the "
        "partition-and-heal rep, the CRC corruption rep, seed-replay "
        "verdicts — the rows ROADMAP item 2a owed",
    )
    ap.add_argument(
        "--net_only", action="store_true",
        help="run ONLY the netchaos phase (skips aggregate/curve/chaos)",
    )
    ap.add_argument("--net_hosts", type=int, default=1, help="pod hosts in the netchaos phase")
    ap.add_argument("--net_points", default="10:0.001,50:0.01,100:0.02", help="comma-separated rtt_ms:loss rows")
    ap.add_argument("--net_rtt_ms", type=float, default=50.0, help="the (rtt, loss) row the gate applies to")
    ap.add_argument("--net_loss", type=float, default=0.01)
    ap.add_argument("--net_gate", type=float, default=0.85)
    ap.add_argument("--net_seed", type=int, default=0)
    args = ap.parse_args()
    args.lags = [int(x) for x in str(args.lags).split(",") if x != ""]
    host_counts = [int(x) for x in str(args.hosts).split(",") if x != ""]

    from distributed_ba3c_tpu.utils.devicelock import stderr_print

    failures = []
    net = None
    if args.net or args.net_only:
        net = _phase_net(args)
        if not net["gate_passed"]:
            failures.append(
                net["gate_error"]
                or f"netchaos DCN gate FAILED: {net['gate_row_over_clean']}x"
                f" clean at {args.net_rtt_ms:.0f}ms/{args.net_loss:.3f} "
                f"(gate >= {args.net_gate})"
            )
        if not net["partition"]["recovered"]:
            failures.append(
                f"netchaos partition-and-heal FAILED: {net['partition']}"
            )
        if not net["corrupt"]["all_typed"]:
            failures.append(
                f"netchaos corruption rep FAILED (untyped or zero rejects): "
                f"{net['corrupt']}"
            )
        if not net["replay_ok"]:
            failures.append(
                "netchaos seed-replay mismatch (rep not reproducible)"
            )
        if args.net_only:
            out = {
                "metric": "netchaos_pod_dcn_over_clean",
                "value": net["gate_row_over_clean"],
                "unit": "ratio (degraded/clean ingest env-steps/s)",
                "hosts": args.net_hosts,
                "sims_per_host": args.sims_per_host,
                "segments_per_block": args.segments_per_block,
                "unroll_len": args.unroll_len,
                "image_size": args.image_size,
                "fc_units": args.fc_units,
                "seconds": args.seconds,
                "windows": args.windows,
                "max_staleness": args.max_staleness,
                "net": net,
            }
            import jax

            # a CPU instrument by design: name the platform its rates came from
            out["platform"] = jax.default_backend()
            print(json.dumps(out))
            if failures:
                for msg in failures:
                    stderr_print(msg)
                return 1
            return 0

    aggregate = []
    for n in host_counts:
        r = _phase_aggregate(args, n)
        aggregate.append(r)
        stderr_print(
            f"aggregate {n} host(s): {r['rate']:>9.1f} env-steps/s "
            f"({r['updates']} updates, {r['ingest_blocks']} blocks, "
            f"{r['ingest_dropped']} dropped)"
        )
    by_hosts = {r["hosts"]: r["rate"] for r in aggregate}
    scaling = None
    if 1 in by_hosts and 2 in by_hosts:
        scaling = round(by_hosts[2] / max(by_hosts[1], 1e-9), 4)
        if scaling < args.gate:
            failures.append(
                f"aggregate scaling gate FAILED: 2-host rate {by_hosts[2]:.1f}"
                f" is {scaling:.2f}x the single-host {by_hosts[1]:.1f} "
                f"(gate: >= {args.gate}x at equal per-host shape)"
            )

    curve = None
    if not args.skip_curve:
        curve = _phase_staleness_curve(args)
        for p in curve["curve"]:
            stderr_print(
                f"staleness lag {p['lag']}: value_lag_mae "
                f"{p['value_lag_mae_mean']:.5f}, mean_rho {p['mean_rho']:.4f}"
            )
        rd = curve["rejection_demo"]
        stderr_print(
            f"rejection demo: bound {rd['bound']}, driver lag "
            f"{rd['driver_lag']} -> {rd['rejected']} rejected / "
            f"{rd['consumed']} consumed (loop kept draining)"
        )
        if rd["rejected"] < 1:
            failures.append(
                "staleness bound never rejected a block in the demo"
            )
        lag0 = next((p for p in curve["curve"] if p["lag"] == 0), None)
        lag_hi = curve["curve"][-1]
        # inversion check needs the lag-0 anchor; a --lags without 0 still
        # gets its points measured and printed, just not this verdict
        if (
            lag0 is not None
            and lag_hi["value_lag_mae_mean"] < lag0["value_lag_mae_mean"]
        ):
            failures.append(
                "staleness curve inverted: value_lag_mae at the highest "
                "lag is below lag 0"
            )

    chaos = None
    if not args.skip_chaos:
        chaos = _phase_host_kill(args)
        stderr_print(
            f"host-kill: killed at v{chaos.get('killed_at_version')}, "
            f"survivor progress {chaos.get('survivor_progress')}, "
            f"rejoined at v{chaos.get('rejoined_at_version')}, "
            f"respawns {chaos.get('respawns')}, learner restarts "
            f"{chaos.get('learner_restarts')}"
        )
        if not chaos["recovered"]:
            failures.append(
                f"host-loss chaos rep FAILED to recover without a learner "
                f"restart: {chaos}"
            )

    out = {
        "metric": "pod_aggregate_env_steps_per_sec",
        "value": by_hosts.get(max(host_counts), None),
        "unit": "env-steps/sec (learner-ingest aggregate)",
        "hosts": host_counts,
        "aggregate": aggregate,
        "scaling_2_over_1": scaling,
        "gate": args.gate,
        "gate_passed": scaling is None or scaling >= args.gate,
        "sims_per_host": args.sims_per_host,
        "segments_per_block": args.segments_per_block,
        "unroll_len": args.unroll_len,
        "image_size": args.image_size,
        "fc_units": args.fc_units,
        "seconds": args.seconds,
        "windows": args.windows,
        "max_staleness": args.max_staleness,
        "staleness": curve,
        "host_kill": chaos,
        "net": net,
    }
    # evidence prints BEFORE the verdict (plane_bench/chaos_bench precedent)
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
