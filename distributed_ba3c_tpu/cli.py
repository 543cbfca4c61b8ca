"""CLI assembly: flags → wired-up training/eval run.

Reference equivalent: ``src/train.py`` ``main``/``get_config`` (SURVEY.md §2.1
#1, §1 L7). The flag surface mirrors the reference's
(``--job_name/--task_index/--ps_hosts/--worker_hosts`` cluster flags, the
hyperparameter flags, ``--load``, ``--task``), and the trainer-selection slot
BASELINE.json pins is here: ``--trainer=tpu_sync_ba3c`` (default) selects the
mesh-sharded synchronous learner; ``--trainer=tpu_vtrace_ba3c`` the V-trace
off-policy variant.

PS-compat note: with the parameter-server plane gone (gradients are a psum
over ICI, SURVEY.md §2.12), ``--job_name ps`` is accepted and exits
immediately with an explanatory message — cluster launch scripts that spawn
ps tasks keep working, the ps tasks just have nothing to host.
"""

from __future__ import annotations

import argparse
import functools
import os
import queue
from typing import Optional

from distributed_ba3c_tpu.config import BA3CConfig


_MODEL_HELP = (
    "the policy, by its name in models/policy.py's registry: {models}. "
    "{default} is the reference's conv stack; the others are token-sequence "
    "policies that carry state (each one's module says what it is): "
    "--trainer tpu_fused_ba3c with a token env such as jax:recall, "
    "--rollout_len = the episode length")
_MODEL_CUT_HELP = (
    "what one chip holds of --model, by name, from the policy module's CUTS "
    "(which says what each holds), the default first: {cuts}; the widths "
    "are the model file's, as published")


class _Parser(argparse.ArgumentParser):
    """Names --model's and --model_cut's choices when help is asked for:
    they are the registry's and each policy module's, and listing the cuts
    imports every policy, which no run does only to parse its flags."""

    def format_help(self):
        from distributed_ba3c_tpu.models import policy

        cuts = "; ".join(f"{name}: {' | '.join(names)}"
                         for name, names in policy.cuts_by_model().items())
        for action in self._actions:
            if action.dest == "model":
                action.help = _MODEL_HELP.format(
                    models=" | ".join(policy.MODELS), default=policy.DEFAULT_MODEL)
            elif action.dest == "model_cut":
                action.help = _MODEL_CUT_HELP.format(cuts=cuts)
        return super().format_help()


def make_parser() -> argparse.ArgumentParser:
    from distributed_ba3c_tpu.models import policy

    p = _Parser(
        description="TPU-native Distributed-BA3C",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    # -- reference cluster-spec surface (SURVEY.md §1 L7) ------------------
    p.add_argument("--job_name", choices=["ps", "worker"], default="worker")
    p.add_argument("--task_index", type=int, default=0)
    p.add_argument("--ps_hosts", default="", help="accepted for CLI compat; unused (no parameter servers on TPU)")
    p.add_argument("--worker_hosts", default="", help="comma-separated worker host list (multi-host DCN bootstrap)")
    # -- trainer selection slot (BASELINE.json gate) -----------------------
    p.add_argument(
        "--trainer",
        default="tpu_sync_ba3c",
        choices=["tpu_sync_ba3c", "tpu_vtrace_ba3c", "tpu_fused_ba3c"],
        help="learner backend: sync psum A2C, V-trace off-policy, or fully on-device fused rollout+update",
    )
    # -- run mode ----------------------------------------------------------
    p.add_argument("--task", default="train", choices=["train", "eval", "play"])
    p.add_argument("--env", default="fake", help="fake | jax:<name> (on-device env, e.g. jax:pong) | cpp:<name> (native batched core) | gym:<name> (gymnasium adapter) | zmq:<game> (REMOTE env-server fleets play <game> and connect to --pipe_c2s/--pipe_s2c; no local simulators)")
    p.add_argument(
        "--wire",
        default="auto",
        choices=["auto", "block-shm", "block", "per-env"],
        help="actor-plane wire protocol for batched env servers (cpp:*): "
        "block-shm = tiny control messages + obs through a /dev/shm ring "
        "(same-host, fastest); block = one zero-copy multipart message per "
        "server per step (the tcp:// remote-fleet wire); per-env = B "
        "separate msgpack messages per step (reference-compatible compat "
        "foil); auto = block-shm when /dev/shm is available, else block "
        "(docs/actor_plane.md). The master autodetects per message, so "
        "mixed fleets work; per-process simulators (fake/gym:/jax:) always "
        "speak per-env",
    )
    p.add_argument(
        "--wire_crc",
        action="store_true",
        help="CRC32 integrity framing on every wire codec (block, "
        "block-shm control, per-env, pod params/experience): a corrupted "
        "or truncated frame becomes a typed corrupt_frame reject at the "
        "receiver instead of a silently wrong array. Exported as "
        "BA3C_WIRE_CRC=1 so spawned env servers / pod hosts agree "
        "(docs/netchaos.md); worth ~one memory pass per message — "
        "recommended for any real-DCN fleet, off by default on loopback",
    )
    p.add_argument("--load", default=None, help="checkpoint dir to resume from")
    p.add_argument("--logdir", default="train_log/ba3c")
    # -- hyperparams (reference argparse defaults, SURVEY.md §2.9) ---------
    p.add_argument("--learning_rate", type=float, default=None)
    p.add_argument("--entropy_beta", type=float, default=None)
    p.add_argument("--gamma", type=float, default=None)
    p.add_argument("--batch_size", type=int, default=None)
    p.add_argument("--local_time_max", type=int, default=None)
    p.add_argument("--simulator_procs", type=int, default=None)
    p.add_argument("--predict_batch_size", type=int, default=None)
    p.add_argument("--predictor_threads", type=int, default=None)
    p.add_argument("--fc_units", type=int, default=None)
    p.add_argument("--image_size", type=int, default=None, help="square observation size")
    p.add_argument("--frame_history", type=int, default=None)
    p.add_argument("--grad_clip_norm", type=float, default=None)
    p.add_argument("--adam_epsilon", type=float, default=None)
    p.add_argument("--reward_clip", type=float, default=None, help="clip learning rewards to [-c, c] (0=off); episode scores stay raw")
    # -- loop shape --------------------------------------------------------
    p.add_argument("--steps_per_epoch", type=int, default=1000)
    p.add_argument("--max_epoch", type=int, default=100)
    # None sentinel so external-fleet mode can tell an EXPLICIT --nr_eval
    # (worth a warning when dropped) from the default
    p.add_argument("--nr_eval", type=int, default=None)
    p.add_argument("--eval_every", type=int, default=1, help="epochs between Evaluator runs")
    p.add_argument("--eval_max_steps", type=int, default=10000, help="greedy-eval step horizon (fused trainer; must cover a full episode)")
    p.add_argument("--num_actions", type=int, default=4)
    p.add_argument("--mesh_data", type=int, default=None, help="data-axis size (defaults to all devices)")
    p.add_argument("--publish_every", type=int, default=1)
    p.add_argument("--model", default=policy.DEFAULT_MODEL, help=_MODEL_HELP)
    p.add_argument("--model_cut", default=None, help=_MODEL_CUT_HELP)
    p.add_argument("--rollout_len", type=int, default=20, help="fused-trainer rollout length per update")
    p.add_argument("--grad_chunk_samples", type=int, default=4096, help="fused-trainer learner chunk size (HBM activation cap)")
    p.add_argument("--actor_timeout", type=float, default=120.0, help="seconds of actor silence before its state is dropped (0=off)")
    p.add_argument("--entropy_beta_final", type=float, default=None, help="anneal entropy beta to this over max_epoch (ScheduledHyperParamSetter)")
    p.add_argument("--learning_rate_final", type=float, default=None, help="anneal LR to this over max_epoch (ScheduledHyperParamSetter)")
    p.add_argument("--anneal", default="linear", choices=["linear", "exp"], help="shape of the *_final anneals: linear or geometric (exp)")
    p.add_argument("--anneal_lr", default=None, choices=["linear", "exp"], help="override --anneal for learning_rate only (β and lr want different shapes: β drops early, lr holds through the mid-game)")
    p.add_argument("--anneal_beta", default=None, choices=["linear", "exp"], help="override --anneal for entropy_beta only")
    # -- multi-fleet macro-batching (docs/actor_plane.md) ------------------
    p.add_argument(
        "--fleets", type=int, default=1,
        help="N independent actor fleets feeding this learner (ZMQ-plane "
        "trainers, train task): each fleet gets its own pipe pair (derived "
        "from --pipe_c2s/--pipe_s2c or the ipc defaults), master, "
        "predictor, supervisor and telemetry identity (master.f<k>...); "
        "per-fleet queues merge through a fair round-robin collator and "
        "the learner runs the gradient-accumulation MACRO step — N "
        "full-recipe sub-batches, one update, fleet axis sharded over "
        "chips so every chip steps at its full-occupancy batch "
        "(docs/actor_plane.md). --simulator_procs is the TOTAL across "
        "fleets and must divide evenly",
    )
    p.add_argument(
        "--fleet_accum", type=int, default=1,
        help="fused --overlap only: rollout windows accumulated per "
        "update via the fused.macro_learner program — the fused half of "
        "multi-fleet macro-batching (per-update effective batch grows "
        "K-fold at unchanged per-window occupancy; V-trace corrects the "
        "1..K-update behavior lag)",
    )
    # -- elastic fleet orchestration (docs/orchestration.md) ---------------
    p.add_argument(
        "--fleet_min", type=int, default=0,
        help="autoscaler LOWER bound, in env-server processes (0 = the "
        "launch size). Local fleets (cpp:/fake/gym:/jax:) only — external "
        "zmq: fleets are supervised on their own hosts "
        "(scripts/launch_env_fleet.py)",
    )
    p.add_argument(
        "--fleet_max", type=int, default=0,
        help="autoscaler UPPER bound, in env-server processes (0 = the "
        "launch size). fleet_max > fleet_min enables the telemetry-driven "
        "autoscaler: the fleet grows when the train queue starves and "
        "shrinks under blocked-put backpressure (docs/orchestration.md)",
    )
    p.add_argument(
        "--autoscale_interval", type=float, default=2.0,
        help="seconds between autoscaler policy ticks",
    )
    # -- SLO-aware serving plane (docs/serving.md) -------------------------
    p.add_argument(
        "--serve_slo_ms", type=float, default=0.0,
        help="predictor serving deadline budget in ms (0 = off). Every "
        "queued predict task gets deadline = admit + slo; tasks the "
        "scheduler proves can't make it are SHED with a typed reject "
        "(masters fall back to a uniform-random action) and a full "
        "admission queue rejects fast instead of queueing unboundedly",
    )
    p.add_argument(
        "--canary_load", default=None,
        help="checkpoint dir served as the 'canary' policy on "
        "--canary_fraction of live predict traffic (multi-policy serving; "
        "per-policy rows on the telemetry endpoint)",
    )
    p.add_argument(
        "--canary_fraction", type=float, default=0.0,
        help="fraction of predict traffic routed to --canary_load "
        "(deterministic group-granular deficit split, no RNG, batch "
        "occupancy preserved)",
    )
    p.add_argument(
        "--shadow_load", default=None,
        help="checkpoint dir served as the 'shadow' policy: mirrors EVERY "
        "served batch, results dropped before any caller — pure "
        "observability (tele/predictor/shadow_* series)",
    )
    p.add_argument(
        "--serve_replicas", type=int, default=1,
        help="serve each fleet's predict traffic from R replicated "
        "serving planes behind the SLO router (predict/router.py): "
        "least-loaded dispatch with deadline-aware overflow, per-replica "
        "health from their telemetry series, typed re-shed of a dead "
        "replica's traffic. 1 = the single PR-9 plane, unchanged",
    )
    p.add_argument(
        "--serve_replicas_max", type=int, default=0,
        help="enable the serving autoscaler up to this replica bound "
        "(requires --serve_slo_ms; grows from the --serve_replicas base, "
        "routing the plane even at a base of 1): replicas are "
        "added on served-p99/shed-rate SLO pressure and retired on "
        "slack, every decision flight-recorded (orchestrate/serving.py). "
        "0 = fixed replica count",
    )
    p.add_argument(
        "--canary_autopromote", action="store_true",
        help="hand the --canary_load candidate to the PromotionController "
        "(requires --serve_replicas > 1, --serve_slo_ms and --fleets 1): "
        "auto-ROLLBACK on canary SLO breach is armed from live "
        "latency/shed evidence; reward-based auto-PROMOTION additionally "
        "needs a reward feed (PromotionController.observe_reward — see "
        "docs/serving.md). Off = the canary split is static, as before",
    )
    p.add_argument("--profiler_port", type=int, default=0, help="start jax.profiler server on this port (0=off)")
    p.add_argument("--telemetry_port", type=int, default=0, help="serve the telemetry scrape endpoint on this port (0=off): /metrics Prometheus text, /json raw snapshots, /flight the live flight-recorder ring, /trace the span buffer (docs/observability.md)")
    p.add_argument("--trace_sample", type=int, default=0, help="trace 1 in N block steps through the distributed trace plane (0=off): sampled causal spans env-step->learner-step with per-hop hop_<name>_s histograms, scraped at /trace and rendered by scripts/trace_dump.py (docs/observability.md)")
    p.add_argument("--pipe_c2s", default=None, help="master experience-plane bind address, e.g. tcp://0.0.0.0:5555 (default: per-pid ipc://)")
    p.add_argument("--pipe_s2c", default=None, help="master action-plane bind address, e.g. tcp://0.0.0.0:5556 (default: per-pid ipc://)")
    p.add_argument("--max_to_keep", type=int, default=3, help="checkpoints retained (besides best); raise to keep every eval-epoch checkpoint for post-hoc crossing verification")
    p.add_argument("--steps_per_dispatch", type=int, default=1, help="fused trainer: wrap K update steps in one lax.scan program (one host dispatch per K updates; must divide --steps_per_epoch). Removes per-step dispatch overhead without relying on host pipelining. With --overlap, K actor/learner dispatch PAIRS per facade call instead")
    p.add_argument("--overlap", action="store_true", help="fused trainer: split the single fused program into two overlapped compiled programs — rollout k+1 runs concurrently with learner k (policy lag 1, V-trace-corrected; docs/overlap.md)")
    p.add_argument("--rollout_dtype", default="float32", choices=["float32", "bfloat16", "int8"], help="rollout/serving forward precision, END TO END (the learner always keeps f32): with --overlap it is the actor program's params-snapshot dtype; on the ZMQ trainers it is the BatchedPredictor's param storage (every policy publish casts on device). bfloat16 halves the forward's param-read bandwidth; int8 quarters it with per-channel symmetric weight quantization (requires a calibration source: --quant_spec or --quant_calibrate; heads stay f32; docs/ingest.md). Audit-pinned as predict.server_bf16 / fused.actor_bf16 / predict.server_int8 / fused.actor_int8")
    p.add_argument("--quant_spec", default=None, help="int8 rung: path to a frozen QuantSpec JSON (quantize/spec.py) carrying the per-layer activation scales — the offline/pre-frozen calibration source. Exactly one of --quant_spec / --quant_calibrate with --rollout_dtype int8")
    p.add_argument("--quant_calibrate", type=int, default=0, help="int8 rung: calibrate activation scales live from the first N served batches (ZMQ trainers: the PR-9 shadow tap observes real traffic, serving stays f32 until the spec freezes, then the plane switches to int8 in place; fused --overlap trainer: N f32 rollout windows through the actor's own scan body before the int8 program is built). 0 = off")
    p.add_argument("--ingest_staging", default="on", choices=["on", "off"], help="ZMQ trainers: zero-copy pinned-staging ingest (data/staging.py) — collate writes obs bytes straight into preallocated double-buffered staging arrays (ONE host copy per block, ingest_copies_total proves it) and the next batch's H2D dispatches behind the running step. off = the legacy materialize->collate->device_put chain (the plane_bench --ingest foil)")
    p.add_argument("--rank_stall_timeout", type=float, default=0, help="multi-host: seconds without proven progress (beats land after the dispatch-window metrics fetch, after eval, and after the collective save) before a rank declares a peer dead and exits 75 (0 = default 600s when multi-host; -1 disables the watchdog; the limit self-raises to 2x the slowest healthy window). Relaunch with --load to resume")
    p.add_argument("--seed", type=int, default=0, help="fused trainer: PRNG seed for params/envs/action sampling (whole-trajectory determinism per seed; multi-seed runs disclose seed selection in RESULTS.md)")
    p.add_argument(
        "--dump_topology", action="store_true",
        help="print the TopologySpec JSON this flag set describes and "
        "exit (migration aid toward `python -m "
        "distributed_ba3c_tpu.orchestrate --topology spec.json`; "
        "docs/topology.md)",
    )
    p.add_argument("--tpu_lock", default="wait", choices=["wait", "fail", "off"], help="host-local TPU-claim mutex (utils/devicelock.py): wait = queue behind the current holder, fail = exit with the holder's pid/run, off = no guard. CPU-platform runs never take the lock")
    return p


def env_num_actions(args) -> int:
    """Derive the action-space size from the selected env (every trainer must
    build the policy head against the ENV's space, not the flag default)."""
    if args.env.startswith(("jax:", "cpp:", "zmq:")) and args.env != "zmq:":
        # jaxenv and the C++ core keep identical action maps (tested
        # parity); zmq:<game> names the game the EXTERNAL fleets play, so
        # the policy head still gets the right action space. An unknown
        # zmq: game fails LOUDLY — a silent --num_actions fallback would
        # train a wrong-sized policy head against the fleet.
        from distributed_ba3c_tpu.envs import jaxenv

        try:
            return jaxenv.get_env(args.env.split(":", 1)[1]).num_actions
        except ValueError:
            if not args.env.startswith("zmq:"):
                raise
            raise SystemExit(
                f"--env {args.env}: unknown game — for fleets playing a "
                "game this build doesn't know, use bare '--env zmq:' plus "
                "an explicit --num_actions"
            )
    return args.num_actions


def build_config(args) -> BA3CConfig:
    cfg = BA3CConfig()
    over = {}
    for f in (
        "learning_rate entropy_beta gamma batch_size local_time_max "
        "simulator_procs predict_batch_size predictor_threads fc_units "
        "frame_history grad_clip_norm adam_epsilon reward_clip"
    ).split():
        v = getattr(args, f)
        if v is not None:
            over[f] = v
    if args.image_size is not None:
        over["image_size"] = (args.image_size, args.image_size)
    over["num_actions"] = env_num_actions(args)
    return cfg.replace(**over)


def _build_player_factory(args, cfg: BA3CConfig):
    if args.env == "fake" or args.env.startswith("fake:"):
        from distributed_ba3c_tpu.envs.fake import build_fake_player

        return functools.partial(
            build_fake_player,
            image_size=cfg.image_size,
            frame_history=cfg.frame_history,
            num_actions=cfg.num_actions,
        )
    if args.env.startswith("jax:"):
        try:
            from distributed_ba3c_tpu.envs.jaxenv.host_adapter import (
                build_jax_player,
            )
        except ImportError as e:
            raise SystemExit(
                f"--env {args.env}: on-device env module unavailable ({e})"
            )
        return functools.partial(
            build_jax_player,
            name=args.env.split(":", 1)[1],
            frame_history=cfg.frame_history,
        )
    if args.env.startswith("cpp:"):
        from distributed_ba3c_tpu.envs import native

        if not native.available():
            raise SystemExit(
                f"--env {args.env}: native core unavailable — `make -C cpp` "
                "failed or could not run (its output is logged above)"
            )
        return functools.partial(
            native.build_cpp_player,
            name=args.env.split(":", 1)[1],
            frame_history=cfg.frame_history,
        )
    if args.env.startswith("gym:"):
        from distributed_ba3c_tpu.envs.gym_adapter import build_gym_player

        return functools.partial(
            build_gym_player,
            name=args.env.split(":", 1)[1],
            frame_history=cfg.frame_history,
            image_size=cfg.image_size,
        )
    if args.env.startswith("zmq:"):
        # external env servers already speak the simulator wire protocol —
        # there is no in-process player to build (train mode handles zmq:
        # before calling this; only --task eval/play land here)
        raise SystemExit(
            "--env zmq: has no in-process player (external fleets own the "
            "envs) — --task eval/play need a local env, e.g. --env cpp:pong"
        )
    raise ValueError(f"unknown --env {args.env!r}")


def main(argv: Optional[list] = None) -> int:
    parser = make_parser()
    args = parser.parse_args(argv)
    nr_eval_explicit = args.nr_eval is not None
    if args.nr_eval is None:
        args.nr_eval = 8

    if args.job_name == "ps":
        print(
            "ps job is obsolete on TPU: parameters are replicated in HBM and "
            "gradients ride a psum over ICI (no parameter servers). Exiting."
        )
        return 0

    # Spec-level validation BEFORE the lock: in wait mode a misconfigured
    # run would otherwise queue for hours behind the holder only to fail on
    # a check that needs no device (jax-touching validation stays below —
    # env-module imports may init the backend, which must not precede the
    # lock). The rules themselves live in TopologySpec (orchestrate/
    # topology.py) — the flag surface and a --topology document reject the
    # SAME impossible deployments, as clean exit-2 usage errors.
    from distributed_ba3c_tpu.orchestrate.topology import (
        TopologyError,
        TopologySpec,
    )

    try:
        topo = TopologySpec.from_flags(args)
    except TopologyError as e:
        parser.error(str(e))
    if args.dump_topology:
        print(topo.to_json())
        return 0

    # Take the host-local TPU claim BEFORE the first jax backend touch: a
    # chip belongs to one process, and queueing here beats failing inside
    # libtpu (OPERATIONS.md; utils/devicelock.py). No-op on the CPU platform.
    from distributed_ba3c_tpu.utils.devicelock import guard_tpu

    guard_tpu(args.logdir, mode=args.tpu_lock)  # held for process lifetime

    import jax

    from distributed_ba3c_tpu.utils.backend import configure_compile_cache

    configure_compile_cache()  # before the first jit
    _plat = os.environ.get("JAX_PLATFORMS", "")

    # Multi-host bootstrap BEFORE any device is touched (reference: the
    # ClusterSpec/Server must exist before graph placement, SURVEY.md §3.1).
    from distributed_ba3c_tpu.parallel.distributed import (
        initialize_from_flags,
        is_chief,
        local_batch_slice,
        make_global_mesh,
    )

    _multi_host = len([h for h in args.worker_hosts.split(",") if h]) > 1
    if (_plat == "cpu" or not _plat) and _multi_host:
        # CPU cross-process collectives need gloo. Only when actually
        # multi-process: recent jaxlib builds gloo against the distributed
        # runtime client, and single-host (client=None) fails backend init
        # (found by the BA3C_SANITIZE=1 e2e job — the backend error
        # predates any actor traffic).
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except Exception:
            pass
    distributed = initialize_from_flags(args.worker_hosts, args.task_index)
    # base (chief) logdir: shared artifacts — checkpoints (orbax collective
    # saves need ONE path on every process) and hyper.txt (all hosts must
    # read the SAME live-hyperparam file or their updates diverge)
    base_logdir = args.logdir
    if distributed and not is_chief():
        # non-chief hosts keep their own log dir (chief owns stat.json)
        args.logdir = f"{args.logdir}-worker{args.task_index}"
    # shared checkpoint dir for ALL trainers incl. fused (collective saves)
    args.shared_ckpt_dir = os.path.join(base_logdir, "checkpoints")
    # ONE hyper.txt for every host (fused loop live overrides; the ZMQ
    # trainers' HumanHyperParamSetter gets the same dir below)
    args.shared_hyper_dir = base_logdir

    from distributed_ba3c_tpu.models.policy import build_model, refuse_carry
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh
    from distributed_ba3c_tpu.parallel.train_step import (
        create_train_state,
        make_train_step,
    )
    from distributed_ba3c_tpu.utils import logger

    cfg = build_config(args)
    try:
        model = build_model(args.model, cfg, args.model_cut)
        if args.task != "train" or args.trainer != "tpu_fused_ba3c":
            refuse_carry(model, f"--task {args.task} --trainer {args.trainer}")
    except ValueError as e:
        parser.error(str(e))
    optimizer = make_optimizer(
        cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm
    )

    if args.profiler_port:
        from distributed_ba3c_tpu.utils.profiling import start_server

        start_server(args.profiler_port)

    # telemetry plane (docs/observability.md): postmortem dumps land in the
    # logdir, and a launcher's SIGTERM stall-kill leaves the flight ring on
    # disk instead of a truncated log
    from distributed_ba3c_tpu import telemetry

    telemetry.configure(args.logdir)
    if args.logdir:
        # spawned children (env servers, simulators) read this at import —
        # without it their postmortem dumps land in /tmp, not the logdir
        os.environ["BA3C_FLIGHT_DIR"] = args.logdir
    if args.trace_sample > 0:
        # arm the trace plane here AND in the env var: spawned env-server
        # children read BA3C_TRACE at import, exactly the BA3C_TELEMETRY
        # inheritance idiom (telemetry/tracing.py)
        telemetry.tracing.set_sampling(args.trace_sample)
        os.environ["BA3C_TRACE"] = str(args.trace_sample)
    if args.wire_crc:
        # arm CRC framing here AND in the env var: spawned env servers and
        # pod hosts read BA3C_WIRE_CRC at import — a fleet where only one
        # side frames would reject nothing and verify nothing
        from distributed_ba3c_tpu.utils.serialize import set_wire_crc

        set_wire_crc(True)
        os.environ["BA3C_WIRE_CRC"] = "1"
    if args.task == "train":
        telemetry.install_signal_dump()

    if args.task == "eval":
        state = create_train_state(jax.random.PRNGKey(0), model, cfg, optimizer)
        return _run_eval(args, cfg, model, state)
    if args.task == "play":
        state = create_train_state(jax.random.PRNGKey(0), model, cfg, optimizer)
        return _run_play(args, cfg, model, state)

    if args.trainer == "tpu_fused_ba3c":
        return _run_fused(args, cfg, model, optimizer)

    state = create_train_state(jax.random.PRNGKey(0), model, cfg, optimizer)

    if distributed:
        mesh = make_global_mesh(num_model=1)
    else:
        mesh = make_mesh(num_data=args.mesh_data, num_model=1)

    from distributed_ba3c_tpu.actors.master import BA3CSimulatorMaster
    from distributed_ba3c_tpu.actors.simulator import (
        SimulatorProcess,
        default_pipes,
    )
    from distributed_ba3c_tpu.actors.vtrace_master import VTraceSimulatorMaster
    from distributed_ba3c_tpu.data.dataflow import (
        FleetMergeFeed,
        RolloutFeed,
        TrainFeed,
        collate_rollout,
        collate_train,
    )
    from distributed_ba3c_tpu.parallel.train_step import make_macro_train_step
    from distributed_ba3c_tpu.parallel.vtrace_step import (
        make_vtrace_macro_step,
        make_vtrace_train_step,
    )
    from distributed_ba3c_tpu.predict.server import BatchedPredictor
    from distributed_ba3c_tpu.train.callbacks import (
        Evaluator,
        HumanHyperParamSetter,
        MaxSaver,
        ModelSaver,
        PeriodicTrigger,
        ScheduledHyperParamSetter,
        StartProcOrThread,
        StatPrinter,
    )
    from distributed_ba3c_tpu.train.trainer import Trainer, TrainLoopConfig

    # --env zmq: = REMOTE actor fleets (BASELINE config #3's topology): no
    # local simulators — external env servers (CppEnvServerProcess or any
    # wire-compatible speaker) connect to this learner's tcp:// pipes.
    external_fleet = args.env.startswith("zmq:")
    if external_fleet:
        # endpoint presence was validated pre-lock at the top of main()
        build_player = None
    else:
        build_player = _build_player_factory(args, cfg)
        # train-mode episode guards (reference get_player(train=True) stacked
        # PreventStuck + LimitLength around the simulators; eval unguarded)
        from distributed_ba3c_tpu.envs.wrappers import guarded_player

        sim_build_player = functools.partial(
            guarded_player,
            base_build=build_player,
            episode_length_cap=cfg.episode_length_cap,
            stuck_limit=30,
            stuck_action=1,
        )
    # explicit pipe addresses (tcp:// for cross-host fleets) override the
    # per-pid ipc:// defaults; the master BINDS, env servers connect.
    # --fleets > 1 derives per-fleet pairs from this base (actors/fleet.py
    # fleet_pipes: fleet 0 keeps it verbatim)
    if args.pipe_c2s and args.pipe_s2c:
        c2s, s2c = args.pipe_c2s, args.pipe_s2c
    elif args.pipe_c2s or args.pipe_s2c:
        raise SystemExit("--pipe_c2s and --pipe_s2c must be given together")
    else:
        c2s, s2c = default_pipes()
    score_q: queue.Queue = queue.Queue(maxsize=4096)
    n_data = mesh.shape["data"]
    n_hosts = jax.process_count()
    n_fleets = args.fleets
    multi_fleet = n_fleets > 1
    if multi_fleet and distributed:
        raise SystemExit(
            "--fleets > 1 runs N fleets behind ONE single-host learner — "
            "for multi-host deployments run one learner (with its fleets) "
            "per host, or use --worker_hosts with --fleets 1"
        )
    if multi_fleet and n_fleets % n_data:
        raise SystemExit(
            f"--fleets {n_fleets} must be divisible by the mesh data axis "
            f"({n_data}): the macro step assigns whole fleets to chips — "
            "set --mesh_data to a divisor of --fleets"
        )
    if multi_fleet and cfg.simulator_procs % n_fleets:
        raise SystemExit(
            f"--simulator_procs {cfg.simulator_procs} must split evenly "
            f"across --fleets {n_fleets}"
        )

    # per-fleet predictor factory: every fleet serves the same policy
    # table (canary/shadow included), each behind its own scheduler
    from distributed_ba3c_tpu.actors.fleet import (
        FanoutPredictors,
        build_fleet_planes,
    )

    _policy_extras = []
    if args.canary_load or args.shadow_load:
        from distributed_ba3c_tpu.train.checkpoint import CheckpointManager

        def _policy_params(ckpt_dir):
            return CheckpointManager(ckpt_dir).restore(
                jax.device_get(state)
            ).params

        if args.canary_load:
            _policy_extras.append(
                ("canary", _policy_params(args.canary_load),
                 args.canary_fraction)
            )
        if args.shadow_load:
            _policy_extras.append(
                ("shadow", _policy_params(args.shadow_load), None)
            )

    # int8 rung: a frozen spec file is loaded ONCE and shared by every
    # replica (one calibration per plane); --quant_calibrate instead hands
    # each replica a live CalibrationTap over its own served traffic
    _quant_spec = None
    if args.quant_spec:
        from distributed_ba3c_tpu.quantize import QuantSpec

        _quant_spec = QuantSpec.load(args.quant_spec)

    def _build_replica(tele_role_r: str):
        # THE sanctioned serving factory: handed to the fleet assembly
        # (and to the ReplicaSet under --serve_replicas), lifecycle owned
        # by cli's startables / the router's owned ReplicaSet
        return BatchedPredictor(  # ba3clint: disable=A14 — the sanctioned fleet-assembly factory
            model,
            state.params,
            batch_size=cfg.predict_batch_size,
            num_threads=cfg.predictor_threads,
            slo_ms=args.serve_slo_ms,
            tele_role=tele_role_r,
            # the quantized rollout forward (--rollout_dtype bfloat16/int8):
            # serving-side param storage only — the learner publishes and
            # keeps full precision (audit entries predict.server_bf16 /
            # predict.server_int8)
            rollout_dtype=args.rollout_dtype,
            quant_spec=_quant_spec,
            quant_calibrate=args.quant_calibrate,
        )

    # serving-plane control loops grown by the routed path (the per-fleet
    # ReplicaAutoscaler, the fleet-0 PromotionController) and the routed
    # ReplicaSets themselves — all reconciler resources, named here
    serving_extras = []
    replica_sets = []

    def make_predictor(k: int, tele_role: str):
        R = args.serve_replicas
        # --serve_replicas_max above the base count forces the ROUTED
        # plane even at R == 1: the autoscaler needs a router/ReplicaSet
        # to grow into, so the modifier is honored, never silently dropped
        routed = R > 1 or bool(
            args.serve_replicas_max and args.serve_replicas_max > R
        )
        if not routed:
            pred = _build_replica(tele_role)
            # multi-policy serving (docs/serving.md): canary/shadow
            # checkpoints are pinned policies behind the one scheduler —
            # the learner's update_params publishes only touch 'default'
            for name, params_k, fraction in _policy_extras:
                pred.add_policy(name, params_k)
                if name == "canary":
                    pred.set_canary("canary", fraction)
                else:
                    pred.set_shadow("shadow")
            # precompile every serving bucket now — a first-time bucket
            # compile mid-training stalls the whole actor plane
            pred.warmup(cfg.state_shape)
            return pred
        # the ROUTED plane (ISSUE 15, docs/serving.md): R replicas behind
        # the SLO router; the master holds "a predictor" either way
        from distributed_ba3c_tpu.orchestrate.serving import (
            PromotionController,
            ReplicaAutoscaler,
            ReplicaSet,
            ServingScalerPolicy,
        )
        from distributed_ba3c_tpu.predict.router import (
            ServingRouter,
            replica_role,
        )

        router = ServingRouter(
            tele_role=tele_role.replace("predictor", "router")
        )
        rs = ReplicaSet(
            router,
            factory=lambda idx: _build_replica(replica_role(tele_role, idx)),
            min_replicas=R,
            max_replicas=max(R, args.serve_replicas_max or R),
            warm=lambda p: p.warmup(cfg.state_shape),
        )
        # the topology reconciler owns the dead-replica sweep (its
        # ServingResource ticks rs.reconcile) — no per-set corpse thread
        rs.start(R, reconcile_thread=False)
        replica_sets.append((k, rs))
        # ONE startable handle for the whole routed plane: router.stop()
        # closes its owned ReplicaSet (replicas included)
        router.replica_set = rs
        # policies live at ROUTER level so autoscale-grown replicas are
        # seeded with the same table before they take traffic
        for name, params_k, fraction in _policy_extras:
            if name == "canary" and args.canary_autopromote:
                continue  # the PromotionController owns the canary below
            router.add_policy(name, params_k)
            if name == "canary":
                router.set_canary("canary", fraction)
            else:
                router.set_shadow("shadow")
        if args.serve_replicas_max and args.serve_replicas_max > R:
            serving_extras.append((f"serving-autoscaler-f{k}", ReplicaAutoscaler(
                rs,
                ServingScalerPolicy(slo_ms=args.serve_slo_ms),
                interval_s=args.autoscale_interval,
            )))
        if args.canary_autopromote and k == 0:
            ctrl = PromotionController(
                router,
                fraction=args.canary_fraction,
                slo_ms=args.serve_slo_ms,
            )
            canary_params = next(
                p for n, p, _ in _policy_extras if n == "canary"
            )
            ctrl.start_canary(canary_params)
            serving_extras.append(("canary-promotion", ctrl))
        return router

    if args.trainer == "tpu_vtrace_ba3c":
        # segments per fleet sub-batch: ~batch_size transitions. Single
        # fleet keeps the data-axis rounding (segment axis shards over
        # chips); multi-fleet needs none — the FLEET axis shards, and each
        # chip runs whole full-recipe sub-batches (macro-batching)
        if multi_fleet:
            step = make_vtrace_macro_step(
                model, optimizer, cfg, mesh, n_fleets=n_fleets
            )
            n_seg = max(1, cfg.batch_size // cfg.local_time_max)
        else:
            step = make_vtrace_train_step(model, optimizer, cfg, mesh)
            n_seg = max(1, cfg.batch_size // cfg.local_time_max)
            n_seg = max(n_data, (n_seg // n_data) * n_data)
            assert n_seg % n_hosts == 0, (n_seg, n_hosts)
        per_fleet_items = n_seg // n_hosts
        samples_per_step = n_fleets * n_seg * cfg.local_time_max

        def make_master(k, c2s_k, s2c_k, pred, tele_role):
            m = VTraceSimulatorMaster(
                c2s_k,
                s2c_k,
                pred,
                unroll_len=cfg.local_time_max,
                score_queue=score_q,
                actor_timeout=args.actor_timeout or None,
                reward_clip=cfg.reward_clip,
                tele_role=tele_role,
            )
            # ring-safety input: the feed's per-fleet collate holder pins
            # ring views too
            m.feed_batch = per_fleet_items
            return m

    else:
        if multi_fleet:
            step = make_macro_train_step(
                model, optimizer, cfg, mesh, n_fleets=n_fleets
            )
        else:
            step = make_train_step(model, optimizer, cfg, mesh)
            if distributed:
                local_batch_slice(cfg.batch_size)  # asserts host divisibility
        per_fleet_items = cfg.batch_size // n_hosts
        samples_per_step = n_fleets * cfg.batch_size

        def make_master(k, c2s_k, s2c_k, pred, tele_role):
            m = BA3CSimulatorMaster(
                c2s_k,
                s2c_k,
                pred,
                gamma=cfg.gamma,
                local_time_max=cfg.local_time_max,
                score_queue=score_q,
                actor_timeout=args.actor_timeout or None,
                reward_clip=cfg.reward_clip,
                tele_role=tele_role,
            )
            # ring-safety input: the feed's per-fleet collate holder pins
            # ring views too
            m.feed_batch = per_fleet_items
            return m

    # Local fleets are owned by a FleetSupervisor (docs/orchestration.md):
    # crashed/wedged servers respawn with backoff behind a restart-budget
    # circuit breaker, stale shm rings are reclaimed at spawn, and
    # --fleet_min/--fleet_max attach the telemetry-driven autoscaler
    # (PER-FLEET bounds when --fleets > 1 — each fleet gets its own
    # supervisor + policy loop over its own master's signals).
    from distributed_ba3c_tpu.orchestrate import (
        Autoscaler,
        FleetSpec,
        FleetSupervisor,
        master_signals,
    )

    def _fleet_bounds(n_servers: int) -> tuple:
        lo = args.fleet_min or n_servers
        hi = args.fleet_max or n_servers
        if not lo <= n_servers <= hi:
            raise SystemExit(
                f"launch fleet size {n_servers} servers is outside "
                f"[--fleet_min {lo}, --fleet_max {hi}] — size the launch "
                "fleet (--simulator_procs, split per fleet) inside the "
                "bounds"
            )
        return lo, hi

    def _maybe_autoscaler(supervisor, m):
        if supervisor.spec.fleet_max > supervisor.spec.fleet_min:
            # elastic bounds requested: the policy loop watches THIS
            # fleet's master backpressure signals (never its own heartbeats)
            return Autoscaler(
                supervisor,
                master_signals(m),
                interval_s=args.autoscale_interval,
            )
        return None

    make_supervision = None
    if external_fleet:
        # remote fleets own the envs; nothing to start (or supervise)
        # locally — scripts/launch_env_fleet.py supervises on its host
        pass
    elif args.env.startswith("cpp:"):
        # batched native servers: each process hosts up to 16 envs in lockstep
        from distributed_ba3c_tpu.envs import native

        game = args.env.split(":", 1)[1]
        wire = args.wire
        if wire == "auto":
            from distributed_ba3c_tpu.utils import shm

            wire = "block-shm" if shm.available() else "block"
        total = cfg.simulator_procs // n_fleets  # envs per fleet
        per = min(16, total)
        if wire != "per-env" and per > cfg.predict_batch_size:
            # fail at startup, not as an exception inside the master's
            # receive loop mid-run: a block must fit the serving bucket
            raise SystemExit(
                f"--predict_batch_size {cfg.predict_batch_size} is smaller "
                f"than the env-server block size {per}: the block wire "
                "serves a whole block in one predictor call — raise "
                f"--predict_batch_size to >= {per} or use --wire per-env"
            )

        def ring_cap(m, b: int):
            # size each server's shm ring for THIS run's actual buffering
            # (queue + feed holder + flush horizon) so the master's check
            # never refuses a config the defaults could have sized for;
            # 25% headroom. Every input is read off the fleet's master and
            # fed to the SAME utils/shm.py formula the master's attach-time
            # check uses — sizing and refusal cannot drift
            if wire != "block-shm":
                return None
            from distributed_ba3c_tpu.utils.shm import min_safe_cap

            need = min_safe_cap(
                b,
                int(getattr(m.queue, "maxsize", 0)),
                int(getattr(m, "feed_batch", 0)),
                int(getattr(m, "ring_steps_per_item", 1)),
                int(
                    getattr(m, "local_time_max", 0)
                    or getattr(m, "unroll_len", 0)
                ),
                cfg.frame_history,
            )
            return max(
                native.CppEnvServerProcess.SHM_RING_MIN_CAP,
                native.CppEnvServerProcess.SHM_RING_STEPS // max(1, b),
                int(need * 1.25) + 1,
            )

        n_servers = (total + per - 1) // per
        lo, hi = _fleet_bounds(n_servers)

        def make_supervision(k, c2s_k, s2c_k, m):
            # fleet-tagged ident prefixes keep the telemetry sender table
            # (and prune-event slot mapping) distinct across fleets; ring
            # names namespace themselves through the per-fleet c2s hash
            # (utils/shm.py ring_name)
            def prefix(i):
                return (
                    f"f{k}-cppsim-{i}" if multi_fleet else f"cppsim-{i}"
                )

            def cpp_factory(i):
                # ragged last INITIAL slot keeps the per-fleet env count
                # exact; slots grown past it host the full block. Ring
                # caps are sized per-slot from the run's actual buffering.
                n = per
                remaining = total - i * per
                if 0 < remaining < n:
                    n = remaining
                # construction only parameterizes the slot — the
                # FleetSupervisor this factory is handed to owns the spawn
                return native.CppEnvServerProcess(  # ba3clint: disable=A8
                    i,
                    c2s_k,
                    s2c_k,
                    game=game,
                    n_envs=n,
                    frame_history=cfg.frame_history,
                    wire=wire,
                    shm_ring_cap=ring_cap(m, n),
                    ident_prefix=prefix(i),
                )

            sup = FleetSupervisor(
                FleetSpec(
                    pipe_c2s=c2s_k, pipe_s2c=s2c_k, game=game,
                    envs_per_server=per, frame_history=cfg.frame_history,
                    wire=wire, fleet_size=n_servers, fleet_min=lo,
                    fleet_max=hi,
                ),
                factory=cpp_factory,
                ident_prefix=prefix,
            )
            return sup, _maybe_autoscaler(sup, m)

    else:
        per_fleet_sims = cfg.simulator_procs // n_fleets
        lo, hi = _fleet_bounds(per_fleet_sims)

        def make_supervision(k, c2s_k, s2c_k, m):
            # per-fleet global index stride keeps python-simulator idents
            # ("simulator-<idx>") distinct across fleets — SimulatorProcess
            # derives its wire ident from idx alone
            base = k * 10000

            sup = FleetSupervisor(
                FleetSpec(
                    pipe_c2s=c2s_k, pipe_s2c=s2c_k, envs_per_server=1,
                    frame_history=cfg.frame_history, wire="per-env",
                    fleet_size=per_fleet_sims, fleet_min=lo, fleet_max=hi,
                ),
                # same parameterize-only contract as cpp_factory above
                factory=lambda i: SimulatorProcess(  # ba3clint: disable=A8
                    base + i, c2s_k, s2c_k, sim_build_player
                ),
                ident_prefix=lambda i: f"simulator-{base + i}",
            )
            return sup, _maybe_autoscaler(sup, m)

    planes = build_fleet_planes(  # ba3clint: disable=A8 — factories above only parameterize; each fleet's FleetSupervisor owns its spawns
        n_fleets, c2s, s2c, make_predictor, make_master, make_supervision
    )
    if external_fleet:
        for pl in planes:
            logger.info(
                "external-fleet mode (fleet %d): master pipes bound at %s "
                "(c2s) / %s (s2c) — waiting for env servers to connect",
                pl.fleet, pl.pipe_c2s, pl.pipe_s2c,
            )
    masters = [pl.master for pl in planes]
    # the staged-ingest plane (docs/ingest.md): one HostStagingRing the
    # feed's collate writes into (one host copy per block), wrapped by a
    # DeviceIngest that dispatches the NEXT batch's H2D behind the
    # running step (Trainer.run_step's prefetch call)
    staging_on = args.ingest_staging == "on"
    staging_ring = None
    if staging_on:
        from distributed_ba3c_tpu.data.staging import (
            DeviceIngest,
            HostStagingRing,
        )

        staging_ring = HostStagingRing()
    if multi_fleet:
        # fair round-robin merge of the per-fleet queues into stacked
        # [K, ...] macro batches (data/dataflow.py) — the layout the macro
        # step shards fleet-major over the mesh
        feed = FleetMergeFeed(
            [m.queue for m in masters],
            per_fleet_items,
            collate=(
                collate_rollout
                if args.trainer == "tpu_vtrace_ba3c"
                else collate_train
            ),
            staging=staging_ring,
        )
        predictor = FanoutPredictors([pl.predictor for pl in planes])
    else:
        if args.trainer == "tpu_vtrace_ba3c":
            feed = RolloutFeed(
                masters[0].queue, per_fleet_items, staging=staging_ring
            )
        else:
            feed = TrainFeed(
                masters[0].queue, per_fleet_items, staging=staging_ring
            )
        predictor = planes[0].predictor
    if staging_on:
        feed = DeviceIngest(feed, step.batch_sharding)

    # Order matters: Evaluator adds its stats BEFORE StatPrinter finalizes the
    # epoch record, and MaxSaver reads the monitored stat from that record.
    chief = is_chief()
    # Where an Evaluator runs, keep-best follows the GREEDY eval score (the
    # reference MaxSaver kept the Evaluator's best); otherwise fall back to
    # the sampling-policy mean.
    run_eval = chief and args.nr_eval > 0 and build_player is not None
    if chief and nr_eval_explicit and args.nr_eval > 0 and build_player is None:
        # external-fleet mode (--env zmq:) has no local player to evaluate
        # with: say so instead of silently changing the keep-best policy
        logger.warn(
            "--nr_eval %d ignored: no local player in --env %s mode; "
            "MaxSaver keep-best falls back to the sampling-policy mean_score",
            args.nr_eval, args.env,
        )
    # scrape endpoint: start/stop with the rest of the plane (it satisfies
    # the StartProcOrThread protocol — start/stop/join/close)
    tele_servers = (
        [telemetry.TelemetryServer(args.telemetry_port)]
        if args.telemetry_port
        else []
    )
    # start order: every fleet's predictor+master, then the merge feed,
    # then ONE reconciler over every supervised resource (spawning servers
    # before their master's receive loop is live would park the whole
    # fleet in its first recv)
    startables = [pl.predictor for pl in planes]
    if multi_fleet:
        # the fan-out facade owns pump threads: it rides the same
        # lifecycle, FIRST so its pumps stop before any predictor they
        # publish into does (start() is a no-op — pumps run from ctor)
        startables.insert(0, predictor)
    startables += masters
    startables.append(feed)
    # Every controller that used to ride the startables list on its own
    # thread — fleet supervisors, fleet autoscalers, routed ReplicaSets'
    # corpse sweep, the serving autoscaler/promotion loops — is now a
    # resource of ONE generic reconcile loop (orchestrate/reconcile.py):
    # observe → diff → act under the spec's backoff + restart-budget
    # policy, every heal decision flight-recorded with its snapshot.
    from distributed_ba3c_tpu.orchestrate import (
        FleetResource,
        PolicyResource,
        Reconciler,
        ServingResource,
    )

    reconciler = Reconciler(policy=topo.reconcile)
    for pl in planes:
        if pl.supervisor is not None:
            reconciler.add(FleetResource(f"fleet{pl.fleet}", pl.supervisor))
        if pl.autoscaler is not None:
            reconciler.add(PolicyResource(
                f"fleet-autoscaler-f{pl.fleet}", pl.autoscaler,
                interval_s=pl.autoscaler.interval_s,
            ))
    for k, rs in replica_sets:
        reconciler.add(ServingResource(f"serving-f{k}", rs))
    for name, ctrl in serving_extras:
        reconciler.add(PolicyResource(
            name, ctrl, interval_s=ctrl.interval_s,
        ))
    if reconciler.resources():
        startables.append(reconciler)
    callbacks = [
        StartProcOrThread(startables + tele_servers),
        HumanHyperParamSetter("learning_rate", shared_dir=base_logdir),
        HumanHyperParamSetter("entropy_beta", shared_dir=base_logdir),
        StatPrinter(),
        # ONE checkpoint dir for every host: orbax saves are collective and
        # must target the same path on all processes
        ModelSaver(
            ckpt_dir=os.path.join(base_logdir, "checkpoints"),
            max_to_keep=args.max_to_keep,
        ),
        MaxSaver(monitor="eval_mean_score" if run_eval else "mean_score"),
    ]
    if run_eval:
        # chief-only eval, matching the reference's chief-worker summary
        # role; MUST run before StatPrinter so eval stats land in THIS
        # epoch's record (MaxSaver reads that record)
        stat_printer_idx = next(
            i for i, cb in enumerate(callbacks) if isinstance(cb, StatPrinter)
        )
        callbacks.insert(
            stat_printer_idx,
            PeriodicTrigger(
                Evaluator(args.nr_eval, build_player),
                every_k_epochs=args.eval_every,
            ),
        )
    # reference-signature LR/β schedules (SURVEY.md §2.9), CLI-activated
    if args.learning_rate_final is not None:
        callbacks.append(
            ScheduledHyperParamSetter(
                "learning_rate",
                [(1, cfg.learning_rate), (args.max_epoch, args.learning_rate_final)],
                interp=args.anneal_lr or args.anneal,
            )
        )
    if args.entropy_beta_final is not None:
        callbacks.append(
            ScheduledHyperParamSetter(
                "entropy_beta",
                [(1, cfg.entropy_beta), (args.max_epoch, args.entropy_beta_final)],
                interp=args.anneal_beta or args.anneal,
            )
        )
    from distributed_ba3c_tpu.train.experiment import ExperimentLogger

    callbacks.append(ExperimentLogger())
    trainer = Trainer(
        TrainLoopConfig(
            steps_per_epoch=args.steps_per_epoch,
            max_epoch=args.max_epoch,
            log_dir=args.logdir,
            publish_every=args.publish_every,
            rank_stall_timeout=args.rank_stall_timeout,
        ),
        cfg,
        step,
        state,
        feed,
        callbacks,
        predictor=predictor,
        score_queue=score_q,
        is_chief=chief,
        samples_per_step=samples_per_step,
    )
    if args.load:
        trainer.restore(args.load)
    trainer.train()
    return 0


def _run_eval(args, cfg, model, state) -> int:
    import jax

    from distributed_ba3c_tpu.predict.server import BatchedPredictor
    from distributed_ba3c_tpu.train.checkpoint import CheckpointManager
    from distributed_ba3c_tpu.train.eval import eval_model
    from distributed_ba3c_tpu.utils import logger

    if args.load:
        mgr = CheckpointManager(args.load)
        state = mgr.restore(jax.device_get(state))
    # synchronous single-user eval tooling, not the serving tier: only
    # predict_batch is ever called, no routed traffic exists to bypass
    predictor = BatchedPredictor(  # ba3clint: disable=A14 — sync eval tool, predict_batch only
        model, state.params, batch_size=max(args.nr_eval, 1), greedy=True
    )
    build_player = _build_player_factory(args, cfg)

    def predict(states):
        actions, _, _ = predictor.predict_batch(states)
        return actions

    mean, mx = eval_model(predict, build_player, args.nr_eval)
    logger.info("eval over %d episodes: mean=%.2f max=%.2f", args.nr_eval, mean, mx)
    print(f"mean_score={mean:.3f} max_score={mx:.3f}")
    return 0


def _run_play(args, cfg, model, state) -> int:
    """Replay mode (reference ``play_n_episodes``): run ``--nr_eval`` greedy
    episodes one at a time, printing per-step action/reward so a human can
    watch the policy (no render surface in this build: the step trace IS the
    visualization)."""
    import jax
    import numpy as np

    from distributed_ba3c_tpu.predict.server import BatchedPredictor
    from distributed_ba3c_tpu.train.checkpoint import CheckpointManager

    if args.load:
        mgr = CheckpointManager(args.load)
        state = mgr.restore(jax.device_get(state))
    predictor = BatchedPredictor(model, state.params, batch_size=1, greedy=True)  # ba3clint: disable=A14 — sync play tool, predict_batch only
    build_player = _build_player_factory(args, cfg)

    for ep in range(max(args.nr_eval, 1)):
        player = build_player(ep)
        score, t = 0.0, 0
        while True:
            s = np.asarray(player.current_state())[None]
            actions, values, _ = predictor.predict_batch(s)
            a = int(actions[0])
            r, is_over = player.action(a)
            score += r
            if r != 0 or t % 50 == 0:
                print(
                    f"episode {ep} step {t:5d} | action {a} | reward {r:+.1f} "
                    f"| score {score:+.1f} | V(s) {float(values[0]):+.3f}"
                )
            t += 1
            if is_over or t >= cfg.episode_length_cap:
                break
        print(f"episode {ep} finished: score {score:+.1f} in {t} steps")
    return 0


def _run_fused(args, cfg, model, optimizer) -> int:
    try:
        from distributed_ba3c_tpu.fused.loop import run_fused_training
    except ImportError:
        raise SystemExit(
            "--trainer=tpu_fused_ba3c requires the on-device env module "
            "(distributed_ba3c_tpu.fused); not available in this build"
        )
    return run_fused_training(args, cfg, model, optimizer)
