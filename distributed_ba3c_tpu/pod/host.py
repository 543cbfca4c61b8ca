"""The pod actor host: one process, one complete actor plane, zero learner.

    python -m distributed_ba3c_tpu.pod.host \\
        --host_id 0 --learner_c2s tcp://10.0.0.1:5555 \\
        --learner_s2c tcp://10.0.0.1:5556 --env fake --n_sims 4

What runs inside (docs/pod.md): a :class:`StaleParamsCache` subscribed to
the learner's params plane, a warmed :class:`BatchedPredictor` served from
that cache, a :class:`PodSimulatorMaster` binding HOST-LOCAL pipes for a
supervised env fleet, and an :class:`ExperienceShipper` collating unroll
segments into stamped [T, B] blocks pushed to the learner. The host's
policy is always *some* version behind — that is the design, not a bug:
every shipped block carries the version it was collected under, and the
learner's V-trace corrects the measured lag exactly (the behavior
log-probs AND values ride in the block).

The reference ran this role as ~50 bare simulator processes per worker
with the policy forward on the learner's parameter-server round-trip
(SURVEY.md §3.2); here the forward is host-local against the stale cache,
so actor throughput is completely decoupled from both the learner's step
time and the params RTT — the IMPALA shape (Espeholt et al. 2018).

This process never touches the TPU: it runs jax on CPU for the predictor
forward only. Supervision comes from orchestrate/pod.py (respawn with
backoff; the chaos host-loss scenario SIGKILLs exactly this process and
the respawned cache rejoins at the current version via the fetch channel).
"""

from __future__ import annotations

import argparse
import collections
import functools
import os
import signal
import sys
import threading
from typing import List, Optional

# the host is an actor-plane process: CPU jax only, decided before the
# first jax import (same guard as the test harness / launch_env_fleet)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.telemetry import tracing
from distributed_ba3c_tpu.actors.vtrace_master import VTraceSimulatorMaster
from distributed_ba3c_tpu.data.dataflow import claim_trace, collate_rollout
from distributed_ba3c_tpu.pod.cache import StaleParamsCache, VersionGatedPredictor
from distributed_ba3c_tpu.pod.linkstate import LinkHealth
from distributed_ba3c_tpu.pod.wire import pack_experience, pod_endpoints, pod_role
from distributed_ba3c_tpu.utils import logger
from distributed_ba3c_tpu.utils.concurrency import StoppableThread


class PodSimulatorMaster(VTraceSimulatorMaster):
    """VTraceSimulatorMaster whose segments carry ``behavior_values``.

    The V-trace plane deliberately drops the behavior value (its learner
    never reads it); the pod learner's staleness accounting
    (``value_lag_mae``) is built on it. ONE flag, not copied emission
    paths: the base class records the value per transition already and
    emits the key only when asked — so a flush/ring fix lands on both
    planes at once (the make_finish_update lesson)."""

    record_values = True


class ExperienceShipper(StoppableThread):
    """Collate unroll segments into stamped blocks; push them upstream.

    The stamp is ``cache.version`` read when the block's FIRST segment is
    banked — the OLDEST version any of its transitions could have been
    served under (the cache can refresh several times while the holder
    fills, and measured lag = learner − stamp, so stamping any newer
    would make the ``--max_staleness`` bound looser than the data; the
    conservative stamp can only over-measure, never under-measure, and
    the correction itself reads recorded log-probs, not the stamp).

    Partition tolerance (docs/netchaos.md): the PUSH socket carries an
    explicit SNDHWM so a partitioned ingest can buffer at most
    ``snd_hwm`` blocks inside libzmq — never unbounded learner-side RAM
    growth on the host. When that bound bites (``zmq.Again``) the block
    spills into a bounded DROP-OLDEST buffer (``ship_backpressure_total``
    counts every refusal, ``shipped_dropped_total`` counts blocks the
    spill evicted), the ``experience`` LinkHealth machine tracks the
    silence, and the spill re-drains oldest-first the moment a send lands
    again — a heal ships the freshest bounded window of history, rollout
    never blocked for a microsecond of it.
    """

    def __init__(
        self,
        master: PodSimulatorMaster,
        cache: StaleParamsCache,
        experience_addr: str,
        host: int,
        segments_per_block: int,
        tele_role: Optional[str] = None,
        snd_hwm: int = 8,
        spill_depth: int = 64,
        degraded_after_s: float = 3.0,
        partitioned_after_s: float = 10.0,
    ):
        super().__init__(daemon=True, name=f"pod-shipper-h{host}")
        import zmq

        self.master = master
        self.cache = cache
        self.host = int(host)
        self.segments_per_block = max(1, int(segments_per_block))
        self.context = zmq.Context()
        self._push = self.context.socket(zmq.PUSH)
        self._push.setsockopt(zmq.LINGER, 0)
        # the explicit BOUND on learner-ward buffering: libzmq holds at
        # most this many blocks for a slow/partitioned ingest; everything
        # past it is this class's accounted spill, not silent RAM
        self._push.setsockopt(zmq.SNDHWM, max(1, int(snd_hwm)))
        self._push.connect(experience_addr)
        self._spill: collections.deque = collections.deque()
        self._spill_depth = max(1, int(spill_depth))
        role = tele_role or pod_role(host)
        self.tele_role = role
        tele = telemetry.registry(role)
        self._c_shipped = tele.counter("shipped_blocks_total")
        self._c_dropped = tele.counter("shipped_dropped_total")
        self._c_backpressure = tele.counter("ship_backpressure_total")
        tele.gauge("ship_spill_depth", fn=lambda: len(self._spill))
        self.link = LinkHealth(
            "experience", role,
            degraded_after_s=degraded_after_s,
            partitioned_after_s=partitioned_after_s,
        )

    def _scalars(self) -> dict:
        """The piggybacked host-progress snapshot (folded into the
        learner-side ``pod.host<k>`` mirror by pod/ingest.py)."""
        m = telemetry.registry(self.master.tele_role).scalars()
        p = telemetry.registry(self.tele_role).scalars()
        return {
            "env_steps_total": m.get("datapoints_total", 0.0),
            "train_queue_depth": m.get("train_queue_depth", 0.0),
            "params_version": float(self.cache.version),
            "params_refreshes_total": p.get("params_refreshes_total", 0.0),
            "stale_params_sheds_total": p.get("stale_params_sheds_total", 0.0),
            "shipped_blocks_total": p.get("shipped_blocks_total", 0.0),
            "shipped_dropped_total": p.get("shipped_dropped_total", 0.0),
            "ship_backpressure_total": p.get("ship_backpressure_total", 0.0),
            "params_fetch_retries_total": p.get(
                "params_fetch_retries_total", 0.0
            ),
            "params_corrupt_total": p.get("params_corrupt_total", 0.0),
            "params_malformed_total": p.get("params_malformed_total", 0.0),
        }

    def _try_send(self, frames) -> bool:
        """One non-blocking send attempt; True when libzmq accepted the
        message. Acceptance beats the link (a partitioned peer stops
        accepting within SNDHWM messages); refusal is the typed
        backpressure account."""
        import zmq

        try:
            self._push.send_multipart(frames, zmq.NOBLOCK, copy=False)
        except zmq.Again:
            self._c_backpressure.inc()
            self.link.poll()
            return False
        self._c_shipped.inc()
        self.link.beat()
        return True

    def _ship(self, frames) -> None:
        """Ship oldest-first through the bounded drop-oldest spill."""
        self._spill.append(frames)
        while len(self._spill) > self._spill_depth:
            # the bound bites: shed the OLDEST block — under staleness
            # semantics old experience is the cheapest to lose (its lag
            # would be measured and possibly gate-rejected anyway)
            self._spill.popleft()
            self._c_dropped.inc()
        while self._spill and self._try_send(self._spill[0]):
            self._spill.popleft()

    def run(self) -> None:
        import queue as _queue

        import zmq

        holder: List[dict] = []
        stamp = (0, 0)  # (epoch, version) at the block's first segment
        trace = None  # sampled trace riding the block being collated
        while not self.stopped():
            try:
                # bounded single-attempt get (NOT queue_get_stoppable,
                # which only returns on item-or-stop): idle ticks must
                # still drain the spill and poll the link so a heal is
                # taken within one timeout even when rollout is quiet
                seg = self.master.queue.get(timeout=0.2)
            except _queue.Empty:
                if self._spill:
                    try:
                        while self._spill and self._try_send(self._spill[0]):
                            self._spill.popleft()
                    except zmq.ZMQError:
                        return  # socket torn down (close raced run)
                # no spill and nothing to ship = no attempts = no evidence:
                # the link state FREEZES at its last observed value (an
                # idle host must not drift to "partitioned" on silence it
                # caused itself — only refused sends are evidence here)
                continue
            ref = claim_trace(seg)
            if ref is not None:
                # emit -> shipper drain: the host-side ship wait (one
                # trace per shipped block, claimed once)
                trace = trace or ref.hop("ship_wait", self.tele_role)
            if not holder:
                stamp = (self.cache.epoch or 0, self.cache.version)
            holder.append(seg)
            if len(holder) < self.segments_per_block:
                continue
            batch = collate_rollout(holder)
            holder = []
            ctx = None
            if trace is not None:
                # collate on the host, then hand the trace across the
                # process boundary: the context carries this host's
                # monotonic stamp (clock handshake) so the learner's
                # pod_wire span lands on one aligned timeline
                trace = trace.hop("host_collate", self.tele_role)
                ctx = tracing.encode_context(trace.trace_id, trace.parent_id)
                trace = None
            frames = pack_experience(
                self.host, stamp[1], batch, self._scalars(), epoch=stamp[0],
                trace=ctx,
            )
            try:
                self._ship(frames)
            except zmq.ZMQError:
                return  # socket torn down mid-send (close raced run)

    def close(self) -> None:
        self.stop()
        if self.is_alive():
            self.join(timeout=2)
        try:
            self._push.close(0)
        except Exception:
            pass
        self.context.term()


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m distributed_ba3c_tpu.pod.host",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--host_id", type=int, required=True)
    p.add_argument("--learner_c2s", required=True, help="the learner's BASE c2s pipe (pod channels derive from it, pod/wire.py)")
    p.add_argument("--learner_s2c", required=True)
    p.add_argument("--env", default="fake", help="fake | cpp:<game> (the host-local fleet)")
    p.add_argument("--n_sims", type=int, default=4, help="fake: simulator processes; cpp: total envs on this host")
    p.add_argument("--unroll_len", type=int, default=5)
    p.add_argument("--segments_per_block", type=int, default=16, help="unroll segments collated per shipped block (the block's B)")
    p.add_argument("--max_staleness", type=int, default=0, help="host-side shed bound in params versions (0 = no host gate; the learner's gate still bounds)")
    p.add_argument("--first_params_timeout", type=float, default=120.0)
    p.add_argument("--image_size", type=int, default=84)
    p.add_argument("--frame_history", type=int, default=4)
    p.add_argument("--num_actions", type=int, default=4)
    p.add_argument("--fc_units", type=int, default=512)
    p.add_argument("--predict_batch_size", type=int, default=16)
    p.add_argument("--reward_clip", type=float, default=0.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument(
        "--rollout_dtype", default="float32",
        choices=["float32", "bfloat16", "int8"],
        help="the host predictor's param-storage precision (the cached "
        "params arrive f32 from the learner and are cast at publish; "
        "audit entries predict.server_bf16 / predict.server_int8) — the "
        "actor-host half of the quantized rollout forward. int8 requires "
        "--quant_spec (pod hosts calibrate nothing: the spec is frozen "
        "once, centrally, and shipped to every host so the fleet serves "
        "ONE quantization)",
    )
    p.add_argument(
        "--quant_spec", default=None,
        help="frozen QuantSpec JSON for --rollout_dtype int8 "
        "(distributed_ba3c_tpu/quantize/; calibrate centrally via the "
        "serving tier's CalibrationTap or quantize.calibrate_offline)",
    )
    return p


def main(argv: Optional[list] = None) -> int:
    parser = make_parser()
    args = parser.parse_args(argv)
    # exit-2 usage errors, not tracebacks: the int8 rung needs its frozen
    # calibration, and a spec on a non-int8 host is a confused launch
    if args.rollout_dtype == "int8" and not args.quant_spec:
        parser.error(
            "--rollout_dtype int8 requires --quant_spec FILE (pod hosts "
            "serve a centrally frozen calibration — see docs/ingest.md)"
        )
    if args.quant_spec and args.rollout_dtype != "int8":
        parser.error(
            "--quant_spec only applies to --rollout_dtype int8"
        )
    role = pod_role(args.host_id)

    # the host is CPU-only BY CONTRACT (it must never contend for the
    # learner's chip): force the platform even when the operator's shell
    # exports something else. The env var reaches the simulator children;
    # the config update reaches THIS process, whose imports above already
    # loaded jax (it read the shell's JAX_PLATFORMS then)
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

    from distributed_ba3c_tpu.actors.simulator import SimulatorProcess, default_pipes
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.orchestrate import FleetSpec, FleetSupervisor
    from distributed_ba3c_tpu.predict.server import BatchedPredictor

    cfg = BA3CConfig(
        image_size=(args.image_size, args.image_size),
        frame_history=args.frame_history,
        num_actions=args.num_actions,
        fc_units=args.fc_units,
        predict_batch_size=args.predict_batch_size,
        reward_clip=args.reward_clip,
        local_time_max=args.unroll_len,
    )
    model = build_model(DEFAULT_MODEL, cfg)
    quant_spec = None
    if args.quant_spec:
        from distributed_ba3c_tpu.quantize import QuantSpec

        quant_spec = QuantSpec.load(args.quant_spec)
        logger.info(
            "[pod host %d] int8 serving from frozen spec %s (%s, %d batches)",
            args.host_id, quant_spec.sha256()[:12], quant_spec.method,
            quant_spec.calibration_batches,
        )
    endpoints = pod_endpoints(args.learner_c2s, args.learner_s2c)

    # 1. params plane first: there is nothing to roll out before a policy
    cache = StaleParamsCache(endpoints, host=args.host_id)
    cache.start()
    logger.info(
        "[pod host %d] waiting for first params (pub %s, fetch %s)",
        args.host_id, endpoints.params_pub, endpoints.params_fetch,
    )
    if not cache.wait_first(args.first_params_timeout):
        logger.error(
            "[pod host %d] no params within %.0fs — is the learner up?",
            args.host_id, args.first_params_timeout,
        )
        cache.close()
        return 3

    # 2. the serving plane, fed from the cache (the ONE sanctioned
    # update_params path — versioned by construction)
    predictor = BatchedPredictor(  # ba3clint: disable=A14 — the pod host's cache-fed plane: the VersionGatedPredictor wrap is its router-equivalent front
        model,
        cache.params,
        batch_size=cfg.predict_batch_size,
        seed=args.seed + 1000 * args.host_id,
        tele_role="predictor",
        rollout_dtype=args.rollout_dtype,
        quant_spec=quant_spec,
    )
    predictor.warmup(cfg.state_shape)
    cache.on_update(lambda params, version: predictor.update_params(params))
    serving = predictor
    if args.max_staleness > 0:
        serving = VersionGatedPredictor(
            predictor, cache.behind, args.max_staleness, tele_role=role,
            # a params-partitioned host sheds through the SAME typed gate:
            # behind() cannot grow while no broadcast arrives, so the
            # link-state machine is the staleness signal that survives a
            # partition (docs/netchaos.md)
            partitioned_fn=cache.params_partitioned,
        )

    # 3. the host-local actor plane
    c2s, s2c = default_pipes(name=f"ba3c-pod-h{args.host_id}")
    master = PodSimulatorMaster(
        c2s, s2c, serving,
        unroll_len=args.unroll_len,
        reward_clip=cfg.reward_clip,
        tele_role="master",
    )
    master.feed_batch = args.segments_per_block

    if args.env == "fake":
        from distributed_ba3c_tpu.envs.fake import build_fake_player
        from distributed_ba3c_tpu.envs.wrappers import guarded_player

        build_player = functools.partial(
            build_fake_player,
            image_size=cfg.image_size,
            frame_history=cfg.frame_history,
            num_actions=cfg.num_actions,
        )
        sim_build_player = functools.partial(
            guarded_player,
            base_build=build_player,
            episode_length_cap=cfg.episode_length_cap,
            stuck_limit=30,
            stuck_action=1,
        )
        spec = FleetSpec(
            pipe_c2s=c2s, pipe_s2c=s2c, envs_per_server=1, wire="per-env",
            frame_history=cfg.frame_history, fleet_size=args.n_sims,
            fleet_min=args.n_sims, fleet_max=args.n_sims,
        )
        base = args.host_id * 10000  # distinct sim idents across hosts
        supervisor = FleetSupervisor(
            spec,
            # parameterize-only factory: the supervisor owns the spawn
            factory=lambda i: SimulatorProcess(  # ba3clint: disable=A8
                base + i, c2s, s2c, sim_build_player
            ),
            ident_prefix=lambda i: f"simulator-{base + i}",
        )
    elif args.env.startswith("cpp:"):
        from distributed_ba3c_tpu.envs import native

        if not native.available():
            logger.error("native env core not built: run `make -C cpp`")
            return 2
        game = args.env.split(":", 1)[1]
        per = min(16, args.n_sims)
        n_servers = (args.n_sims + per - 1) // per
        spec = FleetSpec(
            pipe_c2s=c2s, pipe_s2c=s2c, game=game, envs_per_server=per,
            frame_history=cfg.frame_history, wire="block",
            fleet_size=n_servers, fleet_min=n_servers, fleet_max=n_servers,
            base_idx=args.host_id * 10000,
        )
        from distributed_ba3c_tpu.orchestrate import default_factory

        supervisor = FleetSupervisor(
            spec, factory=default_factory(spec, total_envs=args.n_sims)
        )
    else:
        logger.error("unknown --env %r (fake | cpp:<game>)", args.env)
        return 2

    # 4. the upstream shipper
    shipper = ExperienceShipper(
        master, cache, endpoints.experience, args.host_id,
        args.segments_per_block,
    )

    stop_evt = threading.Event()

    def _on_signal(signum, frame):
        stop_evt.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    # start order: serving + master before the fleet (servers spawned
    # before the receive loop is live would park in their first recv)
    predictor.start()
    master.start()
    shipper.start()
    supervisor.start()
    logger.info(
        "[pod host %d] actor plane up: %s sims of %s, shipping %d-segment "
        "blocks to %s", args.host_id, args.n_sims, args.env,
        args.segments_per_block, endpoints.experience,
    )
    try:
        while not stop_evt.is_set():
            stop_evt.wait(0.5)
    finally:
        supervisor.stop()
        supervisor.join(timeout=5)
        supervisor.close()
        shipper.close()
        master.close()
        predictor.stop()
        predictor.join(timeout=5)
        cache.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
