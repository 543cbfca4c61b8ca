"""Pod orchestration: N supervised actor-host processes, one learner.

The composition ROADMAP item 2 asked for, assembled from machinery that
already exists: :class:`FleetSupervisor` supervises whole ACTOR HOSTS
(``python -m distributed_ba3c_tpu.pod.host`` subprocesses) exactly the
way it supervises env servers — respawn with backoff, restart-budget
circuit breaker, every decision flight-recorded — while the learner side
is the in-process :class:`PodLearnerPlane` (publisher + ingest + the
bounded-staleness learner). The chaos host-loss scenario SIGKILLs a whole
host's process GROUP mid-run: the learner keeps training on the
surviving hosts' blocks, the supervisor respawns the host, and its cache
rejoins at the current version over the fetch channel — no learner
restart (scripts/pod_bench.py gates on it).

Entry point::

    python -m distributed_ba3c_tpu.orchestrate --pod_hosts 2 \\
        --pipe_c2s tcp://127.0.0.1:15555 --pipe_s2c tcp://127.0.0.1:15556 \\
        --logdir runs/pod --updates 500
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
from typing import Callable, List, Optional

# NO top-level jax import: orchestrate/ is imported by jax-free actor-host
# launchers (scripts/launch_env_fleet.py's contract); only the learner
# plane below touches jax, lazily

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.orchestrate.spec import FleetSpec
from distributed_ba3c_tpu.orchestrate.supervisor import FleetSupervisor
from distributed_ba3c_tpu.utils import logger

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


class _HostProc:
    """Process-like wrapper over one actor-host subprocess (the duck type
    FleetSupervisor's lifecycle expects: start/is_alive/terminate/kill/
    join/pid/exitcode). Owns its session, so kill/terminate act on the
    whole process GROUP — a SIGKILLed host must not leak its simulator
    children (they would otherwise survive as orphans parked in recv on
    the dead master's pipes)."""

    def __init__(self, argv: List[str]):
        self._argv = argv
        self._proc: Optional[subprocess.Popen] = None

    def start(self) -> None:
        env = dict(os.environ)
        # FORCED, not setdefault: actor hosts never claim a TPU — a
        # learner launched with JAX_PLATFORMS=tpu,cpu exported must not
        # hand N children a claim on the chip it holds (each would fail on
        # libtpu's lockfile at jax init and burn the respawn budget into
        # the circuit breaker)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = _REPO_ROOT + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        self._proc = subprocess.Popen(
            self._argv, start_new_session=True, env=env
        )

    @property
    def pid(self) -> Optional[int]:
        return self._proc.pid if self._proc else None

    @property
    def exitcode(self) -> Optional[int]:
        return self._proc.returncode if self._proc else None

    def is_alive(self) -> bool:
        return self._proc is not None and self._proc.poll() is None

    def _signal_group(self, sig: int) -> None:
        if self._proc is None:
            return
        try:
            os.killpg(self._proc.pid, sig)
        except (OSError, ProcessLookupError):
            pass

    def terminate(self) -> None:
        self._signal_group(signal.SIGTERM)

    def kill(self) -> None:
        self._signal_group(signal.SIGKILL)

    def join(self, timeout: Optional[float] = None) -> None:
        if self._proc is None:
            return
        try:
            self._proc.wait(timeout)
        except subprocess.TimeoutExpired:
            pass


def host_argv(
    host_id: int,
    learner_c2s: str,
    learner_s2c: str,
    env: str = "fake",
    n_sims: int = 4,
    unroll_len: int = 5,
    segments_per_block: int = 16,
    max_staleness: int = 0,
    image_size: int = 84,
    frame_history: int = 4,
    num_actions: int = 4,
    fc_units: int = 512,
    predict_batch_size: int = 16,
    python: Optional[str] = None,
) -> List[str]:
    """The canonical actor-host launch line (one formula — the supervisor
    factory, the bench and the operator runbook must not drift)."""
    return [
        python or sys.executable, "-m", "distributed_ba3c_tpu.pod.host",
        "--host_id", str(host_id),
        "--learner_c2s", learner_c2s,
        "--learner_s2c", learner_s2c,
        "--env", env,
        "--n_sims", str(n_sims),
        "--unroll_len", str(unroll_len),
        "--segments_per_block", str(segments_per_block),
        "--max_staleness", str(max_staleness),
        "--image_size", str(image_size),
        "--frame_history", str(frame_history),
        "--num_actions", str(num_actions),
        "--fc_units", str(fc_units),
        "--predict_batch_size", str(predict_batch_size),
    ]


class PodSupervisor(FleetSupervisor):
    """FleetSupervisor whose slots are whole actor hosts.

    ``make_argv(host_id)`` builds the host launch line (:func:`host_argv`
    partial'd by the caller). Slot index == host id — a respawned host
    rejoins under the same identity, its cache re-fetching the current
    params version (the pod's incarnation-reset analogue)."""

    def __init__(
        self,
        n_hosts: int,
        make_argv: Callable[[int], List[str]],
        poll_interval_s: float = 0.25,
        backoff_base_s: float = 0.25,
    ):
        spec = FleetSpec(
            envs_per_server=1,
            wire="per-env",  # spec validation; the hosts own their wires
            fleet_size=n_hosts,
            fleet_min=n_hosts,
            fleet_max=n_hosts,
            backoff_base_s=backoff_base_s,
            backoff_max_s=10.0,
            stable_after_s=10.0,
        )
        super().__init__(
            spec,
            factory=lambda i: _HostProc(make_argv(i)),
            ident_prefix=lambda i: f"pod-host-{i}",
            poll_interval_s=poll_interval_s,
        )

    def sigkill_slot(self, idx: int) -> bool:
        """SIGKILL a host's whole process group (chaos host-loss): the
        host AND its simulator children die instantly, no goodbye on any
        wire — exactly losing the machine."""
        with self._lock:
            slot = self._slots.get(idx)
            proc = slot.proc if slot is not None else None
        if proc is None or not proc.is_alive():
            return False
        proc.kill()
        return True


class PodLearnerPlane:
    """The learner half of a pod, assembled: params publisher + stamped
    ingest + the bounded-staleness PodLearner, on localhost or real tcp.

    ``step_once`` consumes one stamped batch (or times out); the caller
    owns the loop — the orchestrate pod mode and scripts/pod_bench.py
    both drive it.
    """

    def __init__(
        self,
        cfg: BA3CConfig,
        pipe_c2s: str,
        pipe_s2c: str,
        max_staleness: Optional[int] = None,
        publish_every: int = 1,
        ingest_depth: int = 16,
        seed: int = 0,
        mesh=None,
    ):
        import jax

        from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
        from distributed_ba3c_tpu.ops.gradproc import make_optimizer
        from distributed_ba3c_tpu.parallel.mesh import make_mesh
        from distributed_ba3c_tpu.parallel.train_step import create_train_state
        from distributed_ba3c_tpu.pod.ingest import PodIngest
        from distributed_ba3c_tpu.pod.learner import (
            PodLearner,
            make_pod_learner_step,
        )
        from distributed_ba3c_tpu.pod.publisher import ParamsPublisher
        from distributed_ba3c_tpu.pod.wire import pod_endpoints

        self.cfg = cfg
        self.endpoints = pod_endpoints(pipe_c2s, pipe_s2c)
        model = build_model(DEFAULT_MODEL, cfg)
        optimizer = make_optimizer(
            cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm
        )
        # a 1-device mesh by default: host-fed block shapes are the hosts'
        # choice and must not have to divide a device count; a caller with
        # a real mesh (and host shapes sized for it) passes its own
        mesh = mesh or make_mesh(num_data=1, devices=jax.devices()[:1])
        step = make_pod_learner_step(model, optimizer, cfg, mesh)
        state = create_train_state(
            jax.random.PRNGKey(seed), model, cfg, optimizer
        )
        self.publisher = ParamsPublisher(self.endpoints)
        self.learner = PodLearner(
            step, state, cfg,
            publisher=self.publisher,
            max_staleness=max_staleness,
            publish_every=publish_every,
            # every buffered StampedBatch holds a stager slot: the ring
            # must cover the ingest depth (+ one staging, one in-flight)
            # or a backed-up learner degrades to per-block fresh
            # allocations — the cost the stager exists to remove
            stager_slots=ingest_depth + 2,
        )
        # the learner's own BlockStager on the ingest receive thread: the
        # wire→staging copy overlaps the learner step, and the learner
        # loop only pays the async device transfer (docs/ingest.md)
        self.ingest = PodIngest(
            self.endpoints, depth=ingest_depth, stager=self.learner.stager
        )

    def start(self) -> None:
        self.publisher.start()
        self.ingest.start()
        logger.info(
            "pod learner plane up: params %s / %s, experience %s",
            self.endpoints.params_pub, self.endpoints.params_fetch,
            self.endpoints.experience,
        )

    def step_once(self, timeout: float = 1.0) -> Optional[dict]:
        stamped = self.ingest.next_batch(timeout)
        if stamped is None:
            return None
        return self.learner.consume(stamped)

    def close(self) -> None:
        self.ingest.close()
        self.publisher.close()


def run_pod(args) -> int:
    """The orchestrate pod mode: learner in-process, hosts supervised.

    This process holds the chip; the actor hosts it spawns are forced to
    the CPU platform (:class:`_HostProc`), so none of them claims it."""
    from distributed_ba3c_tpu.utils.backend import (
        configure_compile_cache,
        log_device_info,
    )

    configure_compile_cache()
    log_device_info()
    cfg = BA3CConfig(
        image_size=(args.pod_image_size, args.pod_image_size),
        frame_history=args.pod_frame_history,
        num_actions=args.pod_num_actions,
        fc_units=args.pod_fc_units,
        local_time_max=args.pod_unroll_len,
        predict_batch_size=args.pod_predict_batch_size,
    )
    plane = PodLearnerPlane(
        cfg,
        args.pipe_c2s,
        args.pipe_s2c,
        max_staleness=args.max_staleness if args.max_staleness >= 0 else None,
        publish_every=args.publish_every,
    )
    plane.start()
    sup = PodSupervisor(
        args.pod_hosts,
        lambda i: host_argv(
            i, args.pipe_c2s, args.pipe_s2c,
            env=args.pod_env,
            n_sims=args.pod_sims,
            unroll_len=args.pod_unroll_len,
            segments_per_block=args.pod_segments,
            max_staleness=max(0, args.max_staleness),
            image_size=args.pod_image_size,
            frame_history=args.pod_frame_history,
            num_actions=args.pod_num_actions,
            fc_units=args.pod_fc_units,
            predict_batch_size=args.pod_predict_batch_size,
        ),
    )
    sup.start()
    reg = telemetry.registry("learner")
    try:
        updates = 0
        while args.updates <= 0 or updates < args.updates:
            m = plane.step_once(timeout=1.0)
            if m is not None:
                updates += 1
                if updates % 50 == 0:
                    logger.info(
                        "[pod] update %d (version %d, value_lag_mae %.4f, "
                        "ingested %d blocks)",
                        updates, plane.learner.version,
                        reg.gauge("value_lag_mae").value(),
                        int(reg.counter("pod_ingest_blocks_total").value()),
                    )
        return 0
    except KeyboardInterrupt:
        return 0
    finally:
        sup.stop()
        sup.join(timeout=5)
        sup.close()
        plane.close()
        telemetry.dump("pod run complete")
