"""The shared network-phase driver behind chaos_bench --net / pod_bench --net.

One rig shape (docs/netchaos.md): a real localhost pod — one
:class:`PodLearnerPlane`, N supervised ``pod.host`` subprocesses — with a
:class:`NetChaosPlane` interposed on every pod channel via
:meth:`wrap_pod`. The pod is deliberately the topology under test: its
links are the asynchronous DCN-shaped ones (params broadcast, experience
ship) where degraded networks are survivable by design — the lockstep
actor wires pay a full RTT per env step and belong to a host, not a DCN.

Reps this module knows how to run:

- **throughput** (:func:`run_throughput_rep`): ingest-side env-steps/s
  through QUIET proxies (the control arm prices the proxy itself out of
  the gate) vs under a DCN schedule (:func:`dcn_schedule`, e.g. 50 ms
  RTT + 1% loss). Gate: degraded >= 0.85x clean.
- **partition-and-heal** (:func:`run_partition_rep`): all three pod
  links stop moving bytes for a timed window mid-measurement, then heal.
  Recovery must be complete (ingest resumes, the cache re-syncs to the
  current version) with ZERO learner restarts and ZERO host respawns —
  only typed, counted sheds/rejects/backpressure.
- **integrity** (:func:`run_corrupt_rep`): live corruption/truncation
  injection on the experience + params links with CRC framing armed —
  every mangled frame must land as a typed ``corrupt_frame`` reject
  (``pod_corrupt_frames_total`` / ``params_corrupt_total``) while
  training continues.

Every rep embeds the schedule JSON, the injected-event summary and the
seed-replay verdict (:meth:`NetChaosPlane.replay_check`) — the committed
artifact is reproducible from itself.
"""

from __future__ import annotations

import dataclasses
import os
import socket
import time
from typing import Dict, List, Optional, Tuple

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.netchaos.plane import NetChaosPlane
from distributed_ba3c_tpu.netchaos.schedule import (
    FaultSchedule,
    LinkFaults,
    Partition,
)
from distributed_ba3c_tpu.pod.wire import pod_role
from distributed_ba3c_tpu.telemetry.attribution import stall_attribution
from distributed_ba3c_tpu.utils.serialize import set_wire_crc

#: the pod's three DCN-shaped links, as wrap_pod names them
POD_LINKS = ("params_pub", "params_fetch", "experience")


@dataclasses.dataclass
class NetShape:
    """One rig shape (CI-sized by default; the committed capture scales)."""

    hosts: int = 1
    sims_per_host: int = 2
    segments_per_block: int = 8
    unroll_len: int = 5
    image_size: int = 16
    fc_units: int = 16
    #: host-side staleness bound (0 = ungated host; the partition rep
    #: sheds through the learner gate / link-state machine regardless)
    max_staleness: int = 8
    warmup_timeout: float = 240.0


def free_base() -> Tuple[str, str]:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"tcp://127.0.0.1:{port}", f"tcp://127.0.0.1:{port + 1}"


def quiet_schedule(seed: int = 0) -> FaultSchedule:
    """The control arm: proxies pumping, zero faults — the gate compares
    network degradation against the injector's own cost, not against its
    absence."""
    return FaultSchedule({}, seed=seed)


def dcn_schedule(
    rtt_ms: float = 50.0,
    loss: float = 0.01,
    seed: int = 0,
    jitter_frac: float = 0.2,
) -> FaultSchedule:
    """Emulated-DCN faults on every pod link: half the RTT each way,
    proportional jitter, i.i.d. loss."""
    f = LinkFaults(
        latency_ms=rtt_ms / 2.0,
        jitter_ms=rtt_ms / 2.0 * jitter_frac,
        drop=loss,
    )
    return FaultSchedule({name: f for name in POD_LINKS}, seed=seed)


def partition_schedule(
    start_s: float, dur_s: float, seed: int = 0, direction: str = "both"
) -> FaultSchedule:
    """Timed full (or asymmetric) partition of every pod link, relative
    to the rig's post-warmup clock rebase."""
    f = LinkFaults(
        partitions=(Partition(start_s, start_s + dur_s, direction),)
    )
    return FaultSchedule({name: f for name in POD_LINKS}, seed=seed)


def corrupt_schedule(
    corrupt: float = 0.05, truncate: float = 0.05, seed: int = 0
) -> FaultSchedule:
    """Live integrity injection on the data-bearing links."""
    f = LinkFaults(corrupt=corrupt, truncate=truncate)
    return FaultSchedule(
        {"experience": f, "params_pub": f}, seed=seed
    )


class PodNetRig:
    """One pod under one schedule; the rep functions drive it."""

    def __init__(self, shape: NetShape, schedule: FaultSchedule, crc: bool = True):
        from distributed_ba3c_tpu.config import BA3CConfig
        from distributed_ba3c_tpu.orchestrate.pod import (
            PodLearnerPlane,
            PodSupervisor,
            host_argv,
        )

        telemetry.reset_all()
        # CRC framing is armed process-wide AND in the env so the
        # supervised host subprocesses frame their shipped blocks too —
        # and RESTORED at close(): a later same-process phase (pod_bench
        # --net runs before the aggregate phases) must not silently
        # measure with framing it did not ask for
        from distributed_ba3c_tpu.utils.serialize import wire_crc_enabled

        self._prev_crc = wire_crc_enabled()
        self._prev_crc_env = os.environ.get("BA3C_WIRE_CRC")
        if crc:
            set_wire_crc(True)
            os.environ["BA3C_WIRE_CRC"] = "1"
        self.shape = shape
        cfg = BA3CConfig(
            image_size=(shape.image_size, shape.image_size),
            frame_history=4,
            num_actions=4,
            fc_units=shape.fc_units,
            local_time_max=shape.unroll_len,
            predict_batch_size=16,
        )
        c2s, s2c = free_base()
        self.plane = PodLearnerPlane(
            cfg, c2s, s2c,
            max_staleness=shape.max_staleness or None,
        )
        self.plane.start()
        # tight front HWM: the emulated wire holds ~4 blocks in flight, so
        # a partition backs pressure into the HOST's bounds (SNDHWM ->
        # spill -> ship_backpressure_total) instead of hiding inside a
        # 1000-message proxy buffer
        # arm_on_start=False: timed windows stay dormant through the
        # unknowable-length warmup and come live at the post-warmup
        # rebase — so [2s, 12s) means measurement time, not boot time
        self.nc = NetChaosPlane(
            schedule, push_pull_front_hwm=4, arm_on_start=False
        )
        host_base = self.nc.wrap_pod(c2s, s2c)
        self.nc.start()
        self.sup = PodSupervisor(
            shape.hosts,
            lambda i: host_argv(
                i, host_base[0], host_base[1], env="fake",
                n_sims=shape.sims_per_host,
                unroll_len=shape.unroll_len,
                segments_per_block=shape.segments_per_block,
                max_staleness=shape.max_staleness,
                image_size=shape.image_size, frame_history=4,
                num_actions=4, fc_units=shape.fc_units,
            ),
            backoff_base_s=0.25,
        )
        self.sup.start()
        self._quiesced = False
        reg = telemetry.registry("learner")
        self._c_steps = reg.counter("pod_ingest_env_steps_total")
        self._c_blocks = reg.counter("pod_ingest_blocks_total")

    # -- driving ------------------------------------------------------------
    def warmup(self) -> None:
        deadline = time.monotonic() + self.shape.warmup_timeout
        while time.monotonic() < deadline:
            self.plane.step_once(timeout=0.2)
            hosts_up = len([
                r for r in telemetry.all_registries()
                if r.startswith("pod.host")
            ])
            if (
                self._c_blocks.value() >= 2 * self.shape.hosts
                and hosts_up >= self.shape.hosts
            ):
                # the measurement clock starts NOW: partition windows are
                # relative to this rebase, never to the jax-import warmup
                self.nc.rebase_clock()
                return
        raise RuntimeError(
            f"pod produced no warmup blocks from {self.shape.hosts} "
            f"host(s) through netchaos — {stall_attribution()}"
        )

    def drain(self, seconds: float) -> Tuple[float, int]:
        """Drain the learner for ``seconds``; (env-steps/s, blocks)."""
        n0, b0 = self._c_steps.value(), self._c_blocks.value()
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            self.plane.step_once(timeout=0.05)
        dt = time.perf_counter() - t0
        return (
            round((self._c_steps.value() - n0) / dt, 1),
            int(self._c_blocks.value() - b0),
        )

    def measure(self, seconds: float, windows: int) -> List[float]:
        return [self.drain(seconds)[0] for _ in range(max(1, windows))]

    def host_scalars(self, k: int = 0) -> Dict[str, float]:
        return telemetry.registry(pod_role(k)).scalars()

    def learner_scalars(self) -> Dict[str, float]:
        return telemetry.registry("learner").scalars()

    def evidence(self) -> dict:
        """The rep's standing evidence block: schedule, events, replay."""
        ls = self.learner_scalars()
        return {
            "schedule": self.nc.schedule.to_json(),
            "seed": self.nc.schedule.seed,
            "injected": self.nc.summary(),
            "replay": self.nc.replay_check(),
            "publisher_links": self.plane.publisher.link_states(),
            "ingest_blocks": int(ls.get("pod_ingest_blocks_total", 0)),
            "ingest_dropped": int(ls.get("pod_ingest_dropped_total", 0)),
            "ingest_rejected": int(ls.get("pod_ingest_rejected_total", 0)),
            "pod_corrupt_frames": int(ls.get("pod_corrupt_frames_total", 0)),
            "stale_rejected": int(ls.get("stale_blocks_rejected_total", 0)),
            "host0": self.host_scalars(0),
        }

    def quiesce(self) -> None:
        """Stop the traffic sources (hosts, then proxies) and let the
        ingest drain what the pumps flushed. Evidence — the event log,
        the replay diff against live ``_seq`` counters, the typed-reject
        totals — is only race-free AFTER this: a message processed
        between snapshotting events and reading sequence counters would
        read as a spurious seed mismatch."""
        if self._quiesced:
            return
        self._quiesced = True
        self.sup.stop()
        self.sup.join(timeout=5)
        self.sup.close()
        self.nc.stop()
        for p in self.nc.proxies:
            if p.is_alive():
                p.join(timeout=2)
        deadline = time.monotonic() + 3
        while time.monotonic() < deadline:
            if self.plane.step_once(timeout=0.2) is None:
                break

    def close(self) -> None:
        self.quiesce()
        self.nc.close()
        self.plane.close()
        set_wire_crc(self._prev_crc)
        if self._prev_crc_env is None:
            os.environ.pop("BA3C_WIRE_CRC", None)
        else:
            os.environ["BA3C_WIRE_CRC"] = self._prev_crc_env


# ---------------------------------------------------------------------------
# reps
# ---------------------------------------------------------------------------

def run_throughput_rep(
    shape: NetShape,
    schedule: FaultSchedule,
    seconds: float,
    windows: int,
) -> dict:
    rig = PodNetRig(shape, schedule)
    try:
        rig.warmup()
        rates = rig.measure(seconds, windows)
        out = {
            "rate": max(rates),  # best window: the repo's scheduler filter
            "window_rates": rates,
            "updates": int(rig.plane.learner.version),
        }
        rig.quiesce()  # evidence/replay is only race-free on a still rig
        out.update(rig.evidence())
        return out
    finally:
        rig.close()


def run_partition_rep(
    shape: NetShape,
    seed: int,
    pre_s: float = 2.0,
    partition_s: float = 4.0,
    heal_s: float = 8.0,
) -> dict:
    """Full partition of every pod link mid-run, then heal; recovery must
    be restart-free and fully typed."""
    pre_s = max(pre_s, 1.0)  # the drain slack math below needs room
    partition_s = max(partition_s, 2.0)
    schedule = partition_schedule(pre_s, partition_s, seed=seed)
    rig = PodNetRig(shape, schedule)
    out: dict = {"recovered": False}
    try:
        rig.warmup()
        # drains deliberately leave 0.25 s slack around each window
        # boundary: the heal releases a burst of everything the wire and
        # the host's spill held, and measuring it inside the "partition"
        # window would mask the stall the rep exists to show
        pre_rate, pre_blocks = rig.drain(pre_s - 0.25)
        v_at_partition = int(rig.plane.learner.version)
        rig.drain(0.5)  # spans the partition-start boundary, discarded
        part_rate, part_blocks = rig.drain(partition_s - 1.0)
        rig.drain(0.75)  # spans the heal boundary, discarded
        heal_rate, heal_blocks = rig.drain(heal_s)
        # the killed-link rejoin proof: the host's mirrored params_version
        # must pass the partition-time publish frontier after the heal
        deadline = time.monotonic() + 60
        rejoined = None
        while time.monotonic() < deadline:
            rig.plane.step_once(timeout=0.2)
            v = rig.host_scalars(0).get("params_version", -1)
            if v >= v_at_partition:
                rejoined = v
                break
        rig.quiesce()  # evidence/replay is only race-free on a still rig
        orch = telemetry.registry("orchestrator").scalars()
        host0 = rig.host_scalars(0)
        out.update({
            "pre": {"rate": pre_rate, "blocks": pre_blocks},
            "partition": {"rate": part_rate, "blocks": part_blocks},
            "heal": {"rate": heal_rate, "blocks": heal_blocks},
            "version_at_partition": v_at_partition,
            "rejoined_at_version": rejoined,
            "learner_restarts": int(orch.get("learner_restarts_total", 0)),
            "host_respawns": int(orch.get("server_respawns_total", 0)),
            "ship_backpressure": int(
                host0.get("ship_backpressure_total", 0)
            ),
            "shipped_dropped": int(host0.get("shipped_dropped_total", 0)),
            "fetch_retries": int(
                host0.get("params_fetch_retries_total", 0)
            ),
        })
        out.update(rig.evidence())
        out["recovered"] = bool(
            rejoined is not None
            and heal_blocks > 0
            # the partition actually STALLED the link (< half the clean
            # rate strictly inside the window; ~0 in practice)
            and part_rate < 0.5 * max(pre_rate, 1.0)
            and out["learner_restarts"] == 0
            and out["host_respawns"] == 0
        )
        return out
    finally:
        rig.close()


def run_corrupt_rep(
    shape: NetShape, seed: int, seconds: float = 6.0
) -> dict:
    """Live corruption/truncation against CRC-armed codecs: every mangled
    frame is a typed reject, training continues."""
    rig = PodNetRig(shape, corrupt_schedule(seed=seed), crc=True)
    try:
        rig.warmup()
        rate, blocks = rig.drain(seconds)
        rig.quiesce()  # every in-flight mangled frame delivered + decoded
        out = {"rate": rate, "blocks": blocks}
        out.update(rig.evidence())
        injected = out["injected"]
        mangled = injected.get("corrupt", 0) + injected.get("truncate", 0)
        # the gate is EVERY-frame-typed on the lossless link: experience
        # mangles all reach the bound PULL ingest after the quiesce, so
        # pod typed rejects must cover them one-for-one. params_pub
        # mangles can be legitimately shed by SUB HWM before delivery —
        # their typed counters are evidence, not a 1:1 bound.
        exp_mangled = sum(
            1 for e in rig.nc.events()
            if e["link"] == "experience" and e["kind"] in ("corrupt", "truncate")
        )
        pod_typed = out["pod_corrupt_frames"] + out["ingest_rejected"]
        typed = pod_typed + int(
            out["host0"].get("params_corrupt_total", 0)
        ) + int(
            out["host0"].get("params_malformed_total", 0)
        )
        out["injected_mangled"] = mangled
        out["experience_mangled"] = exp_mangled
        out["typed_rejects"] = typed
        out["all_typed"] = bool(
            mangled > 0
            and blocks > 0
            and exp_mangled > 0
            and pod_typed >= exp_mangled
        )
        return out
    finally:
        rig.close()
