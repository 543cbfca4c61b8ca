"""The bounded-staleness learner: measured params lag, gated and exported.

The overlap split (fused/overlap.py) proved the decisive property: the
V-trace gradient body reads the block's RECORDED behavior log-probs, so
the off-policy correction is exact at any params lag — lag never enters
the compiled program, only the data. This module turns that property into
the pod's learner plane:

- :func:`make_pod_learner_step` builds the ``pod.learner`` program — the
  SAME gradient body and update tail as ``fused.learner``
  (make_block_grads / make_finish_update), compiled standalone so
  host-fed blocks of any [T, B] shape drive it without an actor program
  attached. ``tests/test_pod.py`` pins lag-0 bit-exactness against the
  fused step (the overlap parity contract, extended).
- :class:`StalenessGate` measures each block's lag (learner version minus
  the block's collection stamp), exports it as the ``params_lag``
  histogram, and REJECTS blocks beyond ``max_staleness`` with a typed
  counter — the reference cluster's silent staleness made measurable and
  bounded (SURVEY.md §3.4).
- :class:`PodLearner` ties gate + step + versioning + publish cadence
  together: every accepted block is one update, every update bumps the
  version, every ``publish_every``-th version goes out over the
  :class:`~distributed_ba3c_tpu.pod.publisher.ParamsPublisher`, and
  ``value_lag_mae`` is maintained as a first-class SLO gauge.
- :class:`LaggedBlockDriver` generalizes the overlap schedule's fixed
  lag-1 to ANY measured lag k, device-free: a ring of params snapshots
  (taken through the overlap step's own ``prep`` program, so nothing ever
  aliases learner-donated buffers) feeds the actor program the policy of
  k versions ago. It exists for the staleness-vs-learning-quality curve
  (scripts/pod_bench.py) and the lag-k oracle tests — the measurement the
  reference paper never published.
"""

from __future__ import annotations

import collections
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.audit import tripwire_jit
from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.fused.overlap import (
    TrajBlock,
    make_block_grads,
    make_finish_update,
)
from distributed_ba3c_tpu.models.a3c import BA3CNet
from distributed_ba3c_tpu.models.policy import refuse_carry
from distributed_ba3c_tpu.parallel.mesh import DATA_AXIS
from distributed_ba3c_tpu.parallel.train_step import TrainState

import optax


def make_pod_learner_step(
    model: BA3CNet,
    optimizer: optax.GradientTransformation,
    cfg: BA3CConfig,
    mesh: Mesh,
    grad_chunk_samples: int = 4096,
) -> Callable:
    """The pod's compiled learner: fn(train, block, beta, lr) -> (train, m).

    Identical math to ``fused.learner`` (shared factories), registered as
    its own audit entry point ``pod.learner`` — host-fed blocks arrive at
    whatever [T, B] the actor hosts collate, which must stay ONE warmed
    shape per run (the BA3C_AUDIT=1 tripwire raises on a mid-run reshape,
    exactly the predictor-bucket contract).
    """
    refuse_carry(model, "the pod learner")
    block_grads = make_block_grads(model, cfg, grad_chunk_samples)
    finish_update = make_finish_update(optimizer)

    def local_learner(train: TrainState, block: TrajBlock, entropy_beta,
                      learning_rate):
        grads, aux = block_grads(train.params, block, entropy_beta)
        return finish_update(train, grads, aux, block.rewards, learning_rate)

    batch_spec = P(DATA_AXIS)
    tb_spec = P(None, DATA_AXIS)  # time-major leaves
    block_specs = TrajBlock(
        states=tb_spec,
        actions=tb_spec,
        rewards=tb_spec,
        dones=tb_spec,
        behavior_log_probs=tb_spec,
        behavior_values=tb_spec,
        bootstrap_state=batch_spec,
    )
    sharded = jax.shard_map(
        local_learner,
        mesh=mesh,
        in_specs=(P(), block_specs, P(), P()),
        out_specs=(P(), P()),
    )
    # registered audit entry point (distributed_ba3c_tpu/audit.py): donated
    # train state, exactly-once grad psum; the block stays undonated (a
    # host-fed block is consumed once, but the LaggedBlockDriver's blocks
    # are the actor program's double-buffer slots — same contract as
    # fused.learner keeps both callers correct)
    jitted = tripwire_jit("pod.learner", sharded, donate_argnums=(0,))

    def step(train: TrainState, block: TrajBlock, entropy_beta,
             learning_rate=None):
        if learning_rate is None:
            learning_rate = cfg.learning_rate
        return jitted(
            train,
            block,
            jnp.asarray(entropy_beta, jnp.float32),
            jnp.asarray(learning_rate, jnp.float32),
        )

    step.state_sharding = NamedSharding(mesh, P())
    step.block_sharding = TrajBlock(
        states=NamedSharding(mesh, tb_spec),
        actions=NamedSharding(mesh, tb_spec),
        rewards=NamedSharding(mesh, tb_spec),
        dones=NamedSharding(mesh, tb_spec),
        behavior_log_probs=NamedSharding(mesh, tb_spec),
        behavior_values=NamedSharding(mesh, tb_spec),
        bootstrap_state=NamedSharding(mesh, batch_spec),
    )
    step.mesh = mesh
    step.audit_jit = jitted  # tools/ba3caudit traces THIS program
    return step


def batch_to_block(
    batch: Dict[str, np.ndarray], block_sharding: Optional[TrajBlock] = None
) -> TrajBlock:
    """Host [T, B] experience batch (pod/wire.py EXPERIENCE_KEYS layout) →
    a device TrajBlock. Dtypes are coerced here, in one place: the wire
    ships whatever the collate produced, the program's input contract
    lives with the program.

    COMPAT path: seven fresh allocations per block. The consuming loop
    (:meth:`PodLearner.consume`) stages through a
    :class:`~distributed_ba3c_tpu.data.staging.BlockStager` instead —
    one copy into a REUSED per-shape buffer, ready-fenced against the
    in-flight H2D — so this stays for one-shot callers only."""
    from distributed_ba3c_tpu.data.staging import count_legacy_copies

    count_legacy_copies(1.0)
    leaves = TrajBlock(
        # sanctioned compat copies — PodLearner's BlockStager is the
        # budget path (reused buffers, same dtype coercion)
        states=np.ascontiguousarray(batch["state"], np.uint8),  # ba3clint: disable=A13
        actions=np.ascontiguousarray(batch["action"], np.int32),  # ba3clint: disable=A13
        rewards=np.ascontiguousarray(batch["reward"], np.float32),  # ba3clint: disable=A13
        dones=np.ascontiguousarray(batch["done"], np.float32),  # ba3clint: disable=A13
        behavior_log_probs=np.ascontiguousarray(  # ba3clint: disable=A13
            batch["behavior_log_probs"], np.float32
        ),
        behavior_values=np.ascontiguousarray(  # ba3clint: disable=A13
            batch["behavior_values"], np.float32
        ),
        bootstrap_state=np.ascontiguousarray(  # ba3clint: disable=A13
            batch["bootstrap_state"], np.uint8
        ),
    )
    if block_sharding is None:
        return leaves
    return jax.tree_util.tree_map(jax.device_put, leaves, block_sharding)


class StalenessGate:
    """Measure every block's params lag; bound it when asked.

    ``admit(block_version, current_version)`` returns the measured lag
    (>= 0), or None when the block is beyond ``max_staleness`` — rejected
    with the ``stale_blocks_rejected_total`` typed counter and a flight
    event, never an exception: the consuming loop must keep draining so
    host backpressure cannot build behind a burst of stale blocks.
    ``max_staleness=None`` measures without bounding (the histogram and
    the SLO gauges still export).
    """

    def __init__(
        self, max_staleness: Optional[int] = None, tele_role: str = "learner"
    ):
        self.max_staleness = (
            None if max_staleness is None else int(max_staleness)
        )
        tele = telemetry.registry(tele_role)
        self._h_lag = tele.histogram("params_lag", unit=1)
        self._c_rejected = tele.counter("stale_blocks_rejected_total")
        self._g_bound = tele.gauge("pod_max_staleness")
        self._g_bound.set(-1 if self.max_staleness is None else self.max_staleness)
        self._g_last_lag = tele.gauge("params_lag_last")

    def admit(
        self,
        block_version: int,
        current_version: int,
        host: Optional[int] = None,
    ) -> Optional[int]:
        lag = max(0, int(current_version) - int(block_version))
        self._h_lag.observe(lag)
        self._g_last_lag.set(lag)
        if self.max_staleness is not None and lag > self.max_staleness:
            self._c_rejected.inc()
            telemetry.record(
                "stale_block_rejected",
                lag=lag,
                bound=self.max_staleness,
                host=host,
                block_version=int(block_version),
                learner_version=int(current_version),
            )
            return None
        return lag


class PodLearner:
    """Versioned consumption of stamped blocks: gate → update → publish.

    One instance, one consuming thread (the pod learner loop). ``state``
    is device_put with the step's sharding here; hyperparameters are
    plain mutable attributes (the pod loop owns its schedule)."""

    def __init__(
        self,
        step: Callable,
        state: TrainState,
        cfg: BA3CConfig,
        publisher: Optional[Any] = None,
        max_staleness: Optional[int] = None,
        publish_every: int = 1,
        tele_role: str = "learner",
        stager_slots: int = 4,
    ):
        self.step = step
        self.state = jax.device_put(state, step.state_sharding)
        self.cfg = cfg
        self.publisher = publisher
        self.publish_every = max(1, int(publish_every))
        if (
            max_staleness is not None
            and max_staleness < self.publish_every
        ):
            # lag is measured in UPDATES but hosts can only be stamped
            # with PUBLISHED versions: just before each publish a
            # perfectly-current host's blocks carry apparent lag up to
            # publish_every - 1, so a tighter bound would shed healthy
            # experience forever — a config lie, refused at construction
            raise ValueError(
                f"max_staleness {max_staleness} < publish_every "
                f"{self.publish_every}: blocks are stamped with published "
                "versions, so the bound must cover at least one publish "
                "interval or a healthy pod persistently rejects fresh "
                "experience"
            )
        self.entropy_beta = cfg.entropy_beta
        self.learning_rate = cfg.learning_rate
        self.version = 0
        # staged ingest (data/staging.py): ONE copy per block into a
        # reused per-shape buffer replaces batch_to_block's seven fresh
        # ascontiguousarray allocations; hand this same stager to
        # PodIngest so the wire→staging write runs on the receive thread,
        # overlapping the learner's step (docs/ingest.md). When wired
        # into an ingest, ``stager_slots`` must cover the ingest DEPTH
        # (every buffered StampedBatch holds a slot) or the backlogged
        # regime degrades to per-block transient allocations — the very
        # cost the stager removes (orchestrate/pod.py sizes it depth+2)
        from distributed_ba3c_tpu.data.staging import BlockStager

        self.stager = BlockStager(slots=stager_slots, tele_role=tele_role)
        self.gate = StalenessGate(max_staleness, tele_role=tele_role)
        self._tele_role = tele_role
        tele = telemetry.registry(tele_role)
        self._c_updates = tele.counter("pod_updates_total")
        self._c_epoch_mismatch = tele.counter("epoch_mismatch_blocks_total")
        self._g_version = tele.gauge("pod_learner_version")
        self._g_lag_mae = tele.gauge("value_lag_mae")
        self.last_metrics: Optional[dict] = None
        if publisher is not None:
            # version 0 goes out immediately: actor hosts need SOME policy
            # before the first update exists (the late-joiner fetch answers
            # with this same snapshot)
            self._publish()

    def _publish(self) -> None:
        # device_get AFTER the last dispatched update resolves (it blocks
        # on the param futures) and BEFORE the next step call donates the
        # buffers — the same anti-aliasing contract as fused.prep, paid
        # here as one host copy per publish interval
        self.publisher.publish(
            self.version,
            jax.device_get(self.state.params),
            step=int(self.state.step),
        )

    def consume(self, stamped) -> Optional[dict]:
        """Gate + update on one ingest batch (pod/ingest.py StampedBatch);
        returns the update's metrics, or None when the block was rejected."""
        ref = getattr(stamped, "trace", None)
        if (
            self.publisher is not None
            and getattr(stamped, "epoch", 0)
            and stamped.epoch != self.publisher.epoch
        ):
            # a block stamped under a DIFFERENT publisher lifetime (the
            # host outlived a learner restart, or a foreign learner's
            # host misdelivered): its version counts in a lineage this
            # learner does not own, so no lag can honestly be measured —
            # typed rejection, and the host's cache will adopt OUR epoch
            # from the next broadcast
            self._c_epoch_mismatch.inc()
            telemetry.record(
                "pod_epoch_mismatch",
                host=stamped.host,
                block_epoch=stamped.epoch,
                learner_epoch=self.publisher.epoch,
            )
            if ref is not None:
                # same visibility contract as the staleness-gate
                # rejection below: a rejected block's trace ENDS with a
                # verdict span, never a silent disappearance
                ref.hop(
                    "epoch_gate", self._tele_role,
                    tags={"rejected": True, "reason": "epoch_mismatch"},
                )
            self._release_staged(stamped)
            return None
        lag = self.gate.admit(stamped.version, self.version, stamped.host)
        if lag is None:
            if ref is not None:
                # the trace ends HERE, visibly: a rejected block's last
                # span is the gate verdict, not a silent disappearance
                ref.hop(
                    "staleness_gate", self._tele_role,
                    tags={"rejected": True, "lag": "over_bound"},
                )
            self._release_staged(stamped)
            return None
        if ref is not None:
            ref = ref.hop(
                "staleness_gate", self._tele_role, tags={"lag": lag}
            )
        block = self._stage_block(stamped)
        if ref is not None:
            ref = ref.hop("pod_ingest_stage", self._tele_role)
        out = self._update(block)
        if ref is not None:
            ref.hop("pod_learner_step", self._tele_role)
        return out

    def _stage_block(self, stamped) -> TrajBlock:
        """Admitted block → device TrajBlock through the staging path: the
        wire views (or a receive-thread pre-staged block, pod/ingest.py)
        cross the host exactly once."""
        from distributed_ba3c_tpu.data.staging import StagedBlock

        staged = stamped.batch
        if not isinstance(staged, StagedBlock):
            staged = self.stager.copy_in(staged)
        return self.stager.to_device(staged, self.step.block_sharding)

    def _release_staged(self, stamped) -> None:
        """A rejected block's receive-thread staging slot must go back in
        rotation without a transfer."""
        from distributed_ba3c_tpu.data.staging import StagedBlock

        if isinstance(stamped.batch, StagedBlock):
            self.stager.cancel(stamped.batch)

    def consume_block(self, block: TrajBlock, block_version: int,
                      host: Optional[int] = None) -> Optional[dict]:
        """Gate + update on an already-device-resident TrajBlock (the
        LaggedBlockDriver path)."""
        lag = self.gate.admit(block_version, self.version, host)
        if lag is None:
            return None
        return self._update(block)

    def _update(self, block: TrajBlock) -> dict:
        self.state, metrics = self.step(
            self.state, block, self.entropy_beta, self.learning_rate
        )
        self.version += 1
        self._c_updates.inc()
        self._g_version.set(self.version)
        # the SLO gauge reads the latest update's fetched value — one
        # scalar fetch per update; the pod learner loop is host-paced
        # (ingest wait dominates), so this sync is not a schedule hazard
        self._g_lag_mae.set(float(metrics["value_lag_mae"]))
        self.last_metrics = metrics
        if self.publisher is not None and self.version % self.publish_every == 0:
            self._publish()
        return metrics


class LaggedBlockDriver:
    """Drive rollout at the policy of ``lag`` versions ago, device-free.

    The overlap split's schedule generalized: a ring of ``lag + 1`` params
    snapshots (each taken through the overlap step's ``prep`` program —
    never aliasing learner-donated buffers) hands the actor program the
    OLDEST version's snapshot, and the learner consumes each block stamped
    with that version. At ``lag=0`` the schedule is exactly the overlap
    lag-0 sequence, which is the fused step's — the parity anchor. The
    first ``lag`` iterations ramp (the ring is still filling), which the
    ``params_lag`` histogram shows honestly.
    """

    def __init__(self, overlap_step, learner: PodLearner, lag: int):
        if lag < 0:
            raise ValueError(f"lag must be >= 0, got {lag}")
        self.actor_jit = overlap_step.actor_jit
        self.prep_jit = overlap_step.prep_jit
        self.learner = learner
        self.lag = int(lag)
        self.astate = None
        self._snaps: collections.deque = collections.deque()

    def prime(self, overlap_state) -> None:
        """Adopt a fresh OverlapState (overlap_step.put's output): the env
        carry drives the actor; the train state replaces the learner's."""
        self.astate = overlap_state.actor
        self.learner.state = overlap_state.train

    def iterate(self) -> Optional[dict]:
        """One rollout + one (possibly rejected) update; returns the
        update metrics or None if the gate rejected the block."""
        if self.astate is None:
            raise RuntimeError("prime() the driver with an OverlapState first")
        snap = self.prep_jit(self.learner.state.params)
        self._snaps.append((self.learner.version, snap))
        while len(self._snaps) > self.lag + 1:
            self._snaps.popleft()
        version, aparams = self._snaps[0]
        self.astate, block = self.actor_jit(aparams, self.astate)
        return self.learner.consume_block(block, version)
