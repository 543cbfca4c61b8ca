"""The trainer: epochs × steps main loop over the mesh-sharded update.

Reference equivalent (SURVEY.md §2.5 #13-15, call stack §3.1):
``Trainer.train() -> main_loop()`` with callback dispatch. What changed,
TPU-first:

- ``run_step``'s ``sess.run(train_op)`` + async PS gradient push becomes one
  jitted shard_map step with the grads psum'd over the mesh (§3.4 replaced).
- ``QueueInput``/``EnqueueThread`` become ``TrainFeed`` (host batcher thread)
  + ``jax.device_put`` at the head of each step: device dispatch is async,
  so staging the next batch overlaps the previous step's execution (see the
  ``run_step`` note — no explicit double buffer exists or is needed).
- The predict towers' shared-variable reads become an explicit params publish
  to the BatchedPredictor every ``publish_every`` steps (on-device ref swap,
  no host copy).
"""

from __future__ import annotations

import dataclasses
import queue
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.train.callbacks import Callback, Callbacks
from distributed_ba3c_tpu.utils import logger
from distributed_ba3c_tpu.utils.stats import StatCounter, StatHolder


@dataclasses.dataclass
class TrainLoopConfig:
    """Loop shape + wiring (reference ``TrainConfig``, SURVEY.md §2.5 #13)."""

    steps_per_epoch: int = 1000
    max_epoch: int = 100
    log_dir: Optional[str] = None
    publish_every: int = 1  # params → predictor every N steps
    feed_timeout: float = 120.0
    # multi-host only: secs without epoch progress before declaring a peer
    # rank dead and exiting 75 (0 → 600s default when process_count > 1)
    rank_stall_timeout: float = 0.0


class Trainer:
    """Owns the TrainState, the jitted step, and the callback lifecycle."""

    def __init__(
        self,
        config: TrainLoopConfig,
        cfg: BA3CConfig,
        step_fn: Callable,  # from make_train_step
        state,  # TrainState (host or device)
        feed,  # TrainFeed-like: next_batch(timeout)
        callbacks: List[Callback],
        predictor=None,  # BatchedPredictor to publish params to
        score_queue: Optional[queue.Queue] = None,
        is_chief: bool = True,
        samples_per_step: Optional[int] = None,
    ):
        self.config = config
        self.cfg = cfg
        self.step_fn = step_fn
        self.state = jax.device_put(state, step_fn.state_sharding)
        self.feed = feed
        self.predictor = predictor
        self.score_queue = score_queue
        self.is_chief = is_chief

        self.hyperparams: Dict[str, float] = {
            "learning_rate": cfg.learning_rate,
            "entropy_beta": cfg.entropy_beta,
        }
        self.global_step = 0
        self.epoch_num = 0
        self.batch_size = samples_per_step or cfg.batch_size
        from distributed_ba3c_tpu.utils.backend import log_device_info

        self.stat_holder = StatHolder(
            config.log_dir, run_info={"device": log_device_info()}
        )
        self.score_counter: Optional[StatCounter] = StatCounter()
        self.last_mean_score: Optional[float] = None
        self.ckpt_manager = None  # set by ModelSaver
        self.metrics = None
        self._pending_trace = None  # sampled trace between stage + step
        self._first_dispatch = True
        self._callbacks = Callbacks(callbacks)

        # telemetry (docs/observability.md): the learner registry is the
        # single account of training progress — StatPrinter derives its fps
        # from these counters instead of keeping its own step count
        tele = telemetry.registry("learner")
        self._c_steps = tele.counter("train_steps_total")
        self._c_samples = tele.counter("train_samples_total")
        self._h_step = tele.histogram("step_s", unit=1e-6)

    # -- predictor glue ----------------------------------------------------
    def predictor_fn(self) -> Callable[[np.ndarray], np.ndarray]:
        """Greedy batched predict on CURRENT params (for Evaluator)."""
        assert self.predictor is not None

        def predict(states: np.ndarray) -> np.ndarray:
            _, _, greedy_actions = self.predictor.predict_batch(states)
            return greedy_actions

        return predict

    def _publish_params(self):
        if self.predictor is not None:
            # COPY before publishing: the train step donates the state buffers
            # (donate_argnums), so the predictor must never alias them — an
            # in-flight forward reading a donated-and-reused buffer crashes in
            # native code. The copy is one small device-to-device transfer.
            params = jax.tree_util.tree_map(jnp.copy, self.state.params)
            # sanctioned single-host publish: the version IS the train
            # step (publish_every cadence), and the pod plane replaces
            # this path entirely when hosts serve from the stale cache
            self.predictor.update_params(params)  # ba3clint: disable=A10

    def _drain_scores(self):
        if self.score_queue is None:
            return
        while True:
            try:
                self.score_counter.feed(self.score_queue.get_nowait())
            except queue.Empty:
                return

    # -- loop --------------------------------------------------------------
    def _put(self, v, sharding):
        """Host batch → sharded device array.

        Single-host: plain device_put. Multi-host: each process feeds its
        LOCAL rows and jax assembles the global array from per-host shards
        (the replacement for the reference's per-worker queue; each TF
        worker likewise only saw its own simulators' batches, SURVEY §3.4).
        """
        if jax.process_count() > 1:
            return jax.make_array_from_process_local_data(sharding, v)
        return jax.device_put(v, sharding)

    def _next_device_batch(self):
        if getattr(self.feed, "is_device_ingest", False):
            # staged pipeline (data/staging.py DeviceIngest): the batch's
            # H2D was dispatched behind the PREVIOUS step (run_step's
            # prefetch call) whenever the feed kept up — the claim here is
            # then just a handoff, and the ingest/h2d_copy hops were
            # already recorded by the pipeline
            batch = self.feed.next_batch(timeout=self.config.feed_timeout)
            self._pending_trace = batch.pop("_trace", None)
            return batch
        batch = self.feed.next_batch(timeout=self.config.feed_timeout)
        # a sampled trace rode the batch through the feed (tracing.py):
        # claim it before staging — device_put must never see the ref
        trace = batch.pop("_trace", None)
        sharding = self.step_fn.batch_sharding
        if isinstance(sharding, dict):
            out = {k: self._put(v, sharding[k]) for k, v in batch.items()}
        else:
            out = {k: self._put(v, sharding) for k, v in batch.items()}
        if trace is not None:
            # feed handoff -> staged on device (host-side ingest hop)
            self._pending_trace = trace.hop("ingest", "learner")
        return out

    def run_step(self) -> None:
        # Overlap note: step_fn dispatch is ASYNC, so fetching/staging the
        # next batch at the head of the next call already overlaps the
        # device's execution of this step — no explicit double buffer is
        # needed (and none is claimed; a post-step staging fetch was tried
        # and reverted: it could starve at shutdown and discard the final
        # step's accounting). The overlap is bounded by trigger_step
        # callbacks that fetch metrics (StatPrinter samples every N steps).
        t0 = time.monotonic()
        batch = self._next_device_batch()
        t_claimed = time.monotonic()
        if self._pending_trace is not None:
            # sampled steps only: the jax.profiler step region carries the
            # trace/span ids, so a chip-session capture lines up with the
            # host spans by id (utils/profiling.py; no-op cost when no
            # profiler session is attached)
            from distributed_ba3c_tpu.utils.profiling import step_annotation

            with step_annotation(
                "train_step", self.global_step,
                trace_id=self._pending_trace.trace_id,
                span_id=self._pending_trace.parent_id,
            ):
                self.state, self.metrics = self.step_fn(
                    self.state,
                    batch,
                    self.hyperparams["entropy_beta"],
                    self.hyperparams["learning_rate"],
                )
        else:
            self.state, self.metrics = self.step_fn(
                self.state,
                batch,
                self.hyperparams["entropy_beta"],
                self.hyperparams["learning_rate"],
            )
        self.global_step += 1
        if self._first_dispatch:
            # trace + compile (or cache read) + the first execution, from
            # the moment the first batch was in hand: set-up time, recorded
            # apart from the steady rate. One host sync, on this process's
            # first step only.
            self._first_dispatch = False
            jax.block_until_ready(self.metrics)
            self.stat_holder.add_stat(
                "first_dispatch_s", time.monotonic() - t_claimed
            )
        prefetch = getattr(self.feed, "prefetch", None)
        if prefetch is not None:
            # staged pipeline: dispatch the NEXT batch's H2D right behind
            # the step dispatch above, so the transfer overlaps the
            # device's execution of THIS step. Non-blocking by contract —
            # the shutdown-starvation and lost-accounting failure modes
            # that reverted the old post-step staging fetch (see the
            # Overlap note above) were properties of a BLOCKING fetch
            prefetch()
        if self._pending_trace is not None:
            # host-side dispatch of the update (device execution is async;
            # a chip-session jax.profiler capture correlates via the
            # step_annotation trace/span tags — utils/profiling.py)
            self._pending_trace.hop(
                "learner_step", "learner", tags={"step": self.global_step}
            )
            self._pending_trace = None
        # step latency here covers feed wait + staging + async dispatch —
        # the host-side budget (device execution overlaps the next call)
        self._h_step.observe(time.monotonic() - t0)
        self._c_steps.inc()
        self._c_samples.inc(self.batch_size)
        if self.global_step % self.config.publish_every == 0:
            self._publish_params()
        self._drain_scores()
        self._callbacks.trigger_step(self.metrics)

    def train(self) -> None:
        self._callbacks.setup(self)
        if self.config.log_dir:
            logger.set_logger_dir(self.config.log_dir)
        self._callbacks.before_train()
        logger.info(
            "learner state on devices %s",
            sorted(d.id for d in self.state.step.sharding.device_set),
        )
        self._publish_params()
        # multi-host rank-failure detection (SURVEY §5): a dead peer wedges
        # this rank in the next psum forever; the watchdog converts that into
        # a bounded-time exit 75 so the launcher can resume from checkpoints
        from distributed_ba3c_tpu.parallel.watchdog import (
            LockstepWatchdog,
            resolve_timeout,
        )

        try:
            with LockstepWatchdog(
                resolve_timeout(getattr(self.config, "rank_stall_timeout", 0)),
                what=f"rank {jax.process_index()}/{jax.process_count()} "
                "epoch loop",
            ) as watchdog:
                for self.epoch_num in range(1, self.config.max_epoch + 1):
                    for _ in range(self.config.steps_per_epoch):
                        self.run_step()
                    self._callbacks.trigger_epoch()
                    watchdog.beat()
        except KeyboardInterrupt:
            logger.warn("training interrupted")
        except queue.Empty:
            # feed starvation is a FAILURE (dead actor plane), not a clean
            # shutdown — propagate so launchers/CI see a non-zero exit
            logger.error(
                "train feed starved for %.0fs — actor plane dead?",
                self.config.feed_timeout,
            )
            raise RuntimeError("train feed starved; actor plane dead") from None
        finally:
            self._callbacks.after_train()
            # close the TB event writer (a never-joined background thread
            # otherwise — the exact leak class behind the round-1 deadlock)
            self.stat_holder.close()

    # -- resume ------------------------------------------------------------
    def restore(self, ckpt_dir: str, step: Optional[int] = None) -> None:
        """Resume params/opt/step from a checkpoint directory (--load)."""
        from distributed_ba3c_tpu.train.checkpoint import CheckpointManager

        mgr = CheckpointManager(ckpt_dir)
        restored = mgr.restore(jax.device_get(self.state), step)
        self.state = jax.device_put(restored, self.step_fn.state_sharding)
        self.global_step = int(self.state.step)
        self._publish_params()
        logger.info("restored checkpoint at step %d", self.global_step)
