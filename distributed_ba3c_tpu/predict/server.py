"""SLO-aware serving plane: continuous batching, deadline admission, N policies.

Reference equivalent (SURVEY.md §3.3): ``MultiThreadAsyncPredictor`` /
``PredictorWorkerThread`` — N threads each draining a shared queue into a
``sess.run`` on a predict tower, best-effort, no latency contract. The
TPU-native redesign (BASELINE.json + ROADMAP item 2, docs/serving.md):

- ONE compiled function per policy: forward + categorical sample on device;
  action sampling never returns logits to the host. Batch shapes are padded
  to warmed pow-2 buckets so XLA compiles a handful of programs once.
- **Continuous batching**: a single scheduler thread keeps up to
  ``dispatch_depth`` device calls in flight and admits freshly queued tasks
  into the NEXT bucket the moment the current one is dispatched — the fetch
  of call k happens only after call k+1 is enqueued (the overlap lesson,
  docs/overlap.md: the host must never sync between dispatches), so the
  device never idles between micro-batches. The in-flight call IS the
  coalesce window; the ``coalesce_ms`` timer only applies when the device
  is idle.
- **Deadline admission + load shedding**: every task can carry a deadline
  (defaulted from ``slo_ms``); the scheduler sheds tasks that cannot make
  their deadline BEFORE spending device time on them, and a bounded
  admission queue turns overload into fast typed rejection
  (:class:`ShedReject`) instead of unbounded latency. Tasks without a
  deadline keep the training plane's backpressure contract (blocking put).
- **Multi-policy serving**: N checkpoints hot simultaneously behind the one
  scheduler (``add_policy``); each task carries a policy id, a canary
  fraction routes live traffic deterministically (``set_canary``), and a
  shadow policy (``set_shadow``) sees every served batch with its results
  dropped before any caller — per-policy row counters keep the evaluation
  observable (docs/observability.md).

Weights live in device HBM; the learner publishes fresh params with
``update_params`` (an atomic Python ref swap — canary/shadow policies stay
pinned at their own checkpoints unless explicitly republished).
"""

from __future__ import annotations

import collections
import queue
import re
import threading
import weakref
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ba3c_tpu.models.policy import refuse_carry
from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.telemetry import tracing as _tracing
from distributed_ba3c_tpu.audit import tripwire_jit
from distributed_ba3c_tpu.utils import logger
from distributed_ba3c_tpu.utils.concurrency import (
    FastQueue,
    StoppableThread,
    queue_put_stoppable,
)

#: metric-name grammar for policy ids: they are embedded in Prometheus
#: series names (``policy_<id>_rows_total``), so one junk id would poison
#: every scrape (telemetry/exporters.py enforces the same grammar)
_POLICY_ID_RE = re.compile(r"^[a-z0-9_]{1,32}$")


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


#: serving/actor forward precisions (the learner always keeps f32 — this
#: only selects the PARAMS precision of the serving program, the overlap
#: prep-cast extended to the ZMQ serving plane; models/a3c.py keeps the
#: policy/value heads f32 either way). ``int8`` additionally needs a
#: calibration source: a frozen QuantSpec, or N live-traffic batches
#: through the CalibrationTap (distributed_ba3c_tpu/quantize/).
ROLLOUT_DTYPES = ("float32", "bfloat16", "int8")


class _StagePool:
    """Reused per-shape serving staging buffers with the H2D ready fence.

    ``_launch`` materializes each group ONCE into a pooled buffer (lazy
    block-states views interleave straight in, padding included) instead
    of paying a fresh ``np.asarray`` + pad ``np.concatenate`` per
    dispatch. A buffer goes back to its free list when the dispatches
    that read it are fetched; an UNFETCHED release (the no-tap shadow
    mirror, which must never add a host sync) parks on the pending list
    until its output handle reports ready — reusing the host bytes while
    a transfer may still be reading them is the read-after-donate hazard
    (data/staging.py's fence, serving edition)."""

    __slots__ = ("_free", "_pending", "_c_alloc", "_c_copies")

    def __init__(self, tele):
        self._free: dict = {}     # (shape, dtype str) -> [ndarray, ...]
        self._pending: list = []  # (handle, key, ndarray) awaiting ready
        self._c_alloc = tele.counter("stage_alloc_total")
        self._c_copies = tele.counter("stage_copies_total")

    def _drain(self) -> None:
        still = []
        for handle, key, arr in self._pending:
            if getattr(handle, "is_ready", lambda: True)():
                self._free.setdefault(key, []).append(arr)
            else:
                still.append((handle, key, arr))
        self._pending = still

    def acquire(self, shape: tuple, dtype) -> np.ndarray:
        self._drain()
        key = (tuple(shape), np.dtype(dtype).str)
        free = self._free.get(key)
        if free:
            return free.pop()
        self._c_alloc.inc()
        return np.zeros(shape, dtype)

    def release(self, arr: np.ndarray, handle=None) -> None:
        key = (tuple(arr.shape), arr.dtype.str)
        if handle is None or getattr(handle, "is_ready", lambda: True)():
            self._free.setdefault(key, []).append(arr)
        else:
            self._pending.append((handle, key, arr))

    def count_copy(self) -> None:
        self._c_copies.inc()


class _StageLease:
    """One staged group buffer shared by its primary + shadow dispatches."""

    __slots__ = ("arr", "refs")

    def __init__(self, arr: np.ndarray, refs: int = 1):
        self.arr = arr
        self.refs = refs


class ShedReject:
    """Typed reject delivered to a task's ``shed_callback``.

    ``reason`` is one of:

    - ``"deadline"``: the scheduler proved the task could not be served
      before its deadline (queue wait + estimated device time) and shed it
      WITHOUT spending device time on it;
    - ``"queue_full"``: the bounded admission queue was full — the fast
      overload signal; retry after backing off, or fall back;
    - ``"shutdown"``: the predictor stopped while the task waited.

    The serving router (predict/router.py) adds two fleet-level reasons:

    - ``"replica_lost"``: the replica this task was dispatched to died
      before serving it (the router re-sheds a dead replica's
      outstanding tasks so no caller hangs on a corpse);
    - ``"no_replica"``: no live replica to dispatch to (every one is
      draining/dead, or the router is empty).

    Callers decide the fallback: the actor-plane masters reply with a
    uniform-random action (the behavior log-prob stays correct for
    V-trace); a serving frontend would surface a 429/503 equivalent.
    """

    __slots__ = ("reason", "deadline", "now")

    def __init__(self, reason: str, deadline: Optional[float] = None,
                 now: Optional[float] = None):
        self.reason = reason
        self.deadline = deadline
        self.now = now

    def __repr__(self) -> str:
        return f"ShedReject(reason={self.reason!r}, deadline={self.deadline})"


class _BlockTask:
    """One whole [B, ...] state block awaiting ONE batched callback.

    The block wire's unit of work: B states that arrived as one message and
    leave as one ``int32[B]`` action reply — no per-row splitting, no
    per-row Python bookkeeping anywhere between the socket and the device.
    """

    __slots__ = ("states", "callback", "k", "deadline", "policy", "shed_cb",
                 "t_admit", "trace")

    def __init__(self, states, callback, deadline=None, policy=None,
                 shed_cb=None, trace=None):
        self.states = states
        self.callback = callback
        self.k = states.shape[0]
        self.deadline = deadline
        self.policy = policy
        self.shed_cb = shed_cb
        self.t_admit = 0.0
        self.trace = trace  # tracing.TraceRef for a sampled block step


class _RowTask:
    """One single state row (per-env wire); ``k`` is always 1."""

    __slots__ = ("states", "callback", "k", "deadline", "policy", "shed_cb",
                 "t_admit", "trace")

    def __init__(self, state, callback, deadline=None, policy=None,
                 shed_cb=None, trace=None):
        self.states = state
        self.callback = callback
        self.k = 1
        self.deadline = deadline
        self.policy = policy
        self.shed_cb = shed_cb
        self.t_admit = 0.0
        self.trace = trace  # tracing.TraceRef for a sampled row


class _Inflight:
    """One dispatched-not-yet-fetched device call the scheduler tracks."""

    __slots__ = ("tasks", "n", "policy", "handle", "t_dispatch", "t_oldest",
                 "shadow", "states", "t_dispatch_us", "lease")

    def __init__(self, tasks, n, policy, handle, t_dispatch, t_oldest=0.0,
                 shadow=False, states=None, t_dispatch_us=0, lease=None):
        self.tasks = tasks        # ordered singles-then-blocks; None = shadow
        self.n = n
        self.policy = policy
        self.handle = handle      # (k, dispatched device array)
        self.t_dispatch = t_dispatch
        # admit stamp of the group's FIFO-oldest task — tasks is REORDERED
        # (singles first, matching the batch layout), so latency accounting
        # must not read tasks[0]
        self.t_oldest = t_oldest
        self.shadow = shadow
        self.states = states      # batch kept only for a shadow tap
        # µs dispatch stamp for trace spans (0 when no task is traced —
        # the untraced path never reads the clock for it)
        self.t_dispatch_us = t_dispatch_us
        # _StageLease of the pooled staging buffer this call reads (None
        # for pass-through / sync-path batches); released at _complete
        self.lease = lease


def make_fwd_sample(model, greedy: bool = False) -> Callable:
    """The action server's compiled program: forward + on-device sampling.

    Module-level (not a closure in ``__init__``) so the audit registry
    (distributed_ba3c_tpu/audit.py, entries ``predict.server`` and
    ``predict.server_greedy``) traces the same function the live predictor
    jits — BOTH packed shapes are registered so T5 pins them.
    """
    refuse_carry(model, "the batched action server")

    def fwd_sample(params, states, key):
        out = model.apply({"params": params}, states)
        if greedy:
            actions = jnp.argmax(out.logits, axis=-1)
        else:
            actions = jax.random.categorical(key, out.logits, axis=-1)
        actions = actions.astype(jnp.int32)
        # log mu(a|s): the behavior policy record V-trace needs
        log_probs = jax.nn.log_softmax(out.logits, axis=-1)
        logp = jnp.take_along_axis(log_probs, actions[:, None], axis=-1)[:, 0]
        # PACK everything into ONE array: the host fetches a single
        # buffer per serve. Device readback pays a fixed latency PER ARRAY
        # regardless of size, so four separate fetches cost four round
        # trips per serving call against ~1 ms of compute.
        rows = [actions.astype(jnp.float32), out.value, logp]
        if not greedy:
            # the sampling server also publishes the argmax channel (the
            # Evaluator consumes it without a second device call); under
            # greedy=True row 0 IS the argmax, so the duplicate row is
            # dropped and the packed fetch shrinks to [3, B]
            rows.append(jnp.argmax(out.logits, axis=-1).astype(jnp.float32))
        return jnp.stack(rows)  # [3, B] greedy / [4, B] sampling, float32

    return fwd_sample


class BatchedPredictor:
    """Asynchronous batched (action, value) server with an SLO contract.

    Parameters
    ----------
    model: a flax module with ``apply({'params': p}, states) -> PolicyValue``.
    params: initial parameter pytree for the ``default`` policy.
    batch_size: micro-batch coalesce target (reference PREDICT_BATCH_SIZE);
        the hard bucket cap is the next power of two.
    num_threads: kept for call-site compatibility; the continuous-batching
        scheduler is ONE thread (dispatch order must be owned by one place
        for the depth pipeline), and pipelined dispatch replaces the old
        multi-worker host overlap.
    slo_ms: default deadline budget applied to every queued task (0 = no
        deadlines — the training plane's backpressure semantics).
    queue_depth: admission-queue bound. With deadlines, a full queue is an
        immediate typed reject (fast overload signal); without, a blocking
        backpressure put as before.
    dispatch_depth: device calls kept in flight by the scheduler (2 = the
        continuous-batching default: fetch k only after dispatching k+1).
    clock: monotonic-clock callable (tests inject a fake clock to make
        shed decisions deterministic).
    tele_role: telemetry registry role — ``predictor`` single-fleet,
        ``telemetry.fleet_role("predictor", k)`` when a learner hosts one
        predictor per fleet (docs/observability.md).
    """

    def __init__(
        self,
        model,
        params,
        batch_size: int = 16,
        num_threads: int = 1,
        seed: int = 0,
        greedy: bool = False,
        coalesce_ms: float = 2.0,
        slo_ms: float = 0.0,
        queue_depth: int = 4096,
        dispatch_depth: int = 2,
        clock: Optional[Callable[[], float]] = None,
        tele_role: str = "predictor",
        rollout_dtype: str = "float32",
        quant_spec=None,
        quant_calibrate: int = 0,
        quant_method: str = "absmax",
        quant_percentile: float = 99.9,
    ):
        import time as _time

        self._model = model
        self.num_actions = int(getattr(model, "num_actions", 0) or 0)
        if rollout_dtype not in ROLLOUT_DTYPES:
            raise ValueError(
                f"rollout_dtype must be one of {ROLLOUT_DTYPES}, got "
                f"{rollout_dtype!r}"
            )
        if rollout_dtype == "int8":
            if (quant_spec is None) == (not quant_calibrate):
                raise ValueError(
                    "rollout_dtype='int8' needs exactly ONE calibration "
                    "source: a frozen quant_spec, or quant_calibrate=N "
                    "live batches through the CalibrationTap"
                )
        elif quant_spec is not None or quant_calibrate:
            raise ValueError(
                "quant_spec/quant_calibrate configure the int8 rung — "
                f"they do not apply to rollout_dtype={rollout_dtype!r}"
            )
        self.rollout_dtype = rollout_dtype
        # Every table this predictor serves is COMMITTED to one device. The
        # learner publishes params replicated over its mesh (a different
        # sharding from the freshly initialised table the buckets were
        # warmed with), and jit keys its trace on the sharding: an unplaced
        # publish recompiles every bucket mid-serving. Every predictor of a
        # process lands on the first local device — spreading replicas
        # over the chips of a host is ROADMAP S2.
        self._device = jax.local_devices()[0]
        #: the ACTIVE QuantSpec (int8 serving) — None while f32/bf16, and
        #: None during the live-calibration window (f32 serving until the
        #: tap freezes and the table switches)
        self.quant_spec = None
        # sync-path consistency guard: _switch_to_int8 swaps the compiled
        # program and the policy table together under this lock; the
        # scheduler thread never needs it (the switch runs ON it)
        self._swap_lock = threading.Lock()
        if rollout_dtype == "bfloat16":
            # the overlap split's prep-cast, serving edition: every policy
            # publish casts f32 params to bf16 ON DEVICE (one small pass,
            # amortized over a whole publish interval), halving the
            # forward's param-read bandwidth; the heads stay f32 compute
            # (models/a3c.py) so log mu(a|s) keeps its precision and
            # V-trace clips whatever noise the storage cast adds
            self._cast_params = jax.jit(
                lambda p: jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.bfloat16)  # ba3clint: disable=A16 — THE audited publish cast (entry predict.server_bf16)
                    if x.dtype == jnp.float32 else x,
                    p,
                )
            )
        elif rollout_dtype == "int8" and quant_spec is not None:
            # quantize-on-publish (the bf16 cast's int8 edition): every
            # policy publish runs the f32 -> int8 table build in
            # quantize/qforward.py — per-channel weight scales + the
            # spec's frozen activation scales; the compiled forward
            # depends only on avals, so ONE program serves every publish
            from distributed_ba3c_tpu.quantize import quantize_params

            self.quant_spec = quant_spec
            self._cast_params = jax.jit(
                lambda p: quantize_params(p, quant_spec)
            )
        else:
            self._cast_params = None
        self._policies = {"default": self._put_policy(params)}
        self._batch_size = batch_size
        self._coalesce_s = coalesce_ms / 1000.0
        self._slo_s = slo_ms / 1000.0
        self._depth = max(1, int(dispatch_depth))
        self._clock = clock or _time.monotonic
        # bounded admission queue, deque-based (utils/concurrency.py): at
        # serving rates a mutex+condvar queue.Queue costs a futex per op on
        # sandboxed kernels — the same ceiling the train queue hit in PR 4
        self._queue: FastQueue = FastQueue(maxsize=queue_depth)
        self._key = jax.random.PRNGKey(seed)
        self._key_lock = threading.Lock()
        self._greedy = greedy
        self._stop_evt = threading.Event()
        # serve-time estimate feeding the deadline gate: a DECAYING MAX of
        # dispatch->fetch wall time (includes pipeline wait). Deliberately
        # conservative: the estimator's error mode must be shedding a task
        # that would have made it, never serving one late (docs/serving.md)
        self._est_serve_s = 0.0
        self._inflight_n = 0
        # multi-policy routing state: canary is an atomic (policy, fraction)
        # tuple swap. Routing happens at GROUP granularity in the scheduler
        # (a deficit accumulator — exactly `fraction` of routed rows over
        # time, no RNG): per-task routing would break every group at the
        # policy boundary and collapse batch occupancy whenever canary
        # traffic interleaves.
        self._canary: Optional[Tuple[str, float]] = None
        self._shadow: Optional[str] = None
        self._canary_debt = 0.0  # scheduler-thread only
        self._held = None  # scheduler-local FIFO carry between groups
        #: test/eval tap for shadow results: ``tap(states, actions, policy)``
        #: — when None (production) shadow results are dropped WITHOUT a
        #: host sync
        self.shadow_tap: Optional[Callable] = None

        # telemetry (docs/observability.md): serving-side counters live in
        # the predictor role registry; the bucket-occupancy histogram is
        # what separates "tiny fragmented batches" from "full buckets"
        # when the plane slows down. Unit=1: occupancies are row counts.
        # per-fleet serving identity (telemetry.fleet_role): a learner
        # hosting K fleets runs K predictors, and their occupancy/SLO
        # series must not collapse into one registry (the fn-backed gauges
        # would be silently rebound to whichever predictor came last)
        tele = telemetry.registry(tele_role)
        self.tele_role = tele_role
        self._tele = tele
        self._c_batches = tele.counter("batches_total")
        self._c_rows = tele.counter("rows_total")
        self._c_oversize = tele.counter("blocks_oversize_total")
        self._c_publishes = tele.counter("param_publishes_total")
        self._c_chunked = tele.counter("chunked_calls_total")
        self._c_chunks = tele.counter("chunks_total")
        self._h_occupancy = tele.histogram("batch_rows", unit=1)
        # SLO plane series: sheds are counted in ROWS (a shed block is k
        # lost requests, not one), misses are rows served past their
        # deadline (should stay ~0 — they measure the estimator's error,
        # not the shed policy)
        self._c_sheds = tele.counter("sheds_total")
        self._c_shed_deadline = tele.counter("sheds_deadline_total")
        self._c_shed_full = tele.counter("sheds_queue_full_total")
        self._c_deadline_miss = tele.counter("deadline_misses_total")
        self._h_queue_wait = tele.histogram("queue_wait_s", unit=1e-6)
        self._h_serve = tele.histogram("serve_latency_s", unit=1e-6)
        self._c_shadow_batches = tele.counter("shadow_batches_total")
        self._c_shadow_rows = tele.counter("shadow_rows_total")
        self._c_cb_errors = tele.counter("callback_errors_total")
        self._c_policy_rows = {
            "default": tele.counter("policy_default_rows_total")
        }

        ref = weakref.ref(self)
        tele.gauge(
            "task_queue_depth",
            fn=lambda: p._queue.qsize() if (p := ref()) else 0,
        )
        tele.gauge(
            "slo_ms", fn=lambda: p._slo_s * 1000.0 if (p := ref()) else 0
        )
        tele.gauge(
            "inflight_dispatches",
            fn=lambda: p._inflight_n if (p := ref()) else 0,
        )

        # the serving staging pool (docs/ingest.md): one materialization
        # per dispatched group into a reused buffer, ready-fenced
        self._pool = _StagePool(tele)

        # registered audit entry point (distributed_ba3c_tpu/audit.py).
        # auto_arm=False: the pow-2 bucket warmup is a LEGITIMATE multi-shape
        # compile sequence; warmup() arms the tripwire when it completes, so
        # only a new bucket size appearing mid-serving raises. Continuous
        # batching keeps this contract: every group is padded to a warmed
        # bucket before dispatch. The bf16/int8 variants are their own entry
        # points (predict.server_bf16 / predict.server_int8): different
        # programs, their own T1/T2/T5 pins.
        entry = "predict.server_greedy" if greedy else "predict.server"
        if rollout_dtype == "bfloat16":
            entry += "_bf16"
        if self.quant_spec is not None:
            from distributed_ba3c_tpu.quantize import make_quant_fwd_sample

            self._fwd = tripwire_jit(
                entry + "_int8",
                make_quant_fwd_sample(model, greedy),
                auto_arm=False,
            )
        else:
            self._fwd = tripwire_jit(
                entry,
                make_fwd_sample(model, greedy),
                auto_arm=False,
            )
        # the live-calibration window (rollout_dtype='int8' without a
        # frozen spec): serve f32 while the PR-9 shadow plane mirrors
        # every batch through the CalibrationTap; after N batches the tap
        # freezes and _switch_to_int8 swaps program + table in place
        self._warm_shape = None
        self._warm_dtype = None
        if rollout_dtype == "int8" and self.quant_spec is None:
            from distributed_ba3c_tpu.quantize import CalibrationTap

            self.shadow_tap = CalibrationTap(
                model, params, quant_calibrate,
                method=quant_method, percentile=quant_percentile,
                on_freeze=self._switch_to_int8, tele_role=tele_role,
            )
            self._shadow = "default"
        self.threads: List[StoppableThread] = [
            StoppableThread(
                target=self._scheduler, daemon=True, name="predictor-sched"
            )
        ]

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        for t in self.threads:
            t.start()

    def warmup(self, state_shape, dtype=np.uint8) -> None:
        """Precompile every pow-2 bucket up to batch_size, for EVERY policy.

        Each new bucket size triggers a fresh XLA compile (tens of seconds
        on TPU) the first time it is served; hitting that mid-training
        stalls the whole actor plane. Call once before actors start (and
        after ``add_policy`` — same program, but the warmup proves the
        shapes through)."""
        # remembered for the int8 calibration switch: the swapped-in
        # quantized program must re-prove the same buckets before it
        # takes traffic (same mid-serving-stall contract)
        self._warm_shape = tuple(state_shape)
        self._warm_dtype = dtype
        t0 = self._clock()
        b = 1
        while b <= _next_pow2(self._batch_size):
            self._run_device(np.zeros((b, *state_shape), dtype))
            b *= 2
        # compile seconds of the whole bucket set (set-up time, reported
        # apart from serving; ~0 on a warm persistent cache)
        warmup_s = self._clock() - t0
        self._tele.gauge("warmup_s").set(warmup_s)
        logger.info(
            "%s: buckets 1..%d warmed in %.1fs, params (%s) on %s",
            self.tele_role, _next_pow2(self._batch_size), warmup_s,
            self.serving_dtype, self._device,
        )
        # BA3C_AUDIT=1: buckets compiled — any retrace from here on is a
        # mid-serving stall and raises AuditError
        getattr(self._fwd, "arm", lambda: None)()

    def stop(self) -> None:
        self._stop_evt.set()
        for t in self.threads:
            t.stop()

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for the scheduler thread to exit (it polls with 0.5s
        timeout)."""
        for t in self.threads:
            if t.is_alive():
                t.join(timeout)

    # -- the int8 calibration switch ---------------------------------------
    @property
    def serving_dtype(self) -> str:
        """The precision the table SERVES right now: ``rollout_dtype``,
        except during the int8 live-calibration window (f32 until the
        tap freezes and the switch lands)."""
        if self.rollout_dtype != "int8":
            return self.rollout_dtype
        return "int8" if self.quant_spec is not None else "float32"

    def _switch_to_int8(self, spec) -> None:
        """The CalibrationTap's freeze hook: swap the serving plane to
        int8 IN PLACE — quantize every hot policy, replace the compiled
        program (audit entry gains its ``_int8`` suffix), re-prove the
        warmed buckets, retire the shadow mirror.

        Runs on the scheduler thread (the tap fires from the shadow
        fetch path), so no async dispatch is concurrent with the swap;
        ``_swap_lock`` covers the sync ``predict_batch`` path."""
        from distributed_ba3c_tpu.quantize import (
            make_quant_fwd_sample,
            quantize_params,
        )

        quantize = jax.jit(lambda p: quantize_params(p, spec))
        entry = "predict.server_greedy" if self._greedy else "predict.server"
        fwd = tripwire_jit(
            entry + "_int8",
            make_quant_fwd_sample(self._model, self._greedy),
            auto_arm=False,
        )
        while True:
            # quantize OUTSIDE the lock (device work), commit only if no
            # publish replaced an entry meanwhile — else a fresh f32
            # table would be silently dropped by the rebind
            snapshot = dict(self._policies)
            table = {pid: quantize(p) for pid, p in snapshot.items()}
            with self._swap_lock:
                current = self._policies
                if len(current) == len(snapshot) and all(
                    current.get(pid) is p for pid, p in snapshot.items()
                ):
                    self._cast_params = quantize
                    self._policies = table
                    self._fwd = fwd
                    self.quant_spec = spec
                    break
        # shadow plane retired: the tap saw its N batches; from here the
        # mirror would only double device work
        self._shadow = None
        self.shadow_tap = None
        if self._warm_shape is not None:
            # re-prove the warmed buckets through the NEW program (its
            # own compile set), then re-arm the retrace tripwire
            b = 1
            while b <= _next_pow2(self._batch_size):
                self._run_device(
                    np.zeros((b, *self._warm_shape), self._warm_dtype)
                )
                b *= 2
            getattr(self._fwd, "arm", lambda: None)()
        self._c_publishes.inc()

    # -- policy table ------------------------------------------------------
    def _publish_policy(self, policy_id: str, params) -> None:
        """Commit a publish to the table — cast/quantize OUTSIDE the swap
        lock (device work), then store only if the serving program didn't
        change underneath: a publish racing ``_switch_to_int8`` must land
        through the NEW cast, never as an f32 table behind the int8
        program."""
        while True:
            cast = self._cast_params
            p = jax.device_put(params, self._device)
            if cast is not None:
                p = cast(p)
            with self._swap_lock:
                if self._cast_params is cast:
                    self._policies[policy_id] = p
                    return

    def _put_policy(self, params):
        """Params → the serving table's storage: committed to this
        predictor's device, cast to the rollout dtype (bf16 mode)."""
        p = jax.device_put(params, self._device)
        if self._cast_params is not None:
            p = self._cast_params(p)
        return p

    def add_policy(self, policy_id: str, params) -> None:
        """Make a second checkpoint hot behind the same scheduler.

        ``policy_id`` must match ``[a-z0-9_]{1,32}`` — it is embedded in
        the per-policy telemetry series names."""
        if not _POLICY_ID_RE.match(policy_id):
            raise ValueError(
                f"policy id {policy_id!r} must match {_POLICY_ID_RE.pattern} "
                "(it names Prometheus series)"
            )
        self._publish_policy(policy_id, params)
        self._c_policy_rows.setdefault(
            policy_id, self._tele.counter(f"policy_{policy_id}_rows_total")
        )

    def set_canary(self, policy_id: str, fraction: float) -> None:
        """Route ``fraction`` of un-pinned traffic to ``policy_id``.

        Deterministic deficit-accumulator split at GROUP granularity (no
        RNG, full batch occupancy preserved): over time exactly
        ``fraction`` of routed rows serve the canary. 0 clears the
        canary. Callers that pin ``policy=`` on their tasks bypass
        routing."""
        if fraction <= 0:
            self._canary = None
            return
        if not 0 < fraction <= 1:
            raise ValueError(f"canary fraction {fraction} not in (0, 1]")
        if policy_id not in self._policies:
            raise KeyError(f"unknown policy {policy_id!r} — add_policy first")
        self._canary = (policy_id, float(fraction))

    def set_shadow(self, policy_id: Optional[str]) -> None:
        """Mirror EVERY served batch through ``policy_id``.

        The shadow call is dispatched right after the primary with the
        identical padded batch; its results never reach any caller — they
        are dropped undetched (no host sync) unless a ``shadow_tap`` is
        installed. ``None`` clears."""
        if policy_id is not None and policy_id not in self._policies:
            raise KeyError(f"unknown policy {policy_id!r} — add_policy first")
        self._shadow = policy_id

    def update_params(self, params, policy: str = "default") -> None:
        """Publish fresh weights (atomic ref swap; next batch uses them).

        Only EXISTING policies can be republished — a typo'd id must fail
        loudly, not create a dead entry while the real policy silently
        keeps serving its stale weights."""
        if policy not in self._policies:
            raise KeyError(f"unknown policy {policy!r} — add_policy first")
        self._publish_policy(policy, params)
        self._c_publishes.inc()

    # -- API ---------------------------------------------------------------
    def put_task(
        self,
        state: np.ndarray,
        callback: Callable[[int, float, float], None],
        *,
        deadline: Optional[float] = None,
        policy: Optional[str] = None,
        shed_callback: Optional[Callable[[ShedReject], None]] = None,
        trace=None,
    ) -> bool:
        """Queue one state; ``callback(action, value, logp)`` fires when
        served — logp is log mu(action|state) under the sampling policy.

        ``deadline`` is an absolute clock() time (defaulted from ``slo_ms``
        when set); a task that cannot make it is shed with a typed
        :class:`ShedReject` to ``shed_callback`` instead of served late.
        Tasks arriving after ``stop()`` are rejected the same way (their
        simulators are being torn down too). ``trace`` is a sampled
        tracing.TraceRef — the scheduler attributes its dispatch-wait and
        device-fetch spans under this predictor's role (tracing.py).
        Returns True if admitted."""
        return self._admit(
            _RowTask(state, callback, deadline, policy, shed_callback, trace)
        )

    def put_block_task(
        self,
        states: np.ndarray,
        callback: Callable[[np.ndarray, np.ndarray, np.ndarray], None],
        *,
        deadline: Optional[float] = None,
        policy: Optional[str] = None,
        shed_callback: Optional[Callable[[ShedReject], None]] = None,
        trace=None,
    ) -> bool:
        """Queue one [B, ...] state block (the block wire's whole batch);
        ``callback(actions[B], values[B], logps[B])`` fires ONCE when the
        block is served. The block lands in a warmed pow-2 bucket as a
        unit — no per-row splitting; queued neighbors coalesce into one
        device call up to the bucket cap (continuous batching: the
        in-flight dispatch is the coalesce window). Same deadline/shed
        semantics as :meth:`put_task`; ``trace`` as there."""
        cap = _next_pow2(max(self._batch_size, 1))
        if states.shape[0] > cap:
            self._c_oversize.inc()
            raise ValueError(
                f"block of {states.shape[0]} states exceeds the serving "
                f"bucket ({cap}) — raise predict_batch_size to at least "
                "the env-server block size"
            )
        return self._admit(
            _BlockTask(states, callback, deadline, policy, shed_callback,
                       trace)
        )

    def predict_batch(
        self, states: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Synchronous batched predict: (actions, values, greedy_actions).

        ``actions`` follow the serving policy (sampled, or argmax when
        ``greedy=True``); ``greedy_actions`` are always the argmax — the
        Evaluator consumes those without a second device call. Always the
        ``default`` policy; never queued, never shed."""
        actions, values, _, greedy_actions = self._run_rows(
            np.asarray(states)
        )
        return actions, values, greedy_actions

    # -- admission ---------------------------------------------------------
    def _admit(self, task) -> bool:
        now = self._clock()
        task.t_admit = now
        if task.deadline is None and self._slo_s > 0:
            task.deadline = now + self._slo_s
        # task.policy stays None for routed traffic — the SCHEDULER routes
        # whole groups (see _route_group), so un-pinned tasks all group
        # together and canary splits never fragment batches
        if task.policy is not None and task.policy not in self._policies:
            # validated HERE, in the caller's thread: an unknown id reaching
            # the scheduler would KeyError in _launch and kill the one
            # thread the whole serving plane runs on (and mint a junk
            # per-policy series on the way)
            raise KeyError(
                f"unknown policy {task.policy!r} — add_policy first"
            )
        if self._stop_evt.is_set():
            self._shed(task, "shutdown")
            return False
        if task.deadline is not None:
            # serving contract: a full bounded queue is an IMMEDIATE typed
            # reject — overload must surface as fast rejection the caller
            # can act on, never as unbounded queue latency
            try:
                self._queue.put_nowait(task)
            except queue.Full:
                self._shed(task, "queue_full")
                return False
        else:
            # training contract (no deadline): backpressure pauses the
            # caller, but stays shutdown-responsive
            if not queue_put_stoppable(self._queue, task, self._stop_evt):
                self._shed(task, "shutdown")
                return False
        if self._stop_evt.is_set():
            # the put may have raced PAST the scheduler's final teardown
            # drain — resolve the queue from this thread so no task is
            # ever stranded with neither callback delivered (deque pops
            # are atomic: concurrent drains resolve each task once)
            self._drain_shutdown()
        return True

    def _route_group(self, weight: int) -> str:
        """Resolve an un-pinned group's policy (scheduler thread only).

        Deficit accumulator: each routed group adds ``fraction * weight``
        of canary debt; a group dispatches to the canary when the debt
        covers it. Over time exactly ``fraction`` of routed ROWS serve
        the canary, with no RNG and no group fragmentation."""
        c = self._canary
        if c is None:
            return "default"
        pid, frac = c
        self._canary_debt += frac * weight
        if self._canary_debt >= weight:
            self._canary_debt -= weight
            return pid
        return "default"

    def _shed(self, task, reason: str) -> None:
        self._c_sheds.inc(task.k)
        if reason == "deadline":
            self._c_shed_deadline.inc(task.k)
            # transient-stall recovery: the estimate normally decays only
            # at COMPLETIONS, so a one-off stall that inflates it past the
            # whole SLO budget would shed everything forever — no
            # completions, no decay, a permanent outage (found live: one
            # 446 ms scheduler stall on a busy 1-core host shed 7588/7592
            # rows of an otherwise healthy run). A FRESH task (>80% of its
            # budget left — the estimator, not queue wait, is what killed
            # it) decays the estimate 10%, so after a stall the scheduler
            # probes its way back to serving; a slow probe re-measures the
            # truth and sheds resume, bounding the probe duty cycle.
            if task.deadline is not None:
                budget = task.deadline - task.t_admit
                if budget > 0 and (
                    task.deadline - self._clock() > 0.8 * budget
                ):
                    self._est_serve_s *= 0.9
        elif reason == "queue_full":
            self._c_shed_full.inc(task.k)
        cb = task.shed_cb
        if cb is not None:
            self._fire(cb, ShedReject(reason, task.deadline, self._clock()))

    def _fire(self, fn, *args) -> None:
        """Run one user callback; an exception must not kill the ONE
        thread the whole serving plane runs on (the old N-worker design
        at least left the other workers alive). Counted + flight-recorded
        + logged, never silent — the missing result is the caller's
        signal, a dead scheduler would be nobody's."""
        try:
            fn(*args)
        except Exception as e:
            self._c_cb_errors.inc()
            try:
                telemetry.record(
                    "predictor_callback_error", error=str(e)[:200]
                )
            except Exception:
                pass
            logger.error("predictor callback raised %r", e)

    # -- internals ---------------------------------------------------------
    def _next_key(self):
        with self._key_lock:
            self._key, sub = jax.random.split(self._key)
        return sub

    def _dispatch(self, params, batch: np.ndarray, fwd=None):
        """Pad to the pow-2 bucket and dispatch (async); NO host fetch —
        the scheduler fetches via :meth:`_collect` only after the next
        group is dispatched.

        ``params`` is passed explicitly so a multi-chunk caller serves ONE
        parameter version even if the learner publishes mid-batch; ``fwd``
        likewise pins the compiled program across a chunked call (the
        int8 calibration switch swaps ``self._fwd`` mid-serving)."""
        # device ingest is where a lazy block-states view (block-shm wire)
        # pays its one materialization — jit can't take a BlockStatesView
        batch = np.asarray(batch)
        k = batch.shape[0]
        padded = _next_pow2(max(k, 1))
        if padded != k:
            pad = np.zeros((padded - k, *batch.shape[1:]), batch.dtype)
            batch = np.concatenate([batch, pad], axis=0)
        return k, (fwd if fwd is not None else self._fwd)(
            params, batch, self._next_key()
        )

    def _collect(self, handle):
        """ONE device->host fetch of a dispatched call (see fwd_sample)."""
        k, packed = handle
        return self._unpack(np.asarray(packed), k)

    def _unpack(self, packed: np.ndarray, k: int):
        actions = packed[0, :k].astype(np.int32)
        if packed.shape[0] == 3:
            # greedy server: row 0 IS the argmax channel (make_fwd_sample)
            return actions, packed[1, :k], packed[2, :k], actions
        return actions, packed[1, :k], packed[2, :k], packed[3, :k].astype(
            np.int32
        )

    def _run_device(self, batch: np.ndarray):
        return self._collect(self._dispatch(self._params, batch))

    @property
    def _params(self):
        return self._policies["default"]

    def _run_rows(self, states: np.ndarray):
        """Serve N rows synchronously: (actions, values, logps, greedy).

        Inputs larger than the serving bucket (an Evaluator with more envs
        than ``batch_size``) are chunked to it, so no bucket beyond
        warmup's is ever compiled — bounded device memory, and no
        post-warmup retrace for the BA3C_AUDIT=1 tripwire to refuse. The
        chunked path dispatches EVERY chunk before fetching any: jax
        dispatch is async, so the chunks' compute overlaps while fetches
        (the ~135 ms/array latency documented above) drain in order.
        Params are snapshotted once per call: a learner publish mid-call
        must not split one logical batch across two policies."""
        cap = _next_pow2(max(self._batch_size, 1))
        # program + table snapshotted TOGETHER: the int8 calibration
        # switch swaps both under _swap_lock, and a sync caller must not
        # pair the old program with the new table (or vice versa)
        with self._swap_lock:
            fwd, params = self._fwd, self._params
        if states.shape[0] <= cap:
            return self._collect(self._dispatch(params, states, fwd))
        pending = [
            self._dispatch(params, states[i:i + cap], fwd)
            for i in range(0, states.shape[0], cap)
        ]
        # chunking is worth SEEING on the scrape endpoint: a persistently
        # chunked caller (Evaluator sized past the bucket) serializes
        # fetches and should resize instead (docs/observability.md)
        self._c_chunked.inc()
        self._c_chunks.inc(len(pending))
        parts = [self._collect(h) for h in pending]
        return tuple(np.concatenate(p) for p in zip(*parts))

    # -- the continuous-batching scheduler ---------------------------------
    def _viable(self, task, now: float) -> bool:
        """Can this task still make its deadline if dispatched NOW?

        The decaying-max serve-time estimate already includes pipeline
        wait; the extra 25% headroom absorbs scheduler jitter (group
        assembly, callback bursts, sleep-granularity overshoot on loaded
        hosts). Both biases point the same way: the error mode is
        shedding a task that would have made it, never serving one
        late."""
        return (
            task.deadline is None
            or now + self._est_serve_s * 1.25 <= task.deadline
        )

    def _next_task(self, t: StoppableThread, wait: bool):
        """Pop the next VIABLE task (shedding hopeless ones on the way).

        ``wait``: block stoppably (device idle) vs return None immediately
        (a dispatch is in flight — whatever is queued right now rides the
        next bucket, nothing more)."""
        while True:
            if self._held is not None:
                task, self._held = self._held, None
            elif wait:
                task = t.queue_get_stoppable(self._queue)
                if task is None:
                    return None  # stopping
            else:
                try:
                    task = self._queue.get_nowait()
                except queue.Empty:
                    return None
            if self._viable(task, self._clock()):
                return task
            self._shed(task, "deadline")

    def _assemble(self, t: StoppableThread, idle: bool):
        """Build one ≤-bucket, single-policy group of tasks.

        When the device is idle, waits for a first task and then up to
        ``coalesce_ms`` to multiply the batch (the reference's fetch_batch
        drained greedily — right when a sess.run cost microseconds; one
        device call here costs ~1-10 ms of dispatch latency). When a
        dispatch is already in flight, takes only what is queued NOW: the
        in-flight call is the coalesce window (continuous batching)."""
        import time as _time

        first = self._next_task(t, wait=idle)
        if first is None:
            return None
        cap = _next_pow2(max(self._batch_size, 1))
        tasks, weight = [first], first.k
        deadline = _time.perf_counter() + (self._coalesce_s if idle else 0.0)
        while weight < self._batch_size:
            if self._held is not None:
                tk, self._held = self._held, None
            else:
                remaining = deadline - _time.perf_counter()
                try:
                    if remaining > 0:
                        tk = self._queue.get(timeout=remaining)
                    else:
                        tk = self._queue.get_nowait()
                except queue.Empty:
                    break
            if not self._viable(tk, self._clock()):
                self._shed(tk, "deadline")
                continue
            if tk.policy != first.policy or weight + tk.k > cap:
                # one device call serves ONE policy and ONE bucket; the
                # misfit leads the next group (never reordered past FIFO)
                self._held = tk
                break
            tasks.append(tk)
            weight += tk.k
        return tasks, weight, first.policy

    def _stage_group(self, singles, blocks, weight):
        """The group's ONE materialization: rows interleave straight into
        a pooled, bucket-padded staging buffer (lazy block-states views
        included — data/staging.py's write-into discipline replaces the
        old np.asarray-then-concatenate chain at this site). Returns
        ``(batch, lease)``; lease None = zero-copy pass-through (a lone
        already-bucket-shaped ndarray block, served AS-IS like before).
        Pad rows keep stale bytes: only rows :weight reach any callback,
        so zeroing them every reuse would be a copy with no reader."""
        padded = _next_pow2(max(weight, 1))
        if not singles and len(blocks) == 1:
            b0 = blocks[0].states
            if isinstance(b0, np.ndarray) and b0.shape[0] == padded:
                return b0, None
        if singles:
            first = singles[0].states
            tail = tuple(np.shape(first))  # one row's shape
        else:
            first = blocks[0].states
            tail = tuple(np.shape(first))[1:]  # strip the block axis
        dtype = getattr(first, "dtype", np.uint8)
        buf = self._pool.acquire((padded, *tail), dtype)
        off = 0
        for tk in singles:
            buf[off] = tk.states
            off += 1
        for tk in blocks:
            dest = buf[off : off + tk.k]
            mi = getattr(tk.states, "materialize_into", None)
            if mi is not None:
                mi(dest)
            else:
                dest[...] = tk.states
            off += tk.k
        self._pool.count_copy()
        return buf, _StageLease(buf)

    def _release_lease(self, inf: _Inflight, synced: bool) -> None:
        """One dispatch done with its staging buffer; the buffer frees
        when every sharer (primary + shadow) released. ``synced=False``
        (the unfetched shadow) parks on the ready fence instead — the
        host bytes may still be feeding the transfer."""
        lease = inf.lease
        if lease is None:
            return
        lease.refs -= 1
        if lease.refs == 0:
            self._pool.release(
                lease.arr, None if synced else inf.handle[1]
            )

    def _launch(self, group) -> List[_Inflight]:
        """Dispatch one group (plus its shadow mirror) — no host fetch."""
        tasks, weight, policy = group
        if policy is None:
            policy = self._route_group(weight)  # un-pinned: routed here
        singles = [tk for tk in tasks if isinstance(tk, _RowTask)]
        blocks = [tk for tk in tasks if isinstance(tk, _BlockTask)]
        batch, lease = self._stage_group(singles, blocks, weight)
        now = self._clock()
        # counted at LAUNCH (not fetch) so the series lead the latency
        # histograms by exactly the in-flight window
        self._c_batches.inc()
        self._c_rows.inc(weight)
        self._h_occupancy.observe(weight)
        self._policy_counter(policy).inc(weight)
        # tasks[0] is the group's oldest admit (FIFO pop order) — captured
        # BEFORE the singles-first reorder below
        t_oldest = tasks[0].t_admit
        self._h_queue_wait.observe(max(0.0, now - t_oldest))
        ordered = singles + blocks  # callback offsets follow batch layout
        handle = self._dispatch(self._policies[policy], batch)
        # µs stamp only when a sampled trace rides this group — the
        # untraced path pays one attribute scan, never a clock read
        t_us = (
            _tracing.now_us()
            if any(tk.trace is not None for tk in ordered) else 0
        )
        out = [_Inflight(
            ordered, weight, policy, handle, now,
            t_oldest=t_oldest, t_dispatch_us=t_us, lease=lease,
        )]
        shadow = self._shadow
        if shadow is not None:
            self._c_shadow_batches.inc()
            self._c_shadow_rows.inc(weight)
            if lease is not None:
                lease.refs += 1  # the mirror reads the same staged bytes
            out.append(_Inflight(
                None, weight, shadow,
                self._dispatch(self._policies[shadow], batch), now,
                shadow=True,
                states=batch if self.shadow_tap is not None else None,
                lease=lease,
            ))
        return out

    def _policy_counter(self, policy: str):
        c = self._c_policy_rows.get(policy)
        if c is None:
            self._c_policy_rows[policy] = c = self._tele.counter(
                f"policy_{policy}_rows_total"
            )
        return c

    def _complete(self, inf: _Inflight) -> None:
        """Fetch one in-flight call and fire its callbacks."""
        if inf.shadow:
            tap = self.shadow_tap
            # inf.states is captured at LAUNCH only when a tap was already
            # installed — a tap that appears mid-flight skips this call
            if tap is not None and inf.states is not None:
                actions, _, _, _ = self._collect(inf.handle)
                states = np.asarray(inf.states)[: inf.n]
                if inf.lease is not None:
                    # the tap's states must outlive the staging buffer's
                    # reuse (pad rows are sliced off above for the same
                    # reason: the tap sees exactly the SERVED rows)
                    states = states.copy()
                self._fire(tap, states, actions[: inf.n], inf.policy)
                self._release_lease(inf, synced=True)
            else:
                # no tap: DROP without a host sync — shadow evaluation
                # must never add fetch latency to the serving path; the
                # staging buffer parks on the ready fence instead
                self._release_lease(inf, synced=False)
            return
        actions, values, logps, _ = self._collect(inf.handle)
        self._release_lease(inf, synced=True)
        now = self._clock()
        if inf.t_dispatch_us:
            # sampled spans: dispatch wait (admit -> device dispatch) and
            # device fetch (dispatch -> results on host) attributed under
            # THIS predictor's role — the decomposition of the master-side
            # predict RTT span (tracing.py; docs/observability.md)
            for tk in inf.tasks:
                if tk.trace is not None:
                    tk.trace.hop(
                        "predict_dispatch", self.tele_role,
                        t_end_us=inf.t_dispatch_us,
                    ).hop("predict_fetch", self.tele_role)
                    tk.trace = None  # one attribution per task
        # decaying-max serve-time estimate for the deadline gate: tracks
        # the worst recent dispatch->fetch (incl. pipeline wait) and decays
        # 10% per call so a one-off stall doesn't shed forever
        self._est_serve_s = max(
            self._est_serve_s * 0.9, now - inf.t_dispatch
        )
        self._h_serve.observe(max(0.0, now - inf.t_oldest))
        late = sum(
            tk.k for tk in inf.tasks
            if tk.deadline is not None and now > tk.deadline
        )
        if late:
            # served PAST deadline: the estimator was wrong (it never
            # shed them) — the series that must stay ~0 for the SLO claim
            self._c_deadline_miss.inc(late)
        off = 0
        for tk in inf.tasks:
            if isinstance(tk, _RowTask):
                self._fire(
                    tk.callback,
                    int(actions[off]), float(values[off]), float(logps[off]),
                )
                off += 1
            else:
                self._fire(
                    tk.callback,
                    actions[off:off + tk.k],
                    values[off:off + tk.k],
                    logps[off:off + tk.k],
                )
                off += tk.k

    def _scheduler(self) -> None:
        """The serving loop: dispatch-depth-pipelined continuous batching.

        Invariant (the overlap lesson, docs/overlap.md): the fetch of call
        k happens AFTER the dispatch of call k+1 whenever there is queued
        work — the host never syncs between dispatches, so the device
        never idles between micro-batches."""
        t = threading.current_thread()
        assert isinstance(t, StoppableThread)
        inflight: collections.deque = collections.deque()
        while not t.stopped():
            group = self._assemble(t, idle=not inflight)
            if group is not None:
                inflight.extend(self._launch(group))
            self._inflight_n = len(inflight)
            # fetch the oldest call(s) once the pipeline is full — or when
            # there is nothing new to dispatch (drain toward idle). The
            # loop (not a single pop) keeps the depth bound even when a
            # shadow mirror doubles the handles per group; but a drain
            # completion re-checks the queue before fetching the next
            # handle — work that arrived DURING the blocking fetch must be
            # dispatched before the host blocks again (the no-sync-between-
            # dispatches invariant, applied to the drain path too)
            while inflight and (len(inflight) >= self._depth
                                or group is None):
                self._complete(inflight.popleft())
                self._inflight_n = len(inflight)
                if group is None:
                    break
        # teardown: complete what was dispatched (callers may be waiting),
        # then deliver the promised "shutdown" reject to everything still
        # queued — a caller waiting on either callback to resolve must not
        # hang just because stop() won the race
        while inflight:
            self._complete(inflight.popleft())
        self._inflight_n = 0
        if self._held is not None:
            held, self._held = self._held, None
            self._shed(held, "shutdown")
        self._drain_shutdown()

    def _drain_shutdown(self) -> None:
        """Shed everything still queued with the promised "shutdown"
        reject. Called by the scheduler at teardown AND by ``_admit`` when
        its put raced past that final drain — deque pops are atomic, so
        concurrent drains resolve each task exactly once."""
        while True:
            try:
                task = self._queue.get_nowait()
            except queue.Empty:
                return
            self._shed(task, "shutdown")
