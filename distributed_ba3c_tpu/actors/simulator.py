"""Simulator processes and the master's receive loop (ZMQ experience plane).

Reference equivalent: ``tensorpack/RL/simulator.py`` — ``SimulatorProcess``,
``SimulatorMaster``, ``ClientState``, ``TransitionExperience`` (SURVEY.md §2.3
#8-9, call stack §3.2). Wire protocol, kept byte-compatible in spirit:

    sim -> master (PUSH -> PULL):  msgpack [ident, state u8-array, reward, isOver]
    master -> sim (ROUTER -> DEALER ident-routed): msgpack action

Both pipes default to ipc:// within a host; tcp:// works unchanged for
remote actor hosts (the multi-host layout keeps actors host-side and only
gradients on ICI — SURVEY.md §2.12).

The child-process side imports no jax: children must stay lightweight (the
reference ran ~50 per worker; we target hundreds per TPU host).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import queue
import threading
import time
import weakref
from abc import abstractmethod
from typing import Callable, Dict, List, Optional

import numpy as np
import zmq

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.telemetry import tracing
from distributed_ba3c_tpu.envs.base import RLEnvironment
from distributed_ba3c_tpu.utils import logger, sanitizer
from distributed_ba3c_tpu.utils.concurrency import (
    StoppableThread,
    queue_put_stoppable,
)
from distributed_ba3c_tpu.utils.serialize import (
    CorruptFrameError,
    dumps,
    loads,
    unpack_block,
)


class TransitionExperience:
    """One (state, action, value) awaiting its reward attachment."""

    __slots__ = ("state", "action", "reward", "value", "trace")

    def __init__(self, state, action, value, reward=None, trace=None):
        self.state = state
        self.action = action
        self.value = value
        self.reward = reward
        self.trace = trace  # tracing.TraceRef when this step was sampled


class ClientState:
    """Per-simulator state held by the master, keyed by ZMQ ident."""

    __slots__ = ("memory", "ident", "score", "last_seen", "pending_trace")

    def __init__(self, ident: bytes):
        self.ident = ident
        self.memory: List[TransitionExperience] = []
        self.score = 0.0
        # sampled trace ref parked between receive and the predictor
        # callback (protocol-serialized, see BlockClientState)
        self.pending_trace = None
        # initialized to creation time so a client that NEVER sends again
        # (e.g. resurrected by a late predictor callback after pruning) still
        # ages out instead of being exempt forever. MONOTONIC, not wall
        # clock: an NTP step/suspend would otherwise mass-expire (or
        # immortalize) every actor at once (ba3clint A4 caught this).
        self.last_seen = time.monotonic()


class BlockStep:
    """One lockstep block transition: B states with their chosen actions and
    the rewards/dones that arrive one step later (block wire analogue of
    :class:`TransitionExperience`, but [B]-vectorized)."""

    __slots__ = (
        "states", "actions", "values", "logps", "rewards", "dones", "recv_t",
        "trace",
    )

    def __init__(self, states, actions, values, logps):
        self.states = states      # [B, H, W, hist] u8 (view over the frame)
        self.actions = actions    # [B] i32
        self.values = values      # [B] f32
        self.logps = logps        # [B] f32
        self.rewards = None       # [B] f32, attached by the NEXT message
        self.dones = None         # [B] bool, attached by the NEXT message
        # birth stamp for the e2e env-step -> train-ingest latency series
        # (one monotonic per BLOCK step, not per env — telemetry budget);
        # 0.0 when disabled so the overhead gate's off arm runs the true
        # pre-telemetry hot path (flush sites skip the observe on falsy)
        self.recv_t = time.monotonic() if telemetry.enabled() else 0.0
        # tracing.TraceRef when this step was 1-in-N sampled (None for the
        # untraced (N-1)/N — the flush sites branch on None, never on the
        # sampling math)
        self.trace = None


class BlockStatesView:
    """Lazy channel-last ``[B, H, W, hist]`` states over a shm ring window.

    The block-shm wire ships only the NEWEST obs plane per step; the master
    rebuilds each step's stacked state from ``hist`` consecutive ring slots
    — as views, never as copies, on the hot path. Materialization (the one
    unavoidable channel interleave) happens only where the bytes are
    actually consumed: ``__array__`` for a device dispatch, ``__getitem__``
    per datapoint at the feed's collate.

    ``ages[j]`` = env j's steps since episode reset at THIS step. Envs
    younger than ``hist-1`` have missing history planes, which
    HistoryFramePlayer semantics define as zero — those rows take a small
    copy-and-zero path; everything else stays a view. The window view stays
    valid until the ring wraps onto its slots, which the master's attach-
    time capacity check makes unreachable while consumers keep draining
    (utils/shm.py safety contract).
    """

    __slots__ = ("window", "ages", "shape")

    def __init__(self, window: np.ndarray, ages: np.ndarray):
        self.window = window  # [hist, B, H, W] (ring view, or small copy)
        self.ages = ages      # [B] i64 snapshot for this step
        hist, b, h, w = window.shape
        self.shape = (b, h, w, hist)

    def __len__(self) -> int:
        return self.shape[0]

    @property
    def dtype(self):
        return self.window.dtype

    def __array__(self, dtype=None, copy=None):
        hist = self.window.shape[0]
        # sanctioned materialization: __array__ IS the one copy a consumer
        # that needs the whole block pays (the staged path calls
        # materialize_into instead, so the bytes land in a reused buffer)
        out = np.ascontiguousarray(self.window.transpose(1, 2, 3, 0))  # ba3clint: disable=A13
        for j in np.nonzero(self.ages < hist - 1)[0]:
            out[j, :, :, : hist - 1 - int(self.ages[j])] = 0
        if dtype is not None and dtype != out.dtype:
            out = out.astype(dtype)
        return out

    def materialize_into(self, out: np.ndarray) -> np.ndarray:
        """The ``__array__`` interleave written into a PREALLOCATED buffer
        (data/staging.py): zero allocations, one copy pass — the channel
        interleave happens during the write into ``out``."""
        hist = self.window.shape[0]
        np.copyto(out, self.window.transpose(1, 2, 3, 0))
        for j in np.nonzero(self.ages < hist - 1)[0]:
            out[j, :, :, : hist - 1 - int(self.ages[j])] = 0
        return out

    def __getitem__(self, j: int) -> np.ndarray:
        hist = self.window.shape[0]
        age = int(self.ages[j])
        if age >= hist - 1:
            return self.window[:, j].transpose(1, 2, 0)  # zero-copy view
        # young env: the zeroed history planes need a (small) private copy
        arr = np.ascontiguousarray(self.window[:, j].transpose(1, 2, 0))
        arr[..., : hist - 1 - age] = 0
        return arr


class SegStates:
    """Lazy ``[T, H, W, hist]`` states of ONE env column over T block steps.

    What a V-trace segment's ``"state"`` used to be was
    ``np.stack([st.states[j] for st in seg])`` — a full obs copy paid on
    the MASTER thread at every flush, before collate copied the same
    bytes again. This wrapper defers that materialization to wherever the
    bytes are actually consumed: ``materialize_into`` writes the column
    straight into a staging stripe (data/staging.py — the ingest path's
    ONE copy), ``__array__`` keeps every legacy consumer (the compat
    collate's stack, the pod shipper's wire pack) byte-identical.

    Ring-safety: holding per-step states (ring window views on the
    block-shm wire) until collate is exactly what utils/shm.py's capacity
    formula already budgets — a queued segment counts
    ``ring_steps_per_item = unroll_len`` ring steps, which covers the
    whole [s, s+T] span these references pin.
    """

    __slots__ = ("states", "j", "shape")

    def __init__(self, states: list, j: int):
        self.states = states  # T per-step [B, H, W, hist] state objects
        self.j = int(j)
        self.shape = (len(states), *tuple(np.shape(states[0]))[1:])

    @property
    def dtype(self):
        return getattr(self.states[0], "dtype", np.dtype(np.uint8))

    def __len__(self) -> int:
        return self.shape[0]

    def __array__(self, dtype=None, copy=None):
        out = np.stack([s[self.j] for s in self.states])  # ba3clint: disable=A13 — the compat materialization itself
        if dtype is not None and dtype != out.dtype:
            out = out.astype(dtype)
        return out

    def materialize_into(self, out: np.ndarray) -> np.ndarray:
        """Write the env column into ``out[T, H, W, hist]`` (a staging
        stripe view): one pass, no intermediate stack."""
        j = self.j
        for t, s in enumerate(self.states):
            out[t] = s[j]
        return out


class BlockClientState:
    """Per-BLOCK state: one env-server process = one wire client = B envs.

    Heartbeat/prune happen at this granularity (one ``last_seen`` per
    block — a server is alive or dead as a unit), while the experience
    buffers stay per-env: ``steps`` is the block's shared lockstep history
    and ``start[j]`` indexes each env's first unflushed transition in it
    (envs desynchronize only at episode boundaries / n-step truncations).
    ``ring``/``ages`` are used only by the block-shm wire.
    """

    __slots__ = (
        "ident", "n_envs", "scores", "steps", "start", "last_seen",
        "ring", "ages", "last_step", "pending_trace",
    )

    def __init__(self, ident: bytes, n_envs: int):
        self.ident = ident
        self.n_envs = n_envs
        self.scores = np.zeros(n_envs, np.float64)  # RAW episode scores
        self.steps: List[BlockStep] = []
        self.start = np.zeros(n_envs, np.int64)
        self.last_seen = time.monotonic()
        self.ring = None  # utils.shm.ShmRing once attached (block-shm wire)
        self.ages = np.full(n_envs, -1, np.int64)  # -1: first state pending
        # newest wire step seen; a step that goes BACKWARDS means the server
        # restarted under this ident (master resets the incarnation)
        self.last_step = -1
        # the current message's decoded trace ref, parked here between the
        # receive loop and the predictor callback that creates its
        # BlockStep (safe: the lockstep protocol admits no second message
        # from this ident until that callback ran — the same argument the
        # A3 suppressions on the callbacks make)
        self.pending_trace = None

    def close(self) -> None:
        if self.ring is not None:
            self.ring.close()
            self.ring = None


def default_pipes(name: str = "ba3c") -> tuple[str, str]:
    """ipc:// pipe pair for one host (unique per pid so tests can nest)."""
    base = f"ipc:///tmp/{name}-{os.getpid()}"
    return f"{base}-c2s", f"{base}-s2c"


_spawn_ctx = mp.get_context("spawn")


def _decode_action(raw: bytes, fallback, counter):
    """Decode an action reply; junk must not kill the lockstep loop.

    A corrupt reply frame is the master's bug (or the network's), not a
    reason to lose this simulator's episode state (PR 14 class): repeat
    the previous action, make the drop visible on the
    ``corrupt_action_replies_total`` counter, and keep stepping.
    """
    try:
        return loads(raw)
    except Exception:
        counter.inc()
        return fallback


class SimulatorProcess(_spawn_ctx.Process):  # type: ignore[name-defined]
    """One OS process owning one player; loop: send state, await action, step.

    Reference: ``SimulatorProcess._run`` (SURVEY.md §3.2). ``build_player``
    must be picklable (a top-level function or functools.partial).

    Spawned (not forked): the trainer process is multithreaded (JAX runtime,
    predictor, master) and ``fork()`` from a threaded parent can deadlock the
    child. Child processes import only numpy/zmq modules, never jax.
    """

    def __init__(
        self,
        idx: int,
        pipe_c2s: str,
        pipe_s2c: str,
        build_player: Callable[[int], RLEnvironment],
    ):
        super().__init__(daemon=True, name=f"simulator-{idx}")
        self.idx = idx
        self.c2s = pipe_c2s
        self.s2c = pipe_s2c
        self._build_player = build_player

    def run(self) -> None:
        player = self._build_player(self.idx)
        ident = f"simulator-{self.idx}".encode()
        context = zmq.Context()
        c2s = context.socket(zmq.PUSH)
        c2s.setsockopt(zmq.IDENTITY, ident)
        c2s.set_hwm(4)
        c2s.connect(self.c2s)
        s2c = context.socket(zmq.DEALER)
        s2c.setsockopt(zmq.IDENTITY, ident)
        s2c.connect(self.s2c)

        # child-side telemetry: counters + the piggyback tracker (fleet
        # aggregation, telemetry/wire.py). Disabled (BA3C_TELEMETRY=0) the
        # wire stays at its old 4-element message format. SAME series as
        # the C++ env servers' _tele_setup (envs/native.py) — the fleet
        # aggregation must not depend on which sender type a run uses.
        tele = telemetry.registry("simulator")
        c_steps = tele.counter("env_steps_total")
        c_eps = tele.counter("episodes_total")
        c_rew_pos = tele.counter("reward_pos_sum")
        c_rew_neg = tele.counter("reward_neg_sum")
        c_bad = tele.counter("corrupt_action_replies_total")
        tracker = telemetry.DeltaTracker(tele)

        state = player.current_state()
        reward, is_over = 0.0, False
        action = 0  # repeated on a corrupt reply (see _decode_action)
        step = 0
        env_us = 0  # last env-step duration, shipped in the trace context
        try:
            while True:
                msg = [ident, state, reward, is_over]
                d = None
                if (
                    telemetry.enabled()
                    and step and step % telemetry.PIGGYBACK_EVERY == 0
                ):
                    d = tracker.deltas() or None
                # length-versioned tail: deltas 5th element, sampled trace
                # context 6th (THE one layout implementation — tracing.py)
                tracing.stamp_wire_meta(msg, ident, step, d, env_us)
                c2s.send(dumps(msg))
                action = _decode_action(s2c.recv(), action, c_bad)
                t_env = tracing.now_us() if tracing.enabled() else 0
                reward, is_over = player.action(action)
                c_steps.inc()
                if is_over:
                    c_eps.inc()
                # sign-split like native.py: both halves stay monotonic
                if reward > 0:
                    c_rew_pos.inc(reward)
                elif reward < 0:
                    c_rew_neg.inc(-reward)
                state = player.current_state()
                if t_env:
                    env_us = tracing.now_us() - t_env
                step += 1
        except (KeyboardInterrupt, zmq.ContextTerminated):
            pass
        finally:
            c2s.close(0)
            s2c.close(0)
            context.term()


class SimulatorMaster(threading.Thread):
    """Master thread: multiplexes all simulators, dispatches subclass hooks.

    Reference: ``SimulatorMaster.run`` (SURVEY.md §3.2) — attach the incoming
    reward to the previous transition, fire ``_on_episode_over`` /
    ``_on_datapoint``, then ``_on_state`` for the fresh state. A dedicated
    send thread drains ``send_queue`` so predictor callbacks never block on
    the socket.
    """

    def __init__(
        self,
        pipe_c2s: str,
        pipe_s2c: str,
        actor_timeout: Optional[float] = None,
        reward_clip: float = 0.0,
        tele_role: str = "master",
    ):
        """``actor_timeout``: seconds of silence after which a client's state
        is dropped (failure detection the reference lacked, SURVEY.md §5 —
        a dead simulator would otherwise pin its half-built rollout forever).
        None disables pruning. ``reward_clip``: clip the LEARNING reward to
        [-c, c] (0 = off); episode scores always accumulate raw rewards.
        ``tele_role``: this master's telemetry identity — ``master`` for a
        single-fleet run, ``telemetry.fleet_role("master", k)`` when a
        learner hosts several fleets side by side (each master must own its
        counters/gauges, or K masters' series collapse into one registry
        and every per-fleet signal — autoscaler fill fractions included —
        reads the fleet SUM)."""
        super().__init__(daemon=True, name=f"SimulatorMaster-{tele_role}")
        self.actor_timeout = actor_timeout
        assert reward_clip >= 0, (
            f"reward_clip must be >= 0, got {reward_clip} (a negative bound "
            "would silently map every learning reward to a constant)"
        )
        self.reward_clip = reward_clip
        self._last_prune = float("-inf")  # monotonic: 0.0 is not "long ago" just after boot
        self.context = zmq.Context()
        self.c2s_socket = self.context.socket(zmq.PULL)
        self.c2s_socket.bind(pipe_c2s)
        self.c2s_socket.set_hwm(32)
        self.s2c_socket = self.context.socket(zmq.ROUTER)
        # identity HANDOVER: a respawned env server reconnects with its
        # dead predecessor's DEALER identity (slot-stable idents are what
        # make restarts land as incarnation resets). Without handover,
        # libzmq keeps the identity bound to the old half-dead pipe and
        # REJECTS the new peer — the master's action replies then go
        # nowhere and the respawned server parks in recv() forever (found
        # by the chaos bench: under sustained kill/respawn every slot
        # wedged one by one until the plane flatlined at zero).
        self.s2c_socket.setsockopt(zmq.ROUTER_HANDOVER, 1)
        self.s2c_socket.bind(pipe_s2c)
        self.s2c_socket.set_hwm(32)

        # sanitizer wrapping (BA3C_SANITIZE=1 in tests): the client table's
        # structure is owned by the receive loop, the send queue has exactly
        # one drain thread — plain defaultdict/Queue when disabled
        self.clients: Dict[bytes, ClientState] = sanitizer.wrap_client_table(
            lambda: ClientState(b""), name="SimulatorMaster.clients"
        )
        self.send_queue: "queue.Queue[list]" = sanitizer.wrap_queue(
            queue.Queue(maxsize=1024), name="SimulatorMaster.send_queue"
        )
        self._stop_evt = threading.Event()
        # block-shm ring sizing inputs (read by _shm_states' attach-time
        # safety check): whoever wires a downstream batcher must declare its
        # collate-holder capacity here — those items left the queue but
        # still pin ring views until collate's np.stack copies them
        self.feed_batch = 0

        # -- telemetry (docs/observability.md): counters are fetched ONCE
        # here and kept as attributes so the hot path pays a dict-get per
        # BATCH, never a registry lookup. Gauges bind weakly — the registry
        # outlives any one master and must not pin a closed one alive.
        self.tele_role = tele_role
        # env-server piggyback deltas fold into the matching fleet role
        # (``fleet`` <-> ``master``, ``fleet.f<k>`` <-> ``master.f<k>``):
        # per-fleet senders must not merge into one aggregate registry
        self._fleet_tele_role = (
            "fleet" if tele_role == "master"
            else tele_role.replace("master", "fleet", 1)
        )
        tele = telemetry.registry(tele_role)
        self._flight = telemetry.flight_recorder()
        self._c_per_env_msgs = tele.counter("per_env_msgs_total")
        self._c_block_msgs = tele.counter("block_msgs_total")
        self._c_block_shm_msgs = tele.counter("block_shm_msgs_total")
        self._c_datapoints = tele.counter("datapoints_total")
        self._c_pruned = tele.counter("clients_pruned_total")
        self._c_dropped = tele.counter("clients_dropped_total")
        self._c_rejected = tele.counter("blocks_rejected_total")
        # integrity rejects get their OWN typed counter next to the
        # structural one: a CRC mismatch means bytes changed in flight
        # (netchaos corruption, a flaky NIC), not a version-skewed sender —
        # the operator runbook branches on exactly this distinction
        self._c_corrupt = tele.counter("corrupt_frames_total")
        self._c_incarnation = tele.counter("incarnation_resets_total")
        self._c_blocked_puts = tele.counter("queue_blocked_puts_total")
        self._h_put_wait = tele.histogram("queue_put_wait_s", unit=1e-6)
        self._h_ingest = tele.histogram("e2e_ingest_latency_s", unit=1e-6)
        # SLO-serving fallback accounting (docs/serving.md): rows answered
        # with the uniform-random fallback after the predictor shed the
        # task (deadline/queue_full typed reject)
        self._c_shed_fallbacks = tele.counter("predictor_shed_fallbacks_total")
        # uniform-fallback RNG for shed replies; sheds can be delivered
        # from the admitting thread AND the predictor scheduler thread, and
        # numpy Generators are not thread-safe — same locking convention as
        # the predictor's PRNG key
        self._shed_rng = np.random.default_rng(0)
        self._shed_lock = threading.Lock()
        ref = weakref.ref(self)
        tele.gauge(
            "clients", fn=lambda: len(m.clients) if (m := ref()) else 0
        )
        tele.gauge(
            "send_queue_depth",
            fn=lambda: m.send_queue.qsize() if (m := ref()) else 0,
        )
        # subclasses create self.queue after super().__init__ — read late
        tele.gauge(
            "train_queue_depth",
            fn=lambda: (
                q.qsize()
                if (m := ref()) and (q := getattr(m, "queue", None))
                else 0
            ),
        )
        # capacity next to depth: an autoscaler (or any scraper) reading
        # queue fill over HTTP needs both ends of the fraction on the
        # endpoint — depth alone is meaningless without the bound
        tele.gauge(
            "train_queue_capacity",
            fn=lambda: (
                int(getattr(q, "maxsize", 0) or 0)
                if (m := ref()) and (q := getattr(m, "queue", None))
                else 0
            ),
        )
        tele.gauge(
            "block_backlog_steps",
            fn=lambda: max(
                (
                    len(c.steps)
                    for c in list(getattr(ref(), "clients", {}).values())
                    if isinstance(c, BlockClientState)
                ),
                default=0,
            ),
        )

        def send_loop():
            t = threading.current_thread()
            assert isinstance(t, StoppableThread)
            while not t.stopped():
                msg = t.queue_get_stoppable(self.send_queue, timeout=0.2)
                if msg is None:
                    return
                try:
                    # ROUTER sends never block: an unroutable ident or a
                    # peer past its HWM DROPS the message (MANDATORY off)
                    # — bounded by construction, not by timeout
                    self.s2c_socket.send_multipart(msg)  # ba3clint: disable=A12 — ROUTER drops, never parks
                except zmq.ZMQError:
                    if t.stopped() or self._stop_evt.is_set():
                        return  # socket closed during teardown
                    raise

        self.send_thread = StoppableThread(
            target=send_loop, daemon=True, name="SimulatorMaster-send"
        )
        self.send_thread.start()

    def run(self) -> None:
        poller = zmq.Poller()
        poller.register(self.c2s_socket, zmq.POLLIN)
        # this receive loop is the structural owner of the client table;
        # the sanitizer (when enabled) flags any other thread that
        # creates/deletes entries
        sanitizer.claim_owner(self.clients)

        try:
            while not self._stop_evt.is_set():
                # prune on EVERY iteration (it self-rate-limits): gating it
                # on poll timeouts would starve pruning exactly when the
                # surviving actors keep the socket busy
                self._prune_dead_actors()
                if not poller.poll(timeout=200):
                    continue
                # wire autodetect per message: the per-env protocol is ONE
                # msgpack frame, the block protocol is multipart — so block
                # and per-env speakers can share the same pipe pair (mixed
                # fleets, rolling upgrades). copy=False: the payload frames
                # back the numpy views directly (zero-copy ingest).
                frames = self.c2s_socket.recv_multipart(copy=False)
                if len(frames) == 1:
                    try:
                        msg = loads(frames[0].buffer)
                        ident, state, reward, is_over = msg[:4]
                    except CorruptFrameError as e:
                        # typed integrity reject: the frame's CRC failed —
                        # count it, record it, keep the loop alive (the
                        # lockstep sender re-sends nothing, parks in recv,
                        # and is pruned/respawned like any dead actor)
                        self._c_corrupt.inc()
                        self._flight.record(
                            "corrupt_frame", wire="per-env",
                            error=str(e)[:200],
                        )
                        logger.error("dropping corrupt per-env frame: %s", e)
                        continue
                    except Exception as e:
                        # untrusted wire input (msgpack raises its own
                        # hierarchy): a malformed per-env frame must not
                        # kill the receive loop for every healthy client —
                        # same posture as the block decoder below
                        self._c_rejected.inc()
                        self._flight.record(
                            "per_env_reject", error=str(e)[:200]
                        )
                        logger.error(
                            "dropping undecodable per-env message: %s", e
                        )
                        continue
                    if len(msg) > 4:
                        # length-versioned header: element 5 is the sender's
                        # piggybacked metric deltas (telemetry/wire.py);
                        # plain 4-element messages parse as before
                        telemetry.apply_fleet_deltas(
                            ident, msg[4], role=self._fleet_tele_role
                        )
                    self._c_per_env_msgs.inc()
                    client = self.clients[ident]
                    client.ident = ident
                    client.last_seen = time.monotonic()
                    if len(msg) > 5:
                        # element 6 is a sampled trace context (tracing.py):
                        # handshake the sender's clock, synthesize the
                        # env_step + wire spans, park the ref for the
                        # predictor callback's transition record
                        client.pending_trace = self._recv_trace(ident, msg[5])
                    self._on_message(ident, state, reward, is_over)
                else:
                    self._on_block_frames(frames)
        except zmq.ContextTerminated:
            logger.info("SimulatorMaster context terminated")
        except zmq.ZMQError:
            # teardown race: close() destroyed the sockets while we polled.
            # Only swallow when shutting down — a live-loop ZMQError is a bug.
            if not self._stop_evt.is_set():
                raise
            logger.info("SimulatorMaster socket closed during shutdown")

    #: how many env transitions one train-queue item represents — the
    #: conversion factor a fleet_snapshot consumer needs to turn queue
    #: depth into a sample backlog. (The shipped autoscaler policy works
    #: on the unit-free fill fraction and does not need it; external
    #: scrapers comparing depth against batch sizes do.) Subclasses own
    #: the real value: BA3C 1 datapoint per item, V-trace unroll_len.
    queue_samples_per_item: int = 1

    def fleet_snapshot(self) -> dict:
        """Fleet-size introspection hook (orchestrate/autoscaler.py).

        One consistent read of the backpressure signals the autoscaler
        feeds on, taken from the SAME telemetry counters the scrape
        endpoint exports — the supervisor acts on the master's account of
        the fleet, never on its own duplicate heartbeats. Safe from any
        thread: every field is a GIL-atomic read or a sharded-counter sum.
        """
        q = getattr(self, "queue", None)
        return {
            "clients": len(self.clients),
            "queue_depth": int(q.qsize()) if q is not None else 0,
            "queue_maxsize": int(getattr(q, "maxsize", 0) or 0),
            "queue_samples_per_item": int(self.queue_samples_per_item),
            "blocked_puts_total": float(self._c_blocked_puts.value()),
            "datapoints_total": float(self._c_datapoints.value()),
        }

    def _prune_dead_actors(self) -> None:
        """Drop state of clients silent for > actor_timeout (actor loss is
        tolerated: its partial rollout is discarded, training continues)."""
        if self.actor_timeout is None:
            return
        now = time.monotonic()
        if now - self._last_prune < self.actor_timeout / 4:
            return
        self._last_prune = now
        dead = [
            ident
            for ident, c in self.clients.items()
            if now - c.last_seen > self.actor_timeout
        ]
        # account FIRST, remove LAST: anything polling the client table
        # (the prune tests, a scrape of the clients gauge) must find the
        # counter ticked and the postmortem on disk by the time the client
        # is gone — the reverse order races every observer
        for ident in dead:
            client = self.clients[ident]
            self._c_pruned.inc()
            self._flight.record(
                "prune",
                ident=repr(ident),
                silent_s=round(now - client.last_seen, 3),
                block=isinstance(client, BlockClientState),
            )
            logger.warn(
                "actor %s silent for >%.0fs — dropped its client state",
                ident,
                self.actor_timeout,
            )
        if dead:
            # a prune IS the postmortem moment: the next wedged multi-hour
            # run must find evidence on disk, not in a truncated log
            self._flight.dump("actor prune")
        for ident in dead:
            client = self.clients.pop(ident)
            if isinstance(client, BlockClientState):
                client.close()  # release the shm ring mapping, if any

    def _on_message(self, ident: bytes, state, reward: float, is_over: bool) -> None:
        """Handle one simulator message (overridable; runs in master thread).

        Default semantics: attach the reward to the previous transition, fire
        the episode/datapoint hooks, then request an action for the new state.
        Per-client ordering is serialized by the protocol — the simulator
        blocks on its action, so no second message from ``ident`` can arrive
        before ``_on_state``'s callback has run.
        """
        client = self.clients[ident]
        if len(client.memory) > 0:
            client.memory[-1].reward = self._learn_reward(reward)
            client.score += reward  # scores stay RAW
            if is_over:
                self._on_episode_over(ident)
            else:
                self._on_datapoint(ident)
        self._on_state(state, ident)

    def _learn_reward(self, reward: float) -> float:
        """The LEARNING reward: clipped to [-c, c] when reward_clip is set
        (single definition shared by every master subclass)."""
        c = self.reward_clip
        return max(-c, min(c, reward)) if c else reward

    def _learn_reward_block(self, rewards: np.ndarray) -> np.ndarray:
        """[B]-vectorized :meth:`_learn_reward` (same clip, one np op)."""
        c = self.reward_clip
        return np.clip(rewards, -c, c) if c else rewards

    # -- block wire ingest (docs/actor_plane.md) ---------------------------
    def _on_block_frames(self, frames) -> None:
        """Decode one block message and dispatch the block hooks.

        Two frame layouts, distinguished by frame count:

        - 4 frames (``block``): ``[header, obs[hist,B,H,W] u8, rewards[B]
          f32, dones[B] u8]``. The obs frame is consumed as a TRANSPOSED
          VIEW ([B,H,W,hist] channel-last, what the net eats).
        - 3 frames (``block-shm``): ``[header, rewards, dones]`` with the
          header naming a /dev/shm ring + this step's slot; states become a
          lazy :class:`BlockStatesView` over the ring window.

        Neither wire ever materializes the channel interleave on the hot
        path; the one real copy happens at device ingest (or the feed's
        collate).
        """
        bufs = [f.buffer for f in frames]
        try:
            if len(bufs) == 4:
                meta, (obs, rewards, dones) = unpack_block(bufs)
                base_meta_len = 3  # [ident, step, B]
                self._c_block_msgs.inc()
            else:
                meta, (rewards, dones) = unpack_block(bufs)
                obs = None
                base_meta_len = 8  # [ident, step, B, ring, cap, h, w, hist]
                self._c_block_shm_msgs.inc()
            ident, step, n_envs = bytes(meta[0]), int(meta[1]), int(meta[2])
            if rewards.shape != (n_envs,) or dones.shape != (n_envs,):
                raise ValueError(
                    f"block payload shapes {rewards.shape}/{dones.shape} "
                    f"do not match header n_envs={n_envs}"
                )
            if len(meta) > base_meta_len:
                # length-versioned header: element base+1 is the server's
                # piggybacked metric deltas (telemetry/wire.py); old
                # base-length headers parse exactly as before. A sampled
                # step appends a SECOND element — the trace context
                # (tracing.py) — after a (possibly empty) deltas dict, so
                # positions never shift under either feature alone.
                telemetry.apply_fleet_deltas(
                    ident, meta[base_meta_len], role=self._fleet_tele_role
                )
            trace_elem = (
                meta[base_meta_len + 1]
                if len(meta) > base_meta_len + 1 else None
            )
        except CorruptFrameError as e:
            # typed integrity reject (CRC mismatch — bytes changed in
            # flight): its own counter + flight kind so operators can tell
            # link corruption from sender version skew; never reaches a
            # frombuffer view (serialize.unpack_block verifies first)
            self._c_corrupt.inc()
            self._flight.record(
                "corrupt_frame", wire="block", error=str(e)[:200]
            )
            logger.error("dropping corrupt block frame: %s", e)
            return
        except (ValueError, TypeError, IndexError) as e:
            # wire input is untrusted: a version-mismatched fleet (or any
            # stray sender on the bound port) must not kill the receive
            # loop for every healthy client — skip the message. The sender,
            # if it is a real env server, parks in recv() and gets pruned.
            self._c_rejected.inc()
            self._flight.record("block_reject", error=str(e)[:200])
            logger.error("dropping undecodable block message: %s", e)
            return
        blk = self.clients.get(ident)
        if blk is not None and step <= blk.last_step:
            # step went backwards: a crashed server was RESTARTED under the
            # same ident inside actor_timeout. Its pre-crash state (pending
            # steps awaiting rewards, episode ages, the old ring inode)
            # would misalign every datapoint — drop it and start a fresh
            # incarnation, same semantics as a prune + reconnect.
            self._c_incarnation.inc()
            self._flight.record(
                "incarnation_reset",
                ident=repr(ident), step=step, last_step=blk.last_step,
            )
            logger.warn(
                "block client %s restarted (step %d after %d) — resetting "
                "its state", ident, step, blk.last_step,
            )
            blk.close()
            blk = None
        if blk is None:
            # structural create stays in the master thread (sanitizer-
            # checked); the defaultdict factory would make a per-env
            # ClientState, so block entries are created explicitly
            blk = BlockClientState(ident, n_envs)
            self.clients[ident] = blk
        blk.last_seen = time.monotonic()
        blk.last_step = step
        if trace_elem is not None:
            blk.pending_trace = self._recv_trace(ident, trace_elem)
        dones = dones.astype(bool)
        try:
            if obs is not None:
                # [B,H,W,hist] zero-copy view
                states = obs.transpose(1, 2, 3, 0)
            else:
                states = self._shm_states(blk, meta, step, dones)
            self._on_block_message(ident, states, rewards, dones)
        except (ValueError, NotImplementedError) as e:
            # a misconfigured CLIENT (ring too small for this learner's
            # buffering, or a block speaker against a per-env-only master)
            # must not kill the receive loop for every other client: drop
            # it — the server stays parked in its recv() — and keep serving
            self._c_dropped.inc()
            self._flight.record(
                "client_drop", ident=repr(ident), error=str(e)[:200]
            )
            logger.error(
                "dropping block client %s (it will get no reply and stay "
                "blocked): %s", ident, e,
            )
            del self.clients[ident]
            blk.close()
            self._flight.dump("client drop")

    def _shm_states(self, blk, meta, step: int, dones: np.ndarray):
        """Build the step's lazy states view from the client's shm ring."""
        # meta[2:8] — not full destructuring: a piggybacked header carries
        # one extra telemetry element (telemetry/wire.py)
        n_envs, ring_name, cap, h, w, hist = meta[2:8]
        if blk.ring is None:
            from distributed_ba3c_tpu.utils.shm import ShmRing, min_safe_cap

            # safety contract (utils/shm.py): a datapoint's backing slot
            # must not be reusable while the datapoint can still be alive.
            # A full train queue blocks the master -> action replies stop
            # -> every lockstep server halts within one step, so the live
            # window is bounded by queue depth + the flush horizon.
            q = getattr(self, "queue", None)
            maxsize = getattr(q, "maxsize", 0)
            horizon = int(
                getattr(self, "local_time_max", 0)
                or getattr(self, "unroll_len", 0)
            )
            if maxsize <= 0:
                raise ValueError(
                    "block-shm wire needs a BOUNDED train queue: queue "
                    "backpressure is what stops ring slots from being "
                    "overwritten under live datapoints"
                )
            # the live window counts EVERY queued-or-held item that can pin
            # a ring view, in ring STEPS: queue items plus the downstream
            # feed's collate holder (outside the queue, still views), each
            # spanning ring_steps_per_item steps (1 for BA3C datapoints;
            # unroll_len for V-trace segments, whose bootstrap_state view
            # trails the segment head by a whole unroll), plus the unflushed
            # blk.steps horizon and the hist slots a window reaches back —
            # the one formula lives in utils/shm.py, shared with cli.py's
            # ring sizing
            span = int(getattr(self, "ring_steps_per_item", 1))
            feed = int(getattr(self, "feed_batch", 0))
            needed = min_safe_cap(n_envs, maxsize, feed, span, horizon, hist)
            if cap <= needed:
                raise ValueError(
                    f"shm ring cap {cap} too small for train queue "
                    f"maxsize {maxsize} (+{feed} feed holder) x {span} "
                    f"steps/item at B={n_envs} (+{horizon}-step flush "
                    f"horizon): need > {needed:.0f} — shrink the queue or "
                    "pass a larger shm_ring_cap to the env server"
                )
            blk.ring = ShmRing.attach(ring_name, cap, n_envs, h, w)
            self._flight.record(
                "ring_attach", ident=repr(blk.ident),
                ring=str(ring_name), cap=int(cap),
            )
        ring = blk.ring.arr
        slot = step % cap
        if step >= hist - 1 and slot >= hist - 1:
            window = ring[slot - hist + 1 : slot + 1]  # zero-copy view
        else:
            # wrapped (or pre-history) window: small stack copy, ~hist/cap
            # of steps take this path
            window = np.stack(
                [ring[(step - k) % cap] for k in range(hist - 1, -1, -1)]
            )
        ages = np.where(dones, 0, blk.ages + 1)
        blk.ages = ages
        return BlockStatesView(window, ages)

    def _on_block_message(
        self,
        ident: bytes,
        states: np.ndarray,
        rewards: np.ndarray,
        dones: np.ndarray,
    ) -> None:
        """Block analogue of :meth:`_on_message`: attach (rewards, dones) to
        the previous block step, account episode scores, fire the subclass
        flush hook, then request actions for the fresh states. Per-block
        ordering is protocol-serialized exactly like the per-env wire: the
        server blocks on its action reply, so no second message from
        ``ident`` can arrive before ``_on_block_state``'s callback ran.
        """
        blk = self.clients[ident]
        if blk.pending_trace is not None:
            # flight events recorded while this sampled block is being
            # flushed/dispatched (queue_wait stalls, prunes) get stamped
            # with its trace id — postmortem dumps correlate with /trace
            # (telemetry/recorder.py); two thread-local ops, sampled only
            with tracing.trace_scope(blk.pending_trace.trace_id):
                self._dispatch_block(blk, states, rewards, dones, ident)
        else:
            self._dispatch_block(blk, states, rewards, dones, ident)

    def _dispatch_block(self, blk, states, rewards, dones, ident) -> None:
        if blk.steps:
            last = blk.steps[-1]
            last.rewards = self._learn_reward_block(rewards)
            last.dones = dones
            blk.scores += rewards  # scores stay RAW
            if dones.any():
                score_q = getattr(self, "score_queue", None)
                for j in np.nonzero(dones)[0]:
                    if score_q is not None:
                        try:
                            score_q.put_nowait(float(blk.scores[j]))
                        except queue.Full:
                            pass
                blk.scores[dones] = 0.0
            self._on_block_flush(ident)
        self._on_block_state(states, ident)

    def _on_block_state(self, states: np.ndarray, ident: bytes) -> None:
        """Fresh [B,...] states arrived: request B actions in ONE predictor
        call and record the block transition (subclass hook)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the block wire — "
            "run its env servers with wire='per-env'"
        )

    def _on_block_flush(self, ident: bytes) -> None:
        """Rewards/dones were attached to the newest block step: emit any
        completed experience (n-step windows / unroll segments) per env
        (subclass hook)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not implement the block wire — "
            "run its env servers with wire='per-env'"
        )

    def _drop_flushed_prefix(self, blk: BlockClientState) -> None:
        """Free block steps every env has consumed (and their zmq frames)."""
        m = int(blk.start.min())
        if m:
            del blk.steps[:m]
            blk.start -= m

    # -- serving-plane shed fallbacks (docs/serving.md) --------------------
    def _shed_fallback_block(self, cb, k: int):
        """Fallback reply for a shed block task (predict/server.py's typed
        :class:`ShedReject`): answer with uniform-random actions so the
        lockstep server keeps stepping instead of parking in ``recv()``.
        The recorded behavior log-prob IS correct for the fallback policy
        (log 1/A), so V-trace stays exact and BA3C merely learns from a
        few exploratory steps; value 0 is the honest no-estimate."""

        def shed(reject):
            A = int(getattr(self.predictor, "num_actions", 0) or 0)
            if A <= 0:
                # no known action space to fall back to: leave the server
                # to the prune path and the operator to the flight record
                self._flight.record("shed_no_fallback", reason=reject.reason)
                return
            with self._shed_lock:
                acts = self._shed_rng.integers(0, A, k)
            self._c_shed_fallbacks.inc(k)
            cb(
                np.ascontiguousarray(acts, np.int32),
                np.zeros(k, np.float32),
                np.full(k, -np.log(A), np.float32),
            )

        return shed

    def _shed_fallback_row(self, cb):
        """Per-env-wire analogue of :meth:`_shed_fallback_block`."""

        def shed(reject):
            A = int(getattr(self.predictor, "num_actions", 0) or 0)
            if A <= 0:
                self._flight.record("shed_no_fallback", reason=reject.reason)
                return
            with self._shed_lock:
                a = int(self._shed_rng.integers(0, A))
            self._c_shed_fallbacks.inc()
            cb(a, 0.0, float(-np.log(A)))

        return shed

    def _recv_trace(self, ident: bytes, trace_elem):
        """Decode one received trace-context element (tracing.py).

        Handshakes the sender's monotonic clock, synthesizes the sender's
        ``env_step`` span (duration shipped in the context — env servers
        never expose a scrape endpoint) plus the ``wire`` transit span,
        and returns a TraceRef for this master's own hops — or None on
        junk (wire input is untrusted, the block decoder's posture)."""
        out = tracing.receive_context(
            tracing.decode_context(trace_elem),
            peer=repr(ident), role=self.tele_role, origin_always=True,
        )
        if out is None:
            return None
        trace_id, parent = out
        return tracing.TraceRef(trace_id, parent)

    def send_action(self, ident: bytes, action: int) -> None:
        self._put_stoppable(self.send_queue, [ident, dumps(int(action))])

    def send_block_actions(self, ident: bytes, actions: np.ndarray) -> None:
        """One batched action reply for a whole block: raw int32[B] frame
        (the server ``np.frombuffer``s it — no msgpack on the reply side)."""
        self._put_stoppable(
            self.send_queue,
            [ident, np.ascontiguousarray(actions, np.int32).tobytes()],
        )

    def _put_stoppable(self, q: queue.Queue, item, timeout: float = 0.5) -> bool:
        """Backpressure that stays shutdown-responsive: bounded-timeout puts
        re-checking the stop flag (the plane's only sanctioned blocking put —
        ba3clint A2). Returns False if the master stopped while waiting.

        Telemetry rides the SLOW path only: the common non-blocked put is
        one ``put_nowait`` (same cost as before); a put that actually hits
        backpressure pays two monotonic reads against a wait that is always
        orders of magnitude longer."""
        if self._stop_evt.is_set():
            # the fast path must not outlive stop(): flush loops abort on
            # the first False, same as queue_put_stoppable's own guard
            return False
        try:
            q.put_nowait(item)
            return True
        except queue.Full:
            pass
        self._c_blocked_puts.inc()
        t0 = time.monotonic()
        ok = queue_put_stoppable(q, item, self._stop_evt, timeout)
        waited = time.monotonic() - t0
        self._h_put_wait.observe(waited)
        if waited >= 0.05:
            # the flight ring wants stalls, not the steady-state jitter
            self._flight.record("queue_wait", wait_s=round(waited, 4))
        return ok

    def stop(self) -> None:
        self._stop_evt.set()
        self.send_thread.stop()

    def close(self) -> None:
        """Stop threads and tear down ZMQ without lingering sends.

        Idempotent; joins the receive loop BEFORE destroying the context so
        no ZMQ background thread outlives the master (a leaked io-thread can
        wedge later in-process jit dispatch — the round-1 pytest deadlock).
        """
        self._stop_evt.set()
        self.send_thread.stop()
        self.send_thread.join(timeout=2)
        if self.is_alive():
            self.join(timeout=2)
        try:
            self.context.destroy(linger=0)
        except zmq.ZMQError:
            pass  # already destroyed
        for client in list(self.clients.values()):
            if isinstance(client, BlockClientState):
                client.close()  # release shm ring mappings, if any

    @abstractmethod
    def _on_state(self, state, ident: bytes) -> None:
        """A fresh state arrived: request an action and record the transition."""

    @abstractmethod
    def _on_episode_over(self, ident: bytes) -> None:
        """The client's episode ended (reward already attached)."""

    @abstractmethod
    def _on_datapoint(self, ident: bytes) -> None:
        """A mid-episode transition completed (reward already attached)."""

    def __del__(self):
        try:
            self._stop_evt.set()
            self.send_thread.stop()
            self.context.destroy(0)
        except Exception:
            pass
