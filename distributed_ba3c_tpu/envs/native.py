"""ctypes binding for the C++ batched env core + ZMQ env-server process.

Reference equivalent: the ALE C++ emulator + its Python binding
(``ale_python_interface``/``atari_py``, SURVEY.md §2.10) — here the native
core is ``cpp/env_core.cc`` (build: ``make -C cpp``), exposing a BATCHED
step API so one process drives dozens of envs per call instead of the
reference's one-ALE-per-process layout.

Three integration surfaces:
- :class:`CppBatchedEnv` — raw batched stepper (numpy in/out, zero copies
  beyond the ctypes call).
- :func:`build_cpp_player` — single-env player (envs/base.py protocol) for
  wrappers/eval/SimulatorProcess parity paths.
- :class:`CppEnvServerProcess` — one OS process hosting B envs in lockstep,
  speaking the simulator wire protocol over ZMQ with one DEALER identity per
  env (the master cannot tell it apart from B SimulatorProcesses). Transport
  is thin pyzmq glue — the image ships no zmq.h, so the native side stays
  dependency-free and every hot cycle (physics + render) is C++.
"""

from __future__ import annotations

import ctypes
import multiprocessing as mp
import os
from typing import Optional, Tuple

import numpy as np

_LIB_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "cpp",
    "libba3c_env.so",
)

_lib = None


def _build() -> None:
    """``make -C cpp``: the .so is a build artifact, never committed, and a
    copy left in the tree may predate ``env_core.cc``. make itself decides
    (a no-op when the .so is newer than the source and the Makefile), so a
    stale library is rebuilt and a fresh checkout builds on first use."""
    import subprocess

    cmd = ["make", "-C", os.path.dirname(_LIB_PATH)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise ImportError(
            f"native env core: `{' '.join(cmd)}` could not run: {e!r}"
        ) from e
    if proc.returncode != 0:
        raise ImportError(
            f"native env core: `{' '.join(cmd)}` failed "
            f"(rc {proc.returncode}):\n{proc.stderr[-4000:]}"
        )


def _load():
    global _lib
    if _lib is None:
        _build()
        lib = ctypes.CDLL(_LIB_PATH)
        lib.ba3c_env_create.restype = ctypes.c_void_p
        lib.ba3c_env_create.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_uint64]
        lib.ba3c_env_destroy.argtypes = [ctypes.c_void_p]
        lib.ba3c_env_num_actions.argtypes = [ctypes.c_void_p]
        lib.ba3c_env_num_actions.restype = ctypes.c_int
        lib.ba3c_env_size.argtypes = [ctypes.c_void_p]
        lib.ba3c_env_size.restype = ctypes.c_int
        lib.ba3c_env_reset.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8)]
        lib.ba3c_env_step.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_uint8),
        ]
        lib.ba3c_obs_height.restype = ctypes.c_int
        lib.ba3c_obs_width.restype = ctypes.c_int
        _lib = lib
    return _lib


def available() -> bool:
    """Can the native core be built and loaded here? The reason it cannot
    (the compiler's stderr) is logged, not swallowed."""
    try:
        _load()
    except (ImportError, OSError) as e:
        from distributed_ba3c_tpu.utils import logger

        logger.warn("native env core unavailable: %s", e)
        return False
    return True


class CppBatchedEnv:
    """N native envs stepped in one call. Obs are uint8 [N, 84, 84]."""

    def __init__(self, name: str, n: int, seed: int = 0):
        lib = _load()
        self._lib = lib
        self._handle = lib.ba3c_env_create(name.encode(), n, seed)
        if not self._handle:
            raise ValueError(f"unknown native env {name!r}")
        self.n = n
        self.h = lib.ba3c_obs_height()
        self.w = lib.ba3c_obs_width()
        self.num_actions = lib.ba3c_env_num_actions(self._handle)
        self._obs = np.zeros((n, self.h, self.w), np.uint8)
        self._rew = np.zeros(n, np.float32)
        self._done = np.zeros(n, np.uint8)

    def reset(self) -> np.ndarray:
        self._lib.ba3c_env_reset(
            self._handle,
            self._obs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        return self._obs

    def step(
        self, actions: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """actions int32 [N] -> (obs [N,84,84] u8, rewards [N] f32, dones [N] u8).

        Returned arrays are internal buffers reused every call — copy if kept.
        """
        actions = np.ascontiguousarray(actions, np.int32)
        assert actions.shape == (self.n,)
        self._lib.ba3c_env_step(
            self._handle,
            actions.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            self._obs.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            self._rew.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self._done.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        return self._obs, self._rew, self._done

    def close(self):
        if self._handle:
            self._lib.ba3c_env_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def build_cpp_player(idx: int, name: str = "pong", frame_history: int = 4):
    """Single native env as a history-stacked player (wire-compatible with
    build_fake_player; used by SimulatorProcess/eval parity paths)."""
    from distributed_ba3c_tpu.envs.base import RLEnvironment
    from distributed_ba3c_tpu.envs.wrappers import HistoryFramePlayer

    class _CppPlayer(RLEnvironment):
        def __init__(self):
            self.env = CppBatchedEnv(name, 1, seed=idx)
            self.env.reset()
            self.score = 0.0
            super().__init__()

        def current_state(self):
            return self.env._obs[0].copy()

        def get_action_space_size(self):
            return self.env.num_actions

        def action(self, act):
            _, rew, done = self.env.step(np.array([act], np.int32))
            r, over = float(rew[0]), bool(done[0])
            self.score += r
            if over:
                self.finish_episode(self.score)
                self.score = 0.0
            return r, over

        def restart_episode(self):
            self.env.reset()
            self.score = 0.0

    return HistoryFramePlayer(_CppPlayer(), frame_history)


def _decode_actions(raw: bytes, fallback: np.ndarray, counter) -> np.ndarray:
    """Decode a batched action-reply frame; junk must not kill the loop.

    The env server's lockstep loop is supervisor-owned: a corrupt or
    short reply frame (PR 14 class) repeats the previous actions, makes
    the drop visible on ``corrupt_action_replies_total``, and keeps the
    loop alive instead of raising out of ``_run_block*``.
    """
    try:
        actions = np.frombuffer(raw, np.int32)
    except Exception:
        counter.inc()
        return fallback
    if actions.shape != fallback.shape:
        counter.inc()
        return fallback
    return actions


def _decode_action(raw: bytes, fallback: int, counter) -> int:
    """Per-env twin of :func:`_decode_actions` for the ``per-env`` wire."""
    from distributed_ba3c_tpu.utils.serialize import loads

    try:
        return int(loads(raw))
    except Exception:
        counter.inc()
        return fallback


class CppEnvServerProcess(mp.get_context("spawn").Process):  # type: ignore[misc]
    """One process, B native envs, lockstep-batched stepping, ZMQ transport.

    Three wire modes (docs/actor_plane.md):

    - ``wire="block-shm"`` (default where available): control over ZMQ,
      observation bytes through a /dev/shm ring (utils/shm.py). ONE tiny
      multipart message per STEP — ``[header, rewards[B], dones[B]]``,
      where the header names the ring and the step's slot — and one raw
      ``int32[B]`` action reply. The obs bytes never cross a socket: the
      server memcpys each step's plane into ``ring[step % cap]`` and the
      master reads frame-history windows as numpy views. Same-host only
      (the learner's ipc:// or localhost pipes).
    - ``wire="block"``: ONE multipart message per STEP for the whole block
      — ``[header, obs[hist,B,H,W], rewards[B], dones[B]]`` as raw
      zero-copy frames — and one raw ``int32[B]`` action reply, routed by
      the block's single DEALER identity ``<prefix>*block``. The history
      stack lives in ``[hist, B, H, W]`` layout so the per-step shift is a
      contiguous memmove (~78 us/block vs ~4 ms for the channel-last shift
      at B=32 — measured on this container) and the wire frame is the
      buffer itself; the master consumes transposed VIEWS, so no side of
      the hot path ever materializes the channel-last interleave. This is
      the wire for REMOTE (tcp://) actor fleets.
    - ``wire="per-env"``: the compat/correctness foil — each env gets its
      own DEALER identity ``<prefix>-<i>`` and the per-env msgpack protocol
      matches SimulatorProcess exactly (SURVEY.md §3.2): send
      [ident, stacked_state, reward, isOver], await action. 2·B Python
      socket ops + B msgpack encodes per step; kept because any
      wire-compatible speaker (the reference's own simulators) can
      interleave with it on the same pipes.
    """

    #: default block-shm ring sizing: capacity (in steps) chosen so the
    #: ring is ~8192 env-steps deep regardless of B (~57 MB at 84x84),
    #: which keeps the master's attach-time safety check satisfied for
    #: train queues up to ~8k items at any block size (utils/shm.py)
    SHM_RING_STEPS = 8192
    SHM_RING_MIN_CAP = 64

    def __init__(
        self,
        idx: int,
        pipe_c2s: str,
        pipe_s2c: str,
        game: str = "pong",
        n_envs: int = 16,
        frame_history: int = 4,
        ident_prefix: Optional[str] = None,
        wire: str = "block",
        shm_ring_cap: Optional[int] = None,
    ):
        super().__init__(daemon=True, name=f"cpp-env-server-{idx}")
        assert wire in ("block-shm", "block", "per-env"), wire
        self.idx = idx
        self.c2s = pipe_c2s
        self.s2c = pipe_s2c
        self.game = game
        self.n_envs = n_envs
        self.frame_history = frame_history
        self.ident_prefix = ident_prefix or f"cppsim-{idx}"
        self.wire = wire
        self.shm_ring_cap = shm_ring_cap or max(
            self.SHM_RING_MIN_CAP, self.SHM_RING_STEPS // max(1, n_envs)
        )

    def run(self) -> None:  # child process: no jax
        if self.wire == "block-shm":
            self._run_block_shm()
        elif self.wire == "block":
            self._run_block()
        else:
            self._run_per_env()

    def _tele_setup(self):
        """Child-side telemetry: counters + the piggyback delta tracker.

        Returns ``(count_step, piggyback, extend_meta, c_bad)``:
        ``c_bad`` is the ``corrupt_action_replies_total`` reject counter
        fed to the ``_decode_action*`` helpers; ``count_step``
        is called once per lockstep block step; ``piggyback(step)``
        returns the deltas dict to append to the wire header (or None —
        which keeps the header at its OLD length, so telemetry-disabled
        fleets exercise the pre-telemetry wire format end-to-end);
        ``extend_meta(meta, step, env_us)`` appends the length-versioned
        tail — the deltas element and, on 1-in-N sampled steps, the trace
        context (telemetry/tracing.py) carrying this server's monotonic
        stamp (clock handshake) and its last env-step duration."""
        from distributed_ba3c_tpu import telemetry
        from distributed_ba3c_tpu.telemetry import tracing

        tele = telemetry.registry("simulator")
        c_steps = tele.counter("env_steps_total")
        c_eps = tele.counter("episodes_total")
        # reward split by sign: raw Atari rewards go NEGATIVE (Pong -1),
        # and a decreasing series exported as a Prometheus counter reads
        # as a counter reset (rate() spikes). Two monotonic halves keep
        # counter semantics; net reward = pos - neg at query time.
        c_rew_pos = tele.counter("reward_pos_sum")
        c_rew_neg = tele.counter("reward_neg_sum")
        c_bad = tele.counter("corrupt_action_replies_total")
        tracker = telemetry.DeltaTracker(tele)
        B = self.n_envs

        def count_step(rew, dn) -> None:
            c_steps.inc(B)
            n_done = int(dn.sum())
            if n_done:
                c_eps.inc(n_done)
            pos = float(rew[rew > 0].sum())
            neg = -float(rew[rew < 0].sum())
            if pos:
                c_rew_pos.inc(pos)
            if neg:
                c_rew_neg.inc(neg)

        def piggyback(step: int):
            if not telemetry.enabled():
                return None
            if step == 0 or step % telemetry.PIGGYBACK_EVERY:
                return None
            return tracker.deltas() or None

        ident = f"{self.ident_prefix}*block".encode()

        def extend_meta(meta: list, step: int, env_us: int) -> None:
            # THE one layout implementation lives in tracing.py — the
            # python simulator sender calls the same helper
            tracing.stamp_wire_meta(
                meta, ident, step, piggyback(step), env_us
            )

        return count_step, piggyback, extend_meta, c_bad

    def _run_block_shm(self) -> None:
        import signal

        import zmq

        from distributed_ba3c_tpu.utils.serialize import pack_block
        from distributed_ba3c_tpu.utils.shm import ShmRing

        # terminate() must run the finally block so the ring file is
        # unlinked (a SIGKILLed server's stale file is truncated over at
        # the next create)
        def _term(*_):
            raise SystemExit(0)

        signal.signal(signal.SIGTERM, _term)

        env = CppBatchedEnv(self.game, self.n_envs, seed=self.idx * 10_000)
        obs = env.reset()
        B, H, W, hist = self.n_envs, env.h, env.w, self.frame_history
        cap = self.shm_ring_cap
        ident = f"{self.ident_prefix}*block".encode()
        # the ring name must be STABLE across restarts of this server slot
        # (pipe pair + prefix identify the slot; concurrent fleets differ in
        # pipe address): a crashed/SIGKILLed server leaves its ring file in
        # /dev/shm, and create()'s rename-over reclaims it only if the
        # replacement generates the SAME name — a pid in the name would
        # leak ~57 MB per crash until /dev/shm fills. The name formula is
        # shared with the supervisor's stale-ring reclaim (utils/shm.py)
        from distributed_ba3c_tpu.utils import shm as shm_mod

        ring_name = shm_mod.ring_name(self.c2s, self.ident_prefix)
        ring = ShmRing.create(ring_name, cap, B, H, W)
        rewards = np.zeros(B, np.float32)
        dones = np.zeros(B, np.uint8)
        actions = np.zeros(B, np.int32)  # fallback on a corrupt reply

        ctx = zmq.Context()
        push = ctx.socket(zmq.PUSH)
        push.set_hwm(4)
        push.connect(self.c2s)
        dealer = ctx.socket(zmq.DEALER)
        dealer.setsockopt(zmq.IDENTITY, ident)
        dealer.connect(self.s2c)

        count_step, piggyback, extend_meta, c_bad = self._tele_setup()
        from distributed_ba3c_tpu.telemetry import tracing

        step = 0
        env_us = 0  # last env.step duration, shipped in the trace context
        try:
            while True:
                # the step's obs plane goes into the ring; the wire carries
                # only the header + rewards + dones (the master rebuilds
                # frame-history windows from ring slots — docs/actor_plane.md)
                ring.arr[step % cap] = obs
                meta = [ident, step, B, ring_name, cap, H, W, hist]
                extend_meta(meta, step, env_us)  # length-versioned tail
                # lockstep protocol: parking in send/recv awaiting the
                # action reply IS the env server's contract — a dead
                # master leaves this process to its supervisor (prune +
                # respawn), never to a local timeout
                push.send_multipart(  # ba3clint: disable=A12 — lockstep park, supervisor-owned lifetime
                    pack_block(meta, [rewards, dones]),
                    copy=False,
                )
                actions = _decode_actions(dealer.recv(), actions, c_bad)  # ba3clint: disable=A12 — lockstep park
                t_env = tracing.now_us() if tracing.enabled() else 0
                obs, rew, dn = env.step(actions)
                if t_env:
                    env_us = tracing.now_us() - t_env
                rewards[:] = rew
                dones[:] = dn
                count_step(rew, dn)
                step += 1
        except (KeyboardInterrupt, SystemExit, zmq.ContextTerminated):
            pass
        finally:
            dealer.close(0)
            push.close(0)
            ctx.term()
            ring.close(unlink=True)

    def _run_block(self) -> None:
        import zmq

        from distributed_ba3c_tpu.utils.serialize import pack_block

        env = CppBatchedEnv(self.game, self.n_envs, seed=self.idx * 10_000)
        obs = env.reset()
        B, H, W, hist = self.n_envs, env.h, env.w, self.frame_history
        # [hist, B, H, W]: oldest..newest planes, contiguous — the shift is
        # one contiguous memmove and the whole stack is ONE wire frame
        stacks = np.zeros((hist, B, H, W), np.uint8)
        stacks[-1] = obs
        rewards = np.zeros(B, np.float32)
        dones = np.zeros(B, np.uint8)
        actions = np.zeros(B, np.int32)  # fallback on a corrupt reply
        ident = f"{self.ident_prefix}*block".encode()

        ctx = zmq.Context()
        push = ctx.socket(zmq.PUSH)
        push.set_hwm(4)  # blocks are big; a deep send buffer is pure RAM
        push.connect(self.c2s)
        dealer = ctx.socket(zmq.DEALER)
        dealer.setsockopt(zmq.IDENTITY, ident)
        dealer.connect(self.s2c)

        count_step, piggyback, extend_meta, c_bad = self._tele_setup()
        from distributed_ba3c_tpu.telemetry import tracing

        step = 0
        env_us = 0  # last env.step duration, shipped in the trace context
        try:
            while True:
                meta = [ident, step, B]
                extend_meta(meta, step, env_us)  # length-versioned tail
                # copy=False hands zmq the arrays' own buffers. Safe ONLY
                # because the protocol is lockstep: the master cannot reply
                # with actions before it has received (= fully copied out of
                # this process over ipc/tcp) the observation message, and we
                # do not mutate the buffers until that reply arrives.
                push.send_multipart(  # ba3clint: disable=A12 — lockstep park, supervisor-owned lifetime
                    pack_block(meta, [stacks, rewards, dones]),
                    copy=False,
                )
                actions = _decode_actions(dealer.recv(), actions, c_bad)  # ba3clint: disable=A12 — lockstep park
                t_env = tracing.now_us() if tracing.enabled() else 0
                obs, rew, dn = env.step(actions)
                if t_env:
                    env_us = tracing.now_us() - t_env
                rewards[:] = rew
                dones[:] = dn
                count_step(rew, dn)
                # shift history (contiguous memmove); clear across episode
                # boundaries so the first post-reset state is [0,...,0,obs]
                stacks[:-1] = stacks[1:]
                stacks[-1] = obs
                if dn.any():
                    d = dn.astype(bool)
                    stacks[:-1, d] = 0
                step += 1
        except (KeyboardInterrupt, zmq.ContextTerminated):
            pass
        finally:
            dealer.close(0)
            push.close(0)
            ctx.term()

    def _run_per_env(self) -> None:
        import zmq

        from distributed_ba3c_tpu.utils.serialize import dumps

        env = CppBatchedEnv(self.game, self.n_envs, seed=self.idx * 10_000)
        obs = env.reset()
        B, H, W = self.n_envs, env.h, env.w
        stacks = np.zeros((B, H, W, self.frame_history), np.uint8)
        stacks[..., -1] = obs
        rewards = np.zeros(B, np.float32)
        dones = np.zeros(B, bool)

        ctx = zmq.Context()
        push = ctx.socket(zmq.PUSH)
        push.set_hwm(B + 4)
        push.connect(self.c2s)
        idents = [f"{self.ident_prefix}-{i}".encode() for i in range(B)]
        dealers = []
        for ident in idents:
            s = ctx.socket(zmq.DEALER)
            s.setsockopt(zmq.IDENTITY, ident)
            s.connect(self.s2c)
            dealers.append(s)

        count_step, piggyback, _, c_bad = self._tele_setup()
        actions = np.zeros(B, np.int32)
        step = 0
        try:
            while True:
                tele = piggyback(step)
                # the per-env wire IS the A6 antipattern — kept on purpose
                # as the compat/correctness foil (`--wire per-env`); the
                # block path above is the production wire. Telemetry rides
                # env 0's message as an optional 5th element.
                for i in range(B):
                    msg = [idents[i], stacks[i], float(rewards[i]), bool(dones[i])]
                    if i == 0 and tele is not None:
                        msg.append(tele)
                    push.send(  # ba3clint: disable=A12 — compat foil (lockstep park), see docstring
                        dumps(msg)
                    )
                for i in range(B):
                    actions[i] = _decode_action(
                        dealers[i].recv(),  # ba3clint: disable=A6,A12 — compat foil (lockstep park)
                        int(actions[i]),
                        c_bad,
                    )
                obs, rew, dn = env.step(actions)
                rewards[:] = rew
                dones[:] = dn.astype(bool)
                count_step(rew, dn)
                step += 1
                # shift history; clear across episode boundaries
                stacks[..., :-1] = stacks[..., 1:]
                stacks[..., -1] = obs
                if dones.any():
                    stacks[dones] = 0
                    stacks[dones, :, :, -1] = obs[dones]
        except (KeyboardInterrupt, zmq.ContextTerminated):
            pass
        finally:
            for s in dealers:
                s.close(0)
            push.close(0)
            ctx.term()
