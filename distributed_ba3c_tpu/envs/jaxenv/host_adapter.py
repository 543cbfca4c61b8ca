"""Host-side player adapter for the pure-JAX envs.

Lets the on-device envs (envs/jaxenv/) serve the HOST actor plane too — a
SimulatorProcess child or the Evaluator can run `jax:pong` through the same
player protocol as FakeEnv/ALE (envs/base.py).

Backend policy: a chip belongs to ONE process, the trainer. Simulator
CHILDREN pin themselves to the CPU platform before any back-end exists —
a child that initialised the TPU back-end would fail on libtpu's lockfile
(or, were the chip free, take it from the trainer). In the TRAINER process
(Evaluator / --task eval) the global platform is NEVER mutated; the env's
tiny step is pinned to a CPU device with ``jax.default_device`` so eval
cannot flip the trainer's backend mid-training.
"""

from __future__ import annotations

import multiprocessing
import os

import numpy as np


def _in_child_process() -> bool:
    return multiprocessing.parent_process() is not None


def build_jax_player(idx: int, name: str = "pong", frame_history: int = 4):
    import jax

    if _in_child_process():
        # The env var covers grandchildren; the config update covers THIS
        # process, where unpickling the SimulatorProcess already imported
        # jax (so jax read the parent's JAX_PLATFORMS). Neither touches a
        # back-end — asking jax which back-end is the default would
        # initialise it, which is the claim this must not make.
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")

    from distributed_ba3c_tpu.envs.base import RLEnvironment
    from distributed_ba3c_tpu.envs.jaxenv import get_env
    from distributed_ba3c_tpu.envs.wrappers import HistoryFramePlayer

    env = get_env(name)
    step = jax.jit(env.step)
    # pin the per-step computation to CPU WITHOUT touching global config:
    # one env step is host-scale work; dispatching it to the TPU would
    # serialize against training for no gain. A process restricted to
    # JAX_PLATFORMS=tpu has no CPU back-end and fails here, loudly — the
    # sealed chip machine exports "tpu,cpu".
    cpu = jax.devices("cpu")[0]

    class _JaxPlayer(RLEnvironment):
        def __init__(self):
            with jax.default_device(cpu):
                self.key = jax.random.PRNGKey(idx)
                self.state = env.reset(self.key)
                self.obs = np.asarray(env.render(self.state))
            self.score = 0.0
            super().__init__()

        def current_state(self):
            return self.obs

        def get_action_space_size(self):
            return env.num_actions

        def action(self, act):
            with jax.default_device(cpu):
                self.key, k = jax.random.split(self.key)
                self.state, obs, r, d = step(self.state, np.int32(act), k)
                self.obs = np.asarray(obs)
            r, d = float(r), bool(d)
            self.score += r
            if d:
                self.finish_episode(self.score)
                self.score = 0.0
            return r, d

        def restart_episode(self):
            with jax.default_device(cpu):
                self.key, k = jax.random.split(self.key)
                self.state = env.reset(k)
                self.obs = np.asarray(env.render(self.state))
            self.score = 0.0

    return HistoryFramePlayer(_JaxPlayer(), frame_history)
