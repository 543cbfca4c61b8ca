"""A seeded, checkable token game: recall the prompt (ROADMAP A4).

An episode is ``episode_length`` steps over a vocabulary of ``num_actions``
token ids, and every step is a transition the learner trains on:

- steps ``0 .. prompt_len - 1``: the env shows prompt token ``x_t``, drawn
  uniformly from the vocabulary by the episode's key; any action, reward 0;
- steps ``prompt_len ..``: the env shows the agent's previous action (the
  sequence it is generating) and pays reward 1 where the action is
  ``x_{(t - prompt_len) mod prompt_len}``: the prompt, recalled again and
  again. A verifier, as RL on checkable text tasks has;
- ``done`` at the last step; the env restarts itself from the step's key.

The observation is one int32 token id, not a frame: a policy that carries
state (models/policy.py) reads it as it is. ``jax:recall`` is 64 prompt +
192 recalled tokens over 16,384 ids; ``jax:recall:<ids>:<prompt>:<episode>``
names another size (the CPU tests' small one).

Per the package's env-authoring rule the prompt is read by a mask and a
sum, never by a traced index.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class State(NamedTuple):
    prompt: jax.Array       # [prompt_len] int32
    t: jax.Array            # [] int32 step in the episode
    last_action: jax.Array  # [] int32 the agent's previous action


class RecallEnv:
    """The game at one size; the module-level functions of the other envs
    are this object's methods."""

    obs_shape: Tuple[int, ...] = ()

    def __init__(self, num_actions: int = 16384, prompt_len: int = 64,
                 episode_length: int = 256):
        if not 0 < prompt_len < episode_length:
            raise ValueError("recall: need 0 < prompt < episode length")
        self.num_actions = num_actions
        self.prompt_len = prompt_len
        self.episode_length = episode_length

    def reset(self, key: jax.Array) -> State:
        return State(
            prompt=jax.random.randint(
                key, (self.prompt_len,), 0, self.num_actions, jnp.int32),
            t=jnp.int32(0),
            last_action=jnp.int32(0),
        )

    def _prompt_at(self, state: State, index) -> jax.Array:
        here = jnp.arange(self.prompt_len) == index
        return jnp.sum(jnp.where(here, state.prompt, 0)).astype(jnp.int32)

    def render(self, state: State) -> jax.Array:
        """The token the env shows at ``state.t``."""
        return jnp.where(
            state.t < self.prompt_len,
            self._prompt_at(state, state.t), state.last_action,
        )

    def step(self, state: State, action: jax.Array, key: jax.Array):
        action = action.astype(jnp.int32)
        wanted = self._prompt_at(
            state, (state.t - self.prompt_len) % self.prompt_len)
        reward = ((state.t >= self.prompt_len) & (action == wanted)).astype(
            jnp.float32)
        done = state.t == self.episode_length - 1
        moved = State(prompt=state.prompt, t=state.t + 1, last_action=action)
        fresh = self.reset(key)
        state = jax.tree_util.tree_map(
            lambda a, b: jnp.where(done, a, b), fresh, moved)
        return state, self.render(state), reward, done


def from_spec(spec: str) -> RecallEnv:
    """``recall`` or ``recall:<ids>:<prompt>:<episode>``."""
    parts = spec.split(":")
    if parts[0] != "recall" or len(parts) not in (1, 4):
        raise ValueError(
            f"{spec!r}: expected recall or recall:<ids>:<prompt>:<episode>")
    return RecallEnv(*(int(x) for x in parts[1:]))
