"""Plain reference of the ``jax:recall`` token game, written from its rules.

An episode is ``episode`` steps over ``ids`` token ids. At steps ``0 ..
prompt - 1`` the env shows prompt token ``x_t`` (drawn uniformly from the
ids by the episode's key) and pays nothing. From step ``prompt`` on it shows
the agent's previous action and pays 1 where the action equals
``x_{(t - prompt) mod prompt}``. The episode ends after its last step; the
next one starts from the step's key. Unbatched functions on a dict of
arrays; callers ``vmap`` them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: ids, prompt length, episode length of ``jax:recall``
IDS, PROMPT, EPISODE = 16384, 64, 256


def reset(key, ids=IDS, prompt=PROMPT):
    return {
        "prompt": jax.random.randint(key, (prompt,), 0, ids, jnp.int32),
        "t": jnp.int32(0),
        "last_action": jnp.int32(0),
    }


def shown(state):
    """The token the env shows at the state's step."""
    prompt = state["prompt"].shape[0]
    at = jnp.clip(state["t"], 0, prompt - 1)
    return jnp.where(state["t"] < prompt, state["prompt"][at],
                     state["last_action"])


def step(state, action, key, ids=IDS, episode=EPISODE):
    """-> (state, shown token, reward, done)."""
    prompt = state["prompt"].shape[0]
    t = state["t"]
    wanted = state["prompt"][jnp.mod(t - prompt, prompt)]
    reward = jnp.where((t >= prompt) & (action == wanted), 1.0, 0.0)
    done = t == episode - 1
    fresh = reset(key, ids, prompt)
    moved = {"prompt": state["prompt"], "t": t + 1,
             "last_action": action.astype(jnp.int32)}
    state = {k: jnp.where(done, fresh[k], moved[k]) for k in moved}
    return state, shown(state), reward.astype(jnp.float32), done
