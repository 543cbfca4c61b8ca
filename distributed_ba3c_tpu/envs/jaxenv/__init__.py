"""On-device vectorized environments (pure JAX, gymnax-style).

The TPU-native addition the reference never had (SURVEY.md §7 step 10,
BASELINE.json config #5): env physics and rendering as jit/vmap-able pure
functions, so thousands of envs step per device inside the SAME compiled
program as the learner — zero host round-trips, no ZMQ, no pickle, the
whole actor-learner loop is one XLA computation.

Env functional protocol (unbatched; vmap at the call site):
    env.reset(key) -> state                       (pytree of arrays)
    env.step(state, action, key) -> (state, obs uint8 [H,W], reward, done)
    env.num_actions: int
A token env (recall.py) gives an int32 token id for ``obs`` and has
``env.episode_length``: every episode is that many steps.
Episodes auto-restart on done (same contract as the host player protocol,
envs/base.py) so rollout scans never branch.

Env-authoring rule (measured, v5e): NO per-env dynamic scalar indexing —
``grid[row, col]``, ``centers[idx]``, ``.at[slot].set`` with traced scalars
become batched dynamic gathers/scatters under vmap and ran the WHOLE fused
step 6x slower (space_invaders, before the rewrite). Use one-hot masks,
uniform-grid arithmetic, or separable mask matmuls instead; gathers with
STATE-INDEPENDENT (constant) index arrays are fine (breakout's brick
raster).
"""

from distributed_ba3c_tpu.envs.jaxenv import (
    assault,
    boxing,
    breakout,
    coinrun,
    pong,
    qbert,
    recall,
    seaquest,
    space_invaders,
)


def get_env(name: str):
    if name.split(":")[0] == "recall":
        # a token game, sized by its name (recall.py): an object with the
        # same functions, not a module of constants
        return recall.from_spec(name)
    envs = {
        "pong": pong,
        "breakout": breakout,
        "seaquest": seaquest,
        "qbert": qbert,
        "coinrun": coinrun,
        "space_invaders": space_invaders,
        "boxing": boxing,
        "assault": assault,
    }
    if name not in envs:
        raise ValueError(
            f"unknown jax env {name!r}; have {sorted(envs) + ['recall']}")
    return envs[name]
