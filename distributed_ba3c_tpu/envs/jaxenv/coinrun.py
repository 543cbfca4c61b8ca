"""Pure-JAX CoinRun-like procgen env (BASELINE.json config #5).

The procgen hallmark: every episode's level is PROCEDURALLY GENERATED from
the reset PRNG key — terrain heights (random walk), gaps, spikes, goal
distance and hazard density all differ per episode, so the policy must
generalize across levels instead of memorizing one. Mechanics follow
CoinRun: run right across a side-scrolling platform world, jump gaps and
spikes, touch the coin for +10; falling into a gap or hitting a spike ends
the episode (reward 0).

Per-level DIFFICULTY is part of the distribution (as in procgen, whose
level generator varies section count and hazards): the goal sits
6..62 tiles out (the deliberately easy 6-tile floor — fully protected,
hazard-free levels — is what makes the +10 reachable by exploration at
all) and gap/spike densities scale by a per-level draw. That
spread is what makes the sparse +10 learnable at all — uniform-random play
finishes the short easy levels occasionally (measured: ~37k uniform
episodes on fixed 64-tile max-difficulty levels produced ZERO coins), and
the policy climbs the difficulty distribution from there.

Branch-free jnp platformer physics + scrolling raster render; FRAME_SKIP=1
(procgen-style, no frameskip). Actions (5): 0 noop, 1 left, 2 right, 3 jump,
4 right+jump.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.utils.profiling import ROLLOUT_RENDER, device_scope

num_actions = 5
obs_shape = (84, 84)

LEVEL_LEN = 64        # tiles
MAX_HEIGHT = 5.0      # terrain height in tiles
GAP_P = 0.12          # per-tile gap probability
SPIKE_P = 0.10        # per-tile spike probability (on ground tiles)
GRAVITY = 0.02
JUMP_V = 0.22
RUN_V = 0.12          # tiles per tick
COIN_REWARD = 10.0
MAX_T = 1000
FRAME_SKIP = 1

VIEW_TILES = 12.0     # horizontal tiles visible
VIEW_H_TILES = 8.0    # vertical tiles visible


class State(NamedTuple):
    xy: jax.Array        # [2] (x tiles, y tiles above ground-0)
    vy: jax.Array        # [] vertical velocity
    heights: jax.Array   # [LEVEL_LEN] terrain height (0 = gap)
    spikes: jax.Array    # [LEVEL_LEN] bool
    goal: jax.Array      # [] float32 coin tile (6..LEVEL_LEN-2)
    t: jax.Array         # [] int32


def _gen_level(key: jax.Array):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    # per-level difficulty: goal distance and hazard density both vary
    goal = jax.random.randint(k4, (), 6, LEVEL_LEN - 1).astype(jnp.float32)
    diff = jax.random.uniform(k5, (), minval=0.0, maxval=1.0)
    steps = jax.random.randint(k1, (LEVEL_LEN,), -1, 2)  # -1/0/+1 walk
    heights = jnp.clip(2.0 + jnp.cumsum(steps).astype(jnp.float32), 1.0, MAX_HEIGHT)
    gaps = jax.random.bernoulli(k2, GAP_P * diff, (LEVEL_LEN,))
    # spawn platform and everything from the coin platform on stays solid;
    # no double gaps
    idx = jnp.arange(LEVEL_LEN)
    protected = (idx < 4) | (idx.astype(jnp.float32) >= goal - 2.0)
    gaps = gaps & ~protected & ~jnp.roll(gaps, 1)
    heights = jnp.where(gaps, 0.0, heights)
    spikes = (
        jax.random.bernoulli(k3, SPIKE_P * diff, (LEVEL_LEN,))
        & ~gaps
        & ~protected
        & ~jnp.roll(gaps, 1)
        & ~jnp.roll(gaps, -1)
    )
    return heights, spikes, goal


def reset(key: jax.Array) -> State:
    heights, spikes, goal = _gen_level(key)
    return State(
        xy=jnp.array([1.5, heights[1]]),
        vy=jnp.float32(0.0),
        heights=heights,
        spikes=spikes,
        goal=goal,
        t=jnp.int32(0),
    )


def _ground_at(heights: jax.Array, x: jax.Array) -> jax.Array:
    return heights[jnp.clip(x.astype(jnp.int32), 0, LEVEL_LEN - 1)]


def step(state: State, action: jax.Array, key: jax.Array):
    left = action == 1
    right = (action == 2) | (action == 4)
    jump = (action == 3) | (action == 4)

    x, y = state.xy[0], state.xy[1]
    ground = _ground_at(state.heights, x)
    grounded = (y <= ground + 1e-4) & (ground > 0)

    vx = jnp.where(right, RUN_V, 0.0) - jnp.where(left, RUN_V, 0.0)
    vy = jnp.where(grounded & jump, JUMP_V, state.vy - GRAVITY)
    vy = jnp.where(grounded & ~jump, jnp.maximum(vy, 0.0), vy)

    new_x = jnp.clip(x + vx, 0.5, LEVEL_LEN - 0.5)
    new_ground = _ground_at(state.heights, new_x)
    new_y = y + vy
    # land on terrain (only when falling onto it)
    landing = (vy <= 0) & (new_y <= new_ground) & (new_ground > 0)
    new_y = jnp.where(landing, new_ground, new_y)
    vy = jnp.where(landing, 0.0, vy)
    # can't run through a wall higher than current altitude: stay put
    blocked = (new_ground > y + 0.51) & (new_ground > 0)
    new_x = jnp.where(blocked, x, new_x)
    new_ground = _ground_at(state.heights, new_x)

    # deaths: fell into a gap below zero, or touched a spike while grounded
    fell = new_y < -0.5
    on_spike = (
        state.spikes[jnp.clip(new_x.astype(jnp.int32), 0, LEVEL_LEN - 1)]
        & (new_y <= new_ground + 0.1)
    )
    # win: reach this level's coin platform
    won = new_x >= state.goal - 0.5
    reward = jnp.where(won, COIN_REWARD, 0.0)

    t = state.t + 1
    done = fell | on_spike | won | (t >= MAX_T)

    new_state = State(
        xy=jnp.stack([new_x, new_y]),
        vy=vy,
        heights=state.heights,
        spikes=state.spikes,
        goal=state.goal,
        t=t,
    )
    fresh = reset(key)  # NEW procedurally generated level every episode
    new_state = jax.tree_util.tree_map(
        lambda new, old: jnp.where(done, new, old), fresh, new_state
    )
    return new_state, render(new_state), reward, done


@device_scope(ROLLOUT_RENDER)
def render(state: State) -> jax.Array:
    """Scrolling viewport centered on the agent."""
    h, w = obs_shape
    x0 = state.xy[0] - VIEW_TILES / 2
    # world coords of each pixel
    wx = x0 + (jnp.arange(w, dtype=jnp.float32) + 0.5) * (VIEW_TILES / w)  # [W]
    wy = (VIEW_H_TILES - (jnp.arange(h, dtype=jnp.float32) + 0.5) * (VIEW_H_TILES / h))  # [H] top-down

    tile = jnp.clip(wx.astype(jnp.int32), 0, LEVEL_LEN - 1)
    col_h = state.heights[tile]          # [W]
    col_spike = state.spikes[tile]       # [W]

    ground_px = wy[:, None] <= col_h[None, :]
    frame = ground_px.astype(jnp.uint8) * 110
    spike_px = ground_px & col_spike[None, :] & (wy[:, None] > col_h[None, :] - 0.6)
    frame = jnp.maximum(frame, spike_px.astype(jnp.uint8) * 180)

    # coin at this level's goal platform (one-hot height lookup — no
    # dynamic scalar gather, per the envs/jaxenv authoring rule)
    coin_x = state.goal
    goal_oh = (jnp.arange(LEVEL_LEN).astype(jnp.float32) == coin_x)
    coin_y = jnp.sum(state.heights * goal_oh) + 0.6
    coin = (jnp.abs(wx[None, :] - coin_x) <= 0.4) & (
        jnp.abs(wy[:, None] - coin_y) <= 0.4
    )
    frame = jnp.maximum(frame, coin.astype(jnp.uint8) * 220)

    # agent
    agent = (jnp.abs(wx[None, :] - state.xy[0]) <= 0.35) & (
        jnp.abs(wy[:, None] - (state.xy[1] + 0.45)) <= 0.45
    )
    frame = jnp.maximum(frame, agent.astype(jnp.uint8) * 255)
    return frame
