"""Pure-JAX Seaquest-like env (Atari-4 set, BASELINE.json config #3).

Simplified-but-faithful Seaquest mechanics: the submarine moves in 2D under
water, enemy fish stream across in lanes, torpedoes destroy them for points,
and an oxygen meter forces periodic surfacing — the core control/credit
structure of ALE Seaquest (dive, shoot, manage oxygen) without the sprite
minutiae. Branch-free jnp throughout; FRAME_SKIP=4 agent steps.

Actions (6, ALE-minimal-like): 0 noop, 1 fire, 2 up, 3 down, 4 left, 5 right.
Reward: +20 per fish destroyed (ALE's base fish value), oxygen depletion
death / fish collision costs a life; 3 lives per episode.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.utils.profiling import ROLLOUT_RENDER, device_scope

num_actions = 6
obs_shape = (84, 84)

N_LANES = 4           # enemy lanes at fixed depths
LANE_Y = jnp.array([0.35, 0.5, 0.65, 0.8])
SURFACE_Y = 0.15      # above this = surfacing (refills oxygen)
SUB_SPEED = 0.03
FISH_SPEED = 0.02
TORP_SPEED = 0.08
SUB_R = 0.03          # collision half-extent
FISH_R = 0.025
OXY_MAX = 200.0       # substeps of oxygen
OXY_SURFACE_REFILL = 8.0
LIVES = 3
FISH_POINTS = 20.0
FRAME_SKIP = 4
MAX_T = 5000


class State(NamedTuple):
    sub_xy: jax.Array      # [2]
    fish_x: jax.Array      # [N_LANES] x position of the lane's fish
    fish_dir: jax.Array    # [N_LANES] -1/+1
    fish_alive: jax.Array  # [N_LANES] bool
    torp_xy: jax.Array     # [2] torpedo position
    torp_dir: jax.Array    # [] -1/+1 (fires horizontally, sub's facing)
    torp_live: jax.Array   # [] bool
    facing: jax.Array      # [] -1/+1 last horizontal direction
    oxygen: jax.Array      # [] float
    lives: jax.Array       # [] int32
    t: jax.Array           # [] int32


def reset(key: jax.Array) -> State:
    k1, k2 = jax.random.split(key)
    return State(
        sub_xy=jnp.array([0.5, 0.5]),
        fish_x=jax.random.uniform(k1, (N_LANES,)),
        fish_dir=jnp.where(jax.random.bernoulli(k2, 0.5, (N_LANES,)), 1.0, -1.0),
        fish_alive=jnp.ones(N_LANES, bool),
        torp_xy=jnp.zeros(2),
        torp_dir=jnp.float32(1.0),
        torp_live=jnp.bool_(False),
        facing=jnp.float32(1.0),
        oxygen=jnp.float32(OXY_MAX),
        lives=jnp.int32(LIVES),
        t=jnp.int32(0),
    )


def _substep(state: State, action: jax.Array, key: jax.Array) -> Tuple[State, jax.Array, jax.Array]:
    up = action == 2
    down = action == 3
    left = action == 4
    right = action == 5
    fire = action == 1

    dx = jnp.where(right, 1.0, 0.0) - jnp.where(left, 1.0, 0.0)
    dy = jnp.where(down, 1.0, 0.0) - jnp.where(up, 1.0, 0.0)
    facing = jnp.where(dx != 0, jnp.sign(dx), state.facing)
    sub = jnp.stack(
        [
            jnp.clip(state.sub_xy[0] + dx * SUB_SPEED, 0.05, 0.95),
            jnp.clip(state.sub_xy[1] + dy * SUB_SPEED, 0.08, 0.92),
        ]
    )

    # fish advance; respawn (alive again, random-ish x via key) when off-screen
    fish_x = state.fish_x + state.fish_dir * FISH_SPEED
    off = (fish_x < -0.05) | (fish_x > 1.05)
    respawn_x = jax.random.uniform(key, (N_LANES,))
    fish_x = jnp.where(off, jnp.where(state.fish_dir > 0, -0.05, 1.05), fish_x)
    fish_alive = state.fish_alive | off  # dead fish respawn on wraparound
    # keep deterministic-ish motion; respawn_x reserved for variety on kill
    del respawn_x

    # torpedo
    torp_live = state.torp_live | (fire & ~state.torp_live)
    torp_xy = jnp.where(
        state.torp_live,
        state.torp_xy.at[0].add(state.torp_dir * TORP_SPEED),
        jnp.where(fire, jnp.stack([sub[0], sub[1]]), state.torp_xy),
    )
    torp_dir = jnp.where(state.torp_live, state.torp_dir, facing)
    torp_live = torp_live & (torp_xy[0] > 0.0) & (torp_xy[0] < 1.0)

    # torpedo hits fish (same lane band, x overlap)
    hit = (
        fish_alive
        & torp_live
        & (jnp.abs(fish_x - torp_xy[0]) < FISH_R + 0.02)
        & (jnp.abs(LANE_Y - torp_xy[1]) < 0.04)
    )
    reward = jnp.sum(hit) * FISH_POINTS
    fish_alive = fish_alive & ~hit
    torp_live = torp_live & ~hit.any()

    # fish hits sub
    collide = (
        fish_alive
        & (jnp.abs(fish_x - sub[0]) < FISH_R + SUB_R)
        & (jnp.abs(LANE_Y - sub[1]) < FISH_R + SUB_R)
    ).any()

    # oxygen
    surfaced = sub[1] <= SURFACE_Y
    oxygen = jnp.where(
        surfaced,
        jnp.minimum(state.oxygen + OXY_SURFACE_REFILL, OXY_MAX),
        state.oxygen - 1.0,
    )
    suffocate = oxygen <= 0.0

    lost_life = collide | suffocate
    lives = state.lives - lost_life.astype(jnp.int32)
    # life reset: sub to center, oxygen refilled
    sub = jnp.where(lost_life, jnp.array([0.5, 0.5]), sub)
    oxygen = jnp.where(lost_life, OXY_MAX, oxygen)

    new_state = State(
        sub_xy=sub,
        fish_x=fish_x,
        fish_dir=state.fish_dir,
        fish_alive=fish_alive,
        torp_xy=torp_xy,
        torp_dir=torp_dir,
        torp_live=torp_live,
        facing=facing,
        oxygen=oxygen,
        lives=lives,
        t=state.t,
    )
    return new_state, reward, lost_life


def step(state: State, action: jax.Array, key: jax.Array):
    keys = jax.random.split(key, FRAME_SKIP + 1)
    zero = state.sub_xy[0] * 0.0

    def body(carry, k):
        st, acc = carry
        st, r, _ = _substep(st, action, k)
        return (st, acc + r), None

    (state, reward), _ = jax.lax.scan(body, (state, zero), keys[:FRAME_SKIP])
    state = state._replace(t=state.t + 1)
    done = (state.lives <= 0) | (state.t >= MAX_T)
    fresh = reset(keys[FRAME_SKIP])
    state = jax.tree_util.tree_map(
        lambda new, old: jnp.where(done, new, old), fresh, state
    )
    return state, render(state), reward, done


@device_scope(ROLLOUT_RENDER)
def render(state: State) -> jax.Array:
    h, w = obs_shape
    Y = ((jnp.arange(h, dtype=jnp.float32) + 0.5) / h)[:, None]
    X = ((jnp.arange(w, dtype=jnp.float32) + 0.5) / w)[None, :]

    def rect(cx, cy, hw_, hh_):
        return (jnp.abs(X - cx) <= hw_) & (jnp.abs(Y - cy) <= hh_)

    frame = jnp.zeros((h, w), jnp.uint8)
    # surface line
    frame = jnp.maximum(frame, (jnp.abs(Y - SURFACE_Y) < 0.012).astype(jnp.uint8) * 80)
    # oxygen bar along the top, width proportional to oxygen
    frac = jnp.clip(state.oxygen / OXY_MAX, 0.0, 1.0)
    frame = jnp.maximum(
        frame, ((Y < 0.04) & (X < frac)).astype(jnp.uint8) * 140
    )
    # fish per lane
    fish = jnp.zeros((h, w), bool)
    for i in range(N_LANES):
        fish = fish | (
            rect(state.fish_x[i], LANE_Y[i], FISH_R, FISH_R)
            & state.fish_alive[i]
        )
    frame = jnp.maximum(frame, fish.astype(jnp.uint8) * 180)
    # torpedo
    frame = jnp.maximum(
        frame,
        (rect(state.torp_xy[0], state.torp_xy[1], 0.015, 0.008) & state.torp_live).astype(jnp.uint8) * 220,
    )
    # submarine
    frame = jnp.maximum(
        frame, rect(state.sub_xy[0], state.sub_xy[1], SUB_R, SUB_R).astype(jnp.uint8) * 255
    )
    return frame
