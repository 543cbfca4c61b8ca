"""Pure-JAX Breakout: ALE-compatible reward structure on branch-free physics.

Atari-Breakout parity choices (so BASELINE.md's "Breakout to ~300 mean score"
transfers): 6 rows x 18 columns of bricks, row-dependent points
(bottom-up 1,1,4,4,7,7 like ALE), 5 lives, losing the ball costs a life,
clearing the wall re-fills it (ALE continues to a second wall; score caps
around 864), done when lives run out. Action set: {0}=noop {1}=fire
{2}=right {3}=left (ALE Breakout minimal set is 4 actions).

Brick state is a [6, 18] bool bitmap inside the env state — collision and
scoring are pure gather/scatter ops, vmap-friendly.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.utils.profiling import ROLLOUT_RENDER, device_scope

num_actions = 4
obs_shape = (84, 84)

ROWS, COLS = 6, 18
BRICK_TOP = 0.15     # y of the top brick row
BRICK_H = 0.03
BRICK_REGION_H = ROWS * BRICK_H
PADDLE_Y = 0.92
PADDLE_H = 0.02
PADDLE_W = 0.08
BALL_R = 0.012
PADDLE_SPEED = 0.04
BALL_SPEED = 0.035
LIVES = 5
FRAME_SKIP = 4
MAX_T = 10000  # safety cap on episode length (agent steps)

# ALE row scores, top row first (top rows worth most)
ROW_POINTS = jnp.array([7.0, 7.0, 4.0, 4.0, 1.0, 1.0])


class State(NamedTuple):
    ball_xy: jax.Array   # [2]
    ball_v: jax.Array    # [2]
    paddle_x: jax.Array  # []
    bricks: jax.Array    # [ROWS, COLS] bool
    lives: jax.Array     # [] int32
    in_play: jax.Array   # [] bool (ball launched?)
    t: jax.Array         # [] int32


def reset(key: jax.Array) -> State:
    del key
    return State(
        ball_xy=jnp.array([0.5, PADDLE_Y - 0.05]),
        ball_v=jnp.zeros(2),
        paddle_x=jnp.float32(0.5),
        bricks=jnp.ones((ROWS, COLS), bool),
        lives=jnp.int32(LIVES),
        in_play=jnp.bool_(False),
        t=jnp.int32(0),
    )


def _launch(key: jax.Array) -> jax.Array:
    angle = jax.random.uniform(key, (), minval=0.25 * jnp.pi, maxval=0.75 * jnp.pi)
    return jnp.stack([BALL_SPEED * jnp.cos(angle), -BALL_SPEED * jnp.sin(angle)])


def _brick_index(xy: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(row, col, inside) for a ball position."""
    row = jnp.floor((xy[1] - BRICK_TOP) / BRICK_H).astype(jnp.int32)
    col = jnp.floor(xy[0] * COLS).astype(jnp.int32)
    inside = (row >= 0) & (row < ROWS) & (col >= 0) & (col < COLS)
    return jnp.clip(row, 0, ROWS - 1), jnp.clip(col, 0, COLS - 1), inside


def _substep(state: State, move: jax.Array, fire: jax.Array, key: jax.Array):
    paddle_x = jnp.clip(
        state.paddle_x + move * PADDLE_SPEED, PADDLE_W / 2, 1 - PADDLE_W / 2
    )

    # serve: ball rides the paddle until fire
    launch_v = _launch(key)
    v = jnp.where(state.in_play, state.ball_v, jnp.where(fire, launch_v, jnp.zeros(2)))
    in_play = state.in_play | fire
    xy = jnp.where(
        in_play,
        state.ball_xy + v,
        jnp.stack([paddle_x, PADDLE_Y - 0.05]),
    )

    # walls
    hit_side = (xy[0] < BALL_R) | (xy[0] > 1 - BALL_R)
    v = v.at[0].set(jnp.where(hit_side, -v[0], v[0]))
    xy = xy.at[0].set(jnp.clip(xy[0], BALL_R, 1 - BALL_R))
    hit_top = xy[1] < BALL_R
    v = v.at[1].set(jnp.where(hit_top, -v[1], v[1]))
    xy = xy.at[1].set(jnp.clip(xy[1], BALL_R, 1.0))

    # paddle
    aligned = jnp.abs(xy[0] - paddle_x) <= PADDLE_W / 2 + BALL_R
    hit_paddle = (xy[1] >= PADDLE_Y - PADDLE_H) & (v[1] > 0) & aligned & in_play
    offset = (xy[0] - paddle_x) / (PADDLE_W / 2)
    v = jnp.where(
        hit_paddle,
        jnp.stack([BALL_SPEED * offset, -jnp.abs(v[1])]),
        v,
    )
    xy = xy.at[1].set(jnp.where(hit_paddle, PADDLE_Y - PADDLE_H - BALL_R, xy[1]))

    # bricks
    row, col, inside = _brick_index(xy)
    brick_alive = state.bricks[row, col] & inside & in_play
    reward = jnp.where(brick_alive, ROW_POINTS[row], 0.0)
    bricks = state.bricks.at[row, col].set(
        jnp.where(brick_alive, False, state.bricks[row, col])
    )
    # reflect AND expel the ball from the cell, else it drills through the
    # wall destroying a brick per substep
    from_below = v[1] < 0
    expel_y = jnp.where(
        from_below,
        BRICK_TOP + (row + 1).astype(jnp.float32) * BRICK_H + BALL_R,
        BRICK_TOP + row.astype(jnp.float32) * BRICK_H - BALL_R,
    )
    xy = xy.at[1].set(jnp.where(brick_alive, expel_y, xy[1]))
    v = v.at[1].set(jnp.where(brick_alive, -v[1], v[1]))

    # wall cleared -> refill (ALE second wall)
    cleared = ~bricks.any()
    bricks = jnp.where(cleared, jnp.ones_like(bricks), bricks)

    # ball lost
    lost = xy[1] >= 1.0 - 1e-6
    lives = state.lives - lost.astype(jnp.int32)
    in_play = in_play & ~lost
    xy = jnp.where(lost, jnp.stack([paddle_x, PADDLE_Y - 0.05]), xy)
    v = jnp.where(lost, jnp.zeros(2), v)

    return (
        State(
            ball_xy=xy,
            ball_v=v,
            paddle_x=paddle_x,
            bricks=bricks,
            lives=lives,
            in_play=in_play,
            t=state.t,
        ),
        reward,
    )


def step(state: State, action: jax.Array, key: jax.Array):
    """One agent step = FRAME_SKIP substeps. Auto-restarts when lives hit 0."""
    move = jnp.where(action == 2, 1.0, jnp.where(action == 3, -1.0, 0.0))
    fire = action == 1
    keys = jax.random.split(key, FRAME_SKIP + 1)

    def body(carry, k):
        st, acc = carry
        st, r = _substep(st, move, fire, k)
        return (st, acc + r), None

    # accumulator derived from state so it inherits the same sharding/varying
    # axes as the carry under shard_map (a literal 0.0 would be invariant)
    zero = state.ball_xy[0] * 0.0
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
    ys = (jnp.arange(h, dtype=jnp.float32) + 0.5) / h
    xs = (jnp.arange(w, dtype=jnp.float32) + 0.5) / w
    Y = ys[:, None]
    X = xs[None, :]

    # bricks: map each pixel to its (row, col); lit if alive and in region
    prow = jnp.floor((Y - BRICK_TOP) / BRICK_H).astype(jnp.int32)
    pcol = jnp.floor(X * COLS).astype(jnp.int32)
    in_region = (prow >= 0) & (prow < ROWS) & (pcol >= 0) & (pcol < COLS)
    alive = state.bricks[
        jnp.clip(prow, 0, ROWS - 1), jnp.clip(pcol, 0, COLS - 1)
    ]
    brick_px = in_region & alive

    ball = (jnp.abs(X - state.ball_xy[0]) <= BALL_R) & (
        jnp.abs(Y - state.ball_xy[1]) <= BALL_R
    )
    paddle = (jnp.abs(X - state.paddle_x) <= PADDLE_W / 2) & (
        jnp.abs(Y - PADDLE_Y) <= PADDLE_H
    )
    frame = (ball | paddle).astype(jnp.uint8) * 255
    frame = jnp.maximum(frame, brick_px.astype(jnp.uint8) * 180)
    wall = Y < 0.02
    return jnp.maximum(frame, wall.astype(jnp.uint8) * 80)
