"""Pure-JAX Q*bert-like env (Atari-4 set, BASELINE.json config #3).

Core Q*bert structure: a 6-row pyramid of 21 cubes; hopping onto a cube
flips its color (+25 points the first time, like ALE); flipping every cube
clears the board (+bonus, board refills); hopping off the pyramid or meeting
the bouncing enemy ball costs a life. Branch-free jnp; FRAME_SKIP agent
steps are single hops (Q*bert's hop IS the time quantum, so FRAME_SKIP=1
here — the ALE frameskip corresponds to the hop animation).

Actions (5): 0 noop, 1 up-right, 2 down-right, 3 down-left, 4 up-left
(diagonal hops on the pyramid lattice).

Cube addressing: row r in [0,6), position c in [0,r], flattened index
r*(r+1)/2 + c (21 cubes total).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.utils.profiling import ROLLOUT_RENDER, device_scope

num_actions = 5
obs_shape = (84, 84)

ROWS = 6
N_CUBES = ROWS * (ROWS + 1) // 2  # 21
CUBE_POINTS = 25.0
CLEAR_BONUS = 100.0
LIVES = 3
FRAME_SKIP = 1
MAX_T = 2000

_ROW_OF = jnp.array([r for r in range(ROWS) for _ in range(r + 1)])
_COL_OF = jnp.array([c for r in range(ROWS) for c in range(r + 1)])


def _flat(row: jax.Array, col: jax.Array) -> jax.Array:
    return (row * (row + 1)) // 2 + col


class State(NamedTuple):
    pos: jax.Array      # [2] (row, col) of the agent, int32
    flipped: jax.Array  # [N_CUBES] bool
    ball: jax.Array     # [2] (row, col) of the enemy ball, int32
    ball_live: jax.Array  # [] bool
    lives: jax.Array    # [] int32
    boards: jax.Array   # [] int32 boards cleared (difficulty counter)
    t: jax.Array        # [] int32


def reset(key: jax.Array) -> State:
    del key
    return State(
        pos=jnp.array([0, 0], jnp.int32),
        flipped=jnp.zeros(N_CUBES, bool),
        ball=jnp.array([1, 0], jnp.int32),
        ball_live=jnp.bool_(False),
        lives=jnp.int32(LIVES),
        boards=jnp.int32(0),
        t=jnp.int32(0),
    )


def _hop(pos: jax.Array, action: jax.Array) -> jax.Array:
    """Diagonal lattice moves: rows grow downward; (dr, dc) per action."""
    dr = jnp.where((action == 2) | (action == 3), 1, jnp.where((action == 1) | (action == 4), -1, 0))
    dc = jnp.where(action == 2, 1, jnp.where((action == 4) | (action == 3), 0, jnp.where(action == 1, 0, 0)))
    # up-right (1): (-1, 0); down-right (2): (+1, +1); down-left (3): (+1, 0);
    # up-left (4): (-1, -1)
    dc = jnp.where(action == 1, 0, dc)
    dc = jnp.where(action == 4, -1, dc)
    return pos + jnp.stack([dr, dc])


def step(state: State, action: jax.Array, key: jax.Array):
    k_ball, k_reset = jax.random.split(key)

    new_pos = _hop(state.pos, action)
    moved = action != 0
    row, col = new_pos[0], new_pos[1]
    on_board = (row >= 0) & (row < ROWS) & (col >= 0) & (col <= row)
    fell = moved & ~on_board
    pos = jnp.where(on_board, new_pos, state.pos)

    # flip the landed cube
    idx = _flat(pos[0], pos[1])
    newly = moved & on_board & ~state.flipped[idx]
    flipped = state.flipped.at[idx].set(state.flipped[idx] | (moved & on_board))
    reward = jnp.where(newly, CUBE_POINTS, 0.0)

    # board clear
    cleared = flipped.all()
    reward = reward + jnp.where(cleared, CLEAR_BONUS, 0.0)
    flipped = jnp.where(cleared, jnp.zeros_like(flipped), flipped)
    boards = state.boards + cleared.astype(jnp.int32)

    # enemy ball: spawns at the top, hops downward randomly; falls off bottom
    spawn = ~state.ball_live
    bdc = jax.random.bernoulli(k_ball, 0.5).astype(jnp.int32)
    ball = jnp.where(
        spawn,
        jnp.array([1, 0], jnp.int32),
        state.ball + jnp.stack([jnp.int32(1), bdc]),
    )
    ball_live = ball[0] < ROWS
    ball = jnp.where(ball_live, ball, jnp.array([1, 0], jnp.int32))
    # clamp col onto the row
    ball = ball.at[1].set(jnp.clip(ball[1], 0, ball[0]))

    caught = ball_live & (ball == pos).all()
    lost_life = fell | caught
    lives = state.lives - lost_life.astype(jnp.int32)
    pos = jnp.where(lost_life, jnp.array([0, 0], jnp.int32), pos)

    t = state.t + 1
    done = (lives <= 0) | (t >= MAX_T)
    new_state = State(
        pos=pos,
        flipped=flipped,
        ball=ball,
        ball_live=ball_live | spawn,
        lives=lives,
        boards=boards,
        t=t,
    )
    fresh = reset(k_reset)
    new_state = jax.tree_util.tree_map(
        lambda new, old: jnp.where(done, new, old), fresh, new_state
    )
    return new_state, render(new_state), reward, done


@device_scope(ROLLOUT_RENDER)
def render(state: State) -> jax.Array:
    """Isometric-ish pyramid: cube (r,c) centered at
    x = 0.5 + (c - r/2) * 0.13, y = 0.18 + r * 0.13."""
    h, w = obs_shape
    Y = ((jnp.arange(h, dtype=jnp.float32) + 0.5) / h)[:, None]
    X = ((jnp.arange(w, dtype=jnp.float32) + 0.5) / w)[None, :]

    cx = 0.5 + (_COL_OF.astype(jnp.float32) - _ROW_OF.astype(jnp.float32) / 2) * 0.13
    cy = 0.18 + _ROW_OF.astype(jnp.float32) * 0.13

    # cubes: dim if unflipped, bright if flipped  [N,H,W] -> max over N
    inx = jnp.abs(X[None] - cx[:, None, None]) <= 0.05
    iny = jnp.abs(Y[None] - cy[:, None, None]) <= 0.045
    cube_px = inx & iny
    shade = jnp.where(state.flipped, 200, 100).astype(jnp.uint8)
    frame = jnp.max(cube_px * shade[:, None, None], axis=0).astype(jnp.uint8)

    def at(pos):
        px = 0.5 + (pos[1].astype(jnp.float32) - pos[0].astype(jnp.float32) / 2) * 0.13
        py = 0.18 + pos[0].astype(jnp.float32) * 0.13 - 0.05
        return px, py

    ax, ay = at(state.pos)
    agent = (jnp.abs(X - ax) <= 0.025) & (jnp.abs(Y - ay) <= 0.025)
    frame = jnp.maximum(frame, agent.astype(jnp.uint8) * 255)
    bx, by = at(state.ball)
    ball = (jnp.abs(X - bx) <= 0.02) & (jnp.abs(Y - by) <= 0.02) & state.ball_live
    frame = jnp.maximum(frame, ball.astype(jnp.uint8) * 160)
    return frame
