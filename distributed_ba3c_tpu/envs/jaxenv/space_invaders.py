"""Pure-JAX Space Invaders: ALE-compatible reward structure, branch-free.

ALE parity choices (reference game set, BASELINE.md): 6x6 alien grid
marching horizontally and descending a row at each edge hit; row-dependent
points (top row worth most: 30,25,20,15,10,5 — ALE's 5..30 bottom-up);
one player shot in flight at a time; alien bombs; 3 lives; episode ends
when lives run out or the fleet lands. Clearing the fleet spawns a fresh
wave one row lower-start (score keeps accumulating, as in ALE).
Action set: {0}=noop {1}=fire {2}=right {3}=left {4}=right+fire
{5}=left+fire (ALE SpaceInvaders minimal set is 6 actions).

All collision logic is bitmap gather/scatter over the [6, 6] alien grid —
vmap-friendly, no data-dependent branches.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.utils.profiling import ROLLOUT_RENDER, device_scope

num_actions = 6
obs_shape = (84, 84)

ROWS, COLS = 6, 6
ALIEN_W = 0.07       # half-extent of an alien cell hitbox (x)
ALIEN_H = 0.03       # half-extent (y)
GRID_DX = 0.11       # horizontal spacing between alien columns
GRID_DY = 0.07       # vertical spacing between alien rows
MARCH_SPEED = 0.004
DESCEND = 0.05
PLAYER_Y = 0.93
PLAYER_W = 0.05
PLAYER_SPEED = 0.03
SHOT_SPEED = 0.05
BOMB_SPEED = 0.025
BOMB_P = 0.06        # per-substep probability a bomb drops
N_BOMBS = 3
LIVES = 3
FRAME_SKIP = 4
MAX_T = 10000

# points by row, TOP row first (ALE: bottom row 5 ... top row 30)
ROW_POINTS = jnp.array([30.0, 25.0, 20.0, 15.0, 10.0, 5.0])


class State(NamedTuple):
    aliens: jax.Array     # [ROWS, COLS] bool
    origin: jax.Array     # [2] top-left alien center (x, y)
    dir: jax.Array        # [] float32 march direction (+1/-1)
    player_x: jax.Array   # []
    shot: jax.Array       # [2] player shot position
    shot_live: jax.Array  # [] bool
    bombs: jax.Array      # [N_BOMBS, 2]
    bombs_live: jax.Array  # [N_BOMBS] bool
    lives: jax.Array      # [] int32
    t: jax.Array          # [] int32


def reset(key: jax.Array) -> State:
    del key
    return State(
        aliens=jnp.ones((ROWS, COLS), bool),
        origin=jnp.array([0.18, 0.12]),
        dir=jnp.float32(1.0),
        player_x=jnp.float32(0.5),
        shot=jnp.zeros(2),
        shot_live=jnp.bool_(False),
        bombs=jnp.zeros((N_BOMBS, 2)),
        bombs_live=jnp.zeros(N_BOMBS, bool),
        lives=jnp.int32(LIVES),
        t=jnp.int32(0),
    )


def _alien_centers(origin: jax.Array):
    """[ROWS, COLS, 2] world positions of every grid cell."""
    cx = origin[0] + jnp.arange(COLS, dtype=jnp.float32) * GRID_DX
    cy = origin[1] + jnp.arange(ROWS, dtype=jnp.float32) * GRID_DY
    return cx, cy


def _substep(state: State, move: jax.Array, fire: jax.Array, key: jax.Array):
    k_bomb, k_col = jax.random.split(key)
    player_x = jnp.clip(
        state.player_x + move * PLAYER_SPEED, PLAYER_W, 1 - PLAYER_W
    )

    # fleet march: speed scales up as the fleet thins (classic cadence)
    n_alive = jnp.sum(state.aliens)
    speed = MARCH_SPEED * (1.0 + 2.0 * (1.0 - n_alive / (ROWS * COLS)))
    cx, cy = _alien_centers(state.origin)
    col_alive = state.aliens.any(axis=0)
    # extreme live columns decide the edge bounce
    left = jnp.min(jnp.where(col_alive, cx, jnp.inf))
    right = jnp.max(jnp.where(col_alive, cx, -jnp.inf))
    hit_edge = ((right + ALIEN_W >= 0.98) & (state.dir > 0)) | (
        (left - ALIEN_W <= 0.02) & (state.dir < 0)
    )
    new_dir = jnp.where(hit_edge, -state.dir, state.dir)
    origin = state.origin + jnp.where(
        hit_edge, jnp.array([0.0, DESCEND]), jnp.array([1.0, 0.0]) * speed * state.dir
    )

    # player shot: launch if idle and firing; fly upward
    launch = fire & ~state.shot_live
    shot = jnp.where(
        launch, jnp.stack([player_x, PLAYER_Y - 0.03]), state.shot
    )
    shot = shot.at[1].add(jnp.where(state.shot_live | launch, -SHOT_SPEED, 0.0))
    shot_live = (state.shot_live | launch) & (shot[1] > 0.0)

    # shot vs fleet. NO dynamic gathers anywhere in this env: per-env scalar
    # indexing (aliens[row, col], cx[col], .at[slot].set) lowers to
    # pathological batched gathers under vmap inside the fused program
    # (measured 6x whole-step slowdown); the uniform grid makes every lookup
    # pure arithmetic and every update a one-hot mask.
    colf = jnp.round((shot[0] - origin[0]) / GRID_DX)
    rowf = jnp.round((shot[1] - origin[1]) / GRID_DY)
    colf = jnp.clip(colf, 0.0, COLS - 1.0)
    rowf = jnp.clip(rowf, 0.0, ROWS - 1.0)
    cx_near = origin[0] + colf * GRID_DX
    cy_near = origin[1] + rowf * GRID_DY
    in_cell = (
        (jnp.abs(cx_near - shot[0]) <= ALIEN_W)
        & (jnp.abs(cy_near - shot[1]) <= ALIEN_H)
        & shot_live
    )
    row_oh = jnp.arange(ROWS) == rowf.astype(jnp.int32)    # [ROWS]
    col_oh = jnp.arange(COLS) == colf.astype(jnp.int32)    # [COLS]
    cell = row_oh[:, None] & col_oh[None, :]               # [ROWS, COLS]
    hit = in_cell & (state.aliens & cell).any()
    reward = jnp.where(hit, jnp.sum(ROW_POINTS * row_oh), 0.0)
    aliens = state.aliens & ~(cell & hit)
    shot_live = shot_live & ~hit

    # bombs: lowest live alien of a random column may drop one
    bomb_col = jax.random.randint(k_bomb, (), 0, COLS)
    bcol_oh = jnp.arange(COLS) == bomb_col                 # [COLS]
    alien_col = (aliens & bcol_oh[None, :]).any(axis=1)    # [ROWS]
    col_has = alien_col.any()
    low_row = jnp.max(jnp.where(alien_col, jnp.arange(ROWS), -1))
    drop = (
        (jax.random.uniform(k_col) < BOMB_P)
        & col_has
        & ~state.bombs_live.all()
    )
    slot_oh = jnp.arange(N_BOMBS) == jnp.argmin(state.bombs_live)
    new_bomb = jnp.stack(
        [
            origin[0] + bomb_col.astype(jnp.float32) * GRID_DX,
            origin[1] + low_row.astype(jnp.float32) * GRID_DY + ALIEN_H,
        ]
    )
    place = slot_oh & drop
    bombs = jnp.where(place[:, None], new_bomb[None, :], state.bombs)
    bombs_live = state.bombs_live | place
    bombs = bombs.at[:, 1].add(jnp.where(bombs_live, BOMB_SPEED, 0.0))

    # bombs vs player
    hit_player = (
        bombs_live
        & (jnp.abs(bombs[:, 0] - player_x) <= PLAYER_W)
        & (bombs[:, 1] >= PLAYER_Y - 0.02)
    )
    lives = state.lives - jnp.any(hit_player).astype(jnp.int32)
    bombs_live = bombs_live & ~hit_player & (bombs[:, 1] < 1.0)

    # fleet landed -> all lives lost (game over); use the POST-march row
    # positions so an edge-descend triggers this substep, matching the C++
    # mirror's ordering
    _, cy_post = _alien_centers(origin)
    landed = jnp.any(
        aliens & ((cy_post[:, None] + ALIEN_H) >= PLAYER_Y - 0.02)
    )
    lives = jnp.where(landed, 0, lives)

    # wave cleared -> fresh fleet, slightly lower start
    cleared = ~aliens.any()
    aliens = jnp.where(cleared, jnp.ones_like(aliens), aliens)
    origin = jnp.where(cleared, jnp.array([0.18, 0.16]), origin)

    return (
        State(
            aliens=aliens,
            origin=origin,
            dir=new_dir,
            player_x=player_x,
            shot=shot,
            shot_live=shot_live,
            bombs=bombs,
            bombs_live=bombs_live,
            lives=lives,
            t=state.t,
        ),
        reward,
    )


def step(state: State, action: jax.Array, key: jax.Array):
    """One agent step = FRAME_SKIP substeps; auto-restarts on done."""
    move = jnp.where(
        (action == 2) | (action == 4),
        1.0,
        jnp.where((action == 3) | (action == 5), -1.0, 0.0),
    )
    fire = (action == 1) | (action == 4) | (action == 5)
    keys = jax.random.split(key, FRAME_SKIP + 1)

    def body(carry, k):
        st, acc = carry
        st, r = _substep(st, move, fire, k)
        return (st, acc + r), None

    zero = state.player_x * 0.0
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

    cx, cy = _alien_centers(state.origin)
    # gather-free fleet raster: the indices would depend on the MOVING
    # origin (unlike breakout's static brick grid), and dynamic per-env
    # gathers are pathological under vmap — instead separability gives
    # in_alien = rowhit @ aliens @ colhit^T as two tiny matmuls
    rowhit = (jnp.abs(ys[:, None] - cy[None, :]) <= ALIEN_H)   # [h, ROWS]
    colhit = (jnp.abs(xs[:, None] - cx[None, :]) <= ALIEN_W)   # [w, COLS]
    m = rowhit.astype(jnp.float32) @ state.aliens.astype(jnp.float32)
    in_alien = (m @ colhit.astype(jnp.float32).T) > 0.0        # [h, w]

    player = (jnp.abs(X - state.player_x) <= PLAYER_W) & (
        jnp.abs(Y - PLAYER_Y) <= 0.02
    )
    shot = (
        state.shot_live
        & (jnp.abs(X - state.shot[0]) <= 0.006)
        & (jnp.abs(Y - state.shot[1]) <= 0.015)
    )
    bombs = jnp.zeros_like(player)
    for i in range(N_BOMBS):
        bombs = bombs | (
            state.bombs_live[i]
            & (jnp.abs(X - state.bombs[i, 0]) <= 0.006)
            & (jnp.abs(Y - state.bombs[i, 1]) <= 0.015)
        )
    frame = (player | shot).astype(jnp.uint8) * 255
    frame = jnp.maximum(frame, in_alien.astype(jnp.uint8) * 180)
    frame = jnp.maximum(frame, bombs.astype(jnp.uint8) * 120)
    return frame
