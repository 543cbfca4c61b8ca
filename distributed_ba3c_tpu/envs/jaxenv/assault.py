"""Pure-JAX Assault: ALE-compatible reward structure, branch-free physics.

ALE parity choices (reference game set, BASELINE.md): a mothership cruises
the top of the screen spawning attackers that descend in three lanes and
strafe toward the player's turret; the turret moves horizontally and fires
upward. Points: 21 per attacker destroyed, bonus 42 for a direct
mothership hit (ALE Assault scores in 21-point quanta). Sustained fire
overheats the cannon — a heat gauge charges per shot and cooling forces a
firing pause (the game's signature mechanic). 4 lives; an attacker
reaching the turret row or a bomb hit costs one. Action set: {0}=noop
{1}=fire {2}=up(vent heat) {3}=right {4}=left {5}=right+fire
{6}=left+fire (ALE Assault minimal set is 7 actions).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.utils.profiling import ROLLOUT_RENDER, device_scope

num_actions = 7
obs_shape = (84, 84)

N_LANES = 3
LANE_X = jnp.array([0.25, 0.5, 0.75])
MOTHER_Y = 0.08
MOTHER_W = 0.10
MOTHER_SPEED = 0.006
ATTACKER_W = 0.035
ATTACKER_H = 0.025
DESCEND_SPEED = 0.008
STRAFE = 0.006
SPAWN_P = 0.08
PLAYER_Y = 0.93
PLAYER_W = 0.05
PLAYER_SPEED = 0.03
SHOT_SPEED = 0.06
BOMB_SPEED = 0.02
BOMB_P = 0.04
HEAT_PER_SHOT = 0.45   # a few consecutive shot-cycles overheat
COOL = 0.015           # slower than the ~0.45/15-substep firing duty cycle
VENT_COOL = 0.12
LIVES = 4
FRAME_SKIP = 4
MAX_T = 10000

ATTACKER_POINTS = 21.0
MOTHER_POINTS = 42.0


class State(NamedTuple):
    mother_x: jax.Array     # []
    mother_dir: jax.Array   # []
    att_pos: jax.Array      # [N_LANES, 2] attacker positions
    att_live: jax.Array     # [N_LANES] bool
    bomb: jax.Array         # [2]
    bomb_live: jax.Array    # [] bool
    player_x: jax.Array     # []
    shot: jax.Array         # [2]
    shot_live: jax.Array    # [] bool
    heat: jax.Array         # [] float32 in [0, 1+]; >=1 means jammed
    jammed: jax.Array      # [] bool
    lives: jax.Array        # [] int32
    t: jax.Array            # [] int32


def reset(key: jax.Array) -> State:
    del key
    return State(
        mother_x=jnp.float32(0.5),
        mother_dir=jnp.float32(1.0),
        att_pos=jnp.stack([LANE_X, jnp.full((N_LANES,), MOTHER_Y + 0.05)], -1),
        att_live=jnp.zeros(N_LANES, bool),
        bomb=jnp.zeros(2),
        bomb_live=jnp.bool_(False),
        player_x=jnp.float32(0.5),
        shot=jnp.zeros(2),
        shot_live=jnp.bool_(False),
        heat=jnp.float32(0.0),
        jammed=jnp.bool_(False),
        lives=jnp.int32(LIVES),
        t=jnp.int32(0),
    )


def _substep(state: State, move, fire, vent, key: jax.Array):
    k_spawn, k_lane, k_bomb = jax.random.split(key, 3)
    player_x = jnp.clip(
        state.player_x + move * PLAYER_SPEED, PLAYER_W, 1 - PLAYER_W
    )

    # mothership patrol
    mother_x = state.mother_x + state.mother_dir * MOTHER_SPEED
    bounce = (mother_x > 1 - MOTHER_W) | (mother_x < MOTHER_W)
    mother_dir = jnp.where(bounce, -state.mother_dir, state.mother_dir)
    mother_x = jnp.clip(mother_x, MOTHER_W, 1 - MOTHER_W)

    # spawn an attacker in a random free lane, dropping from the mothership
    # (one-hot lane mask, not att_live[lane]/.at[lane]: per-env scalar
    # gathers/scatters are pathological under vmap — see package rule)
    lane = jax.random.randint(k_lane, (), 0, N_LANES)
    lane_oh = jnp.arange(N_LANES) == lane
    can = ~jnp.any(state.att_live & lane_oh)
    spawn = (jax.random.uniform(k_spawn) < SPAWN_P) & can
    spawn_oh = lane_oh & spawn
    att_pos = jnp.where(
        spawn_oh[:, None],
        jnp.stack([mother_x, MOTHER_Y + 0.05])[None, :],
        state.att_pos,
    )
    att_live = state.att_live | spawn_oh

    # attackers descend and strafe toward the player
    dx = jnp.sign(player_x - att_pos[:, 0]) * STRAFE
    att_pos = att_pos.at[:, 0].add(jnp.where(att_live, dx, 0.0))
    att_pos = att_pos.at[:, 1].add(jnp.where(att_live, DESCEND_SPEED, 0.0))

    # cannon heat: venting (action up) cools fast; a jam persists until the
    # gauge cools below 0.3, and trips when a shot pushes it to the cap
    heat = jnp.maximum(
        state.heat - jnp.where(vent, VENT_COOL, COOL), 0.0
    )
    jammed = state.jammed & (heat > 0.3)
    can_fire = fire & ~state.shot_live & ~jammed
    heat = heat + jnp.where(can_fire, HEAT_PER_SHOT, 0.0)
    jammed = jammed | (heat >= 1.0)
    heat = jnp.minimum(heat, 1.0)

    shot = jnp.where(
        can_fire, jnp.stack([player_x, PLAYER_Y - 0.03]), state.shot
    )
    shot = shot.at[1].add(
        jnp.where(state.shot_live | can_fire, -SHOT_SPEED, 0.0)
    )
    shot_live = (state.shot_live | can_fire) & (shot[1] > 0.0)

    # shot vs attackers
    hit_att = (
        att_live
        & shot_live
        & (jnp.abs(att_pos[:, 0] - shot[0]) <= ATTACKER_W)
        & (jnp.abs(att_pos[:, 1] - shot[1]) <= ATTACKER_H)
    )
    reward = jnp.sum(hit_att) * ATTACKER_POINTS
    att_live = att_live & ~hit_att
    shot_live = shot_live & ~jnp.any(hit_att)

    # shot vs mothership
    hit_mom = (
        shot_live
        & (jnp.abs(mother_x - shot[0]) <= MOTHER_W)
        & (shot[1] <= MOTHER_Y + 0.02)
    )
    reward = reward + jnp.where(hit_mom, MOTHER_POINTS, 0.0)
    shot_live = shot_live & ~hit_mom

    # bombs from a random live attacker (one-hot contraction, not
    # att_pos[bsrc]: per-env scalar gathers are pathological under vmap)
    src_oh = (jnp.arange(N_LANES) == jnp.argmax(att_live)).astype(jnp.float32)
    src_pos = (att_pos * src_oh[:, None]).sum(axis=0)
    drop = (
        (jax.random.uniform(k_bomb) < BOMB_P)
        & att_live.any()
        & ~state.bomb_live
    )
    bomb = jnp.where(drop, src_pos, state.bomb)
    bomb = bomb.at[1].add(jnp.where(state.bomb_live | drop, BOMB_SPEED, 0.0))
    bomb_live = (state.bomb_live | drop) & (bomb[1] < 1.0)

    # hits on the player: bomb, or an attacker reaching the turret row
    bomb_hit = (
        bomb_live
        & (jnp.abs(bomb[0] - player_x) <= PLAYER_W)
        & (bomb[1] >= PLAYER_Y - 0.02)
    )
    reached = att_live & (att_pos[:, 1] >= PLAYER_Y - 0.02)
    lives = state.lives - (bomb_hit | reached.any()).astype(jnp.int32)
    bomb_live = bomb_live & ~bomb_hit
    att_live = att_live & ~reached

    return (
        State(
            mother_x=mother_x,
            mother_dir=mother_dir,
            att_pos=att_pos,
            att_live=att_live,
            bomb=bomb,
            bomb_live=bomb_live,
            player_x=player_x,
            shot=shot,
            shot_live=shot_live,
            heat=heat,
            jammed=jammed,
            lives=lives,
            t=state.t,
        ),
        reward,
    )


def step(state: State, action: jax.Array, key: jax.Array):
    move = jnp.where(
        (action == 3) | (action == 5),
        1.0,
        jnp.where((action == 4) | (action == 6), -1.0, 0.0),
    )
    fire = (action == 1) | (action == 5) | (action == 6)
    vent = action == 2
    keys = jax.random.split(key, FRAME_SKIP + 1)

    def body(carry, k):
        st, acc = carry
        st, r = _substep(st, move, fire, vent, k)
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

    mother = (jnp.abs(X - state.mother_x) <= MOTHER_W) & (
        jnp.abs(Y - MOTHER_Y) <= 0.02
    )
    atts = jnp.zeros_like(mother)
    for i in range(N_LANES):
        atts = atts | (
            state.att_live[i]
            & (jnp.abs(X - state.att_pos[i, 0]) <= ATTACKER_W)
            & (jnp.abs(Y - state.att_pos[i, 1]) <= ATTACKER_H)
        )
    player = (jnp.abs(X - state.player_x) <= PLAYER_W) & (
        jnp.abs(Y - PLAYER_Y) <= 0.02
    )
    shot = (
        state.shot_live
        & (jnp.abs(X - state.shot[0]) <= 0.006)
        & (jnp.abs(Y - state.shot[1]) <= 0.015)
    )
    bomb = (
        state.bomb_live
        & (jnp.abs(X - state.bomb[0]) <= 0.008)
        & (jnp.abs(Y - state.bomb[1]) <= 0.012)
    )
    # heat gauge strip on the right edge; full height = jammed
    gauge = (X > 0.97) & (Y > 1.0 - state.heat)

    frame = (player | shot).astype(jnp.uint8) * 255
    frame = jnp.maximum(frame, mother.astype(jnp.uint8) * 200)
    frame = jnp.maximum(frame, atts.astype(jnp.uint8) * 160)
    frame = jnp.maximum(frame, bomb.astype(jnp.uint8) * 120)
    return jnp.maximum(frame, gauge.astype(jnp.uint8) * 90)
