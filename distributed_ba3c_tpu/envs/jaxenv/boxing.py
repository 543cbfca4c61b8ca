"""Pure-JAX Boxing: ALE-compatible scoring on branch-free ring physics.

ALE parity choices (reference game set, BASELINE.md): two boxers in a
top-down ring; +1 reward per punch landed on the opponent, -1 per punch
taken (ALE Boxing reward = own score delta minus opponent's); KO —
episode ends — when either side reaches 100 landed punches; otherwise a
round lasts "two minutes" (MAX_T agent steps). A perfect agent approaches
+100. Action set: {0}=noop {1}=punch {2}=up {3}=right {4}=left {5}=down
{6..9}=diagonals {10..17}=punch+move (18 actions — ALE Boxing uses the
full set).

The opponent is a scripted pursuer with a punch cooldown and a random
sidestep, the same role ALE's built-in game AI plays; its parameters set
the difficulty of the reward landscape, not the framework surface.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.utils.profiling import ROLLOUT_RENDER, device_scope

num_actions = 18
obs_shape = (84, 84)

RING_LO, RING_HI = 0.08, 0.92
MOVE = 0.022
OPP_MOVE = 0.014
PUNCH_RANGE = 0.10
PUNCH_CD = 4          # substeps between punches
OPP_PUNCH_P = 0.25    # per-substep punch attempt probability when in range
                      # (calibrated so random play nets ~0, like ALE's AI)
KO = 100
FRAME_SKIP = 4
MAX_T = 2000

# action -> (dx, dy, punch): move rows for actions 2..9, punch variants 10..17
_MOVES = jnp.array(
    [
        [0, 0], [0, 0],                      # noop, punch
        [0, -1], [1, 0], [-1, 0], [0, 1],    # up right left down
        [1, -1], [-1, -1], [1, 1], [-1, 1],  # diagonals (ALE order approx)
    ],
    jnp.float32,
)


def _decode(action: jax.Array):
    punch_combo = action >= 10
    base = jnp.where(punch_combo, action - 8, action)  # 10..17 -> 2..9
    base = jnp.clip(base, 0, 9)
    # one-hot contraction, not _MOVES[base]: per-env scalar gathers lower
    # to pathological batched gathers under vmap in the fused program
    oh = (jnp.arange(10) == base).astype(jnp.float32)
    d = oh @ _MOVES
    punch = (action == 1) | punch_combo
    return d[0], d[1], punch


class State(NamedTuple):
    me: jax.Array        # [2] player position
    opp: jax.Array       # [2]
    my_score: jax.Array  # [] int32 punches landed
    op_score: jax.Array  # [] int32
    my_cd: jax.Array     # [] int32 punch cooldown
    op_cd: jax.Array     # [] int32
    t: jax.Array         # [] int32


def reset(key: jax.Array) -> State:
    del key
    return State(
        me=jnp.array([0.3, 0.5]),
        opp=jnp.array([0.7, 0.5]),
        my_score=jnp.int32(0),
        op_score=jnp.int32(0),
        my_cd=jnp.int32(0),
        op_cd=jnp.int32(0),
        t=jnp.int32(0),
    )


def _substep(state: State, dx, dy, punch, key: jax.Array):
    k_side, k_punch = jax.random.split(key)
    me = jnp.clip(
        state.me + jnp.stack([dx, dy]) * MOVE, RING_LO, RING_HI
    )

    # opponent AI: pursue with a random lateral jitter
    delta = me - state.opp
    dist = jnp.linalg.norm(delta) + 1e-6
    chase = delta / dist * OPP_MOVE
    jitter = (jax.random.uniform(k_side, (2,)) - 0.5) * OPP_MOVE
    opp = jnp.clip(state.opp + chase + jitter, RING_LO, RING_HI)

    in_range = jnp.linalg.norm(me - opp) <= PUNCH_RANGE
    my_land = punch & in_range & (state.my_cd <= 0)
    op_try = jax.random.uniform(k_punch) < OPP_PUNCH_P
    op_land = op_try & in_range & (state.op_cd <= 0)

    # landing a punch knocks the punched boxer AWAY from the puncher
    # (delta = me - opp, so -delta/dist points from me toward opp)
    knock = jnp.where(dist > 0, delta / dist, jnp.zeros(2)) * 0.05
    opp = jnp.clip(opp - jnp.where(my_land, knock, 0.0), RING_LO, RING_HI)
    me = jnp.clip(me + jnp.where(op_land, knock, 0.0), RING_LO, RING_HI)

    reward = my_land.astype(jnp.float32) - op_land.astype(jnp.float32)
    return (
        State(
            me=me,
            opp=opp,
            my_score=state.my_score + my_land.astype(jnp.int32),
            op_score=state.op_score + op_land.astype(jnp.int32),
            my_cd=jnp.where(my_land, PUNCH_CD, jnp.maximum(state.my_cd - 1, 0)),
            op_cd=jnp.where(op_land, PUNCH_CD, jnp.maximum(state.op_cd - 1, 0)),
            t=state.t,
        ),
        reward,
    )


def step(state: State, action: jax.Array, key: jax.Array):
    dx, dy, punch = _decode(action)
    keys = jax.random.split(key, FRAME_SKIP + 1)

    def body(carry, k):
        st, acc = carry
        st, r = _substep(st, dx, dy, punch, k)
        return (st, acc + r), None

    zero = state.me[0] * 0.0
    (state, reward), _ = jax.lax.scan(body, (state, zero), keys[:FRAME_SKIP])
    state = state._replace(t=state.t + 1)

    done = (
        (state.my_score >= KO)
        | (state.op_score >= KO)
        | (state.t >= MAX_T)
    )
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

    ring = (
        (jnp.abs(X - RING_LO) < 0.008)
        | (jnp.abs(X - RING_HI) < 0.008)
        | (jnp.abs(Y - RING_LO) < 0.008)
        | (jnp.abs(Y - RING_HI) < 0.008)
    )
    me = (jnp.abs(X - state.me[0]) <= 0.03) & (jnp.abs(Y - state.me[1]) <= 0.03)
    opp = (jnp.abs(X - state.opp[0]) <= 0.03) & (
        jnp.abs(Y - state.opp[1]) <= 0.03
    )
    # score bars along the top edge (white=mine, grey=opponent) so the net
    # can see the count, like ALE's on-screen score
    my_bar = (Y < 0.04) & (X < state.my_score.astype(jnp.float32) / KO)
    op_bar = (Y > 0.96) & (X < state.op_score.astype(jnp.float32) / KO)

    frame = me.astype(jnp.uint8) * 255
    frame = jnp.maximum(frame, opp.astype(jnp.uint8) * 150)
    frame = jnp.maximum(frame, ring.astype(jnp.uint8) * 80)
    frame = jnp.maximum(frame, my_bar.astype(jnp.uint8) * 255)
    return jnp.maximum(frame, op_bar.astype(jnp.uint8) * 120)
