"""Pure-JAX Pong: ALE-Pong-compatible scoring on TPU-friendly physics.

Game rules match Atari Pong's reward structure so the reference's headline
benchmark ("Pong solved at mean score >= 18", BASELINE.md) transfers: a match
is first-to-21 points, reward +1 when the (right, agent) paddle scores, -1
when the scripted left opponent scores, episode return in [-21, 21], done
when either side reaches 21.

Action set mirrors ALE Pong's 6-action space: {0,1} no-op/"fire", {2,4} up,
{3,5} down — so policies and configs transfer between this env, the C++ env
server, and real ALE.

Everything is branch-free jnp (lax.select / masks): one vmap'd step of 4096
envs is a handful of fused elementwise kernels. Physics advances
``frame_skip`` substeps per agent step, matching ALE frameskip=4 semantics
(SURVEY.md §2.9).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.utils.profiling import ROLLOUT_RENDER, device_scope

num_actions = 6
obs_shape = (84, 84)

# court geometry (unit square; render maps to 84x84)
PADDLE_H = 0.16
PADDLE_W = 0.02
AGENT_X = 0.95  # right paddle (the learner)
OPP_X = 0.05    # left paddle (scripted)
BALL_R = 0.015
PADDLE_SPEED = 0.05   # per substep
OPP_SPEED = 0.035     # scripted opponent max speed (slower => beatable)
BALL_SPEED = 0.04
WIN_SCORE = 21
FRAME_SKIP = 4


class State(NamedTuple):
    ball_xy: jax.Array    # [2] float32
    ball_v: jax.Array     # [2] float32
    agent_y: jax.Array    # [] float32
    opp_y: jax.Array      # [] float32
    agent_score: jax.Array  # [] int32
    opp_score: jax.Array    # [] int32
    t: jax.Array            # [] int32 steps in episode


def _serve(key: jax.Array, towards_agent: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Ball at center, random angle, horizontal direction per the server."""
    k1, k2 = jax.random.split(key)
    angle = jax.random.uniform(k1, (), minval=-0.7, maxval=0.7)
    vy = BALL_SPEED * jnp.sin(angle)
    vx = BALL_SPEED * jnp.cos(angle) * jnp.where(towards_agent, 1.0, -1.0)
    jitter = jax.random.uniform(k2, (), minval=-0.1, maxval=0.1)
    return jnp.array([0.5, 0.5 + jitter]), jnp.stack([vx, vy])


def reset(key: jax.Array) -> State:
    xy, v = _serve(key, jnp.bool_(True))
    return State(
        ball_xy=xy,
        ball_v=v,
        agent_y=jnp.float32(0.5),
        opp_y=jnp.float32(0.5),
        agent_score=jnp.int32(0),
        opp_score=jnp.int32(0),
        t=jnp.int32(0),
    )


def _substep(state: State, move: jax.Array, key: jax.Array) -> Tuple[State, jax.Array]:
    """One physics tick. move in {-1,0,+1}. Returns (state, point_reward)."""
    # paddles
    agent_y = jnp.clip(state.agent_y + move * PADDLE_SPEED, PADDLE_H / 2, 1 - PADDLE_H / 2)
    opp_dy = jnp.clip(state.ball_xy[1] - state.opp_y, -OPP_SPEED, OPP_SPEED)
    opp_y = jnp.clip(state.opp_y + opp_dy, PADDLE_H / 2, 1 - PADDLE_H / 2)

    # ball advance
    xy = state.ball_xy + state.ball_v
    v = state.ball_v

    # wall bounce (top/bottom)
    hit_wall = (xy[1] < BALL_R) | (xy[1] > 1 - BALL_R)
    v = v.at[1].set(jnp.where(hit_wall, -v[1], v[1]))
    xy = xy.at[1].set(jnp.clip(xy[1], BALL_R, 1 - BALL_R))

    # paddle bounce: crossing the paddle plane while vertically aligned
    def paddle_bounce(xy, v, paddle_x, paddle_y, moving_right):
        crossing = jnp.where(
            moving_right, xy[0] >= paddle_x - PADDLE_W, xy[0] <= paddle_x + PADDLE_W
        )
        aligned = jnp.abs(xy[1] - paddle_y) <= PADDLE_H / 2 + BALL_R
        hit = crossing & aligned & jnp.where(moving_right, v[0] > 0, v[0] < 0)
        # deflection angle scales with contact offset (classic Pong control)
        offset = (xy[1] - paddle_y) / (PADDLE_H / 2)
        new_vx = jnp.where(hit, -v[0], v[0])
        new_vy = jnp.where(hit, BALL_SPEED * 0.9 * offset, v[1])
        new_x = jnp.where(
            hit,
            jnp.where(moving_right, paddle_x - PADDLE_W - BALL_R, paddle_x + PADDLE_W + BALL_R),
            xy[0],
        )
        return xy.at[0].set(new_x), jnp.stack([new_vx, new_vy]), hit

    xy, v, _ = paddle_bounce(xy, v, AGENT_X, agent_y, jnp.bool_(True))
    xy, v, _ = paddle_bounce(xy, v, OPP_X, opp_y, jnp.bool_(False))

    # scoring: ball passes an end wall
    agent_point = xy[0] <= 0.0   # opponent missed
    opp_point = xy[0] >= 1.0     # agent missed
    scored = agent_point | opp_point
    reward = jnp.where(agent_point, 1.0, jnp.where(opp_point, -1.0, 0.0))

    # re-serve after a point (loser serves toward the scorer, like ALE)
    serve_xy, serve_v = _serve(key, towards_agent=opp_point)
    xy = jnp.where(scored, serve_xy, xy)
    v = jnp.where(scored, serve_v, v)

    return (
        State(
            ball_xy=xy,
            ball_v=v,
            agent_y=agent_y,
            opp_y=opp_y,
            agent_score=state.agent_score + agent_point.astype(jnp.int32),
            opp_score=state.opp_score + opp_point.astype(jnp.int32),
            t=state.t,
        ),
        reward,
    )


def _action_to_move(action: jax.Array) -> jax.Array:
    """ALE 6-action map: 2/4 -> up (-y), 3/5 -> down (+y), else hold."""
    up = (action == 2) | (action == 4)
    down = (action == 3) | (action == 5)
    return jnp.where(up, -1.0, jnp.where(down, 1.0, 0.0))


def step(state: State, action: jax.Array, key: jax.Array) -> Tuple[State, jax.Array, jax.Array, jax.Array]:
    """One agent step = FRAME_SKIP physics substeps (ALE frameskip parity).

    Returns (state, obs uint8 [84,84], reward float32, done bool); the episode
    auto-restarts when either side reaches WIN_SCORE.
    """
    move = _action_to_move(action)
    keys = jax.random.split(key, FRAME_SKIP + 1)

    def body(carry, k):
        st, acc = carry
        st, r = _substep(st, move, k)
        return (st, acc + r), None

    # accumulator derived from state so it inherits the same sharding/varying
    # axes as the carry under shard_map (a literal 0.0 would be invariant)
    zero = state.ball_xy[0] * 0.0
    (state, reward), _ = jax.lax.scan(body, (state, zero), keys[:FRAME_SKIP])
    state = state._replace(t=state.t + 1)

    done = (state.agent_score >= WIN_SCORE) | (state.opp_score >= WIN_SCORE)
    fresh = reset(keys[FRAME_SKIP])
    state = jax.tree_util.tree_map(
        lambda new, old: jnp.where(done, new, old), fresh, state
    )
    return state, render(state), reward, done


@device_scope(ROLLOUT_RENDER)
def render(state: State) -> jax.Array:
    """Rasterize to uint8 [84, 84] (rows = y, cols = x). Pure masks, no loops."""
    h, w = obs_shape
    ys = (jnp.arange(h, dtype=jnp.float32) + 0.5) / h
    xs = (jnp.arange(w, dtype=jnp.float32) + 0.5) / w
    Y = ys[:, None]
    X = xs[None, :]

    def rect(cx, cy, half_w, half_h):
        return (jnp.abs(X - cx) <= half_w) & (jnp.abs(Y - cy) <= half_h)

    ball = rect(state.ball_xy[0], state.ball_xy[1], BALL_R, BALL_R)
    agent = rect(AGENT_X, state.agent_y, PADDLE_W, PADDLE_H / 2)
    opp = rect(OPP_X, state.opp_y, PADDLE_W, PADDLE_H / 2)
    frame = (ball | agent | opp).astype(jnp.uint8) * 255
    # dim background texture so conv nets see court bounds (walls)
    wall = (Y < 0.02) | (Y > 0.98)
    return jnp.maximum(frame, wall.astype(jnp.uint8) * 80)
