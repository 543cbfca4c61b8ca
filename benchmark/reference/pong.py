"""Plain reference of the ``jax:pong`` environment, written from its rules.

A match to 21 on the unit square: the agent holds the right paddle, a
scripted opponent the left; four physics ticks to an agent step; the frame is
an 84x84 uint8 raster of ball, paddles and dim walls. Unbatched functions on a
dict of arrays; callers ``vmap`` them. Random draws follow the order the
rules fix: a step splits its key into one key per tick plus one for the
restart; a serve splits its key into an angle key and a jitter key.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NUM_ACTIONS = 6
SIZE = 84
PADDLE_HALF = 0.08
PADDLE_W = 0.02
AGENT_X = 0.95
OPP_X = 0.05
BALL_R = 0.015
PADDLE_SPEED = 0.05
OPP_SPEED = 0.035
BALL_SPEED = 0.04
WIN_SCORE = 21
TICKS = 4


def _serve(key, towards_agent):
    k_angle, k_jitter = jax.random.split(key)
    angle = jax.random.uniform(k_angle, (), minval=-0.7, maxval=0.7)
    vy = BALL_SPEED * jnp.sin(angle)
    vx = BALL_SPEED * jnp.cos(angle) * jnp.where(towards_agent, 1.0, -1.0)
    jitter = jax.random.uniform(k_jitter, (), minval=-0.1, maxval=0.1)
    return jnp.array([0.5, 0.5 + jitter]), jnp.stack([vx, vy])


def reset(key):
    xy, v = _serve(key, jnp.bool_(True))
    return {
        "ball_xy": xy, "ball_v": v,
        "agent_y": jnp.float32(0.5), "opp_y": jnp.float32(0.5),
        "agent_score": jnp.int32(0), "opp_score": jnp.int32(0),
        "t": jnp.int32(0),
    }


def _bounce(xy, v, paddle_x, paddle_y, moving_right):
    edge = jnp.where(moving_right, paddle_x - PADDLE_W, paddle_x + PADDLE_W)
    crossing = jnp.where(moving_right, xy[0] >= edge, xy[0] <= edge)
    aligned = jnp.abs(xy[1] - paddle_y) <= PADDLE_HALF + BALL_R
    hit = crossing & aligned & jnp.where(moving_right, v[0] > 0, v[0] < 0)
    offset = (xy[1] - paddle_y) / PADDLE_HALF
    vx = jnp.where(hit, -v[0], v[0])
    vy = jnp.where(hit, BALL_SPEED * 0.9 * offset, v[1])
    rest = jnp.where(moving_right, edge - BALL_R, edge + BALL_R)
    return xy.at[0].set(jnp.where(hit, rest, xy[0])), jnp.stack([vx, vy])


def _tick(s, move, key):
    lo, hi = PADDLE_HALF, 1 - PADDLE_HALF
    agent_y = jnp.clip(s["agent_y"] + move * PADDLE_SPEED, lo, hi)
    chase = jnp.clip(s["ball_xy"][1] - s["opp_y"], -OPP_SPEED, OPP_SPEED)
    opp_y = jnp.clip(s["opp_y"] + chase, lo, hi)
    xy = s["ball_xy"] + s["ball_v"]
    v = s["ball_v"]
    wall = (xy[1] < BALL_R) | (xy[1] > 1 - BALL_R)
    v = v.at[1].set(jnp.where(wall, -v[1], v[1]))
    xy = xy.at[1].set(jnp.clip(xy[1], BALL_R, 1 - BALL_R))
    xy, v = _bounce(xy, v, AGENT_X, agent_y, jnp.bool_(True))
    xy, v = _bounce(xy, v, OPP_X, opp_y, jnp.bool_(False))
    agent_point = xy[0] <= 0.0
    opp_point = xy[0] >= 1.0
    scored = agent_point | opp_point
    reward = jnp.where(agent_point, 1.0, jnp.where(opp_point, -1.0, 0.0))
    serve_xy, serve_v = _serve(key, opp_point)
    out = dict(
        s,
        ball_xy=jnp.where(scored, serve_xy, xy),
        ball_v=jnp.where(scored, serve_v, v),
        agent_y=agent_y, opp_y=opp_y,
        agent_score=s["agent_score"] + agent_point.astype(jnp.int32),
        opp_score=s["opp_score"] + opp_point.astype(jnp.int32),
    )
    return out, reward


def step(s, action, key):
    """-> (state, frame uint8 [84, 84], reward, done); restarts when done."""
    up = (action == 2) | (action == 4)
    down = (action == 3) | (action == 5)
    move = jnp.where(up, -1.0, jnp.where(down, 1.0, 0.0))
    keys = jax.random.split(key, TICKS + 1)
    reward = jnp.float32(0.0)
    for i in range(TICKS):
        s, r = _tick(s, move, keys[i])
        reward = reward + r
    s = dict(s, t=s["t"] + 1)
    done = (s["agent_score"] >= WIN_SCORE) | (s["opp_score"] >= WIN_SCORE)
    fresh = reset(keys[TICKS])
    s = {k: jnp.where(done, fresh[k], s[k]) for k in s}
    return s, render(s), reward, done


def render(s):
    centres = (jnp.arange(SIZE, dtype=jnp.float32) + 0.5) / SIZE
    Y, X = centres[:, None], centres[None, :]

    def rect(cx, cy, half_w, half_h):
        return (jnp.abs(X - cx) <= half_w) & (jnp.abs(Y - cy) <= half_h)

    lit = (
        rect(s["ball_xy"][0], s["ball_xy"][1], BALL_R, BALL_R)
        | rect(AGENT_X, s["agent_y"], PADDLE_W, PADDLE_HALF)
        | rect(OPP_X, s["opp_y"], PADDLE_W, PADDLE_HALF)
    )
    wall = (Y < 0.02) | (Y > 0.98)
    return jnp.maximum(lit.astype(jnp.uint8) * 255, wall.astype(jnp.uint8) * 80)
