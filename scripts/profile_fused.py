"""Fused-step cost breakdown on the real chip (VERDICT r1 weak #2).

Times isolated pieces of the fused step at several (n_envs, rollout_len,
chunk) shapes so the optimization is profile-driven, not asserted:

  rollout   — scan of [fwd + sample + env.step + stack update]  (actor side)
  learner   — grad accumulation over the collected trajectory    (learner side)
  full      — the shipped fused step
  env_only  — scan of env.step alone (no net) to price the env+render

Usage: python scripts/profile_fused.py [--trace DIR]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.envs.jaxenv import pong
from distributed_ba3c_tpu.fused.loop import create_fused_state, make_fused_step
from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
from distributed_ba3c_tpu.ops.gradproc import make_optimizer
from distributed_ba3c_tpu.parallel.mesh import make_mesh


def timeit(fn, *args, iters=10):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_full_only(n_envs: int, rollout_len: int, chunk: int):
    cfg = BA3CConfig(num_actions=pong.num_actions)
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh()
    step = make_fused_step(
        model, opt, cfg, mesh, pong, rollout_len=rollout_len,
        grad_chunk_samples=chunk,
    )
    state = step.put(
        create_fused_state(
            jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs, n_shards=1
        )
    )
    try:
        s, m = step(state, cfg.entropy_beta)
        float(m["loss"])
        t0 = time.perf_counter()
        iters = 10
        for _ in range(iters):
            s, m = step(s, cfg.entropy_beta)
        float(m["loss"])
        t_full = (time.perf_counter() - t0) / iters
        sps = n_envs * rollout_len / t_full
        print(
            f"n_envs={n_envs:5d} T={rollout_len:3d} chunk={chunk:6d} | "
            f"full {t_full*1e3:7.2f}ms ({sps:9.0f} sps)",
            flush=True,
        )
    except Exception as e:  # noqa: BLE001
        print(
            f"n_envs={n_envs:5d} T={rollout_len:3d} chunk={chunk:6d} | "
            f"FAILED {type(e).__name__}",
            flush=True,
        )


def bench_shape(n_envs: int, rollout_len: int):
    cfg = BA3CConfig(num_actions=pong.num_actions)
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh()
    step = make_fused_step(model, opt, cfg, mesh, pong, rollout_len=rollout_len)
    state = create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs, n_shards=1
    )
    state = step.put(state)

    # -- full step (carries state: the step donates its input) -------------
    s, m = step(state, cfg.entropy_beta)
    float(m["loss"])
    t0 = time.perf_counter()
    iters = 10
    for _ in range(iters):
        s, m = step(s, cfg.entropy_beta)
    float(m["loss"])
    t_full = (time.perf_counter() - t0) / iters
    state = step.put(
        create_fused_state(
            jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs, n_shards=1
        )
    )

    # -- env only ----------------------------------------------------------
    @jax.jit
    def env_only(env_state, key):
        def body(carry, _):
            es, k = carry
            k, ka, ke = jax.random.split(k, 3)
            actions = jax.random.randint(ka, (n_envs,), 0, pong.num_actions)
            es, obs, r, d = jax.vmap(pong.step)(
                es, actions, jax.random.split(ke, n_envs)
            )
            return (es, k), obs.sum()
        (es, _), sums = jax.lax.scan(body, (env_state, key), None, length=rollout_len)
        return sums.sum()

    t_env = timeit(env_only, state.env_state, jax.random.PRNGKey(1))

    # -- rollout only (fwd + sample + env) ---------------------------------
    params = state.train.params

    @jax.jit
    def rollout_only(params, env_state, stack, key):
        def body(carry, _):
            es, st, k = carry
            out = model.apply({"params": params}, st)
            k, ka, ke = jax.random.split(k, 3)
            a = jax.random.categorical(ka, out.logits, -1).astype(jnp.int32)
            es, obs, r, d = jax.vmap(pong.step)(es, a, jax.random.split(ke, n_envs))
            st = jnp.concatenate([st[..., 1:], obs[..., None]], axis=-1)
            return (es, st, k), (st, a, r, d)
        (es, st, k), traj = jax.lax.scan(
            body, (env_state, stack, key), None, length=rollout_len
        )
        return traj[0].sum()

    t_roll = timeit(
        rollout_only, params, state.env_state, state.obs_stack,
        jax.random.PRNGKey(2),
    )

    # -- learner only on a fixed trajectory --------------------------------
    from distributed_ba3c_tpu.ops.loss import a3c_loss

    states_t = jnp.zeros((rollout_len, n_envs, 84, 84, cfg.frame_history), jnp.uint8)
    actions_t = jnp.zeros((rollout_len, n_envs), jnp.int32)
    returns_t = jnp.zeros((rollout_len, n_envs), jnp.float32)

    @jax.jit
    def learner_only(params, states_t, actions_t, returns_t):
        def chunk_grad(p, chunk):
            sc, ac, rc = chunk
            def loss_fn(pp):
                out = model.apply({"params": pp}, sc)
                l = a3c_loss(out.logits, out.value, ac, rc,
                             entropy_beta=cfg.entropy_beta,
                             value_loss_coef=cfg.value_loss_coef)
                return l.total, l
            return jax.value_and_grad(loss_fn, has_aux=True)(p)

        def acc_body(g_acc, chunk):
            (_, _), g = chunk_grad(params, chunk)
            return jax.tree_util.tree_map(jnp.add, g_acc, g), None

        g0 = jax.tree_util.tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        g, _ = jax.lax.scan(acc_body, g0, (states_t, actions_t, returns_t))
        return jax.tree_util.tree_leaves(g)[0].sum()

    t_learn = timeit(learner_only, params, states_t, actions_t, returns_t)

    # -- learner, single flat [T*B] fwd+bwd (memory permitting) ------------
    flat_states = states_t.reshape(-1, 84, 84, cfg.frame_history)
    flat_actions = actions_t.reshape(-1)
    flat_returns = returns_t.reshape(-1)

    @jax.jit
    def learner_flat(params, s, a, r):
        def loss_fn(pp):
            out = model.apply({"params": pp}, s)
            l = a3c_loss(out.logits, out.value, a, r,
                         entropy_beta=cfg.entropy_beta,
                         value_loss_coef=cfg.value_loss_coef)
            return l.total
        return jax.grad(loss_fn)(params)["Dense_0"]["kernel"].sum()

    try:
        t_flat = timeit(learner_flat, params, flat_states, flat_actions, flat_returns)
    except Exception as e:  # noqa: BLE001
        t_flat = float("nan")
        print(f"  flat learner failed: {type(e).__name__}")

    steps = n_envs * rollout_len
    print(
        f"n_envs={n_envs:5d} T={rollout_len:3d} | "
        f"full {t_full*1e3:7.2f}ms ({steps/t_full:9.0f} sps) | "
        f"rollout {t_roll*1e3:7.2f}ms | env {t_env*1e3:6.2f}ms | "
        f"learner {t_learn*1e3:7.2f}ms | flat {t_flat*1e3:7.2f}ms",
        flush=True,
    )


def bench_attribution(n_envs: int, rollout_len: int, inner: int = 50):
    """Close the full-vs-parts gap (VERDICT r2 #3): price the returns scan,
    the Adam+clip update, and the episode bookkeeping individually, so
    full - (rollout + learner + returns + adam + bookkeeping) is a measured
    residual, not a guess. Each component repeats ``inner`` times INSIDE one
    jitted lax.scan with threaded carries — per-dispatch host latency
    (which can exceed the components themselves) divides out, and the chain
    is unfoldable so XLA cannot elide it."""
    cfg = BA3CConfig(num_actions=pong.num_actions)
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    state = create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs, n_shards=1
    )
    params = state.train.params
    T, B = rollout_len, n_envs
    steps = T * B

    def time_scanned(jitted, carry, outer=5):
        out = jitted(carry)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(outer):
            out = jitted(out)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / (outer * inner)

    # -- n-step discounted returns scan on [T, B] --------------------------
    from distributed_ba3c_tpu.ops.returns import n_step_returns

    @jax.jit
    def returns_rep(carry):
        def body(c, _):
            rew, done, boot = c
            ret = n_step_returns(rew, done, boot, cfg.gamma)
            # thread outputs back into inputs: unfoldable chain
            return (rew + 1e-9 * ret, done, boot + 1e-9 * ret[-1]), None
        out, _ = jax.lax.scan(body, carry, None, length=inner)
        return out

    t_ret = time_scanned(
        returns_rep,
        (
            jnp.zeros((T, B), jnp.float32),
            jnp.zeros((T, B), jnp.bool_),
            jnp.zeros((B,), jnp.float32),
        ),
    )

    # -- Adam + global-norm clip update alone ------------------------------
    import optax

    opt_state = opt.init(params)
    grads = jax.tree_util.tree_map(lambda p: jnp.ones_like(p) * 1e-9, params)

    @jax.jit
    def adam_rep(carry):
        def body(c, _):
            p, os_ = c
            # derive grads from the CARRY so the global-norm reduction and
            # clip scaling are iteration-dependent — loop-invariant grads
            # would let XLA hoist the clip out of the scan
            g = jax.tree_util.tree_map(lambda gl, pl: gl + 1e-12 * pl, grads, p)
            updates, os_ = opt.update(g, os_, p)
            return (optax.apply_updates(p, updates), os_), None
        out, _ = jax.lax.scan(body, carry, None, length=inner)
        return out

    t_adam = time_scanned(adam_rep, (params, opt_state))

    # -- episode bookkeeping (the where/accumulate plane on [T, B]) --------
    @jax.jit
    def book_rep(carry):
        def rep(c, _):
            ep_ret, ep_count, ep_sum, rew, done = c
            def body(cc, td):
                er, cnt, s = cc
                r, d = td
                er = er + r
                cnt = cnt + d.astype(jnp.int32)
                s = s + jnp.where(d, er, 0.0)
                er = jnp.where(d, 0.0, er)
                return (er, cnt, s), None
            (ep_ret, ep_count, ep_sum), _ = jax.lax.scan(
                body, (ep_ret, ep_count, ep_sum), (rew, done)
            )
            return (ep_ret, ep_count, ep_sum, rew + 1e-9 * ep_ret, done), None
        out, _ = jax.lax.scan(rep, carry, None, length=inner)
        return out

    t_book = time_scanned(
        book_rep,
        (
            jnp.zeros(B, jnp.float32),
            jnp.zeros(B, jnp.int32),
            jnp.zeros(B, jnp.float32),
            jnp.zeros((T, B), jnp.float32),
            jnp.zeros((T, B), jnp.bool_),
        ),
    )

    print(
        f"attribution @ {n_envs}x{rollout_len} ({steps} samples/step):\n"
        f"  returns scan  {t_ret*1e6:9.1f} us  ({t_ret/steps*1e9:6.2f} ns/sample)\n"
        f"  adam+clip     {t_adam*1e6:9.1f} us  ({t_adam/steps*1e9:6.2f} ns/sample)\n"
        f"  bookkeeping   {t_book*1e6:9.1f} us  ({t_book/steps*1e9:6.2f} ns/sample)",
        flush=True,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--trace", default=None,
        help="capture to DIR; the full step's ops carry the phases' scopes "
        "(utils/profiling.py: op_time_by_scope reads them)",
    )
    ap.add_argument("--shapes", default="1024x20")
    ap.add_argument(
        "--attribute", action="store_true",
        help="price returns/adam/bookkeeping to close the full-vs-parts gap",
    )
    ap.add_argument(
        "--full-chunks",
        default=None,
        help="comma list of grad_chunk_samples: time the FULL step only",
    )
    ap.add_argument("--tpu_lock", default="wait", choices=["wait", "fail", "off"])
    args = ap.parse_args()

    from distributed_ba3c_tpu.utils.devicelock import guard_tpu

    _lock = guard_tpu("profile_fused", mode=args.tpu_lock)  # noqa: F841
    from distributed_ba3c_tpu.utils.backend import configure_compile_cache

    configure_compile_cache()

    print("devices:", jax.devices(), flush=True)
    shapes = [tuple(map(int, s.split("x"))) for s in args.shapes.split(",")]
    if args.attribute:
        for n, t in shapes:
            bench_attribution(n, t)
        return
    if args.full_chunks:
        for n, t in shapes:
            for c in map(int, args.full_chunks.split(",")):
                bench_full_only(n, t, c)
        return
    if args.trace:
        with jax.profiler.trace(args.trace):
            for n, t in shapes:
                bench_shape(n, t)
    else:
        for n, t in shapes:
            bench_shape(n, t)


if __name__ == "__main__":
    main()
