"""Split-jit experiment: rollout jit + learner jit vs the monolithic fused step.

Hypothesis (from profile_fused.py numbers): the learner runs at ~80% MFU as a
standalone jit on big flat batches but the monolithic rollout+learner program
schedules far worse (memory pressure → remat/spills near OOM). If
t(rollout_jit) + t(learner_jit) << t(monolith), restructure fused/loop.py
into two device calls per step.

``--overlap`` (ISSUE 8): measure the REAL two-program overlap schedule
(fused/overlap.py) instead of the round-1 ad-hoc split — per-program wall
times (medians over ``--reps`` probe reps), the measured learner-hidden
fraction of the actor, and ``learner_window_coverage`` (min(1,
t_learner/t_actor)) — the device-free proxy gate quantity: how much of the
actor's wall time the learner window is long enough to hide. Prints ONE
JSON line on stdout (the repo's bench-tooling contract); diagnostics go to
stderr. PERF.md round 9 records why realized concurrency is additionally
backend-dependent (this jax's CPU client multiplexes every execution onto
one shared intra-op pool).
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.envs.jaxenv import pong
from distributed_ba3c_tpu.fused.loop import create_fused_state, make_fused_step
from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
from distributed_ba3c_tpu.ops.gradproc import inject_learning_rate
from distributed_ba3c_tpu.ops.loss import a3c_loss
from distributed_ba3c_tpu.ops.returns import n_step_returns
from distributed_ba3c_tpu.parallel.mesh import make_mesh

N_ENVS = 1024
T = 20


def profile_overlap(n_envs: int, rollout_len: int, fc_units: int,
                    reps: int) -> dict:
    """Probe the real overlap programs: solo/pair wall times + hiding."""
    from distributed_ba3c_tpu.fused.overlap import make_overlap_step

    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=fc_units)
    model = build_model(DEFAULT_MODEL, cfg)
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer

    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon,
                         cfg.grad_clip_norm)
    mesh = make_mesh()
    n_chips = len(jax.devices())
    step = make_overlap_step(model, opt, cfg, mesh, pong,
                             rollout_len=rollout_len)
    state = step.put(create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong,
        n_envs * n_chips, n_shards=n_chips,
    ))
    t0 = time.perf_counter()
    state, m = step(state, cfg.entropy_beta)
    float(m["loss"])  # compile + warmup fence
    print(f"warmup (compile all programs): {time.perf_counter()-t0:.1f}s",
          file=sys.stderr)
    state, probe = step.probe_overlap(state, cfg.entropy_beta, reps=reps)
    return {
        "metric": "overlap_split_profile",
        # probe carries the device-free proxy gate quantity
        # (learner_window_coverage: the learner window is long enough to
        # hide this fraction of the actor's wall time; realized hiding
        # additionally needs concurrent execution queues, which only a
        # chip run can show; overlap_efficiency is what THIS backend
        # realizes)
        **probe,
        "n_envs": n_envs * n_chips,
        "rollout_len": rollout_len,
        "fc_units": fc_units,
        "n_chips": n_chips,
        "device_kind": jax.devices()[0].device_kind,
    }


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--tpu_lock", default="wait", choices=["wait", "fail", "off"])
    ap.add_argument("--overlap", action="store_true",
                    help="probe the real two-program overlap schedule "
                    "(fused/overlap.py) and print one JSON line")
    ap.add_argument("--n_envs", type=int, default=None,
                    help="--overlap: envs per chip (default 128, the "
                    "flagship shape; shrink for CPU proxy captures)")
    ap.add_argument("--rollout_len", type=int, default=20)
    ap.add_argument("--fc_units", type=int, default=None,
                    help="--overlap: net width (default the real 512; "
                    "shrink for CPU proxy captures)")
    ap.add_argument("--reps", type=int, default=5,
                    help="--overlap: probe repetitions (medians reported)")
    args = ap.parse_args()

    from distributed_ba3c_tpu.utils.devicelock import guard_tpu

    _lock = guard_tpu("profile_split", mode=args.tpu_lock)  # noqa: F841
    from distributed_ba3c_tpu.utils.backend import configure_compile_cache

    configure_compile_cache()

    if args.overlap:
        row = profile_overlap(
            n_envs=args.n_envs or 128,
            rollout_len=args.rollout_len,
            fc_units=args.fc_units or 512,
            reps=args.reps,
        )
        print(json.dumps(row))
        return

    cfg = BA3CConfig(num_actions=pong.num_actions)
    model = build_model(DEFAULT_MODEL, cfg)
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer

    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh()
    state = create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong, N_ENVS, n_shards=1
    )

    # ---------------- rollout jit ----------------
    @jax.jit
    def rollout(params, env_state, stack, key, ep_ret):
        def body(carry, _):
            es, st, k, er = carry
            out = model.apply({"params": params}, st)
            k, ka, ke = jax.random.split(k, 3)
            a = jax.random.categorical(ka, out.logits, -1).astype(jnp.int32)
            es, obs, r, d = jax.vmap(pong.step)(es, a, jax.random.split(ke, N_ENVS))
            keep = (~d).astype(st.dtype)[:, None, None, None]
            st2 = jnp.concatenate([st[..., 1:] * keep, obs[..., None]], axis=-1)
            er = er + r
            return (es, st2, k, er * (1.0 - d.astype(jnp.float32))), (st, a, r, d)

        (es, st, k, er), traj = jax.lax.scan(
            body, (env_state, stack, key, ep_ret), None, length=T
        )
        bootstrap = model.apply({"params": params}, st).value
        states_t, actions_t, rewards_t, dones_t = traj
        returns_t = n_step_returns(
            rewards_t, dones_t.astype(jnp.float32),
            jax.lax.stop_gradient(bootstrap), cfg.gamma,
        )
        return es, st, k, er, states_t, actions_t, returns_t

    # ---------------- learner jit (flat, donates traj) -------------------
    def make_learner(n_chunks):
        def learner(train, states_t, actions_t, returns_t, beta, lr):
            params = train.params
            sf = states_t.reshape(T * N_ENVS, 84, 84, cfg.frame_history)
            af = actions_t.reshape(-1)
            rf = returns_t.reshape(-1)

            def chunk_grad(p, chunk):
                sc, ac, rc = chunk

                def loss_fn(pp):
                    out = model.apply({"params": pp}, sc)
                    l = a3c_loss(out.logits, out.value, ac, rc,
                                 entropy_beta=beta,
                                 value_loss_coef=cfg.value_loss_coef)
                    return l.total, l

                return jax.value_and_grad(loss_fn, has_aux=True)(p)

            if n_chunks == 1:
                (_, aux), grads = chunk_grad(params, (sf, af, rf))
            else:
                C = (T * N_ENVS) // n_chunks
                ch = lambda x: x.reshape(n_chunks, C, *x.shape[1:])  # noqa: E731

                def acc(carry, chunk):
                    g_acc, aux_acc = carry
                    (_, aux), g = chunk_grad(params, chunk)
                    return (
                        jax.tree_util.tree_map(jnp.add, g_acc, g),
                        jax.tree_util.tree_map(jnp.add, aux_acc, aux),
                    ), None

                (_, aux0), g0 = chunk_grad(
                    params, (ch(sf)[0], ch(af)[0], ch(rf)[0])
                )
                (grads, aux), _ = jax.lax.scan(
                    acc, (g0, aux0), (ch(sf)[1:], ch(af)[1:], ch(rf)[1:])
                )
                grads = jax.tree_util.tree_map(lambda g: g / n_chunks, grads)

            import optax

            opt_state = inject_learning_rate(train.opt_state, lr)
            updates, new_opt = opt.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return train.replace(
                step=train.step + 1, params=new_params, opt_state=new_opt
            )

        return jax.jit(learner, donate_argnums=(0, 1, 2, 3))

    env_state, stack, key, ep_ret = (
        state.env_state, state.obs_stack, state.key[0], state.ep_return,
    )
    params = state.train.params
    train = state.train

    for n_chunks in (1, 2, 4):
        try:
            learner = make_learner(n_chunks)
            # warm both
            es, st, k, er, S, A, R = rollout(params, env_state, stack, key, ep_ret)
            train2 = learner(train, S, A, R, cfg.entropy_beta, cfg.learning_rate)
            # warmup sync: a profiler must force the compile before timing
            jax.block_until_ready(train2)  # ba3clint: disable=J1

            iters = 10
            t0 = time.perf_counter()
            es, st, k, er = env_state, stack, key, ep_ret
            tr = train2
            for _ in range(iters):
                es, st, k, er, S, A, R = rollout(tr.params, es, st, k, er)
                tr = learner(tr, S, A, R, cfg.entropy_beta, cfg.learning_rate)
            # measurement fence: the timed region must include execution
            jax.block_until_ready(tr)  # ba3clint: disable=J1
            dt = (time.perf_counter() - t0) / iters
            print(
                f"split n_chunks={n_chunks}: {dt*1e3:7.2f}ms/step "
                f"({N_ENVS*T/dt:9.0f} sps)",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            print(f"split n_chunks={n_chunks}: FAILED {type(e).__name__}", flush=True)

    # monolith reference
    step = make_fused_step(model, opt, cfg, mesh, pong, rollout_len=T,
                           grad_chunk_samples=2048)
    fstate = step.put(
        create_fused_state(jax.random.PRNGKey(0), model, cfg, opt, pong,
                           N_ENVS, n_shards=1)
    )
    s, m = step(fstate, cfg.entropy_beta)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(10):
        s, m = step(s, cfg.entropy_beta)
    float(m["loss"])
    dt = (time.perf_counter() - t0) / 10
    print(f"monolith chunk=2048: {dt*1e3:7.2f}ms/step ({N_ENVS*T/dt:9.0f} sps)")


if __name__ == "__main__":
    main()
