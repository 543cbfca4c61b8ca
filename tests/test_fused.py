"""Fused on-device actor+learner: sharded step runs, learns, tracks episodes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.envs.jaxenv import pong
from distributed_ba3c_tpu.fused.loop import create_fused_state, make_fused_step
from distributed_ba3c_tpu.models.a3c import BA3CNet
from distributed_ba3c_tpu.ops.gradproc import make_optimizer
from distributed_ba3c_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def fused_setup():
    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=16)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh()
    n_data = mesh.shape["data"]
    n_envs = 2 * n_data
    step = make_fused_step(model, opt, cfg, mesh, pong, rollout_len=3)

    def make_state():
        return step.put(
            create_fused_state(
                jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs,
                n_shards=n_data,
            )
        )

    return cfg, step, make_state, n_envs


@pytest.fixture
def fused(fused_setup):
    # fresh state per test: the step DONATES its input state, so a shared
    # module-scoped state would be deleted after the first test touches it
    cfg, step, make_state, n_envs = fused_setup
    return cfg, step, make_state(), n_envs


def test_fused_step_advances_and_is_finite(fused):
    cfg, step, state, n_envs = fused
    state, metrics = step(state, cfg.entropy_beta)
    state, metrics = step(state, cfg.entropy_beta)
    assert int(state.train.step) == 2
    for k, v in metrics.items():
        assert np.isfinite(float(v)), k
    assert state.obs_stack.shape == (n_envs, 84, 84, cfg.frame_history)


def test_fused_params_update_and_lr_zero_freezes(fused):
    cfg, step, state, _ = fused
    p0 = np.asarray(jax.tree_util.tree_leaves(state.train.params)[0]).copy()
    state, _ = step(state, cfg.entropy_beta, learning_rate=0.0)
    p1 = np.asarray(jax.tree_util.tree_leaves(state.train.params)[0])
    np.testing.assert_array_equal(p0, p1)
    state, _ = step(state, cfg.entropy_beta, learning_rate=1e-3)
    p2 = np.asarray(jax.tree_util.tree_leaves(state.train.params)[0])
    assert not np.allclose(p1, p2)


def test_fused_rng_differs_across_shards(fused):
    """Each mesh shard must consume its own RNG stream — identical streams
    would roll identical envs and silently divide the effective batch."""
    cfg, step, state, n_envs = fused
    for _ in range(5):
        state, _ = step(state, cfg.entropy_beta)
    # after a few steps, per-shard env states must have diverged
    ball = np.asarray(state.env_state.ball_xy)  # [n_envs, 2]
    n_data = step.mesh.shape["data"]
    per_shard = ball.reshape(n_data, n_envs // n_data, 2)
    # shard 0's envs should not all equal shard 1's envs
    assert not np.allclose(per_shard[0], per_shard[1])


def test_greedy_eval_runs_and_bounds(fused_setup):
    """On-device greedy Evaluator: completes episodes, returns Pong-bounded
    means, and is deterministic given the same params+key."""
    from distributed_ba3c_tpu.fused.loop import make_greedy_eval
    from distributed_ba3c_tpu.parallel.mesh import make_mesh

    cfg, step, make_state, n_envs = fused_setup
    state = make_state()
    mesh = make_mesh()
    n_data = mesh.shape["data"]
    evaluate = make_greedy_eval(
        BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units),
        cfg,
        mesh,
        pong,
        n_envs=2 * n_data,
        max_steps=900,
    )
    params = jax.device_get(state.train.params)
    mean, mx, n = evaluate(params, jax.random.PRNGKey(7))
    assert n >= 1, "greedy eval completed no episodes in 900 steps"
    assert -21.0 <= mean <= 21.0 and -21.0 <= mx <= 21.0
    mean2, mx2, n2 = evaluate(params, jax.random.PRNGKey(7))
    assert (mean2, mx2, n2) == (mean, mx, n)


def test_fused_episode_accounting(fused):
    """Run enough steps that the still-ish random policy finishes matches;
    episode counters must rise and mean return must be within Pong bounds."""
    cfg, step, state, _ = fused
    for _ in range(10):
        state, metrics = step(state, cfg.entropy_beta)
    eps = float(metrics["episodes"])
    if eps > 0:
        mean_ret = float(metrics["episode_return_sum"]) / eps
        assert -21.0 <= mean_ret <= 21.0
    # ep_return accumulators stay bounded
    assert np.all(np.abs(np.asarray(state.ep_return)) <= 21.0 + 1e-6)


def test_scanned_dispatch_matches_sequential_steps(fused_setup):
    """steps_per_dispatch=K parity against K sequential dispatches.

    With learning_rate=0 the params are frozen, so both variants consume the
    IDENTICAL key sequence and must produce bit-identical env trajectories,
    frame stacks, and episode counters — exercising the whole scan plumbing.
    (With a live lr, bit-equality across differently-compiled programs is
    not a sound contract: XLA fuses the scan body differently, a 1-ulp logit
    change flips a sampled action, and the RL trajectory is chaotic.)"""
    cfg, step, make_state, n_envs = fused_setup
    mesh = make_mesh()
    n_data = mesh.shape["data"]
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    K = 4
    step_k = make_fused_step(
        model, opt, cfg, mesh, pong, rollout_len=3, steps_per_dispatch=K
    )

    def fresh(putter):
        return putter(
            create_fused_state(
                jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs,
                n_shards=n_data,
            )
        )

    # --- lr=0: params frozen => trajectories must be bit-identical ---
    state_seq = fresh(step.put)
    for _ in range(K):
        state_seq, m_seq = step(state_seq, cfg.entropy_beta, learning_rate=0.0)
    state_scan = fresh(step_k.put)
    state_scan, m_scan = step_k(state_scan, cfg.entropy_beta, learning_rate=0.0)

    assert int(state_scan.train.step) == int(state_seq.train.step) == K
    np.testing.assert_array_equal(
        np.asarray(state_seq.obs_stack), np.asarray(state_scan.obs_stack)
    )
    np.testing.assert_array_equal(
        np.asarray(state_seq.ep_count), np.asarray(state_scan.ep_count)
    )
    np.testing.assert_array_equal(
        np.asarray(state_seq.ep_return), np.asarray(state_scan.ep_return)
    )
    # cumulative counters: scan's LAST-step metric == sequential's last
    assert float(m_scan["episodes"]) == float(m_seq["episodes"])
    assert float(m_scan["episode_return_sum"]) == float(
        m_seq["episode_return_sum"]
    )

    # --- live lr: the scanned program must actually train ---
    state_live = fresh(step_k.put)
    p0 = np.asarray(jax.tree_util.tree_leaves(state_live.train.params)[0]).copy()
    state_live, m_live = step_k(state_live, cfg.entropy_beta)
    assert int(state_live.train.step) == K
    p1 = np.asarray(jax.tree_util.tree_leaves(state_live.train.params)[0])
    assert not np.array_equal(p0, p1), "scanned dispatch did not update params"
    for k, v in m_live.items():
        assert np.isfinite(float(v)), k


@pytest.mark.parametrize(
    "steps_per_epoch, k", [(4, 3), (10, 4), (4, 8)],
    ids=["4_by_3", "10_by_4", "k_over_epoch"],
)
def test_run_fused_training_rejects_k_not_dividing_epoch(
    tmp_path, monkeypatch, steps_per_epoch, k
):
    """--steps_per_dispatch K must divide --steps_per_epoch: the driver
    refuses before it builds (or compiles) a step. cli.main catches the same
    rule first through TopologySpec; this is the loop's own check, which a
    caller of run_fused_training meets."""
    from distributed_ba3c_tpu import cli
    from distributed_ba3c_tpu.fused import loop

    def no_step(*a, **kw):
        raise AssertionError("a step was built before K was checked")

    monkeypatch.setattr(loop, "make_fused_step", no_step)
    args = cli.make_parser().parse_args([
        "--trainer", "tpu_fused_ba3c", "--env", "jax:pong",
        "--batch_size", "8", "--rollout_len", "2", "--fc_units", "16",
        "--steps_per_epoch", str(steps_per_epoch),
        "--steps_per_dispatch", str(k),
        "--logdir", str(tmp_path), "--tpu_lock", "off",
    ])
    cfg = cli.build_config(args)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    with pytest.raises(SystemExit, match="must divide --steps_per_epoch"):
        loop.run_fused_training(args, cfg, model, opt)
