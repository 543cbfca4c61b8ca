"""Shared checkpoint-evaluation setup for the standalone eval scripts.

One construction path for (CheckpointManager, restore target, on-device
greedy evaluator) so `scripts/eval_fused.py` and `scripts/eval_sweep.py`
cannot drift — the n_eval rounding here is load-bearing: the evaluator
shards its env batch over the mesh's data axis, so the env count must be a
positive multiple of it or envs are silently dropped (and a threshold gate
like ``n >= nr_eval`` becomes unsatisfiable).
"""

from __future__ import annotations

import jax

from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.envs import jaxenv
from distributed_ba3c_tpu.fused.loop import make_greedy_eval
from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
from distributed_ba3c_tpu.ops.gradproc import make_optimizer
from distributed_ba3c_tpu.parallel.mesh import DATA_AXIS, make_mesh
from distributed_ba3c_tpu.parallel.train_step import create_train_state
from distributed_ba3c_tpu.train.checkpoint import CheckpointManager


def make_checkpoint_evaluator(
    env_spec: str, load: str, nr_eval: int, max_steps: int, fc_units: int = 512
):
    """Returns ``(mgr, target, evaluate, n_eval)``.

    ``target`` is a host-side TrainState structure for ``mgr.restore``;
    ``evaluate(params, seed_int)`` runs the on-device greedy Evaluator over
    ``n_eval`` envs (``nr_eval`` rounded up to a positive multiple of the
    mesh's data-axis size).
    """
    env = jaxenv.get_env(env_spec.split(":", 1)[1])
    cfg = BA3CConfig(num_actions=env.num_actions, fc_units=fc_units)
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    target = jax.device_get(
        create_train_state(jax.random.PRNGKey(0), model, cfg, opt)
    )
    mgr = CheckpointManager(load)
    mesh = make_mesh()
    n_data = mesh.shape[DATA_AXIS]
    n_eval = max(n_data, (max(nr_eval, 1) + n_data - 1) // n_data * n_data)
    evaluate = make_greedy_eval(
        model, cfg, mesh, env, n_envs=n_eval, max_steps=max_steps
    )
    return mgr, target, evaluate, n_eval
