"""V-trace (IMPALA-style) train step over rollout batches.

BASELINE.json config #4: "BA3C + V-trace off-policy correction under
actor/learner lag". The reference tolerated actor/learner staleness silently
(async PS updates, SURVEY.md §3.4); the synchronous TPU learner corrects it
explicitly with clipped importance weights (ops/vtrace.py).

Batch layout (time-major, matching the reverse scan):
    state:              [T, B, H, W, C] uint8
    action:             [T, B] int32
    reward:             [T, B] float32
    done:               [T, B] float32/bool
    behavior_log_probs: [T, B] float32  (log mu(a|s) recorded by the actor)
    bootstrap_state:    [B, H, W, C] uint8 (s_T for the value bootstrap)

Sharding: batch axis B over the mesh's data axis; the model forward runs on
[T*B] flattened states so the convs see one large MXU-friendly batch.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ba3c_tpu.audit import tripwire_jit
from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.models.a3c import BA3CNet
from distributed_ba3c_tpu.models.policy import refuse_carry
from distributed_ba3c_tpu.ops.gradproc import grad_summaries
from distributed_ba3c_tpu.ops.vtrace import vtrace_returns
from distributed_ba3c_tpu.parallel.mesh import DATA_AXIS, shard_local
from distributed_ba3c_tpu.parallel.train_step import (
    TrainState,
    apply_grads,
    macro_accumulate,
)


def _make_vtrace_loss_fn(
    model: BA3CNet,
    cfg: BA3CConfig,
    batch: Dict[str, jax.Array],
    entropy_beta: jax.Array,
):
    """The per-(sub-)batch V-trace loss closure — ONE definition shared by
    the single step and the multi-fleet macro step (the macro step must
    optimize exactly the single step's objective, sub-batch by sub-batch;
    V-trace couples TIME within an env column but never envs, so equal-size
    sub-batch gradient means equal the full-batch gradient)."""
    T, B = batch["action"].shape

    def loss_fn(params):
        # one big forward over T*B + B states (conv batch stays MXU-sized)
        flat = batch["state"].reshape((T * B, *batch["state"].shape[2:]))
        all_states = jnp.concatenate([flat, batch["bootstrap_state"]], axis=0)
        out = model.apply({"params": params}, all_states)
        logits = out.logits[: T * B].reshape((T, B, -1))
        values = out.value[: T * B].reshape((T, B))
        bootstrap_value = out.value[T * B :]

        log_probs = jax.nn.log_softmax(logits, axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)
        target_lp = jnp.take_along_axis(
            log_probs, batch["action"][..., None].astype(jnp.int32), axis=-1
        )[..., 0]

        vt = vtrace_returns(
            behaviour_log_probs=batch["behavior_log_probs"],
            target_log_probs=jax.lax.stop_gradient(target_lp),
            rewards=batch["reward"],
            dones=batch["done"],
            values=jax.lax.stop_gradient(values),
            bootstrap_value=jax.lax.stop_gradient(bootstrap_value),
            gamma=cfg.gamma,
        )

        policy_loss = -jnp.mean(target_lp * vt.pg_advantages)
        value_loss = 0.5 * jnp.mean(jnp.square(values - vt.vs))
        entropy = -jnp.mean(jnp.sum(probs * log_probs, axis=-1))
        total = (
            policy_loss
            + cfg.value_loss_coef * value_loss
            - entropy_beta * entropy
        )
        aux = {
            "loss": total,
            "policy_loss": policy_loss,
            "value_loss": value_loss,
            "entropy": entropy,
            "mean_rho": jnp.mean(vt.clipped_rhos),
            "pred_value": jnp.mean(values),
        }
        return total, aux

    return loss_fn


def _local_step(
    model: BA3CNet,
    optimizer: optax.GradientTransformation,
    cfg: BA3CConfig,
    state: TrainState,
    batch: Dict[str, jax.Array],
    entropy_beta: jax.Array,
    learning_rate: jax.Array,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    loss_fn = _make_vtrace_loss_fn(model, cfg, batch, entropy_beta)
    (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        shard_local(state.params)
    )
    grads = jax.lax.psum(grads, DATA_AXIS)
    n_data = jax.lax.axis_size(DATA_AXIS)
    grads = jax.tree_util.tree_map(lambda g: g / n_data, grads)

    new_state = apply_grads(optimizer, state, grads, learning_rate)
    metrics = {**aux, **grad_summaries(grads)}
    metrics = {k: jax.lax.pmean(v, DATA_AXIS) for k, v in metrics.items()}
    return new_state, metrics


def make_vtrace_train_step(
    model: BA3CNet,
    optimizer: optax.GradientTransformation,
    cfg: BA3CConfig,
    mesh: Mesh,
) -> Callable:
    """Jitted mesh-sharded V-trace step: fn(state, batch, beta, lr)."""
    refuse_carry(model, "the V-trace actor-plane learner")
    replicated = P()
    specs = {
        "state": P(None, DATA_AXIS),
        "action": P(None, DATA_AXIS),
        "reward": P(None, DATA_AXIS),
        "done": P(None, DATA_AXIS),
        "behavior_log_probs": P(None, DATA_AXIS),
        "bootstrap_state": P(DATA_AXIS),
    }
    body = functools.partial(_local_step, model, optimizer, cfg)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(replicated, specs, replicated, replicated),
        out_specs=(replicated, replicated),
    )
    # registered audit entry point (distributed_ba3c_tpu/audit.py)
    jitted = tripwire_jit("parallel.vtrace_step", sharded, donate_argnums=(0,))

    def step(state, batch, entropy_beta, learning_rate=None):
        if learning_rate is None:
            learning_rate = cfg.learning_rate
        return jitted(
            state,
            batch,
            jnp.asarray(entropy_beta, jnp.float32),
            jnp.asarray(learning_rate, jnp.float32),
        )

    step.batch_sharding = {
        k: NamedSharding(mesh, s) for k, s in specs.items()
    }
    step.state_sharding = NamedSharding(mesh, replicated)
    step.mesh = mesh
    step.audit_jit = jitted  # tools/ba3caudit traces THIS program
    return step


def make_vtrace_macro_step(
    model: BA3CNet,
    optimizer: optax.GradientTransformation,
    cfg: BA3CConfig,
    mesh: Mesh,
    n_fleets: int,
) -> Callable:
    """The multi-fleet V-trace macro step: N fleet sub-batches, ONE update.

    Batch layout: every make_vtrace_train_step leaf gains a leading FLEET
    axis (``state [K, T, B, ...]``, ``bootstrap_state [K, B, ...]``, ...)
    and the FLEET axis shards over the mesh's data axis — whole fleets to
    chips, never ``B/D`` slivers, so each chip's fwd+bwd runs the full
    per-fleet unroll batch (docs/actor_plane.md). Chips hosting several
    fleets accumulate sequentially (parallel/train_step.py
    macro_accumulate); ONE gradient psum means over every fleet. V-trace
    couples time within an env column but never envs, so the accumulated
    mean equals the ``[T, K*B]`` full-batch gradient to fp tolerance
    (tests/test_fleet.py pins it).

    Registered audit entry: ``parallel.vtrace_macro_step``.
    """
    refuse_carry(model, "the V-trace macro learner")
    if n_fleets < 1:
        raise ValueError(f"n_fleets must be >= 1, got {n_fleets}")
    n_data = mesh.shape[DATA_AXIS]
    if n_fleets % n_data:
        raise ValueError(
            f"n_fleets {n_fleets} must be divisible by the mesh data axis "
            f"{n_data}: fleets shard fleet-major over chips (whole "
            "sub-batches, never slivers)"
        )
    n_local = n_fleets // n_data

    def local_macro_step(state, batch, entropy_beta, learning_rate):
        def loss_grad_one(params, sub):
            loss_fn = _make_vtrace_loss_fn(model, cfg, sub, entropy_beta)
            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        grads, aux = macro_accumulate(
            loss_grad_one, state.params, batch, n_local
        )
        # ONE collective for the whole macro batch (T3 census unchanged)
        grads = jax.lax.psum(grads, DATA_AXIS)
        grads = jax.tree_util.tree_map(lambda g: g / n_data, grads)
        new_state = apply_grads(optimizer, state, grads, learning_rate)
        metrics = {**aux, **grad_summaries(grads)}
        metrics = {k: jax.lax.pmean(v, DATA_AXIS) for k, v in metrics.items()}
        return new_state, metrics

    replicated = P()
    fleet_spec = P(DATA_AXIS)  # leading = FLEET axis on every leaf
    specs = {
        "state": fleet_spec,
        "action": fleet_spec,
        "reward": fleet_spec,
        "done": fleet_spec,
        "behavior_log_probs": fleet_spec,
        "bootstrap_state": fleet_spec,
    }
    sharded = jax.shard_map(
        local_macro_step,
        mesh=mesh,
        in_specs=(replicated, specs, replicated, replicated),
        out_specs=(replicated, replicated),
    )
    # registered audit entry point (distributed_ba3c_tpu/audit.py)
    jitted = tripwire_jit(
        "parallel.vtrace_macro_step", sharded, donate_argnums=(0,)
    )

    def step(state, batch, entropy_beta, learning_rate=None):
        if learning_rate is None:
            learning_rate = cfg.learning_rate
        return jitted(
            state,
            batch,
            jnp.asarray(entropy_beta, jnp.float32),
            jnp.asarray(learning_rate, jnp.float32),
        )

    step.batch_sharding = {
        k: NamedSharding(mesh, s) for k, s in specs.items()
    }
    step.state_sharding = NamedSharding(mesh, replicated)
    step.mesh = mesh
    step.n_fleets = n_fleets
    step.audit_jit = jitted  # tools/ba3caudit traces THIS program
    return step
