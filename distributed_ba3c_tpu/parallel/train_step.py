"""The synchronous data-parallel BA3C train step.

Replaces, wholesale (SURVEY.md §3.4): the reference's
``sess.run(train_op)`` → per-variable async gradient push to parameter servers
over gRPC. Here: each device computes gradients on its batch shard, a single
``lax.psum`` averages them over the ICI ``data`` axis, and every device applies
the identical Adam update to its replicated params. One jitted computation, no
staleness, no PS.

Sharding layout:
  params/opt_state: replicated (PartitionSpec())
  batch:            sharded on the leading axis (PartitionSpec('data'))
The step is expressed with ``jax.shard_map`` so the collective is explicit and
the compiled module is identical regardless of host count (multi-host just
widens the mesh; see parallel/distributed.py).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ba3c_tpu.audit import tripwire_jit
from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.models import policy
from distributed_ba3c_tpu.models.a3c import BA3CNet
from distributed_ba3c_tpu.ops.gradproc import grad_summaries, inject_learning_rate
from distributed_ba3c_tpu.ops.loss import a3c_loss
from distributed_ba3c_tpu.parallel.mesh import DATA_AXIS, shard_local


class TrainState(struct.PyTreeNode):
    """Learner state: params + optimizer state + step counter.

    Reference equivalent: the TF variables living on parameter servers plus the
    global_step (SURVEY.md §2.5). Replicated across the mesh.
    """

    step: jax.Array
    params: Any
    opt_state: Any


def create_train_state(
    rng: jax.Array,
    model: BA3CNet,
    cfg: BA3CConfig,
    optimizer: optax.GradientTransformation,
) -> TrainState:
    params = policy.init_params(model, rng, cfg)
    opt_state = optimizer.init(params)
    if inject_learning_rate(opt_state, 0.0) is opt_state:
        from distributed_ba3c_tpu.utils import logger

        logger.warn(
            "optimizer has no injectable learning_rate leaf — runtime LR "
            "schedules (ScheduledHyperParamSetter etc.) will be SILENT no-ops;"
            " build it with ops.gradproc.make_optimizer"
        )
    return TrainState(step=jnp.zeros((), jnp.int32), params=params, opt_state=opt_state)


def _make_loss_fn(
    model: BA3CNet,
    cfg: BA3CConfig,
    batch: Dict[str, jax.Array],
    entropy_beta: jax.Array,
):
    """The per-(sub-)batch A3C loss closure — ONE definition shared by the
    single step and the multi-fleet macro step (parity between the two is a
    contract, not luck: the macro step must optimize exactly the objective
    the single step does, sub-batch by sub-batch)."""

    def loss_fn(params):
        out = model.apply({"params": params}, batch["state"])
        loss = a3c_loss(
            out.logits,
            out.value,
            batch["action"],
            batch["return"],
            entropy_beta=entropy_beta,
            value_loss_coef=cfg.value_loss_coef,
            huber_delta=cfg.value_huber_delta,
        )
        return loss.total, loss

    return loss_fn


def apply_grads(
    optimizer: optax.GradientTransformation,
    state: TrainState,
    grads,
    learning_rate: jax.Array,
) -> TrainState:
    """Shared tail of every learner step: LR injection + Adam + step bump."""
    opt_state = inject_learning_rate(state.opt_state, learning_rate)
    updates, new_opt_state = optimizer.update(grads, opt_state, state.params)
    new_params = optax.apply_updates(state.params, updates)
    return TrainState(
        step=state.step + 1, params=new_params, opt_state=new_opt_state
    )


def _local_step(
    model: BA3CNet,
    optimizer: optax.GradientTransformation,
    cfg: BA3CConfig,
    state: TrainState,
    batch: Dict[str, jax.Array],
    entropy_beta: jax.Array,
    learning_rate: jax.Array,
) -> Tuple[TrainState, Dict[str, jax.Array]]:
    """Per-device shard-local step body; runs inside shard_map."""

    loss_fn = _make_loss_fn(model, cfg, batch, entropy_beta)
    (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        shard_local(state.params)
    )

    # The one collective that replaces the reference's whole PS gradient
    # plane: shard-local grads (parallel/mesh.py shard_local) summed over the
    # data axis, then divided by its size for the global batch mean.
    # tools/ba3caudit T3 counts exactly one such reduction per param leaf.
    grads = jax.lax.psum(grads, DATA_AXIS)
    n_data = jax.lax.axis_size(DATA_AXIS)
    grads = jax.tree_util.tree_map(lambda g: g / n_data, grads)

    new_state = apply_grads(optimizer, state, grads, learning_rate)

    metrics = {
        "loss": aux.total,
        "policy_loss": aux.policy_loss,
        "value_loss": aux.value_loss,
        "entropy": aux.entropy,
        "advantage": aux.advantage,
        "pred_value": aux.pred_value,
        **grad_summaries(grads),
    }
    metrics = {k: jax.lax.pmean(v, DATA_AXIS) for k, v in metrics.items()}
    return new_state, metrics


def make_train_step(
    model: BA3CNet,
    optimizer: optax.GradientTransformation,
    cfg: BA3CConfig,
    mesh: Mesh,
) -> Callable[[TrainState, Dict[str, jax.Array], jax.Array], Tuple[TrainState, Dict[str, jax.Array]]]:
    """Build the jitted, mesh-sharded train step.

    Returns fn(state, batch, entropy_beta) -> (state, metrics) with donated
    state buffers. ``batch`` leading dim must be divisible by the mesh's data
    axis size. Place the first ``state`` with ``step.state_sharding`` (the
    Trainer does): jit keys its trace on input shardings, so an unplaced
    state compiles the step once for itself and once more for the
    mesh-replicated state the step returns.
    """
    policy.refuse_carry(model, "the sync data-parallel train step")
    replicated = P()
    batch_spec = P(DATA_AXIS)

    body = functools.partial(_local_step, model, optimizer, cfg)
    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(replicated, batch_spec, replicated, replicated),
        out_specs=(replicated, replicated),
    )

    # registered audit entry point (distributed_ba3c_tpu/audit.py): under
    # BA3C_AUDIT=1 a post-warmup retrace raises instead of silently stalling
    jitted = tripwire_jit("parallel.train_step", sharded, donate_argnums=(0,))

    def step(state, batch, entropy_beta, learning_rate=None):
        if learning_rate is None:
            learning_rate = cfg.learning_rate
        return jitted(
            state,
            batch,
            jnp.asarray(entropy_beta, jnp.float32),
            jnp.asarray(learning_rate, jnp.float32),
        )

    # expose shardings so callers can device_put batches asynchronously
    step.batch_sharding = NamedSharding(mesh, batch_spec)
    step.state_sharding = NamedSharding(mesh, replicated)
    step.mesh = mesh
    step.audit_jit = jitted  # tools/ba3caudit traces THIS program
    return step


def macro_accumulate(loss_grad_one, params, batch, n_local: int):
    """Mean of per-sub-batch (grads, aux) over the local fleet axis.

    ``batch`` leaves are ``[n_local, ...]`` (this shard's fleets);
    ``loss_grad_one(params, sub)`` returns ``((loss, aux), grads)``. The
    accumulation is a ``lax.scan`` over fleets — ONE fwd+bwd program
    reused per sub-batch, activations bounded to a single sub-batch (the
    whole point: every sub-batch runs at its full per-chip occupancy
    instead of a 1/K sliver). Mean-of-equal-size-sub-batch grads equals
    the full-macro-batch gradient; tests/test_fleet.py pins it to fp
    tolerance against the single step on the concatenated batch.

    Shared by the BA3C and V-trace macro steps — the accumulation
    schedule (first sub-batch unrolled, rest scanned, symmetric mean) is
    one definition, same idiom as the fused learner's chunk accumulation
    (fused/loop.py). The returned grads are SHARD-LOCAL (differentiated
    against :func:`shard_local` params): the caller owns the update's one
    psum.
    """
    params = shard_local(params)
    first = jax.tree_util.tree_map(lambda x: x[0], batch)
    (_, aux0), g0 = loss_grad_one(params, first)
    if n_local == 1:
        return g0, aux0

    def acc_body(carry, sub):
        g_acc, aux_acc = carry
        (_, aux), g = loss_grad_one(params, sub)
        g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
        aux_acc = jax.tree_util.tree_map(jnp.add, aux_acc, aux)
        return (g_acc, aux_acc), None

    rest = jax.tree_util.tree_map(lambda x: x[1:], batch)
    (grads, aux_sum), _ = jax.lax.scan(acc_body, (g0, aux0), rest)
    grads = jax.tree_util.tree_map(lambda g: g / n_local, grads)
    aux = jax.tree_util.tree_map(lambda a: a / n_local, aux_sum)
    return grads, aux


def make_macro_train_step(
    model: BA3CNet,
    optimizer: optax.GradientTransformation,
    cfg: BA3CConfig,
    mesh: Mesh,
    n_fleets: int,
) -> Callable:
    """The multi-fleet macro step: N fleet sub-batches, ONE update.

    Batch layout (vs make_train_step's flat ``[B]`` leaves): every leaf
    gains a leading FLEET axis — ``state [K, B, ...]``, ``action [K, B]``,
    ``return [K, B]`` — and it is the FLEET axis that shards over the
    mesh's data axis. That inversion is the macro-batching contract
    (docs/actor_plane.md): a data-parallel deployment assigns whole fleets
    to chips, so each chip's fwd+bwd runs at the full per-fleet batch ``B``
    (the recipe batch) instead of the ``B/D`` sliver that wastes the MXU
    (PERF.md's 65.6k -> ~38k shard ladder). Chips hosting several fleets
    (K > D) accumulate their sub-batch gradients sequentially; the one
    gradient psum then means over every fleet — mathematically the
    ``[K*B]`` full-batch update, structurally K full-occupancy programs.

    Registered audit entry: ``parallel.train_macro_step``.
    """
    policy.refuse_carry(model, "the multi-fleet macro train step")
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
            loss_fn = _make_loss_fn(model, cfg, sub, entropy_beta)
            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        grads, aux = macro_accumulate(
            loss_grad_one, state.params, batch, n_local
        )
        # ONE collective for the whole macro batch (T3 census unchanged):
        # the psum sums over the data axis, the divide completes the mean
        # over all K fleets
        grads = jax.lax.psum(grads, DATA_AXIS)
        grads = jax.tree_util.tree_map(lambda g: g / n_data, grads)
        new_state = apply_grads(optimizer, state, grads, learning_rate)
        metrics = {
            "loss": aux.total,
            "policy_loss": aux.policy_loss,
            "value_loss": aux.value_loss,
            "entropy": aux.entropy,
            "advantage": aux.advantage,
            "pred_value": aux.pred_value,
            **grad_summaries(grads),
        }
        metrics = {k: jax.lax.pmean(v, DATA_AXIS) for k, v in metrics.items()}
        return new_state, metrics

    replicated = P()
    batch_spec = P(DATA_AXIS)  # leading = FLEET axis
    sharded = jax.shard_map(
        local_macro_step,
        mesh=mesh,
        in_specs=(replicated, batch_spec, replicated, replicated),
        out_specs=(replicated, replicated),
    )
    # registered audit entry point (distributed_ba3c_tpu/audit.py)
    jitted = tripwire_jit(
        "parallel.train_macro_step", sharded, donate_argnums=(0,)
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

    step.batch_sharding = NamedSharding(mesh, batch_spec)
    step.state_sharding = NamedSharding(mesh, replicated)
    step.mesh = mesh
    step.n_fleets = n_fleets
    step.audit_jit = jitted  # tools/ba3caudit traces THIS program
    return step
