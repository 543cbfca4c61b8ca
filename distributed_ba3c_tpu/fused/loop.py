"""The fused rollout+update step and its training loop.

Structure of one fused step (all inside one jit, shard_map'd over the mesh's
``data`` axis; B envs per device):

    lax.scan over T rollout steps:
        forward policy on the frame stack  (bf16 convs on the MXU; in
                                            sub-batches when B is large)
        sample actions (on-device categorical)
        vmap(env.step): physics + uint8 render for B envs
        update frame stacks, episode-return accumulators
    bootstrap value on the final stacks
    n-step returns (reverse scan, done-masked)   ops/returns.py
    a3c loss over the [T*B] flat batch           ops/loss.py
    grads → mean over data axis → Adam update    (the one collective)

A policy that carries state (models/policy.py; a token-sequence model with
a K/V cache and short-conv state) runs through the same step: its carry
rides in the rollout scan and in ``FusedState.policy_carry``, reset where an
episode ends; the rollout is served from one bfloat16 snapshot of the
weights an update; the learner takes whole episodes ``[B, T]`` in chunks of
envs through the policy's causal unroll. Everything else (returns, the
chunked-gradient scan, the one psum, clip + Adam, metrics) is shared.

The rollout forward runs without gradient tracking; the loss recomputes the
forward over the collected stacks — standard A2C, and on TPU the recompute is
cheaper than storing activations (HBM-bandwidth-bound regime).

Actor/learner lag is ZERO here (perfectly on-policy), so the plain A3C loss
is exact; the V-trace path exists for the lagged ZMQ plane.

RNG layout: ``FusedState.key`` is a [n_shards] typed-key array sharded over
the data axis — each shard consumes its own stream, so no two devices roll
identical envs. Episode stats are per-env arrays (sharded with the env
batch) and psum'd into scalars only inside the metrics.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ba3c_tpu.audit import tripwire_jit
from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.models import a3c as a3c_model, policy
from distributed_ba3c_tpu.models.a3c import BA3CNet
from distributed_ba3c_tpu.ops.gradproc import grad_summaries, inject_learning_rate
from distributed_ba3c_tpu.ops.loss import a3c_loss
from distributed_ba3c_tpu.ops.returns import n_step_returns
from distributed_ba3c_tpu.parallel.mesh import DATA_AXIS, shard_local
from distributed_ba3c_tpu.parallel.train_step import TrainState
from distributed_ba3c_tpu.utils import backend, profiling
from distributed_ba3c_tpu.utils.profiling import device_scope, host_span

#: metrics that accumulate IN STATE across an epoch (reset by the outer
#: loop): the K-step scan reduction takes their LAST value, every other
#: metric is mean-averaged over the dispatch window. local_step asserts
#: each of these is in its metrics dict so the two sites cannot
#: desynchronize (ADVICE r4 #3).
CUMULATIVE_METRICS = ("episodes", "episode_return_sum")
#: a sequence policy's update also gives what it generated: the tokens each
#: env showed and the actions drawn, ``[T, B_global]`` int32 (131 KB each at
#: 128 x 256). The K-step scan keeps the last update's. What reads them: a
#: run's log of its own generations, and the benchmark's comparison, which
#: used to read the actions off a second compiled rollout (two compilations
#: of one bfloat16 forward do not draw the same 32,768 tokens: PERF.md, PR 26)
TRAJECTORY_METRICS = ("tokens", "actions")

#: ``BA3CNet``'s measured number and rule live beside the model
#: (models/a3c.py): how many stacks an inference forward takes at once
forward_sub_batch = a3c_model.forward_sub_batch


def sub_batched(apply_fn):
    """``apply_fn(params, stack)`` in sequential sub-batches of
    :func:`forward_sub_batch` stacks, or as it is where that says None. The
    network has no op across samples: each sample's arithmetic is the same
    either way."""

    def apply(params, stack):
        B = stack.shape[0]
        size = forward_sub_batch(B)
        if size is None:
            return apply_fn(params, stack)
        # one scope component, "sub_batch": under ``returns`` the same
        # call reads as profiling.RETURNS_SUB_BATCH
        with device_scope(profiling.ROLLOUT_POLICY_SUB_BATCH):
            # slices of the batch where it lies, not rows of a reshape to
            # [B // size, size, ...]: the v5e keeps the batch of these
            # frames on the lanes, and there that reshape moves every frame
            # (PERF.md, PR 25: 0.28 us a sample, against 0.07 this way)
            out = jax.lax.map(
                lambda start: apply_fn(
                    params, jax.lax.dynamic_slice_in_dim(stack, start, size)
                ),
                jnp.arange(0, B, size),
            )
        return jax.tree_util.tree_map(
            lambda x: x.reshape(B, *x.shape[2:]), out
        )

    return apply


def learner_chunks(n_rows: int, n_samples: int, grad_chunk_samples: int) -> int:
    """Equal chunks the learner cuts ``n_rows`` rows of ``n_samples``
    transitions into: the fewest of at most ``grad_chunk_samples`` each
    that divide the rows (a row is one transition, or for a sequence
    policy one env's whole episode)."""
    n_chunks = max(1, -(-n_samples // grad_chunk_samples))
    while n_rows % n_chunks:
        n_chunks += 1
    return n_chunks


class FusedState(struct.PyTreeNode):
    train: TrainState
    env_state: Any            # batched env pytree, leaves [B_global, ...]
    obs_stack: jax.Array      # [B_global, H, W, hist] uint8; of a token env
                              # the int32 token each env shows, [B_global]
    key: jax.Array            # [n_shards] typed PRNG keys, sharded on data axis
    ep_return: jax.Array      # [B_global] running episode return
    ep_count: jax.Array       # [B_global] int32 completed episodes per env
    ep_return_sum: jax.Array  # [B_global] float32 sum of completed returns per env
    #: of a policy that carries state (models/policy.py): (the policy's
    #: carry, leaves [B_global, ...]; bool [B_global], the next observation
    #: opens an episode). Empty for a stateless policy.
    policy_carry: Any = ()


def rollout_sub_batch_of(mesh: Mesh) -> Callable:
    """fn(n_envs) -> :func:`forward_sub_batch` of one shard of ``n_envs``
    global envs on ``mesh`` (a built step's ``rollout_sub_batch``)."""
    return lambda n_envs: forward_sub_batch(n_envs // mesh.shape[DATA_AXIS])


def make_rollout_body(model, cfg: BA3CConfig, env, params,
                      record_log_probs: bool = False, apply_fn=None):
    """The per-step rollout scan body — ONE implementation shared by the
    fused step and the overlap actor program (fused/overlap.py).

    Sharing it is what makes the overlap path's lag-0 parity test a real
    contract: both programs consume the identical key sequence and action
    sampling math, so a frozen-params run is bit-exact across them. With
    ``record_log_probs`` the trajectory tuple grows a fifth element —
    log mu(a_t|s_t) of the sampled action (the V-trace behavior term);
    without it the emitted jaxpr is unchanged from the pre-split fused
    body (the audit manifest pins that).

    ``apply_fn(params, stack) -> PolicyValue`` overrides the forward
    while keeping the key sequence/sampling math identical — the int8
    actor program (quantize/qforward.py) passes its quantized apply and
    ``params`` becomes the int8 serving table. Whichever forward it is
    runs through :func:`sub_batched`: the split has to be made HERE, so
    that every program built from this body draws the same actions.

    For a policy that carries state the body's carry has a seventh
    element, ``(policy carry, fresh)``, beside the stateless six; the
    observation is the token the env shows, ``params`` is what
    ``model.rollout_params`` gave, and the forward is ``model.step``, never
    split (``FORWARD_SUB_BATCH`` is ``BA3CNet``'s number).
    """
    def draw_and_step(key, logits, env_state):
        B = logits.shape[0]
        with device_scope(profiling.ROLLOUT_SAMPLE):
            key, k_act, k_env = jax.random.split(key, 3)
            actions = jax.random.categorical(
                k_act, logits, axis=-1
            ).astype(jnp.int32)
        with device_scope(profiling.ROLLOUT_ENV_STEP):
            env_keys = jax.random.split(k_env, B)
            env_state, obs, reward, done = jax.vmap(env.step)(
                env_state, actions, env_keys
            )
        return key, actions, env_state, obs, reward, done

    def account(reward, done, ep_ret, ep_cnt, ep_sum):
        # episode bookkeeping (done ⇒ env auto-restarted inside step);
        # scores accumulate RAW rewards, the learner sees clipped ones
        ep_ret = ep_ret + reward
        donef = done.astype(jnp.float32)
        ep_sum = ep_sum + ep_ret * donef
        ep_cnt = ep_cnt + done.astype(jnp.int32)
        ep_ret = ep_ret * (1.0 - donef)
        r_learn = (
            jnp.clip(reward, -cfg.reward_clip, cfg.reward_clip)
            if cfg.reward_clip
            else reward
        )
        return ep_ret, ep_cnt, ep_sum, donef, r_learn

    if policy.carries_state(model):
        if apply_fn is not None or record_log_probs:
            raise ValueError(
                "a policy that carries state is served by its own step: no "
                "apply_fn override and no behaviour log-probs"
            )

        def sequence_body(carry, _):
            # the stateless body's six, and beside them (the policy's carry,
            # which envs' observation opens an episode)
            env_state, obs, key, ep_ret, ep_cnt, ep_sum, (held, fresh) = carry
            with device_scope(profiling.ROLLOUT_POLICY):
                out, held = model.step(params, obs, held, fresh)
            key, actions, env_state, new_obs, reward, done = draw_and_step(
                key, out.logits, env_state
            )
            with device_scope(profiling.ROLLOUT_STACK):
                ep_ret, ep_cnt, ep_sum, donef, r_learn = account(
                    reward, done, ep_ret, ep_cnt, ep_sum
                )
            return (env_state, new_obs, key, ep_ret, ep_cnt, ep_sum,
                    (held, done)), (obs, actions, r_learn, donef)

        return sequence_body

    if apply_fn is None:
        apply_fn = lambda p, stack: model.apply({"params": p}, stack)  # noqa: E731
    apply_fn = sub_batched(apply_fn)

    def rollout_body(carry, _):
        env_state, stack, key, ep_ret, ep_cnt, ep_sum = carry
        with device_scope(profiling.ROLLOUT_POLICY):
            out = apply_fn(params, stack)
        key, actions, env_state, obs, reward, done = draw_and_step(
            key, out.logits, env_state
        )
        with device_scope(profiling.ROLLOUT_STACK):
            # a done frame must not leak history into the new episode: zero
            # the carried history via a mask multiply (single fused pass —
            # cheaper than building a zeroed copy and where-selecting)
            keep = (~done).astype(stack.dtype)[:, None, None, None]
            new_stack = jnp.concatenate(
                [stack[..., 1:] * keep, obs[..., None]], axis=-1
            )
            ep_ret, ep_cnt, ep_sum, donef, r_learn = account(
                reward, done, ep_ret, ep_cnt, ep_sum
            )
        ys = (stack, actions, r_learn, donef)
        if record_log_probs:
            # behavior log-prob of the SAMPLED action at the ROLLOUT
            # policy — the mu term of the V-trace correction — plus the
            # behavior value (the learner's value-drift-across-lag
            # diagnostic, and it keeps the value head LIVE in the actor
            # program so jit input pruning cannot renumber the donated
            # leaves the T2 audit pins). The heads always emit f32
            # (models/a3c.py), so both stay f32 even under a bf16
            # rollout-forward snapshot.
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(out.logits, axis=-1),
                actions[:, None], axis=-1,
            )[:, 0]
            ys = ys + (lp, out.value)
        return (env_state, new_stack, key, ep_ret, ep_cnt, ep_sum), ys

    return rollout_body


def make_put_batched(batched: "NamedSharding"):
    """Host array (GLOBAL shape) -> array sharded on the data axis.

    Multi-host: every process builds the identical global state (same
    PRNG seed) and contributes its host-major row block — the mesh's
    data axis is laid out host-major (parallel/distributed.py), so the
    local rows are exactly this process's slice. Shared by the fused and
    overlap steps so their multi-host placement cannot drift."""

    def _put_batched(x):
        n_proc = jax.process_count()
        if n_proc == 1:
            return jax.device_put(x, batched)
        x = np.asarray(x)
        B = x.shape[0]
        assert B % n_proc == 0, (B, n_proc)
        per = B // n_proc
        k = jax.process_index()
        return jax.make_array_from_process_local_data(
            batched, x[k * per : (k + 1) * per]
        )

    return _put_batched


def create_fused_state(
    rng: jax.Array,
    model: BA3CNet,
    cfg: BA3CConfig,
    optimizer: optax.GradientTransformation,
    env,
    n_envs: int,
    n_shards: int = 1,
) -> FusedState:
    """Build the global fused state (host-side; ``jax.device_put`` it with the
    step's ``state_sharding`` before use)."""
    from distributed_ba3c_tpu.parallel.train_step import create_train_state

    train = create_train_state(rng, model, cfg, optimizer)
    keys = jax.random.split(jax.random.fold_in(rng, 1), n_envs)
    env_state = jax.vmap(env.reset)(keys)
    obs = jax.vmap(env.render)(env_state)  # [B, H, W], or [B] token ids
    policy_carry = ()
    if policy.carries_state(model):
        stack = obs
        policy_carry = (model.init_carry(n_envs), jnp.ones(n_envs, bool))
    else:
        stack = jnp.zeros(
            (n_envs, *obs.shape[1:], cfg.frame_history), jnp.uint8)
        stack = stack.at[..., -1].set(obs)
    shard_keys = jax.vmap(
        lambda i: jax.random.fold_in(jax.random.fold_in(rng, 2), i)
    )(jnp.arange(n_shards))
    return FusedState(
        train=train,
        env_state=env_state,
        obs_stack=stack,
        key=shard_keys,
        ep_return=jnp.zeros(n_envs, jnp.float32),
        ep_count=jnp.zeros(n_envs, jnp.int32),
        ep_return_sum=jnp.zeros(n_envs, jnp.float32),
        policy_carry=policy_carry,
    )


def make_fused_step(
    model: BA3CNet,
    optimizer: optax.GradientTransformation,
    cfg: BA3CConfig,
    mesh: Mesh,
    env,
    rollout_len: int = 20,
    grad_chunk_samples: int = 4096,
    steps_per_dispatch: int = 1,
) -> Callable:
    """Build fn(state, entropy_beta, lr) -> (state, metrics), fully on-device.

    ``grad_chunk_samples`` bounds the per-fwd+bwd batch in the learner (HBM
    activation cap). Measured on the 16 GB v5e in an earlier round, on
    other code: 5120 fits inside the full fused program, 10240 OOMs;
    throughput is flat across 1024-5120 (the convs' MXU utilization is
    channel-count-bound, not batch-bound), so the default stays comfortably
    under the cliff.

    ``steps_per_dispatch`` > 1 wraps that many full update steps in one
    ``lax.scan`` inside the jitted program: one host dispatch per K updates.
    At small per-step programs the per-dispatch host overhead is a tax
    unless host pipelining hides it; scanning removes the dependence on
    pipelining entirely. β/lr are scan-carried scalars, so one
    dispatch spans only steps sharing a hyperparam setting (the epoch loop
    already changes them per epoch only).

    A policy that carries state (``sequence`` below) is learned from whole
    episodes: ``rollout_len`` has to be the env's episode length, so that
    every segment starts at a reset and the learner's unroll starts from
    the empty carry. A segment that starts mid-episode would need the carry
    at its first step on the learner's side (ROADMAP A1) and is refused.
    """
    sequence = policy.carries_state(model)
    if sequence and getattr(env, "episode_length", None) != rollout_len:
        raise ValueError(
            f"{type(model).__name__} carries state and is learned from whole "
            f"episodes: --rollout_len {rollout_len} has to be the env's "
            f"episode length ({getattr(env, 'episode_length', None)}); a "
            "segment that starts mid-episode is not supported"
        )

    def carry_gauges(policy_carry):
        if not sequence or not hasattr(model, "carry_gauges"):
            return {}
        return model.carry_gauges(policy_carry[0])

    def local_step(state: FusedState, entropy_beta, learning_rate):
        params = state.train.params
        key = state.key[0]  # this shard's scalar key

        act_params = params
        if sequence:
            # every decode step reads every weight: read 2 bytes of each,
            # from one snapshot an update, not 4 from the float32 table
            with device_scope(profiling.ROLLOUT), device_scope(
                    profiling.ROLLOUT_WEIGHTS_BF16):
                act_params = model.rollout_params(params)
        rollout_body = make_rollout_body(model, cfg, env, act_params)

        carry0 = (
            state.env_state,
            state.obs_stack,
            key,
            state.ep_return,
            state.ep_count,
            state.ep_return_sum,
        )
        if sequence:
            carry0 = carry0 + (state.policy_carry,)
        with device_scope(profiling.ROLLOUT):
            carry, traj = jax.lax.scan(
                rollout_body, carry0, None, length=rollout_len
            )
        env_state, stack, key, ep_ret, ep_cnt, ep_sum = carry[:6]
        policy_carry = carry[6] if sequence else ()
        states_t, actions_t, rewards_t, dones_t = traj  # [T, B, ...]

        # bootstrap from the post-rollout stack (no gradient)
        with device_scope(profiling.RETURNS):
            if sequence:
                bootstrap = model.step(act_params, stack, *policy_carry)[0].value
            else:
                bootstrap = sub_batched(
                    lambda p, s: model.apply({"params": p}, s).value
                )(params, stack)
            returns_t = n_step_returns(
                rewards_t, dones_t, jax.lax.stop_gradient(bootstrap), cfg.gamma
            )

        T, B = actions_t.shape

        # Learner: fwd+bwd over the FLAT [T*B] batch in as few chunks as HBM
        # allows. Chunking (equal sizes) only bounds activation memory;
        # mean-of-chunk-grads equals the full-batch gradient. On the v5e the
        # learner's cost a sample is flat in the chunk (PERF.md section 5:
        # 12.61 us at chunks of 2,560, 12.36 at 4,096), so unlike the
        # inference forward (``sub_batched``: cheaper a sample at 256 stacks
        # than at 512 and over) it gains nothing from smaller pieces.
        #
        # A sequence policy's rows are whole episodes ``[B, T]`` and a chunk
        # is so many envs (4,096 samples = 16 envs x 256, or 4 x 1,024): its
        # forward is the policy's causal unroll, which also counts
        # (``counters``: whatever the unroll's ``aux`` holds, summed over the
        # chunks) and may hand over loss terms of its own under
        # ``policy.LOSS_TERMS`` (added to the total, averaged like the loss).
        def chunk_grad(p, chunk):
            states_c, actions_c, returns_c = chunk

            def loss_fn(pp):
                counters, own = {}, {}
                if sequence:
                    out, counters = model.unroll(pp, states_c)
                    # the terms the policy owns (models/policy.py): added to
                    # what is differentiated, whatever they are
                    counters = dict(counters)
                    own = counters.pop(policy.LOSS_TERMS, {})
                else:
                    out = model.apply({"params": pp}, states_c)
                with device_scope(profiling.LEARNER_LOSS):
                    loss = a3c_loss(
                        *(samples(x) for x in (
                            out.logits, out.value, actions_c, returns_c)),
                        entropy_beta=entropy_beta,
                        value_loss_coef=cfg.value_loss_coef,
                        huber_delta=cfg.value_huber_delta,
                    )
                total = loss.total
                for term in own.values():
                    total = total + jnp.sum(term)
                return total, ((loss, own), counters)

            with device_scope(profiling.LEARNER):
                return jax.value_and_grad(loss_fn, has_aux=True)(p)

        if sequence:
            n_rows = B
            flat = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
            # a chunk's [envs, T, ...] as the loss's independent samples
            samples = lambda x: x.reshape(-1, *x.shape[2:])  # noqa: E731
        else:
            n_rows = T * B
            flat = lambda x: x.reshape(T * B, *x.shape[2:])  # noqa: E731
            samples = lambda x: x  # noqa: E731
        states_f, actions_f, returns_f = (
            flat(states_t),
            flat(actions_t),
            flat(returns_t),
        )
        n_chunks = learner_chunks(n_rows, T * B, grad_chunk_samples)
        # chunk grads stay shard-local; ONE psum after the accumulation
        p_local = shard_local(params)
        if n_chunks == 1:
            (_, (aux, counters)), grads = chunk_grad(
                p_local, (states_f, actions_f, returns_f)
            )
        else:
            C = n_rows // n_chunks
            chunked = lambda x: x.reshape(n_chunks, C, *x.shape[1:])  # noqa: E731

            def acc_body(carry, chunk):
                g_acc, aux_acc = carry
                (_, aux), g = chunk_grad(p_local, chunk)
                # 13.5 MB of float32 a chunk for the conv policy; 2 GB for a
                # 508 M-parameter one (6 GB moved): time worth a name
                with device_scope(profiling.GRAD_REDUCE):
                    g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                aux_acc = jax.tree_util.tree_map(jnp.add, aux_acc, aux)
                return (g_acc, aux_acc), None

            (_, aux0), g0 = chunk_grad(
                p_local,
                (chunked(states_f)[0], chunked(actions_f)[0], chunked(returns_f)[0]),
            )
            (grads, aux_sum), _ = jax.lax.scan(
                acc_body,
                (g0, aux0),
                (
                    chunked(states_f)[1:],
                    chunked(actions_f)[1:],
                    chunked(returns_f)[1:],
                ),
            )
            with device_scope(profiling.GRAD_REDUCE):
                grads = jax.tree_util.tree_map(lambda g: g / n_chunks, grads)
            aux_sum, counters = aux_sum
            aux = jax.tree_util.tree_map(lambda a: a / n_chunks, aux_sum)
        aux, own_terms = aux
        with device_scope(profiling.GRAD_REDUCE):
            grads = jax.lax.psum(grads, DATA_AXIS)
            n_data = jax.lax.axis_size(DATA_AXIS)
            grads = jax.tree_util.tree_map(lambda g: g / n_data, grads)

        with device_scope(profiling.OPTIMIZER):
            opt_state = inject_learning_rate(
                state.train.opt_state, learning_rate
            )
            updates, new_opt_state = optimizer.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)

        new_state = FusedState(
            train=TrainState(
                step=state.train.step + 1,
                params=new_params,
                opt_state=new_opt_state,
            ),
            env_state=env_state,
            obs_stack=stack,
            key=key[None],
            ep_return=ep_ret,
            ep_count=ep_cnt,
            ep_return_sum=ep_sum,
            policy_carry=policy_carry,
        )
        with device_scope(profiling.METRICS):
            metrics = {
                "loss": aux.total,
                "policy_loss": aux.policy_loss,
                "value_loss": aux.value_loss,
                "entropy": aux.entropy,
                "pred_value": aux.pred_value,
                **grad_summaries(grads),
                "reward_per_step": jnp.mean(rewards_t),
            }
            # a policy's own loss terms, under its own names: means over
            # the chunks and the shards, as the loss's parts are
            metrics.update(own_terms)
            metrics = {
                k: jax.lax.pmean(v, DATA_AXIS) for k, v in metrics.items()
            }
            # cumulative-in-state metrics MUST be listed in
            # CUMULATIVE_METRICS: that's what tells the K>1 scan reduction
            # to take the last value instead of the window mean (ADVICE r4 #3)
            metrics["episodes"] = jax.lax.psum(jnp.sum(ep_cnt), DATA_AXIS)
            metrics["episode_return_sum"] = jax.lax.psum(
                jnp.sum(ep_sum), DATA_AXIS
            )
            # a sequence policy's counters, whatever its unroll counts: this
            # update's, summed over the mesh
            for k, v in counters.items():
                metrics[k] = jax.lax.psum(v, DATA_AXIS)
            # and its gauges of the carry as the rollout left it (the
            # largest over the mesh): docs/policy_protocol.md
            for k, v in carry_gauges(policy_carry).items():
                metrics[k] = jax.lax.pmax(v, DATA_AXIS)
            if sequence:
                # every shard's block into its columns of [T, B_global], then
                # a psum: the same on every shard (an all_gather's result is
                # typed as varying, which a replicated output may not be)
                shards = jax.lax.axis_size(DATA_AXIS)
                at = jax.lax.axis_index(DATA_AXIS) * B
                for k, v in zip(TRAJECTORY_METRICS, (states_t, actions_t),
                                strict=True):
                    whole = jnp.zeros((T, shards * B), v.dtype)
                    metrics[k] = jax.lax.psum(
                        jax.lax.dynamic_update_slice(whole, v, (0, at)),
                        DATA_AXIS)
        assert set(CUMULATIVE_METRICS) <= set(metrics)
        return new_state, metrics

    def multi_step(state: FusedState, entropy_beta, learning_rate):
        if steps_per_dispatch == 1:
            return local_step(state, entropy_beta, learning_rate)

        def body(s, _):
            return local_step(s, entropy_beta, learning_rate)

        state, ms = jax.lax.scan(body, state, None, length=steps_per_dispatch)
        # cumulative-in-state metrics (reset once per epoch by the outer
        # loop): the LAST step's psum is "so far"; loss-like metrics
        # average over the dispatch window
        metrics = {
            k: (v[-1] if k in CUMULATIVE_METRICS + TRAJECTORY_METRICS
                else jnp.mean(v, axis=0))
            for k, v in ms.items()
        }
        return state, metrics

    batch_spec = P(DATA_AXIS)
    env_state_struct = jax.eval_shape(env.reset, jax.random.PRNGKey(0))
    # pytree-prefix specs: train=P() replicates the whole TrainState subtree
    state_specs = FusedState(
        train=P(),
        env_state=jax.tree_util.tree_map(lambda _: batch_spec, env_state_struct),
        obs_stack=batch_spec,
        key=P(DATA_AXIS),
        ep_return=batch_spec,
        ep_count=batch_spec,
        ep_return_sum=batch_spec,
        policy_carry=jax.tree_util.tree_map(
            lambda _: batch_spec,
            jax.eval_shape(
                lambda: (model.init_carry(1), jnp.ones(1, bool))
            ) if sequence else (),
        ),
    )

    sharded = jax.shard_map(
        multi_step,
        mesh=mesh,
        in_specs=(state_specs, P(), P()),
        out_specs=(state_specs, P()),
    )
    # registered audit entry point (distributed_ba3c_tpu/audit.py)
    jitted = tripwire_jit("fused.step", sharded, donate_argnums=(0,))

    def step(state, entropy_beta, learning_rate=None):
        call = profiling.count_step_call()
        if call <= profiling.RECORDED_STEP_CALLS:
            return first_calls(call, state, entropy_beta, learning_rate)
        with host_span(profiling.SPAN_STEP):
            if learning_rate is None:
                learning_rate = cfg.learning_rate
            with host_span(profiling.SPAN_STEP_HYPER):
                entropy_beta = jnp.asarray(entropy_beta, jnp.float32)
                learning_rate = jnp.asarray(learning_rate, jnp.float32)
            with host_span(profiling.SPAN_STEP_ENQUEUE):
                return jitted(state, entropy_beta, learning_rate)

    def first_calls(call, state, entropy_beta, learning_rate):
        """``step()`` with the host's clock round its three spans: outside a
        capture the spans are inert, and start-up runs outside one. The
        durations go to the start-up record (utils/backend.py) as the event
        ``fused.step#<call>``, on the clock of the compiler's intervals.

        A second copy of ``step()``'s body, so that ``step()`` pays one
        integer a call and no more: an edit to one goes into the other
        (tests/test_startup_record.py holds the two to the same results)."""
        t0 = time.monotonic()
        with host_span(profiling.SPAN_STEP):
            if learning_rate is None:
                learning_rate = cfg.learning_rate
            with host_span(profiling.SPAN_STEP_HYPER):
                entropy_beta = jnp.asarray(entropy_beta, jnp.float32)
                learning_rate = jnp.asarray(learning_rate, jnp.float32)
            t1 = time.monotonic()
            with host_span(profiling.SPAN_STEP_ENQUEUE):
                out = jitted(state, entropy_beta, learning_rate)
            t2 = time.monotonic()
        backend.startup_event(
            f"{backend.STEP_EVENT}{call}", t0, time.monotonic(),
            hyper_s=t1 - t0, enqueue_s=t2 - t1)
        return out

    replicated = NamedSharding(mesh, P())
    batched = NamedSharding(mesh, batch_spec)
    _put_batched = make_put_batched(batched)

    def put(state: FusedState) -> FusedState:
        """device_put a host FusedState with the step's shardings."""
        return FusedState(
            train=jax.device_put(state.train, replicated),
            env_state=jax.tree_util.tree_map(_put_batched, state.env_state),
            obs_stack=_put_batched(state.obs_stack),
            key=_put_batched(state.key),
            ep_return=_put_batched(state.ep_return),
            ep_count=_put_batched(state.ep_count),
            ep_return_sum=_put_batched(state.ep_return_sum),
            policy_carry=jax.tree_util.tree_map(
                _put_batched, state.policy_carry),
        )

    def reset_episode_stats(state: FusedState, n_envs: int) -> FusedState:
        """Zero the per-env episode accumulators for the next epoch window.

        A step-provided hook because the overlap step keeps these fields
        inside its ActorState (fused/overlap.py) — the epoch loop calls the
        hook instead of reaching into the state layout."""
        return state.replace(
            ep_count=_put_batched(jnp.zeros(n_envs, jnp.int32)),
            ep_return_sum=_put_batched(jnp.zeros(n_envs, jnp.float32)),
        )

    step.put = put
    step.put_batched = _put_batched
    step.replicated_sharding = replicated
    step.batch_sharding = batched
    step.mesh = mesh
    step.rollout_len = rollout_len
    step.rollout_sub_batch = (
        (lambda n_envs: None) if sequence else rollout_sub_batch_of(mesh))
    step.steps_per_dispatch = steps_per_dispatch
    step.reset_episode_stats = reset_episode_stats
    #: fn(fetched metrics) -> an epoch's scalars of the policy's own counters
    #: and gauges (a sequence policy's ``epoch_stats``)
    step.policy_stats = getattr(model, "epoch_stats", lambda metrics: {})
    step.audit_jit = jitted  # tools/ba3caudit traces THIS program
    return step


def make_greedy_eval(
    model: BA3CNet,
    cfg: BA3CConfig,
    mesh: Mesh,
    env,
    n_envs: int,
    max_steps: int = 3000,
) -> Callable:
    """Build fn(params, key) -> (mean_return, max_return, n_episodes).

    The fused trainer's Evaluator (reference ``Evaluator``/``eval_with_funcs``,
    SURVEY.md §3.5): greedy (argmax) episodes, fully on-device — fresh envs
    roll in lockstep under one jit; each env contributes its FIRST completed
    episode so long-running envs don't bias the mean toward short episodes.
    """
    policy.refuse_carry(model, "the greedy on-device evaluator")

    def local_eval(params, seed):
        B = n_envs // mesh.shape[DATA_AXIS]
        # per-shard stream from a replicated seed: axis_index-folding keeps
        # this multi-host safe (no host-side sharded key array to assemble)
        key = jax.random.fold_in(
            jax.random.PRNGKey(seed), jax.lax.axis_index(DATA_AXIS)
        )
        k_reset, key = jax.random.split(key)
        env_state = jax.vmap(env.reset)(jax.random.split(k_reset, B))
        # reset() fields built from constants are axis-INVARIANT under
        # shard_map until the first data-dependent step, which breaks the
        # env's internal scan carries — mark the whole state varying up front
        env_state = shard_local(env_state)
        obs = jax.vmap(env.render)(env_state)
        stack = jnp.zeros((B, *obs.shape[1:], cfg.frame_history), jnp.uint8)
        stack = stack.at[..., -1].set(obs)

        def body(carry, _):
            env_state, stack, key, ep_ret, done_ret, done_mask = carry
            out = model.apply({"params": params}, stack)
            actions = jnp.argmax(out.logits, axis=-1).astype(jnp.int32)
            key, k_env = jax.random.split(key)
            env_state, obs, reward, done = jax.vmap(env.step)(
                env_state, actions, jax.random.split(k_env, B)
            )
            ep_ret = ep_ret + reward
            first_done = done & ~done_mask
            done_ret = jnp.where(first_done, ep_ret, done_ret)
            done_mask = done_mask | done
            ep_ret = ep_ret * (1.0 - done.astype(jnp.float32))
            keep = (~done).astype(stack.dtype)[:, None, None, None]
            stack = jnp.concatenate([stack[..., 1:] * keep, obs[..., None]], -1)
            return (env_state, stack, key, ep_ret, done_ret, done_mask), None

        carry0 = (
            env_state,
            stack,
            key,
            shard_local(jnp.zeros(B, jnp.float32)),
            shard_local(jnp.zeros(B, jnp.float32)),
            shard_local(jnp.zeros(B, bool)),
        )
        (_, _, _, _, done_ret, done_mask), _ = jax.lax.scan(
            body, carry0, None, length=max_steps
        )
        n = jax.lax.psum(jnp.sum(done_mask.astype(jnp.int32)), DATA_AXIS)
        s = jax.lax.psum(jnp.sum(jnp.where(done_mask, done_ret, 0.0)), DATA_AXIS)
        mx = jax.lax.pmax(
            jnp.max(jnp.where(done_mask, done_ret, -jnp.inf)), DATA_AXIS
        )
        return s / jnp.maximum(n, 1), mx, n

    sharded = jax.shard_map(
        local_eval,
        mesh=mesh,
        in_specs=(P(), P()),
        out_specs=(P(), P(), P()),
    )
    # registered audit entry point (distributed_ba3c_tpu/audit.py)
    jitted = tripwire_jit("fused.greedy_eval", sharded)

    def evaluate(params, seed):
        """``seed``: int (preferred) — PRNGKey arrays are coerced."""
        arr = np.asarray(
            jax.random.key_data(seed)
            if jnp.issubdtype(getattr(seed, "dtype", np.int32), jax.dtypes.prng_key)
            else seed
        )
        if arr.ndim:
            arr = arr.reshape(-1)[-1]
        mean, mx, n = jitted(params, jnp.uint32(arr))
        return float(mean), float(mx), int(n)

    evaluate.audit_jit = jitted  # tools/ba3caudit traces THIS program
    return evaluate


def run_fused_training(args, cfg: BA3CConfig, model, optimizer) -> int:
    """CLI driver for --trainer=tpu_fused_ba3c (env must be jax:<name>)."""
    from distributed_ba3c_tpu.envs import jaxenv
    from distributed_ba3c_tpu.parallel.mesh import make_mesh
    from distributed_ba3c_tpu.train.checkpoint import CheckpointManager
    from distributed_ba3c_tpu.utils import logger
    from distributed_ba3c_tpu.utils.backend import log_device_info
    from distributed_ba3c_tpu.utils.stats import StatHolder

    if not args.env.startswith("jax:"):
        raise SystemExit("--trainer=tpu_fused_ba3c requires --env jax:<name>")
    logger.set_logger_dir(args.logdir)
    device = log_device_info()
    env = jaxenv.get_env(args.env.split(":", 1)[1])
    cfg = cfg.replace(num_actions=env.num_actions)
    sequence = policy.carries_state(model)
    if sequence:
        model = model.for_env(env)
        if getattr(args, "rollout_dtype", "float32") != "float32":
            # before the int8 arm calibrates; --overlap is refused where its
            # step is built
            policy.refuse_carry(model, "--rollout_dtype (a served table)")
    else:
        model = dataclasses.replace(model, num_actions=env.num_actions)

    if jax.process_count() > 1:
        # multi-host: global host-major mesh; every process runs this loop
        # in lockstep (the psum inside the step synchronizes the update)
        from distributed_ba3c_tpu.parallel.distributed import make_global_mesh

        mesh = make_global_mesh(num_model=1)
    else:
        mesh = make_mesh(num_data=args.mesh_data, num_model=1)
    n_data = mesh.shape[DATA_AXIS]
    rollout_len = args.rollout_len
    envs_per_device = max(1, cfg.batch_size // rollout_len)
    n_envs = envs_per_device * n_data
    k_dispatch = max(1, getattr(args, "steps_per_dispatch", 1))
    if args.steps_per_epoch % k_dispatch:
        raise SystemExit(
            f"--steps_per_dispatch {k_dispatch} must divide "
            f"--steps_per_epoch {args.steps_per_epoch}"
        )
    fleet_accum = max(1, getattr(args, "fleet_accum", 1) or 1)
    # state BEFORE the step build: the int8 rung's pre-training env
    # calibration needs the run's actual starting params (restored ones
    # on a resume — calibrating against re-initialized weights would
    # freeze scales for a policy the actor never plays)
    # the one-off phases below are ``startup`` events (utils/backend.py):
    # what a resume spends before its first update, by name, on the start-up
    # line; a dump of one that hung names the phase that did not end
    with backend.startup_phase("state_init"):
        state = create_fused_state(
            jax.random.PRNGKey(getattr(args, "seed", 0) or 0),
            model, cfg, optimizer, env, n_envs, n_shards=n_data,
        )
    if args.load:
        with backend.startup_phase("restore"):
            mgr = CheckpointManager(args.load)
            restored = mgr.restore(jax.device_get(state.train))
            state = state.replace(train=restored)
        logger.info("resumed train state at step %d", int(restored.step))
    rollout_dtype = getattr(args, "rollout_dtype", "float32")
    quant_spec = None
    if rollout_dtype == "int8":
        # calibration source resolution (cli.py/TopologySpec validated
        # exactly-one-of): a frozen spec file, or N offline env-rollout
        # windows through the same scan body the actor program runs
        from distributed_ba3c_tpu.quantize import QuantSpec, calibrate_from_env

        if getattr(args, "quant_spec", None):
            quant_spec = QuantSpec.load(args.quant_spec)
        else:
            with backend.startup_phase("calibrate"):
                quant_spec = calibrate_from_env(
                    model, cfg, env, state.train.params,
                    jax.random.PRNGKey(getattr(args, "seed", 0) or 0),
                    n_envs=n_envs,
                    batches=int(getattr(args, "quant_calibrate", 0) or 0),
                    rollout_len=rollout_len,
                )
        logger.info(
            "int8 rollout forward: quant spec %s (%d calibration batches)",
            quant_spec.sha256()[:12], quant_spec.calibration_batches,
        )
    with backend.startup_phase("build_step"):
        if getattr(args, "overlap", False):
            # two overlapped compiled programs (rollout k+1 concurrent with
            # learner k, lag-1 V-trace correction) instead of the single
            # fused program — docs/overlap.md. --fleet_accum K adds the macro
            # learner: K rollout windows ("fleets") accumulated into ONE
            # update (docs/actor_plane.md multi-fleet macro-batching)
            from distributed_ba3c_tpu.fused.overlap import make_overlap_step

            step = make_overlap_step(
                model, optimizer, cfg, mesh, env, rollout_len,
                grad_chunk_samples=args.grad_chunk_samples,
                steps_per_dispatch=k_dispatch,
                rollout_dtype=rollout_dtype,
                macro_fleets=fleet_accum,
                quant_spec=quant_spec,
            )
        else:
            step = make_fused_step(
                model, optimizer, cfg, mesh, env, rollout_len,
                grad_chunk_samples=args.grad_chunk_samples,
                steps_per_dispatch=k_dispatch,
            )
    run_shape = {
        "steps_per_epoch": args.steps_per_epoch,
        "batch_size": cfg.batch_size,
        "rollout_len": rollout_len,
        "max_epoch": args.max_epoch,
    }
    shape_mismatch = False
    if args.load:
        # schedule-shape guard: the resumed epoch counter is
        # step // steps_per_epoch, so a different shape silently stretches
        # or shifts the anneal — warn loudly when the shapes disagree
        prev = mgr.read_run_meta()
        for k, v in run_shape.items():
            if k in prev and prev[k] != v:
                shape_mismatch = True
                logger.warn(
                    "resume shape mismatch: %s was %s at save time, now %s — "
                    "the LR/beta anneal will NOT continue where it left off",
                    k, prev[k], v,
                )
    with backend.startup_phase("put"):
        state = step.put(state)
    logger.info(
        "learner state on devices %s, env batch on devices %s",
        sorted(d.id for d in state.train.step.sharding.device_set),
        sorted(d.id for d in step.batch_sharding.device_set),
    )

    holder = StatHolder(args.logdir, run_info={"device": device})
    # one SHARED checkpoint dir across hosts (orbax saves are collective)
    ckpt = CheckpointManager(
        getattr(args, "shared_ckpt_dir", None) or f"{args.logdir}/checkpoints",
        max_to_keep=getattr(args, "max_to_keep", 3),
    )
    if not shape_mismatch:
        # on a MISMATCHED resume, keep the original shape on record so the
        # warning keeps firing on every later resume (overwriting here
        # would mute the guard after its first catch)
        ckpt.write_run_meta(**run_shape)
    # each update consumes fleet_accum rollout windows: the fps/samples
    # account must bill every env-step or the rate under-reports K-fold
    samples_per_iter = n_envs * rollout_len * fleet_accum
    logger.info(
        "fused training: %d envs x %d rollout x %d accum windows = "
        "%d samples/iter on %d devices, inference forwards of %s stacks",
        n_envs,
        rollout_len,
        fleet_accum,
        samples_per_iter,
        n_data,
        step.rollout_sub_batch(n_envs) or "all a device's",
    )

    # runtime-scheduled hyperparams (reference ScheduledHyperParamSetter
    # semantics): anneal over epochs when *_final flags are given. --anneal
    # exp interpolates geometrically — it reaches the low-β/low-lr regime
    # (where Pong's endgame learning happens) in half the epochs a linear
    # ramp spends at plateau values.
    def sched(v0, v1, epoch, mode=None):
        if v1 is None or args.max_epoch <= 1:
            return v0
        from distributed_ba3c_tpu.train.callbacks import anneal_interp

        f = (epoch - 1) / (args.max_epoch - 1)
        return anneal_interp(
            v0, v1, f, mode or getattr(args, "anneal", "linear")
        )

    # greedy on-device Evaluator (reference Evaluator, SURVEY.md §3.5):
    # nr_eval envs rounded up to the mesh's data axis
    n_eval = max(n_data, (max(args.nr_eval, 1) + n_data - 1) // n_data * n_data)
    evaluate = None
    if sequence:
        logger.info(
            "no greedy evaluator for a policy that carries state: "
            "mean_score of the training rollouts is the run's score"
        )
    else:
        evaluate = make_greedy_eval(
            model, cfg, mesh, env, n_eval, max_steps=args.eval_max_steps
        )

    # telemetry scrape endpoint (docs/observability.md): the fused loop has
    # no actor plane, but its learner counters + flight ring are still the
    # run's live view (--telemetry_port)
    from distributed_ba3c_tpu import telemetry

    tele_server = None
    if getattr(args, "telemetry_port", 0):
        tele_server = telemetry.TelemetryServer(args.telemetry_port)
        tele_server.start()
    try:
        _fused_epoch_loop(
            args, cfg, step, state, holder, ckpt, samples_per_iter,
            n_envs, sched, evaluate,
        )
    finally:
        if tele_server is not None:
            tele_server.stop()
            tele_server.join(timeout=2)
            tele_server.close()
        holder.close()
    return 0


def _fused_epoch_loop(
    args, cfg, step, state, holder, ckpt, samples_per_iter, n_envs, sched,
    evaluate,
):
    from distributed_ba3c_tpu.utils import logger

    # Resume CONTINUES the schedule: the epoch counter derives from the
    # restored global step, so a stall-kill + --load (run_with_resume.sh)
    # picks up the anneal where it left off instead of restarting it —
    # --max_epoch is the run's TOTAL epoch budget across resumes.
    epoch0 = int(state.train.step) // max(args.steps_per_epoch, 1)
    if epoch0 > 0:
        logger.info(
            "resume: continuing at epoch %d/%d (restored step %d)",
            epoch0 + 1, args.max_epoch, int(state.train.step),
        )
    if epoch0 >= args.max_epoch:
        # a warm-start fine-tune wants a FRESH logdir (the anneal maps over
        # epochs 1..max_epoch of the loaded step count); loud, not silent
        logger.warn(
            "loaded step %d already covers --max_epoch %d x %d steps: "
            "nothing to train (raise --max_epoch to extend the run)",
            int(state.train.step), args.max_epoch, args.steps_per_epoch,
        )
    # live hyperparam overrides (reference HumanHyperParamSetter, SURVEY
    # §2.7 #21): the CHIEF reads <base_logdir>/hyper.txt each epoch and the
    # values are broadcast — per-rank file reads could race a mid-run edit
    # and silently diverge the psum'd update, so only the chief's read counts
    hyper_dir = getattr(args, "shared_hyper_dir", None) or args.logdir
    hyper_path = os.path.join(hyper_dir, "hyper.txt") if hyper_dir else None

    def live_hyper(lr, beta):
        if hyper_path is not None and jax.process_index() == 0:
            from distributed_ba3c_tpu.train.callbacks import read_hyper_file

            overrides = read_hyper_file(hyper_path)
            lr = overrides.get("learning_rate", lr)
            beta = overrides.get("entropy_beta", beta)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            lr, beta = multihost_utils.broadcast_one_to_all(
                np.asarray([lr, beta], np.float32)
            ).tolist()
        return lr, beta

    beta_mode = getattr(args, "anneal_beta", None)
    lr_mode = getattr(args, "anneal_lr", None)
    # rank-failure detection (SURVEY §5): in multi-host runs a dead peer
    # wedges this rank in the next psum/save barrier forever — the watchdog
    # turns that undefined hang into a bounded-time nonzero exit so the
    # launcher can relaunch every rank with --load on the shared checkpoints
    from distributed_ba3c_tpu.parallel.watchdog import (
        LockstepWatchdog,
        resolve_timeout,
    )

    with LockstepWatchdog(
        resolve_timeout(getattr(args, "rank_stall_timeout", 0)),
        what=f"rank {jax.process_index()}/{jax.process_count()} epoch loop",
    ) as watchdog:
        _fused_epoch_body(
            args, cfg, step, state, holder, ckpt, samples_per_iter, n_envs,
            sched, evaluate, epoch0, live_hyper, beta_mode, lr_mode, watchdog,
        )


def _fused_epoch_body(
    args, cfg, step, state, holder, ckpt, samples_per_iter, n_envs, sched,
    evaluate, epoch0, live_hyper, beta_mode, lr_mode, watchdog,
):
    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.utils import logger

    tele = telemetry.registry("learner")
    c_steps = tele.counter("train_steps_total")
    c_samples = tele.counter("train_samples_total")
    c_episodes = tele.counter("episodes_total")
    h_epoch = tele.histogram("epoch_s", unit=1e-3)
    best = -np.inf
    first_eval_done = False
    first_dispatch_s = None
    for epoch in range(epoch0 + 1, args.max_epoch + 1):
        beta = sched(cfg.entropy_beta, args.entropy_beta_final, epoch, beta_mode)
        lr = sched(cfg.learning_rate, args.learning_rate_final, epoch, lr_mode)
        lr, beta = live_hyper(lr, beta)
        t0 = time.monotonic()
        metrics = None
        for _ in range(args.steps_per_epoch // step.steps_per_dispatch):
            state, metrics = step(state, beta, lr)
            if first_dispatch_s is None:
                # trace + compile (or cache read) + the first execution:
                # set-up time, recorded apart from the steady rate. One
                # host sync, on this process's first dispatch only.
                jax.block_until_ready(metrics)  # ba3clint: disable=J1 — first dispatch only, guarded above
                done = time.monotonic()
                first_dispatch_s = done - t0
                holder.add_stat("first_dispatch_s", first_dispatch_s)
                # the first update is complete: start-up ends here, and a
                # compilation from now on is a steady-state one
                backend.startup_event("first_update", t0, done)
                backend.report_startup(done)
        with host_span(profiling.SPAN_EPOCH_FETCH):
            # scalars; a sequence policy's counters are small arrays
            metrics = {
                k: float(v) if np.ndim(v) == 0 else np.asarray(v)
                for k, v in metrics.items()
            }
        # the fetch above forced every dispatch's collectives to completion:
        # proven progress — don't charge the upcoming eval/save to the
        # compute window's stall budget
        watchdog.beat()
        dt = time.monotonic() - t0
        fps = args.steps_per_epoch * samples_per_iter / dt
        # one batched account per epoch window (the loop's own dispatch
        # cadence) — scrape-visible progress without per-step host syncs
        c_steps.inc(args.steps_per_epoch)
        c_samples.inc(args.steps_per_epoch * samples_per_iter)
        c_episodes.inc(int(metrics["episodes"]))
        h_epoch.observe(dt)
        mean_ret = (
            metrics["episode_return_sum"] / metrics["episodes"]
            if metrics["episodes"] > 0
            else float("nan")
        )
        # reset the per-env episode accumulators for the next window
        # (step-provided hook: the fused and overlap steps keep these
        # fields in different state layouts)
        state = step.reset_episode_stats(state, n_envs)
        if os.environ.get("BA3C_PARAM_DIGEST"):
            # divergence detector for multi-host runs: ranks log this line
            # per epoch; any mismatch across ranks means the psum'd update
            # broke lockstep (costs a params device_get — debug only)
            leaves = jax.tree_util.tree_leaves(
                # epoch-boundary debug fetch, explicitly opt-in and costed
                # in the comment above — not a per-step sync
                jax.device_get(state.train.params)  # ba3clint: disable=J1
            )
            logger.info(
                "param_digest %s",
                " ".join(f"{np.float64(np.sum(l)):.10e}" for l in leaves),
            )
        # greedy eval — the number the north-star (Pong >= 18) is defined on
        eval_mean = float("nan")
        if evaluate is not None and epoch % max(args.eval_every, 1) == 0:
            if not first_eval_done:
                # the first eval window includes the eval program's XLA
                # compile — give it the same grace as the first train
                # compile or a tightly-sized timeout 75-loops right here
                watchdog.grace()
                first_eval_done = True
            with host_span(profiling.SPAN_EPOCH_EVAL):
                eval_mean, eval_max, eval_n = evaluate(
                    state.train.params, 1000 + epoch
                )
            if eval_n > 0:
                holder.add_stat("eval_mean_score", eval_mean)
                holder.add_stat("eval_max_score", eval_max)
            else:
                # no episode finished inside the eval horizon (long rallies):
                # 0/1 would masquerade as a real score — report nothing
                eval_mean = float("nan")
            # eval done: a slow 128-episode eval must not eat into the
            # save window's stall budget
            watchdog.beat()
        holder.add_stat("epoch", epoch)
        holder.add_stat("global_step", int(state.train.step))
        holder.add_stat("fps", fps)
        if np.isfinite(mean_ret):
            holder.add_stat("mean_score", mean_ret)
        if metrics["episodes"] > 0:
            # approximate mean episode length: every env-step this epoch is
            # a training step, so samples/episodes ≈ ep length (the timid-
            # policy regression signature is this number climbing while
            # eval falls — CoinRun diagnosis, BASELINE config #5)
            holder.add_stat(
                "ep_len_approx",
                args.steps_per_epoch * samples_per_iter / metrics["episodes"],
            )
        for k in ("loss", "policy_loss", "value_loss", "entropy", "grad_norm"):
            holder.add_stat(k, metrics[k])
        # what a sequence policy makes of its own counters and gauges (the
        # overlap step drives none and has no such hook)
        policy_stats = getattr(step, "policy_stats", None)
        if policy_stats is not None:
            holder.add_stats(policy_stats(metrics))
        for k in ("mean_rho", "value_lag_mae"):
            # overlap-mode series (fused/overlap.py): how hard V-trace is
            # clipping and how far the value fn moved across the lag
            if k in metrics:
                holder.add_stat(k, metrics[k])
        if telemetry.enabled():
            # same series the scrape endpoint serves, into stat.json/TB
            holder.add_stats(telemetry.export_scalars(roles=("learner",)))
        holder.finalize()
        logger.info(
            "epoch %d | env-steps/s %.0f | mean_score %.2f (%d eps) | eval %.2f | loss %.4f entropy %.3f",
            epoch,
            fps,
            mean_ret,
            int(metrics["episodes"]),
            eval_mean,
            metrics["loss"],
            metrics["entropy"],
        )
        # epoch-boundary checkpoint: the fetch is the save's payload, once
        # per epoch — not a per-step sync
        with host_span(profiling.SPAN_EPOCH_CHECKPOINT):
            ckpt.save(jax.device_get(state.train), int(state.train.step))  # ba3clint: disable=J1
        telemetry.record("checkpoint", step=int(state.train.step))
        # keep-best on GREEDY EVAL (not training-policy returns): the
        # reference's MaxSaver tracked the Evaluator's number
        if np.isfinite(eval_mean) and eval_mean > best:
            best = eval_mean
            ckpt.mark_best(int(state.train.step), eval_mean)
        # global progress proven (metrics fetched + collective save done):
        # re-arm the rank-failure watchdog for the next epoch
        watchdog.beat()
