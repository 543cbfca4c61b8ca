"""Overlapped rollout/learner programs: the fused step split in two.

The fused step (fused/loop.py) serializes, inside ONE compiled program, the
small-batch low-occupancy rollout forwards with the large-batch learner
fwd+bwd — so the rollout's ~1.6 us/sample residual (PERF.md round 3
attribution) is ADDED to the learner instead of hidden behind it. This
module splits the step into two overlapped compiled programs with double
buffering:

    actor program   (``fused.actor``):   rollout scan over T steps at the
        policy of update k-1, producing a trajectory block (states,
        actions, clipped rewards, dones, behavior log-probs, bootstrap
        stack) into a device-resident slot. Donation-aliased on its env
        carry; collective-free (everything it touches is per-shard).
    learner program (``fused.learner``): V-trace-corrected fwd+bwd on the
        block from step k-1 (policy lag 1 — exactly the staleness
        ops/vtrace.py's clipped importance weights correct, the IMPALA
        result the ISSUE leans on), gradient psum, Adam. Donates the
        train state.

Schedule per iteration (host dispatches, all async — NO host sync between
them; ba3clint rule J6 ``overlap-sync-hazard`` guards this):

    aparams     = prep(train.params)            # snapshot (copy or bf16 cast)
    astate, b'  = actor(aparams, astate)        # rollout k+1   (donates astate)
    train, m    = learner(train, b, beta, lr)   # learner k     (donates train)
    b = b'

The ``prep`` snapshot is load-bearing, not a convenience: the learner
donates the param buffers, and a donated write cannot begin while another
in-flight program still reads the same buffers — an actor reading
``train.params`` directly would serialize the learner behind the whole
rollout. Reading a SNAPSHOT breaks that anti-dependency, so the two big
programs share no buffers at all and the runtime is free to execute
rollout k+1 concurrently with learner k. In bf16 mode the snapshot IS the
cast (params -> bf16), which also halves the actor's param-read bandwidth;
the policy heads stay f32 (models/a3c.py), so behavior log-probs are f32
either way and V-trace clips whatever precision noise the cast adds.

Double buffering falls out of donation: block k is a live device slot
while the actor writes block k+1 into fresh buffers; when the learner
(which does NOT donate the block — its buffers alias no output) finishes,
block k's refcount drops and XLA reuses the slot for block k+2. Two block
allocations alternate; nothing is copied.

Lag:
    lag=1 (default)  rollout k+1 runs concurrently with learner k; the
                     behavior policy is one update stale and V-trace
                     corrects it.
    lag=0            actor and learner run back-to-back on the SAME block
                     (no overlap). With frozen params this is bit-exact
                     with the fused step — the parity contract
                     tests/test_overlap.py pins.
"""

from __future__ import annotations

import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ba3c_tpu.audit import tripwire_jit
from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.fused.loop import (
    CUMULATIVE_METRICS,
    FusedState,
    make_put_batched,
    make_rollout_body,
    rollout_sub_batch_of,
)
from distributed_ba3c_tpu.models.a3c import BA3CNet
from distributed_ba3c_tpu.models.policy import refuse_carry
from distributed_ba3c_tpu.ops.gradproc import grad_summaries, inject_learning_rate
from distributed_ba3c_tpu.ops.vtrace import vtrace_returns
from distributed_ba3c_tpu.parallel.mesh import DATA_AXIS, shard_local
from distributed_ba3c_tpu.parallel.train_step import (
    TrainState,
    macro_accumulate,
)

import optax

ROLLOUT_DTYPES = ("float32", "bfloat16", "int8")


def make_block_grads(
    model: BA3CNet, cfg: BA3CConfig, grad_chunk_samples: int = 4096
) -> Callable:
    """Per-block V-trace grads + aux (env-column chunked) — the ONE
    gradient body the overlap learner, the multi-fleet macro learner AND
    the pod's bounded-staleness learner (pod/learner.py) all run. The
    correction reads the block's recorded behavior log-probs, so it is
    exact at ANY measured params lag — lag never enters the program, only
    the data; that is what lets the pod generalize the overlap split's
    fixed lag-1 without a new gradient path to re-verify."""

    def block_grads(params, block: TrajBlock, entropy_beta):
        # shard-local grads: make_finish_update owns the update's one psum
        params = shard_local(params)
        T, B = block.actions.shape

        # chunk over ENV COLUMNS, not the flat [T*B] batch: V-trace's
        # reverse scan couples a whole env column in time but columns are
        # independent, so mean-of-column-chunk grads equals the full-batch
        # gradient (same HBM-activation-cap role as the fused learner's
        # flat chunks). At the flagship 128x20 shape T*B=2560 <=
        # grad_chunk_samples, so the expected path is one chunk.
        # clamp to B FIRST: an env column (T samples) is the smallest
        # chunk this layout can make, and a start value above B would
        # never find a divisor (the rounding loop below walks upward)
        n_chunks = min(max(1, -(-(T * B) // grad_chunk_samples)), B)
        while B % n_chunks:
            n_chunks += 1
        Bc = B // n_chunks

        def chunk_loss(pp, chunk):
            states_c, actions_c, rewards_c, dones_c, mu_lp_c, mu_v_c, boot_c = chunk
            # one big forward over T*Bc + Bc states (conv batch stays
            # MXU-sized; the bootstrap is valued under the TARGET policy)
            flat = states_c.reshape((T * Bc, *states_c.shape[2:]))
            all_states = jnp.concatenate([flat, boot_c], axis=0)
            out = model.apply({"params": pp}, all_states)
            logits = out.logits[: T * Bc].reshape((T, Bc, -1))
            values = out.value[: T * Bc].reshape((T, Bc))
            bootstrap_value = out.value[T * Bc:]

            log_probs = jax.nn.log_softmax(logits, axis=-1)
            probs = jax.nn.softmax(logits, axis=-1)
            target_lp = jnp.take_along_axis(
                log_probs, actions_c[..., None].astype(jnp.int32), axis=-1
            )[..., 0]

            vt = vtrace_returns(
                behaviour_log_probs=mu_lp_c,
                target_log_probs=jax.lax.stop_gradient(target_lp),
                rewards=rewards_c,
                dones=dones_c,
                values=jax.lax.stop_gradient(values),
                bootstrap_value=jax.lax.stop_gradient(bootstrap_value),
                gamma=cfg.gamma,
            )

            # loss forms mirror ops/loss.py's a3c_loss (incl. the optional
            # Huber value loss) so a lag-0 run optimizes the same objective
            # as the fused step — at zero lag rho == c == 1 and the V-trace
            # targets reduce exactly to the n-step returns.
            policy_loss = -jnp.mean(target_lp * vt.pg_advantages)
            if cfg.value_huber_delta is not None:
                from distributed_ba3c_tpu.ops.symbolic import huber_loss

                value_loss = jnp.mean(
                    huber_loss(values - vt.vs, cfg.value_huber_delta)
                )
            else:
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
                # how far the value function moved across the policy lag —
                # the observable the lag correction story rests on (and
                # it keeps every block input live in the compiled program)
                "value_lag_mae": jnp.mean(
                    jnp.abs(jax.lax.stop_gradient(values) - mu_v_c)
                ),
            }
            return total, aux

        def chunk_grad(pp, chunk):
            return jax.value_and_grad(chunk_loss, has_aux=True)(pp, chunk)

        def col_chunk(x):
            # [T, B, ...] -> [n_chunks, T, Bc, ...] (chunk c = env columns
            # c*Bc:(c+1)*Bc — matches boot.reshape(n_chunks, Bc) below)
            return x.reshape(T, n_chunks, Bc, *x.shape[2:]).swapaxes(0, 1)

        full_chunk = (
            block.states, block.actions, block.rewards, block.dones,
            block.behavior_log_probs, block.behavior_values,
            block.bootstrap_state,
        )
        if n_chunks == 1:
            (_, aux), grads = chunk_grad(params, full_chunk)
        else:
            boot_c = block.bootstrap_state.reshape(
                n_chunks, Bc, *block.bootstrap_state.shape[1:]
            )
            chunks = (
                col_chunk(block.states), col_chunk(block.actions),
                col_chunk(block.rewards), col_chunk(block.dones),
                col_chunk(block.behavior_log_probs),
                col_chunk(block.behavior_values), boot_c,
            )

            def acc_body(carry, chunk):
                g_acc, aux_acc = carry
                (_, aux), g = chunk_grad(params, chunk)
                g_acc = jax.tree_util.tree_map(jnp.add, g_acc, g)
                aux_acc = jax.tree_util.tree_map(jnp.add, aux_acc, aux)
                return (g_acc, aux_acc), None

            first = jax.tree_util.tree_map(lambda x: x[0], chunks)
            (_, aux0), g0 = chunk_grad(params, first)
            rest = jax.tree_util.tree_map(lambda x: x[1:], chunks)
            (grads, aux_sum), _ = jax.lax.scan(acc_body, (g0, aux0), rest)
            grads = jax.tree_util.tree_map(lambda g: g / n_chunks, grads)
            aux = jax.tree_util.tree_map(lambda a: a / n_chunks, aux_sum)
        return grads, aux

    return block_grads


def make_finish_update(optimizer: optax.GradientTransformation) -> Callable:
    """The learner tail — ONE definition for the single, macro and pod
    programs (psum + mean + LR injection + Adam + pmean'd metrics): a tail
    fix applied to one copy must not silently diverge the others (review
    finding, extended to pod/learner.py)."""

    def finish_update(train: TrainState, grads, aux, rewards, learning_rate):
        grads = jax.lax.psum(grads, DATA_AXIS)
        n_data = jax.lax.axis_size(DATA_AXIS)
        grads = jax.tree_util.tree_map(lambda g: g / n_data, grads)

        opt_state = inject_learning_rate(train.opt_state, learning_rate)
        updates, new_opt_state = optimizer.update(
            grads, opt_state, train.params
        )
        new_params = optax.apply_updates(train.params, updates)
        new_train = TrainState(
            step=train.step + 1, params=new_params, opt_state=new_opt_state
        )
        metrics = {
            **aux,
            **grad_summaries(grads),
            "reward_per_step": jnp.mean(rewards),
        }
        metrics = {k: jax.lax.pmean(v, DATA_AXIS) for k, v in metrics.items()}
        return new_train, metrics

    return finish_update


class ActorState(struct.PyTreeNode):
    """The env-side carry of the actor program (FusedState minus train)."""

    env_state: Any            # batched env pytree, leaves [B_global, ...]
    obs_stack: jax.Array      # [B_global, H, W, hist] uint8
    key: jax.Array            # [n_shards] typed PRNG keys, data-sharded
    ep_return: jax.Array      # [B_global] running episode return
    ep_count: jax.Array       # [B_global] int32 completed episodes per env
    ep_return_sum: jax.Array  # [B_global] f32 sum of completed returns


class TrajBlock(struct.PyTreeNode):
    """One rollout's trajectory — the device-resident slot the two
    programs hand off. Time-major to match the V-trace reverse scan."""

    states: jax.Array              # [T, B, H, W, hist] uint8
    actions: jax.Array             # [T, B] int32
    rewards: jax.Array             # [T, B] f32 (clipped learning rewards)
    dones: jax.Array               # [T, B] f32
    behavior_log_probs: jax.Array  # [T, B] f32  log mu(a_t|s_t)
    behavior_values: jax.Array     # [T, B] f32  V_mu(s_t) (lag diagnostic)
    bootstrap_state: jax.Array     # [B, H, W, hist] uint8 (post-rollout)


class OverlapState(struct.PyTreeNode):
    """What the overlap step threads through the epoch loop."""

    train: TrainState
    actor: ActorState
    block: Any = None  # TrajBlock in flight (lag=1) or None (lag=0/fresh)


def make_overlap_step(
    model: BA3CNet,
    optimizer: optax.GradientTransformation,
    cfg: BA3CConfig,
    mesh: Mesh,
    env,
    rollout_len: int = 20,
    grad_chunk_samples: int = 4096,
    steps_per_dispatch: int = 1,
    lag: int = 1,
    rollout_dtype: str = "float32",
    macro_fleets: int = 1,
    quant_spec=None,
) -> Callable:
    """Build the overlapped two-program step facade.

    Same call shape as ``make_fused_step``'s step — fn(state, beta, lr) ->
    (state, metrics) — so ``run_fused_training``'s epoch loop drives either
    interchangeably. ``steps_per_dispatch`` here is the number of
    actor/learner iteration PAIRS dispatched per facade call (all async;
    the epoch loop's metrics fetch is the only sync).

    ``macro_fleets`` > 1 is the fused half of multi-fleet macro-batching
    (docs/actor_plane.md): the actor program runs K rollout windows per
    update — K "fleets" of trajectory blocks under one params snapshot —
    and a MACRO learner (``fused.macro_learner``) accumulates their
    gradients into ONE update. Per-update effective batch grows K-fold
    while every fwd+bwd still runs at the single-window full-occupancy
    shape (the macro-batching contract); behavior lag within the window
    spans 1..K updates and V-trace's clipped importance weights correct
    it exactly as they do the lag-1 schedule.

    ``rollout_dtype="int8"`` builds the quantized actor program (audit
    entry ``fused.actor_int8``): ``quant_spec`` (a calibrated
    :class:`~distributed_ba3c_tpu.quantize.spec.QuantSpec`) is REQUIRED,
    the prep step becomes quantize-on-snapshot (``quantize_params``) and
    the rollout body's forward runs the dequant-free int8 mirror
    (quantize/qforward.py). The learner half is untouched — f32
    throughout, exactly like the bf16 rung.
    """
    refuse_carry(model, "--overlap (the two-program fused step)")
    if lag not in (0, 1):
        raise ValueError(f"lag must be 0 or 1, got {lag}")
    if rollout_dtype not in ROLLOUT_DTYPES:
        raise ValueError(
            f"rollout_dtype must be one of {ROLLOUT_DTYPES}, got {rollout_dtype!r}"
        )
    if rollout_dtype == "int8" and quant_spec is None:
        raise ValueError(
            "rollout_dtype='int8' needs a calibrated quant_spec (load one "
            "with QuantSpec.load, or calibrate via quantize.calibrate)"
        )
    if macro_fleets < 1:
        raise ValueError(f"macro_fleets must be >= 1, got {macro_fleets}")
    if rollout_dtype == "int8":
        from distributed_ba3c_tpu.quantize import (
            make_quant_apply,
            quantize_params,
        )

        quant_apply = make_quant_apply(model, arm="auto")
    else:
        quant_apply = None

    # ---------------- actor program (fused.actor) -------------------------
    def local_actor(params, astate: ActorState):
        key = astate.key[0]  # this shard's scalar key
        rollout_body = make_rollout_body(
            model, cfg, env, params, record_log_probs=True,
            apply_fn=quant_apply,
        )
        carry0 = (
            astate.env_state,
            astate.obs_stack,
            key,
            astate.ep_return,
            astate.ep_count,
            astate.ep_return_sum,
        )
        (env_state, stack, key, ep_ret, ep_cnt, ep_sum), traj = jax.lax.scan(
            rollout_body, carry0, None, length=rollout_len
        )
        states_t, actions_t, rewards_t, dones_t, lp_t, bv_t = traj
        new_astate = ActorState(
            env_state=env_state,
            obs_stack=stack,
            key=key[None],
            ep_return=ep_ret,
            ep_count=ep_cnt,
            ep_return_sum=ep_sum,
        )
        block = TrajBlock(
            states=states_t,
            actions=actions_t,
            rewards=rewards_t,
            dones=dones_t,
            behavior_log_probs=lp_t,
            behavior_values=bv_t,
            bootstrap_state=stack,
        )
        # NO bootstrap forward and NO psums here: the learner values the
        # bootstrap stack under the TARGET policy (vtrace_step idiom), and
        # episode metrics are aggregated by the tiny ep_stats program at
        # window boundaries — the actor stays collective-free (T3) so the
        # single-chip schedule has nothing to wait on.
        return new_astate, block

    batch_spec = P(DATA_AXIS)
    env_state_struct = jax.eval_shape(env.reset, jax.random.PRNGKey(0))
    actor_specs = ActorState(
        env_state=jax.tree_util.tree_map(lambda _: batch_spec, env_state_struct),
        obs_stack=batch_spec,
        key=P(DATA_AXIS),
        ep_return=batch_spec,
        ep_count=batch_spec,
        ep_return_sum=batch_spec,
    )
    tb_spec = P(None, DATA_AXIS)  # time-major leaves
    block_specs = TrajBlock(
        states=tb_spec,
        actions=tb_spec,
        rewards=tb_spec,
        dones=tb_spec,
        behavior_log_probs=tb_spec,
        behavior_values=tb_spec,
        bootstrap_state=batch_spec,
    )
    actor_sharded = jax.shard_map(
        local_actor,
        mesh=mesh,
        in_specs=(P(), actor_specs),
        out_specs=(actor_specs, block_specs),
    )
    # registered audit entry point (distributed_ba3c_tpu/audit.py):
    # donation-aliased env carry, collective-free program
    actor_jit = tripwire_jit("fused.actor", actor_sharded, donate_argnums=(1,))

    # ---------------- prep: the params snapshot ----------------------------
    if rollout_dtype == "int8":
        def prep_fn(params):
            # quantize-on-snapshot: the f32 learner params become the
            # int8 serving table (per-channel weight scales + the frozen
            # activation scales riding in) — every cast lives in
            # quantize/qforward.py behind the fused.actor_int8 audit
            return quantize_params(params, quant_spec)
    elif rollout_dtype == "bfloat16":
        def prep_fn(params):
            # the cast IS the snapshot: bf16 actor-side forward (the
            # block only feeds behavior logits that V-trace clips)
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16)  # ba3clint: disable=A16 — THE audited publish cast (entry fused.actor_bf16)
                if x.dtype == jnp.float32 else x,
                params,
            )
    else:
        def prep_fn(params):
            # a plain device copy — see the module docstring for why the
            # actor must NOT read the learner-donated buffers directly
            return jax.tree_util.tree_map(jnp.copy, params)

    prep_jit = tripwire_jit("fused.prep", prep_fn)

    # ---------------- learner program (fused.learner) ----------------------
    # the gradient body and the update tail are the module-level factories
    # (make_block_grads / make_finish_update) shared with the pod's
    # bounded-staleness learner — pure code motion, identical jaxprs, so
    # the audit manifest's fused.* entries are unchanged
    block_grads = make_block_grads(model, cfg, grad_chunk_samples)
    finish_update = make_finish_update(optimizer)

    def local_learner(train: TrainState, block: TrajBlock, entropy_beta,
                      learning_rate):
        grads, aux = block_grads(train.params, block, entropy_beta)
        return finish_update(train, grads, aux, block.rewards, learning_rate)

    learner_sharded = jax.shard_map(
        local_learner,
        mesh=mesh,
        in_specs=(P(), block_specs, P(), P()),
        out_specs=(P(), P()),
    )
    # registered audit entry point: donated train state, exactly-once grad
    # psum census. The block is deliberately NOT donated — its buffers
    # alias no learner output, and keeping them live is what double
    # buffering means
    learner_jit = tripwire_jit(
        "fused.learner", learner_sharded, donate_argnums=(0,)
    )

    # ---------------- macro learner (fused.macro_learner) ------------------
    # K trajectory blocks -> ONE update: per-block grads (the SAME
    # block_grads body the single learner runs, chunking included) are
    # accumulated with a lax.scan over the stacked fleet axis, then a
    # single psum + Adam. Mean-of-equal-window grads == the [T, K*B]
    # full-batch gradient (V-trace couples time, never envs) — the
    # chunked-vs-full equivalence gate extended over the fleet axis
    # (tests/test_fleet.py pins it against the single learner on
    # env-concatenated blocks).
    macro_learner_jit = None
    if macro_fleets > 1:
        K = macro_fleets

        def local_macro_learner(train: TrainState, blocks, entropy_beta,
                                learning_rate):
            # stack K blocks fleet-major INSIDE the program (XLA fuses the
            # concat into the scan's gather; the facade ships the blocks
            # as-is, no host-side copies), accumulate with the SAME scan
            # idiom as the ZMQ macro steps, finish with the shared tail
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *blocks
            )

            def loss_grad_one(params, blk):
                g, aux = block_grads(params, blk, entropy_beta)
                return (None, aux), g  # macro_accumulate's ((_, aux), g)

            grads, aux = macro_accumulate(
                loss_grad_one, train.params, stacked, K
            )
            return finish_update(
                train, grads, aux, stacked.rewards, learning_rate
            )

        macro_learner_sharded = jax.shard_map(
            local_macro_learner,
            mesh=mesh,
            in_specs=(P(), (block_specs,) * K, P(), P()),
            out_specs=(P(), P()),
        )
        # registered audit entry point: donated train state, exactly-once
        # grad psum for the WHOLE macro batch; the K blocks stay undonated
        # for the same double-buffer reason as the single learner's block
        macro_learner_jit = tripwire_jit(
            "fused.macro_learner", macro_learner_sharded, donate_argnums=(0,)
        )

    # ---------------- ep_stats: window-boundary episode metrics -----------
    def local_ep_stats(ep_cnt, ep_sum):
        return (
            jax.lax.psum(jnp.sum(ep_cnt), DATA_AXIS),
            jax.lax.psum(jnp.sum(ep_sum), DATA_AXIS),
        )

    ep_stats_jit = tripwire_jit(
        "fused.ep_stats",
        jax.shard_map(
            local_ep_stats,
            mesh=mesh,
            in_specs=(batch_spec, batch_spec),
            out_specs=(P(), P()),
        ),
    )

    # ---------------- the facade ------------------------------------------
    def step(state: OverlapState, entropy_beta, learning_rate=None):
        if learning_rate is None:
            learning_rate = cfg.learning_rate
        beta_arr = jnp.asarray(entropy_beta, jnp.float32)
        lr_arr = jnp.asarray(learning_rate, jnp.float32)
        train, astate, block = state.train, state.actor, state.block

        def roll(aparams, astate):
            # macro mode: K rollout windows ("fleets") under ONE snapshot,
            # all dispatches async — the env carry chains through, so the
            # K blocks tile time with no gaps. Single-window mode returns
            # the bare block (the single learner's input shape).
            blocks = []
            for _ in range(macro_fleets):
                astate, b = actor_jit(aparams, astate)
                blocks.append(b)
            return astate, blocks[0] if macro_fleets == 1 else tuple(blocks)

        learn = macro_learner_jit if macro_fleets > 1 else learner_jit
        if lag and block is None:
            # prime the pipeline: one rollout window (or K of them) before
            # the first update so learner k always has its k-1 input resident
            aparams = prep_jit(train.params)
            astate, block = roll(aparams, astate)
        ms = []
        for _ in range(steps_per_dispatch):
            aparams = prep_jit(train.params)
            if lag:
                # the two dispatches the whole module exists for: rollout
                # k+1 (reading only the snapshot) enqueued back-to-back
                # with learner k — no host sync in between (J6)
                astate, next_block = roll(aparams, astate)
                train, m = learn(train, block, beta_arr, lr_arr)
                block = next_block
            else:
                astate, block0 = roll(aparams, astate)
                train, m = learn(train, block0, beta_arr, lr_arr)
            ms.append(m)
        if len(ms) == 1:
            metrics = dict(ms[0])
        else:
            metrics = jax.tree_util.tree_map(
                lambda *xs: jnp.mean(jnp.stack(xs)), *ms
            )
        # cumulative-in-state metrics (fused/loop.py CUMULATIVE_METRICS
        # contract): read once per facade call off the latest env carry —
        # NOT inside the iteration pair, where a cross-shard psum would
        # couple the two programs
        episodes, ep_return_sum = ep_stats_jit(
            astate.ep_count, astate.ep_return_sum
        )
        metrics["episodes"] = episodes
        metrics["episode_return_sum"] = ep_return_sum
        assert set(CUMULATIVE_METRICS) <= set(metrics)
        return (
            OverlapState(train=train, actor=astate, block=block if lag else None),
            metrics,
        )

    replicated = NamedSharding(mesh, P())
    batched = NamedSharding(mesh, batch_spec)
    _put_batched = make_put_batched(batched)

    def put(state: FusedState) -> OverlapState:
        """device_put a host FusedState (create_fused_state's layout) with
        the overlap step's shardings, split into train + actor carry."""
        return OverlapState(
            train=jax.device_put(state.train, replicated),
            actor=ActorState(
                env_state=jax.tree_util.tree_map(_put_batched, state.env_state),
                obs_stack=_put_batched(state.obs_stack),
                key=_put_batched(state.key),
                ep_return=_put_batched(state.ep_return),
                ep_count=_put_batched(state.ep_count),
                ep_return_sum=_put_batched(state.ep_return_sum),
            ),
            block=None,
        )

    def reset_episode_stats(state: OverlapState, n_envs: int) -> OverlapState:
        return state.replace(
            actor=state.actor.replace(
                ep_count=_put_batched(jnp.zeros(n_envs, jnp.int32)),
                ep_return_sum=_put_batched(jnp.zeros(n_envs, jnp.float32)),
            )
        )

    def probe_overlap(state: OverlapState, entropy_beta, learning_rate=None,
                      reps: int = 3):
        """Measure the two programs solo and overlapped; returns
        (advanced_state, measurement dict) and publishes the telemetry
        series (tele/learner/actor_program_ms, learner_program_ms,
        overlap_pair_ms, overlap_efficiency — docs/observability.md).

        This is the ONE sanctioned host-sync site between the two
        dispatches: it exists to measure the very serialization J6
        forbids, runs a handful of iterations OUTSIDE the training hot
        loop, and advances the state it was given so no experience is
        replayed. (A capture of a live run, read with
        ``utils/profiling.op_time_by_scope`` / ``host_spans``, shows the
        same split without a sync.)
        ``overlap_efficiency`` is the learner-hidden fraction of the actor
        program: (t_actor + t_learner - t_pair) / t_actor.
        """
        if macro_fleets > 1:
            raise NotImplementedError(
                "probe_overlap measures the single-window actor/learner "
                "pair — run it on a macro_fleets=1 step (the macro "
                "learner's cost profile is pinned by its own audit entry)"
            )
        if learning_rate is None:
            learning_rate = cfg.learning_rate
        beta_arr = jnp.asarray(entropy_beta, jnp.float32)
        lr_arr = jnp.asarray(learning_rate, jnp.float32)
        train, astate, block = state.train, state.actor, state.block
        if block is None:
            aparams = prep_jit(train.params)
            astate, block = actor_jit(aparams, astate)
            jax.block_until_ready(block)  # ba3clint: disable=J6
        t_actor, t_learner, t_pair = [], [], []
        for _ in range(max(1, reps)):
            # solo actor (fully synced — measurement, not training)
            aparams = prep_jit(train.params)
            jax.block_until_ready(aparams)  # ba3clint: disable=J1
            t0 = time.perf_counter()
            astate, next_block = actor_jit(aparams, astate)
            # measurement fence: the probe times the actor ALONE
            jax.block_until_ready(next_block)  # ba3clint: disable=J1
            t_actor.append(time.perf_counter() - t0)
            # solo learner
            t0 = time.perf_counter()
            train, m = learner_jit(train, block, beta_arr, lr_arr)
            jax.block_until_ready(train)  # ba3clint: disable=J1
            t_learner.append(time.perf_counter() - t0)
            block = next_block
            # overlapped pair: both enqueued, one sync at the end
            aparams = prep_jit(train.params)
            jax.block_until_ready(aparams)  # ba3clint: disable=J1
            t0 = time.perf_counter()
            astate, next_block = actor_jit(aparams, astate)
            train, m = learner_jit(train, block, beta_arr, lr_arr)
            jax.block_until_ready((next_block, train))  # ba3clint: disable=J1
            t_pair.append(time.perf_counter() - t0)
            block = next_block
        med = lambda xs: sorted(xs)[len(xs) // 2]  # noqa: E731
        a_ms, l_ms, p_ms = (
            med(t_actor) * 1e3, med(t_learner) * 1e3, med(t_pair) * 1e3
        )
        hidden = (a_ms + l_ms - p_ms) / a_ms if a_ms > 0 else 0.0
        # the device-free proxy gate quantity (ISSUE 8): how much of the
        # actor's wall time the learner window is LONG enough to hide —
        # computed HERE so every reader of the probe reports one number
        coverage = round(min(1.0, l_ms / a_ms), 4) if a_ms > 0 else None
        from distributed_ba3c_tpu import telemetry

        reg = telemetry.registry("learner")
        reg.gauge("actor_program_ms").set(a_ms)
        reg.gauge("learner_program_ms").set(l_ms)
        reg.gauge("overlap_pair_ms").set(p_ms)
        reg.gauge("overlap_efficiency").set(hidden)
        out = {
            "actor_ms": round(a_ms, 3),
            "learner_ms": round(l_ms, 3),
            "pair_ms": round(p_ms, 3),
            "overlap_efficiency": round(hidden, 4),
            "learner_window_coverage": coverage,
            "reps": max(1, reps),
        }
        return OverlapState(train=train, actor=astate, block=block), out

    step.put = put
    step.put_batched = _put_batched
    step.replicated_sharding = replicated
    step.batch_sharding = batched
    step.mesh = mesh
    step.rollout_len = rollout_len
    step.rollout_sub_batch = rollout_sub_batch_of(mesh)
    step.steps_per_dispatch = steps_per_dispatch
    step.lag = lag
    step.rollout_dtype = rollout_dtype
    step.quant_spec = quant_spec
    step.macro_fleets = macro_fleets
    step.reset_episode_stats = reset_episode_stats
    step.probe_overlap = probe_overlap
    # tools/ba3caudit traces THESE programs (two entries, one step;
    # three with the macro learner)
    step.actor_jit = actor_jit
    step.learner_jit = learner_jit
    step.macro_learner_jit = macro_learner_jit
    # the params-snapshot program: the pod's lagged driver
    # (pod/learner.py LaggedBlockDriver) snapshots THROUGH this same
    # program so its version ring never aliases learner-donated buffers
    step.prep_jit = prep_jit
    return step
