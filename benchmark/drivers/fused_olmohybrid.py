"""Stand ``--trainer tpu_fused_ba3c`` up for the linear-attention hybrid
(``--model olmo-hybrid``) and drive its update.

``drivers/fused_seq.py``'s session (which names the state-space hybrid's
reference, so this policy has a driver of its own): ONE step-and-state
object made from the seed (weights from this policy's reference's own
initialiser), followed through its first update, run through one more and
handed to the window; after the window the program decodes the first
episodes it played token by token through the policy's carry
(``check_seq.py``'s ``logit_gap``). What is inherited is what names no
model: following the updates, warming the dispatch, the decode, the
comparison.

``Session(..., control=...)`` is a control of the comparison and nothing a
run uses: ``fp8_weights`` rounds the program's matrices to float8 e4m3's 3
bits of mantissa (the precision below the configuration's); ``state_bf16``
keeps the delta rule's state in bfloat16, in the decode's carry and between
the learner's chunks (a precision below the stated one in the new mechanism
itself).
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, check_seq
from benchmark.drivers import fused, fused_seq
from benchmark.drivers.fused_sparse import _OneWholeUpdate, _Recording
from benchmark.reference import olmo_hybrid as reference

CONTROLS = ("fp8_weights", "state_bf16")


class Session(fused_seq.Session):
    """One cell's step and state, from set-up through the window."""

    def __init__(self, cell: dict, config: dict, devices, seed: int,
                 control=None):
        from distributed_ba3c_tpu import cli
        from distributed_ba3c_tpu.envs import jaxenv
        from distributed_ba3c_tpu.fused.loop import (
            create_fused_state,
            learner_chunks,
            make_fused_step,
        )
        from distributed_ba3c_tpu.models.policy import build_model
        from distributed_ba3c_tpu.ops.gradproc import make_optimizer
        from distributed_ba3c_tpu.parallel.mesh import make_mesh

        if control not in (None, False) + CONTROLS:
            raise ValueError(f"control {control!r}: one of {CONTROLS}")
        args = cli.make_parser().parse_args(
            list(config["argv"]) + list(cell.get("argv", []))
        )
        cfg = cli.build_config(args)
        env = jaxenv.get_env(args.env.split(":", 1)[1])
        model = build_model(args.model, cfg, args.model_cut).for_env(env)
        if control == "state_bf16":
            model = dataclasses.replace(model, state_dtype=jnp.bfloat16)
        optimizer = make_optimizer(
            cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm
        )
        chips = cell["chips"]
        mesh = make_mesh(num_data=chips, num_model=1, devices=devices[:chips])
        self.devices = list(devices[:chips])
        self.chips = chips
        self.rollout_len = args.rollout_len
        self.n_envs = max(1, cfg.batch_size // args.rollout_len) * chips
        self.beta, self.lr = cfg.entropy_beta, cfg.learning_rate
        self.seed = seed
        self.follow = int(cell["follow_updates"])
        self.limits, self.limits_seq = cell["limits"], cell["limits_seq"]
        self.decode_envs = int(cell["decode_check_envs"])
        self.hyper = dict(config["algorithm"], rollout_len=args.rollout_len)
        self.spec = reference.spec_of(config)
        self.loss_floor = check_seq.loss_floor(cfg.entropy_beta, self.spec["ids"])
        self.prompt_len = env.prompt_len
        self.model = model
        self.step = _Recording(make_fused_step(
            model, optimizer, cfg, mesh, env, args.rollout_len,
            grad_chunk_samples=args.grad_chunk_samples,
            steps_per_dispatch=args.steps_per_dispatch,
        ))
        n_envs, per = self.n_envs, self.n_envs // chips
        self.counters: Dict[str, float] = {
            "rollout_len": args.rollout_len, "envs_per_chip": per,
            "learner_chunks": learner_chunks(
                per, per * args.rollout_len, args.grad_chunk_samples)}
        spec = self.spec

        def build(seed_halves):
            w_key, env_key, shard_keys = fused.seed_keys(seed_halves, chips)
            state = create_fused_state(
                w_key, model, cfg, optimizer, env, n_envs, n_shards=chips
            )
            env_state = jax.vmap(env.reset)(jax.random.split(env_key, n_envs))
            params = reference.init_params(w_key, spec)
            ours = jax.tree_util.tree_map(jnp.shape, state.train.params)
            theirs = jax.tree_util.tree_map(jnp.shape, params)
            if ours != theirs:
                raise ValueError(
                    "the program's parameters are not the configuration's: "
                    f"{ours} against {theirs}")
            if control == "fp8_weights":
                # the matrices: what the rollout's snapshot puts in bfloat16
                served = jax.eval_shape(model.rollout_params, params)
                params = fused_seq._fp8_rounded(params, jax.tree_util.tree_map(
                    lambda s: s.dtype == model.compute_dtype, served))
            return state.replace(
                train=state.train.replace(params=params),
                env_state=env_state, obs_stack=jax.vmap(env.render)(env_state),
                key=shard_keys,
            )

        def decode(params, tokens):
            """tokens [envs, T] through the carry -> logits [envs, T, ids]."""
            served = model.rollout_params(params)

            def one(carry, shown):
                held, fresh = carry
                out, held = model.step(served, shown, held, fresh)
                return (held, jnp.zeros_like(fresh)), out.logits

            carry = (model.init_carry(tokens.shape[0]),
                     jnp.ones(tokens.shape[0], bool))
            _, logits = jax.lax.scan(one, carry, jnp.swapaxes(tokens, 0, 1))
            return jnp.swapaxes(logits, 0, 1)

        self._decode = jax.jit(decode)
        self.state = self.step.put(jax.jit(build)(fused.split_seed(seed)))
        self.program: dict = {}
        self._follow_first_updates()
        self._warm_the_dispatch()

    def start_params(self):
        """The weights the run starts from (the reference's initialiser)."""
        w_key, _, _ = fused.seed_keys(fused.split_seed(self.seed), self.chips)
        return reference.init_params(w_key, self.spec)

    def window(self, seconds: float, tracer=None) -> dict:
        if tracer is not None:  # an update is most of the window: one, whole
            tracer = _OneWholeUpdate(tracer, self.step)
        out = fused.Session.window(self, seconds, tracer)
        last = self.step.last_metrics
        self.counters["carry_bytes_per_env"] = np.asarray(
            last["carry_bytes_per_env"]).tolist()
        for gauge in ("linattn_state_absmax", "linattn_gate_mean"):
            self.counters[gauge] = float(last[gauge])
        print(f"carry: bytes an env by kind (delta-rule states, conv tails, "
              f"K/V, position and last gates) "
              f"{self.counters['carry_bytes_per_env']}; largest |S| of a "
              f"delta-rule state at the window's end "
              f"{self.counters['linattn_state_absmax']:.5g}, mean gate alpha "
              f"{self.counters['linattn_gate_mean']:.5g}")
        return out

    def reference_readings(self, lower=None, actions=None) -> dict:
        """The reference's side: playing ``actions`` (those the program
        drew), and its forward over the tokens the program decodes."""
        self.decode_through_the_carry()
        _, env_key, shard_keys = fused.seed_keys(
            fused.split_seed(self.seed), self.chips)
        params = self.start_params()
        logits = np.asarray(reference.logits_of(
            params, jnp.asarray(self.decode_tokens()), self.spec, lower))
        out = reference.follow_updates(  # consumes ``params``
            params, env_key, shard_keys, self.n_envs, self.spec,
            self.hyper, self.follow, actions, self.prompt_len, lower,
        )
        return dict(
            out,
            first_grad=check.leaf_norms(out["first_grad"]),
            delta=check.leaf_norms(out["delta"]),
            decode_logits=logits,
        )


def setup(cell: dict, config: dict, devices, seed: int, control=None) -> Session:
    return Session(cell, config, devices, seed, control)
