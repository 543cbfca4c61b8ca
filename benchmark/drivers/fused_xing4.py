"""Stand ``--trainer tpu_fused_ba3c`` up for the latent-attention policy on
four residual streams (``--model xing4``) and drive its update.

``drivers/fused_nemotronh.py``'s session (which names the Mamba-2 hybrid's
reference, so this policy has a driver of its own): ONE step-and-state
object made from the seed (weights from this policy's reference's own
initialiser), followed through its first update, run through one more and
handed to the window; after the window the program decodes the first
episodes it played token by token through the policy's carry, THE ABSORBED
ATTENTION over the latent rows against the reference's expanded one
(``check_seq.py``'s ``logit_gap``), and the learner's own forward
(``model.unroll(with_routes=True)`` at the weights the followed update
started from, a chunk of envs at a time, never the timed step) gives the
experts it chose for every token: the reference learns WITH those routes
and says what it would have chosen (``check_lm.py``'s ``route_flip_share``).
The decode's logits are compared against the reference's forward with its
OWN routes. One number more is this driver's: ``mhc_gap_excess``, the
followed update's counter ``mhc_doubly_stochastic_gap`` (how far the
learner's mixing matrices were from doubly stochastic, the mean over tokens
and sub-blocks) against the reference's own over the same tokens, as
``|program / reference - 1|``: the projection did the 20 iterations' work in
float32, or it reads many times the reference's.

``Session(..., control=...)`` is a control of the comparison and nothing a
run uses: ``fp8_weights`` rounds the program's matrices to float8 e4m3's 3
bits of mantissa (the precision below the configuration's);
``streams_bf16`` keeps the residual streams and the hyper-connections'
mappings in bfloat16 (a precision below the stated one inside the new
mechanism itself). Three more are PLANTED FAULTS: ``sinkhorn_5`` (the
program stops the projection after 5 iterations of 20: part of the
mathematics left out), ``yarn_off`` (the decode step alone rotates by the
unscaled frequencies: the two forms of the one layer disagree; the unroll
is sound) and ``half_batch`` (the learner's gradient leaves the later half
of every episode's transitions out; the loss it reports is the sound one,
so only the gradient's and the parameters' norms can tell).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, check_seq
from benchmark.drivers import fused, fused_nemotronh, fused_seq
from benchmark.drivers.fused_nemotronh import _half_batch
from benchmark.drivers.fused_sparse import _OneWholeUpdate, _Recording
from benchmark.reference import xing4 as reference

CONTROLS = ("fp8_weights", "streams_bf16")
FAULTS = ("sinkhorn_5", "yarn_off", "half_batch")


def _fields(model) -> dict:
    return {f.name: getattr(model, f.name)
            for f in dataclasses.fields(model) if f.init}


def _yarn_off(model):
    """``model`` whose decode step alone rotates by ``theta``'s own
    frequencies, as if ``rope_scaling`` were not there (its attention's
    scale stays YaRN's); the unroll is the sound one."""
    class Unscaled(type(model)):
        def rope_frequencies(self):
            half = self.qk_rope_head_dim // 2
            return self.rope_theta ** (
                -jnp.arange(half, dtype=jnp.float32) / half)

    class YarnOff(type(model)):
        def step(self, params, obs, carry, fresh):
            return Unscaled(**_fields(self)).step(params, obs, carry, fresh)

    return YarnOff(**_fields(model))


def faulted(model, control):
    """(the policy the rollout and the decode check run, the policy the
    learner differentiates) under ``control``."""
    if control == "streams_bf16":
        model = dataclasses.replace(model, stream_dtype=jnp.bfloat16)
    elif control == "sinkhorn_5":
        model = dataclasses.replace(model, hc_sinkhorn_iters=5)
    elif control == "yarn_off":
        model = _yarn_off(model)
    return model, _half_batch(model) if control == "half_batch" else model


class Session(fused_nemotronh.Session):
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

        if control not in (None, False) + CONTROLS + FAULTS:
            raise ValueError(f"control {control!r}: one of {CONTROLS + FAULTS}")
        args = cli.make_parser().parse_args(
            list(config["argv"]) + list(cell.get("argv", []))
        )
        cfg = cli.build_config(args)
        env = jaxenv.get_env(args.env.split(":", 1)[1])
        model, learner = faulted(
            build_model(args.model, cfg, args.model_cut).for_env(env), control)
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
        if self.follow != 1:
            raise ValueError("this driver follows one update (the routes it "
                             "hands over are the start weights')")
        self.limits, self.limits_seq = cell["limits"], cell["limits_seq"]
        self.decode_envs = int(cell["decode_check_envs"])
        self.hyper = dict(config["algorithm"], rollout_len=args.rollout_len)
        self.spec = reference.spec_of(config)
        self.loss_floor = check_seq.loss_floor(cfg.entropy_beta, self.spec["ids"])
        self.prompt_len = env.prompt_len
        self.model = model
        self.step = _Recording(make_fused_step(
            learner, optimizer, cfg, mesh, env, args.rollout_len,
            grad_chunk_samples=args.grad_chunk_samples,
            steps_per_dispatch=args.steps_per_dispatch,
        ))
        n_envs, per = self.n_envs, self.n_envs // chips
        n_chunks = learner_chunks(
            per, per * args.rollout_len, args.grad_chunk_samples)
        self.chunk_envs = per // n_chunks
        self.counters: Dict[str, float] = {
            "rollout_len": args.rollout_len, "envs_per_chip": per,
            "learner_chunks": n_chunks}
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

        def decode(policy, params, tokens):
            """tokens [envs, T] through ``policy``'s carry -> logits [envs,
            T, ids]."""
            served = policy.rollout_params(params)

            def one(carry, shown):
                held, fresh = carry
                out, held = policy.step(served, shown, held, fresh)
                return (held, jnp.zeros_like(fresh)), out.logits

            carry = (policy.init_carry(tokens.shape[0]),
                     jnp.ones(tokens.shape[0], bool))
            _, logits = jax.lax.scan(one, carry, jnp.swapaxes(tokens, 0, 1))
            return jnp.swapaxes(logits, 0, 1)

        self._decode = jax.jit(functools.partial(decode, model))
        self._decode_yarn_off = jax.jit(
            functools.partial(decode, _yarn_off(model)))
        self._learner_routes = jax.jit(
            lambda params, tokens: model.unroll(
                params, tokens, with_routes=True)[1]["routes"])
        self.state = self.step.put(jax.jit(build)(fused.split_seed(seed)))
        self.program: dict = {}
        self._follow_first_updates()
        gap_sum, mappings = (float(x) for x in np.asarray(
            self.step.last_metrics["mhc_doubly_stochastic_gap"]))
        self.program["mhc_doubly_stochastic_gap"] = gap_sum / mappings
        self._warm_the_dispatch()

    def decode_with_yarn_off(self) -> np.ndarray:
        """The planted fault ``yarn_off`` on this session's own episodes, at
        the weights the run started from (a sound session's: the
        initialiser's): the logits :meth:`compare` takes as
        ``decode_logits``. Run with the state released."""
        params = jax.device_put(self.start_params(), self.devices[0])
        return np.asarray(self._decode_yarn_off(
            params, jnp.asarray(self.decode_tokens())))

    def start_params(self):
        """The weights the run starts from (the reference's initialiser)."""
        w_key, _, _ = fused.seed_keys(fused.split_seed(self.seed), self.chips)
        return reference.init_params(w_key, self.spec)

    def window(self, seconds: float, tracer=None) -> dict:
        if tracer is not None:  # an update is most of the window: one, whole
            tracer = _OneWholeUpdate(tracer, self.step)
        out = fused.Session.window(self, seconds, tracer)
        last = self.step.last_metrics
        held = np.asarray(last["moe_tokens_per_expert"])
        gap_sum, mappings = (
            float(x) for x in np.asarray(last["mhc_doubly_stochastic_gap"]))
        self.counters.update(
            carry_bytes_per_env=np.asarray(last["carry_bytes_per_env"]).tolist(),
            moe_tokens_per_expert=held.tolist(),
            moe_overflow_blocks=np.asarray(last["moe_overflow_blocks"]).tolist(),
            mhc_doubly_stochastic_gap=gap_sum / max(mappings, 1.0))
        print(f"carry: bytes an env by kind (latent rows, padded to whole "
              f"lanes; position) {self.counters['carry_bytes_per_env']}; the "
              f"window's last update: mean distance of H_res from doubly "
              f"stochastic {self.counters['mhc_doubly_stochastic_gap']:.5g} "
              f"over {mappings:.0f} mappings; tokens routed to the held "
              f"experts a layer {held.sum(-1).astype(int).tolist()} (fullest "
              f"over mean "
              f"{float((held.max(-1) / np.maximum(held.mean(-1), 1e-9)).max()):.4f}), "
              f"overflow blocks {self.counters['moe_overflow_blocks']}")
        return out

    def reference_readings(self, lower=None, actions=None) -> dict:
        """The reference's side: playing ``actions`` (those the program
        drew) and learning with the routes the program's learner used; its
        forward, with its OWN routes, over the tokens the program decodes."""
        self.learner_routes()
        self.decode_through_the_carry()
        _, env_key, shard_keys = fused.seed_keys(
            fused.split_seed(self.seed), self.chips)
        params = self.start_params()
        logits = np.asarray(reference.logits_of(
            params, jnp.asarray(self.decode_tokens()), self.spec, lower))
        out = reference.follow_updates(  # consumes ``params``
            params, env_key, shard_keys, self.n_envs, self.spec,
            self.hyper, self.follow, actions, self.prompt_len, lower,
            routes=self.program["routes"],
        )
        return dict(
            out,
            first_grad=check.leaf_norms(out["first_grad"]),
            delta=check.leaf_norms(out["delta"]),
            decode_logits=logits,
        )


    def compare(self, side: dict, reference_side: dict, limits=None,
                limits_seq=None) -> List[dict]:
        limits_seq = limits_seq or self.limits_seq
        rows = super().compare(side, reference_side, limits, limits_seq)
        ours, theirs = (float(s["mhc_doubly_stochastic_gap"])
                        for s in (side, reference_side))
        excess = abs(ours / max(theirs, 1e-30) - 1.0)
        limit = limits_seq["mhc_gap_excess"]
        rows.append({
            "number": "mhc_gap_excess", "value": excess, "limit": limit,
            "ok": bool(excess <= limit),
            "detail": f"mean distance of H_res from doubly stochastic: the "
                      f"program's learner {ours:.5g}, the reference {theirs:.5g}",
        })
        return rows


def setup(cell: dict, config: dict, devices, seed: int, control=None) -> Session:
    return Session(cell, config, devices, seed, control)
