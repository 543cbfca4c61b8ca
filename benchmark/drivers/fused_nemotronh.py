"""Stand ``--trainer tpu_fused_ba3c`` up for the Mamba-2 and sparse-expert
hybrid (``--model nemotron-h``) and drive its update.

``drivers/fused_seq.py``'s session (which names the state-space hybrid's
reference, so this policy has a driver of its own): ONE step-and-state
object made from the seed (weights from this policy's reference's own
initialiser), followed through its first update, run through one more and
handed to the window; after the window the program decodes the first
episodes it played token by token through the policy's carry
(``check_seq.py``'s ``logit_gap``), and the learner's own forward
(``model.unroll(with_routes=True)`` at the weights the followed update
started from, a chunk of envs at a time, never the timed step) gives the
experts it chose for every token: the reference learns WITH those routes,
as ``drivers/fused_lm.py`` and ``drivers/fused_sparse.py`` hand theirs
over, and says what it would have chosen (``check_lm.py``'s
``route_flip_share``). The decode's logits are compared against the
reference's forward with its OWN routes.

``Session(..., control=...)`` is a control of the comparison and nothing a
run uses: ``fp8_weights`` rounds the program's matrices to float8 e4m3's 3
bits of mantissa (the precision below the configuration's); ``state_bf16``
keeps the recurrence's state in bfloat16, in the decode's carry and between
the learner's chunks (a precision below the stated one in the new mechanism
itself). Two more are PLANTED FAULTS, which the numbers that no precision
moves are held against: ``half_batch`` (the learner's gradient leaves the
later half of every episode's transitions out; the loss it reports is the
sound one, so only the gradient's and the parameters' norms can tell) and
``no_reset`` (the decode opens every episode on the Mamba-2 states and conv
tails another episode left behind, the position reset: ``logit_gap``'s).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, check_seq
from benchmark.drivers import fused, fused_seq
from benchmark.drivers.fused_sparse import _OneWholeUpdate, _Recording
from benchmark.reference import nemotron_h as reference

CONTROLS = ("fp8_weights", "state_bf16")
FAULTS = ("half_batch", "no_reset")


def _half_batch(model):
    """``model`` with the later half of every episode's transitions left out
    of the learner's gradient (the values the loss reads are the sound ones)."""
    class HalfBatch(type(model)):
        def unroll(self, params, tokens, with_routes=False):
            out, aux = super().unroll(params, tokens, with_routes)
            late = jnp.arange(tokens.shape[1]) >= tokens.shape[1] // 2

            def left_out(x):
                mask = late.reshape((1, -1) + (1,) * (x.ndim - 2))
                return jnp.where(mask, jax.lax.stop_gradient(x), x)

            return out._replace(logits=left_out(out.logits),
                                value=left_out(out.value)), aux

    return HalfBatch(**{f.name: getattr(model, f.name)
                        for f in dataclasses.fields(model) if f.init})


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

        if control not in (None, False) + CONTROLS + FAULTS:
            raise ValueError(f"control {control!r}: one of {CONTROLS + FAULTS}")
        args = cli.make_parser().parse_args(
            list(config["argv"]) + list(cell.get("argv", []))
        )
        cfg = cli.build_config(args)
        env = jaxenv.get_env(args.env.split(":", 1)[1])
        model = build_model(args.model, cfg, args.model_cut).for_env(env)
        if control == "state_bf16":
            model = dataclasses.replace(model, state_dtype=jnp.bfloat16)
        learner = _half_batch(model) if control == "half_batch" else model
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

        def decode(params, tokens, stale):
            """tokens [envs, T] through the carry -> logits [envs, T, ids].
            ``stale``: the planted fault ``no_reset``."""
            served = model.rollout_params(params)

            def one(carry, shown):
                held, fresh = carry
                out, held = model.step(served, shown, held, fresh)
                return (held, jnp.zeros_like(fresh)), out.logits

            carry = (model.init_carry(tokens.shape[0]),
                     jnp.ones(tokens.shape[0], bool))
            if stale:
                # what the episodes in the other order leave behind, under
                # a position that starts again
                (left, unset), _ = jax.lax.scan(
                    one, carry, jnp.swapaxes(tokens[::-1], 0, 1))
                carry = (left._replace(pos=jnp.zeros_like(left.pos)), unset)
            _, logits = jax.lax.scan(one, carry, jnp.swapaxes(tokens, 0, 1))
            return jnp.swapaxes(logits, 0, 1)

        self._decode_either = jax.jit(decode, static_argnames="stale")
        self._decode = functools.partial(
            self._decode_either, stale=control == "no_reset")
        self._learner_routes = jax.jit(
            lambda params, tokens: model.unroll(
                params, tokens, with_routes=True)[1]["routes"])
        self.state = self.step.put(jax.jit(build)(fused.split_seed(seed)))
        self.program: dict = {}
        self._follow_first_updates()
        self._warm_the_dispatch()

    def learner_routes(self):
        """The learner's own forward over each chunk of the followed update
        (a shard's envs in order, whole episodes), at the weights it started
        from: ``routes`` ([shards, expert blocks, envs a shard, T, k]), in a
        list of the one update. Run with the state released."""
        if "routes" in self.program:
            return
        per = self.n_envs // self.chips
        params = jax.device_put(self._start, self.devices[0])
        routes = []
        for s in range(self.chips):
            episodes = jnp.swapaxes(jnp.asarray(self.program["tokens"][0][s]), 0, 1)
            parts = [self._learner_routes(params, episodes[lo:lo + self.chunk_envs])
                     for lo in range(0, per, self.chunk_envs)]
            routes.append(np.concatenate([np.asarray(r) for r in parts], axis=1))
        del params
        self.program["routes"] = [np.stack(routes)]

    def decode_without_a_reset(self) -> np.ndarray:
        """The planted fault ``no_reset`` on this session's own episodes, at
        the weights the run started from (a sound session's: the
        initialiser's): the logits :meth:`compare` takes as
        ``decode_logits``. Run with the state released."""
        params = jax.device_put(self.start_params(), self.devices[0])
        return np.asarray(self._decode_either(
            params, jnp.asarray(self.decode_tokens()), stale=True))

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
        self.counters.update(
            carry_bytes_per_env=np.asarray(last["carry_bytes_per_env"]).tolist(),
            moe_tokens_per_expert=held.tolist(),
            moe_overflow_blocks=np.asarray(last["moe_overflow_blocks"]).tolist(),
            ssm_state_absmax=float(last["ssm_state_absmax"]),
            ssm_dt_mean=float(last["ssm_dt_mean"]))
        print(f"carry: bytes an env by kind (Mamba-2 states, conv tails, K/V, "
              f"position and last step sizes) "
              f"{self.counters['carry_bytes_per_env']}; largest |H| of a "
              f"Mamba-2 state at the window's end "
              f"{self.counters['ssm_state_absmax']:.5g}, mean step size dt "
              f"{self.counters['ssm_dt_mean']:.5g}; the window's last update: "
              f"tokens routed to the held experts a block "
              f"{held.sum(-1).astype(int).tolist()} (fullest over mean "
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
        rows = check_seq.compare(
            side, reference_side, limits or self.limits,
            {"logit_gap": limits_seq["logit_gap"]}, self.loss_floor)
        limit = limits_seq["route_flip_share"]
        rows.append({
            "number": "route_flip_share",
            "value": float(reference_side["route_flip_share"]), "limit": limit,
            "ok": bool(reference_side["route_flip_share"] <= limit),
            "detail": "by expert block " + " ".join(
                f"{x:.5f}" for x in reference_side["route_flips_by_layer"]),
        })
        return rows


def setup(cell: dict, config: dict, devices, seed: int, control=None) -> Session:
    return Session(cell, config, devices, seed, control)
