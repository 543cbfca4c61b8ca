"""Stand ``--trainer tpu_fused_ba3c`` up for a dense token-sequence policy
that carries state (``--model phi4-flash``) and drive its update.

``drivers/fused_lm.py``'s scheme without routes: the same step builder
(``make_fused_step`` from ``cli.py``'s parser and config), the same window
(``drivers/fused.py``'s dispatcher and watcher), ONE step-and-state object
made from the seed (weights from the reference's own initialiser), followed
through its first update, run through one more (so that the window's first
dispatch is a call like every later one) and handed to the window; the tokens shown and the
actions drawn come from the step's own metrics. After the window, with the
state released, the program decodes the first episodes it played token by
token through the policy's carry, at the weights the run started from
(``check_seq.py``'s ``logit_gap``).

``Session(..., control=...)`` is a control of the comparison and nothing a
run uses: ``fp8_weights`` rounds the program's matrices to float8 e4m3's 3
bits of mantissa (the precision below the configuration's); ``window_256``
halves the program's window (a fault a sound run must not pass as).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, check_seq
from benchmark.drivers import fused
from benchmark.drivers.fused_lm import _Remembering
from benchmark.reference import phi4_flash as reference

CONTROLS = ("fp8_weights", "window_256")


def _fp8_rounded(params, is_matrix):
    """The leaves ``is_matrix`` marks with the 3 bits of mantissa float8
    e4m3 would keep of them (round to nearest on the float32's own bits; its
    exponent range is no limit under a per-tensor scale, so none is
    applied). Bits, not an ``astype``: on the v5e a convert to float8 and
    back inside one program came out as no rounding at all (PERF.md, PR 26)."""
    def one(x, matrix):
        if not matrix:
            return x
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    return jax.tree_util.tree_map(one, params, is_matrix)


class Session(fused.Session):
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
        if control == "window_256":
            model = dataclasses.replace(model, sliding_window=256)
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
        self.step = _Remembering(make_fused_step(
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
                params = _fp8_rounded(params, jax.tree_util.tree_map(
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

    def _follow_first_updates(self):
        start = jax.device_get(self.state.train.params)
        losses: List[float] = []
        actions: List[np.ndarray] = []
        tokens: List[np.ndarray] = []
        states: List[tuple] = []
        first_grad = None
        per = self.n_envs // self.chips

        def by_shard(x):  # [T, B_global] -> [shards, T, envs a shard]
            return np.stack([np.asarray(x)[:, s * per:(s + 1) * per]
                             for s in range(self.chips)])

        for i in range(self.follow):
            t0 = time.monotonic()
            self.state, metrics = self.step(self.state, self.beta, self.lr)
            losses.append(float(metrics["loss"]))
            first_call_s = time.monotonic() - t0
            actions.append(by_shard(metrics["actions"]))
            tokens.append(by_shard(metrics["tokens"]))
            # the env batch as the update left it and, since that is a fresh
            # episode's whatever was played, every token each env showed
            states.append((
                dict({k: np.asarray(v) for k, v in
                      self.state.env_state._asdict().items()},
                     shown=np.asarray(metrics["tokens"]).T),
                np.asarray(self.state.obs_stack),
            ))
            if i == 0:
                self.counters["first_dispatch_s"] = first_call_s
                mu = jax.device_get(fused._adam_mu(self.state.train.opt_state))
                first_grad = jax.tree_util.tree_map(
                    lambda m: np.asarray(m) / (1 - reference.ADAM_B1), mu
                )
        end = jax.device_get(self.state.train.params)
        delta = jax.tree_util.tree_map(lambda a, b: a - b, end, start)
        self._start = start  # as the program held them (a control's: rounded)
        self.program = {
            "losses": losses,
            "first_grad": check.leaf_norms(first_grad),
            "delta": check.leaf_norms(delta),
            "actions": actions,
            "tokens": tokens,
            "states": states,
        }

    def _warm_the_dispatch(self):
        """One more update before the window opens, counted as set-up. The
        step's first call takes a state made here, its second the state the
        step itself returned: the second call is a new entry in the jit's
        call cache (``_cache_size()`` 1 -> 2; nothing compiles) and drops
        the host copies the comparison fetched, and at this size that held
        the host for 1.19 s against 0.01 s for every later call (my chip
        run, PR 31): the window's first update started that much late and
        the rate took the delay's jitter."""
        self.state, metrics = self.step(self.state, self.beta, self.lr)
        np.asarray(metrics["loss"])  # blocks until the update is done

    def decode_tokens(self) -> np.ndarray:
        """The first episodes the first followed update played, [envs, T]."""
        return np.swapaxes(self.program["tokens"][0][0], 0, 1)[:self.decode_envs]

    def decode_through_the_carry(self):
        """The program's decode over :meth:`decode_tokens`, at the weights
        the run started from. Run with the state released: it puts the
        weights on the device again."""
        if "decode_logits" in self.program:
            return
        params = jax.device_put(self._start, self.devices[0])
        self.program["decode_logits"] = np.asarray(
            self._decode(params, jnp.asarray(self.decode_tokens())))
        self._start = None

    def start_params(self):
        """The weights the run starts from (the reference's initialiser)."""
        w_key, _, _ = fused.seed_keys(fused.split_seed(self.seed), self.chips)
        return reference.init_params(w_key, self.spec)

    def window(self, seconds: float, tracer=None) -> dict:
        out = super().window(seconds, tracer)
        last = self.step.last_metrics
        self.counters["carry_bytes_per_env"] = np.asarray(
            last["carry_bytes_per_env"]).tolist()
        self.counters["ssm_state_absmax"] = float(last["ssm_state_absmax"])
        print(f"carry: bytes an env by kind (state-space, ring, shared K/V, "
              f"position) {self.counters['carry_bytes_per_env']}; largest |s| "
              f"of a state-space state at the window's end "
              f"{self.counters['ssm_state_absmax']:.5g}")
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

    def compare(self, side: dict, reference_side: dict, limits=None,
                limits_seq=None) -> List[dict]:
        return check_seq.compare(
            side, reference_side, limits or self.limits,
            limits_seq or self.limits_seq, self.loss_floor)

    def check(self) -> List[dict]:
        t0 = time.monotonic()
        reference_side = self.reference_readings(actions=self.program["actions"])
        print(f"reference: followed {self.follow} updates in "
              f"{time.monotonic() - t0:.1f} s")
        return self.compare(self.program, reference_side)


def setup(cell: dict, config: dict, devices, seed: int, control=None) -> Session:
    return Session(cell, config, devices, seed, control)
