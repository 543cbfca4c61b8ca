"""Stand ``--trainer tpu_fused_ba3c --model lfm2-moe`` up and drive its update.

``drivers/fused.py``'s scheme for a token-sequence policy that carries
state: the same step builder (``make_fused_step`` from ``cli.py``'s parser
and config, as ``run_fused_training`` builds it), the same window (its
dispatcher and watcher, ``stats.completed_rate``), the same five numbers
through ``benchmark/check.py``, and two more through ``check_lm.py``.

Set-up makes ONE step-and-state object from the seed (weights from the
reference's own initialiser, env keys, per-shard streams), follows it
through its first update(s), and hands that object to the window. What the
followed update needs beside ``fused.py``'s: the tokens shown and the
actions drawn come from the step itself (its metrics ``tokens`` and
``actions``: a second compiled rollout does not draw the same 32,768 tokens
from one bfloat16 forward, and this game's env batch restarts at every
update's end, so the env states could not tell). After the window, with the
state released, the learner's own forward (``model.unroll``, the weights
each followed update started from) over each of its chunks gives the experts
it chose for every token, and its logits over the first chunk.

``Session(..., control=...)`` is a control of the comparison and nothing a
run uses: ``fp8_weights`` rounds the program's matrices to float8 e4m3's 3
bits of mantissa (the precision below the configuration's), ``drop_expert`` zeroes one held
expert's output matrix (a fault a sound run must not pass as).
"""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, check_lm
from benchmark.drivers import fused
from benchmark.reference import lfm2_moe as reference

CONTROLS = ("fp8_weights", "drop_expert")


def _fp8_rounded(params):
    """Matrices with the 3 bits of mantissa float8 e4m3 would keep of them
    (round to nearest on the float32's own bits; its exponent range is no
    limit under a per-tensor scale, so none is applied). Bits, not an
    ``astype``: on the v5e a convert to float8 and back inside one program
    came out as no rounding at all (my chip run, PR 26: every number of
    this control read as the sound run's)."""
    def one(x):
        if x.ndim < 2:
            return x
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        bits = (bits + jnp.uint32(1 << 19)) & jnp.uint32(0xFFF00000)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    return jax.tree_util.tree_map(one, params)


def _without_one_expert(params):
    """Expert 0 of the first expert layer adds nothing."""
    layer = next(k for k in sorted(params) if "router" in params[k])
    w2 = params[layer]["w2"]
    return dict(params, **{layer: dict(params[layer], w2=w2.at[0].set(0.0))})


class _Remembering:
    """The step, keeping the metrics of its last call (the counters)."""

    def __init__(self, step):
        self._step, self.last_metrics = step, None

    def __call__(self, *args):
        out = self._step(*args)
        self.last_metrics = out[1]
        return out

    def __getattr__(self, name):
        return getattr(self._step, name)


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
        self.limits, self.limits_lm = cell["limits"], cell["limits_lm"]
        self.hyper = dict(config["algorithm"], rollout_len=args.rollout_len)
        self.spec = reference.spec_of(config)
        self.prompt_len = env.prompt_len
        self.model = model
        self.step = _Remembering(make_fused_step(
            model, optimizer, cfg, mesh, env, args.rollout_len,
            grad_chunk_samples=args.grad_chunk_samples,
            steps_per_dispatch=args.steps_per_dispatch,
        ))
        n_envs, per = self.n_envs, self.n_envs // chips
        n_chunks = learner_chunks(
            per, per * args.rollout_len, args.grad_chunk_samples)
        self.chunk_envs = per // n_chunks
        self.counters: Dict[str, float] = {
            "rollout_len": args.rollout_len, "learner_chunks": n_chunks}
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
                params = _fp8_rounded(params)
            elif control == "drop_expert":
                params = _without_one_expert(params)
            return state.replace(
                train=state.train.replace(params=params),
                env_state=env_state, obs_stack=jax.vmap(env.render)(env_state),
                key=shard_keys,
            )

        def learner_forward(params, tokens):
            out, aux = model.unroll(params, tokens, with_routes=True)
            return out.logits, aux["routes"]

        self._learner_forward = jax.jit(learner_forward)
        self.state = self.step.put(jax.jit(build)(fused.split_seed(seed)))
        self.program: dict = {}
        self._follow_first_updates()

    def _follow_first_updates(self):
        start = jax.device_get(self.state.train.params)
        losses: List[float] = []
        actions: List[np.ndarray] = []
        tokens: List[np.ndarray] = []
        states: List[tuple] = []
        before = [start]  # the weights each followed update started from
        first_grad = None
        per = self.n_envs // self.chips

        def by_shard(x):  # [T, B_global] -> [shards, T, envs a shard]
            return np.stack([np.asarray(x)[:, s * per:(s + 1) * per]
                             for s in range(self.chips)])

        for i in range(self.follow):
            if i:
                before.append(jax.device_get(self.state.train.params))
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
        self._before = before
        self.program = {
            "losses": losses,
            "first_grad": check.leaf_norms(first_grad),
            "delta": check.leaf_norms(delta),
            "actions": actions,
            "tokens": tokens,
            "states": states,
        }

    def learner_forward(self):
        """The learner's own forward over each chunk of each followed update
        (a shard's envs in order, whole episodes), at the weights that
        update started from: ``routes`` (per update [shards, expert layers,
        envs a shard, T, k]) and ``forward`` (the first chunk of the first
        update: tokens, logits, routes). Run with the state released: it
        puts 2 GB of weights on the device again."""
        if "forward" in self.program:
            return
        per = self.n_envs // self.chips
        routes, forward = [], None
        for params, shown in zip(self._before, self.program["tokens"], strict=True):
            params = jax.device_put(params, self.devices[0])
            chosen = []
            for s in range(self.chips):
                episodes = jnp.swapaxes(jnp.asarray(shown[s]), 0, 1)
                parts = []
                for lo in range(0, per, self.chunk_envs):
                    chunk = episodes[lo:lo + self.chunk_envs]
                    logits, picked = self._learner_forward(params, chunk)
                    parts.append(np.asarray(picked))
                    if forward is None:
                        forward = {"tokens": np.asarray(chunk),
                                   "logits": np.asarray(logits),
                                   "routes": parts[0]}
                    del logits
                chosen.append(np.concatenate(parts, axis=1))
            routes.append(np.stack(chosen))
            del params
        self._before = None
        self.program.update(routes=routes, forward=forward)

    def start_params(self):
        """The weights the run starts from (the reference's initialiser)."""
        w_key, _, _ = fused.seed_keys(fused.split_seed(self.seed), self.chips)
        return reference.init_params(w_key, self.spec)

    def window(self, seconds: float, tracer=None) -> dict:
        out = super().window(seconds, tracer)
        held = np.asarray(self.step.last_metrics["moe_tokens_per_expert"])
        assignments = (self.work_per_update
                       * self.spec["top_k"] * held.shape[0])
        self.counters["moe_tokens_per_expert"] = held.tolist()
        print(f"moe: tokens routed to each held expert in the window's last "
              f"update, by layer {held.astype(int).tolist()}; fullest over "
              f"mean {float((held.max(-1) / held.mean(-1)).max()):.4f}; "
              f"{float(held.sum()) / assignments:.4f} of the "
              f"{assignments} assignments land here")
        return out

    def reference_readings(self, lower=None, actions=None) -> dict:
        """The reference's side: playing ``actions`` (those the program
        drew) and learning with the routes the program's learner chose, and
        its forward over the program's first chunk of tokens, likewise."""
        self.learner_forward()
        _, env_key, shard_keys = fused.seed_keys(
            fused.split_seed(self.seed), self.chips)
        params = self.start_params()
        out = reference.follow_updates(
            params, env_key, shard_keys, self.n_envs, self.spec, self.hyper,
            self.follow, actions, self.prompt_len, lower,
            routes=self.program["routes"],
        )
        ours = self.program["forward"]
        logits, routes = reference.logits_and_routes(
            params, jnp.asarray(ours["tokens"]), self.spec,
            jnp.asarray(ours["routes"]), lower,
        )
        return dict(
            out,
            first_grad=check.leaf_norms(jax.device_get(out["first_grad"])),
            delta=check.leaf_norms(jax.device_get(out["delta"])),
            forward={"logits": np.asarray(logits), "routes": np.asarray(routes)},
        )

    def compare(self, side: dict, reference_side: dict, limits=None,
                limits_lm=None) -> List[dict]:
        return check.compare(
            side, reference_side, limits or self.limits
        ) + check_lm.compare(
            side["forward"], reference_side["forward"],
            limits_lm or self.limits_lm)

    def check(self) -> List[dict]:
        t0 = time.monotonic()
        reference_side = self.reference_readings(actions=self.program["actions"])
        print(f"reference: followed {self.follow} updates in "
              f"{time.monotonic() - t0:.1f} s")
        return self.compare(self.program, reference_side)


def setup(cell: dict, config: dict, devices, seed: int, control=None) -> Session:
    return Session(cell, config, devices, seed, control)
