"""Stand ``--trainer tpu_fused_ba3c --model keye-vl2`` up and drive its
update: routed experts beside attention over the keys an indexer selects.

``drivers/fused_seq.py``'s scheme (the same step builder, window, followed
first update, one more update in set-up, decode through the carry after the
window) with the two choices this policy makes handed to the reference, as
``drivers/fused_lm.py`` hands over its routes: after the window, with the
state released, the learner's own forward (``model.unroll(with_routes=True)``
at the weights the followed update started from, a chunk of envs at a time,
never the timed step) gives the experts it chose for every token and the
keys every query read (the mask's bits, packed); the reference learns WITH
both and says what it would have chosen (``check_sparse.py``). The decode's
logits are compared against the reference's forward with its OWN choices.

``Session(..., control=...)`` is a control of the comparison and nothing a
run uses: ``fp8_weights`` rounds the program's matrices to float8 e4m3's 3
bits of mantissa (the precision below the configuration's); ``topk_1024``
makes the program alone keep half as many keys (a fault a sound run must not
pass as).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, check_seq, check_sparse
from benchmark.drivers import fused, fused_seq
from benchmark.drivers.fused_lm import _Remembering
from benchmark.reference import keye_vl2 as reference

CONTROLS = ("fp8_weights", "topk_1024")


class _Recording(_Remembering):
    """The step, keeping beside its last metrics every call's loss (a device
    scalar each): what a completion is waited on by."""

    def __init__(self, step):
        super().__init__(step)
        self.losses: list = []

    def __call__(self, *args):
        out = super().__call__(*args)
        self.losses.append(out[1]["loss"])
        return out


class _OneWholeUpdate:
    """``run.Tracer`` for updates that outlast the window. ``drivers/fused.
    py``'s window ticks its tracer between dispatches, and here the fourth
    dispatch waits 25 s for a slot: the 10 s window is over before a tick
    could start the profiler a second in, and no capture was written (my
    chip run, PR 34). So the profiler starts at the first tick, before the
    window's first dispatch, and a thread of its own closes it when that
    update completes: one whole update on the chip (the updates run one
    after another there; 4,096 decode steps and 8 learner chunks), not the
    100 s of the window's four."""

    def __init__(self, tracer, step: _Recording):
        self.tracer, self.step, self.thread = tracer, step, None

    def tick(self, elapsed: float):
        if self.thread is None:
            self.tracer.start_at, self.tracer.seconds = 0.0, float("inf")
            self.tracer.tick(elapsed)
            self.thread = threading.Thread(
                target=self._close_at_completion, args=(len(self.step.losses),),
                name="bench-trace-closer")
            self.thread.start()

    def _close_at_completion(self, first: int):
        while len(self.step.losses) <= first:
            time.sleep(0.005)
        np.asarray(self.step.losses[first])  # blocks until that update is done
        self.tracer.close()

    def close(self):
        if self.thread is not None:
            self.thread.join()
        self.tracer.close()


class Session(fused_seq.Session):
    """One cell's step and state, from set-up through the window. Of
    ``fused_seq.Session`` it keeps the followed update, the warmed dispatch
    and the decode through the carry; what names that policy is its own."""

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
        if control == "topk_1024":
            model = dataclasses.replace(model, index_topk=model.index_topk // 2)
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
            raise ValueError("this driver follows one update (its term is read "
                             "off the step's last metrics)")
        self.limits, self.limits_sparse = cell["limits"], cell["limits_sparse"]
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
        n_chunks = learner_chunks(
            per, per * args.rollout_len, args.grad_chunk_samples)
        self.chunk_envs = per // n_chunks
        self.counters: Dict[str, float] = {
            "rollout_len": args.rollout_len, "envs_per_chip": per,
            "learner_chunks": n_chunks, "index_topk": model.index_topk}
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

        def learner_choices(params, tokens):
            _, aux = model.unroll(params, tokens, with_routes=True)
            return aux["routes"], aux["selected"]

        self._decode = jax.jit(decode)
        self._learner_choices = jax.jit(learner_choices)
        self.state = self.step.put(jax.jit(build)(fused.split_seed(seed)))
        self.program: dict = {}
        self._follow_first_updates()
        # the differentiated total: the A2C loss the step reports as ``loss``
        # and the policy's own term beside it, a layer
        term = np.asarray(self.step.last_metrics["indexer_kl"], np.float64)
        self.program["a2c_losses"] = list(self.program["losses"])
        self.program["indexer_kl"] = [term.tolist()]
        self.program["losses"] = [self.program["losses"][0] + float(term.sum())]
        self._warm_the_dispatch()

    def learner_choices(self):
        """The learner's own forward over each chunk of the followed update
        (a shard's envs in order, whole episodes), at the weights it started
        from: ``routes`` ([shards, layers, envs a shard, T, k]) and
        ``selected`` ([shards, layers, envs a shard, T, T / 8] uint8), each
        in a list of the one update. Run with the state released."""
        if "routes" in self.program:
            return
        per = self.n_envs // self.chips
        params = jax.device_put(self._start, self.devices[0])
        routes, selected = [], []
        for s in range(self.chips):
            episodes = jnp.swapaxes(jnp.asarray(self.program["tokens"][0][s]), 0, 1)
            parts = [self._learner_choices(params, episodes[lo:lo + self.chunk_envs])
                     for lo in range(0, per, self.chunk_envs)]
            routes.append(np.concatenate([np.asarray(r) for r, _ in parts], axis=1))
            selected.append(np.concatenate([np.asarray(m) for _, m in parts], axis=1))
        del params
        self.program.update(routes=[np.stack(routes)], selected=[np.stack(selected)])

    def start_params(self):
        """The weights the run starts from (the reference's initialiser)."""
        w_key, _, _ = fused.seed_keys(fused.split_seed(self.seed), self.chips)
        return reference.init_params(w_key, self.spec)

    def window(self, seconds: float, tracer=None) -> dict:
        if tracer is not None:
            tracer = _OneWholeUpdate(tracer, self.step)
        out = fused.Session.window(self, seconds, tracer)
        last = self.step.last_metrics
        held = np.asarray(last["moe_tokens_per_expert"])
        selected, live = (np.asarray(last[k], np.float64)
                          for k in ("dsa_keys_selected", "dsa_keys_live"))
        self.counters.update(
            carry_bytes_per_env=np.asarray(last["carry_bytes_per_env"]).tolist(),
            moe_tokens_per_expert=held.tolist(),
            moe_overflow_blocks=np.asarray(last["moe_overflow_blocks"]).tolist(),
            dsa_keys_selected=selected.tolist(), dsa_keys_live=live.tolist(),
            indexer_kl=np.asarray(last["indexer_kl"]).tolist())
        print(f"carry: bytes an env by kind (K/V, indexer keys, position) "
              f"{self.counters['carry_bytes_per_env']}; the window's last update: "
              f"keys selected / live a layer "
              f"{[round(s / l, 4) for s, l in zip(selected, live, strict=True)]}, "
              f"indexer KL a layer {self.counters['indexer_kl']}, tokens routed "
              f"to the held experts a layer {held.sum(-1).astype(int).tolist()} "
              f"(fullest over mean {float((held.max(-1) / held.mean(-1)).max()):.4f}), "
              f"overflow blocks {self.counters['moe_overflow_blocks']}")
        return out

    def reference_readings(self, lower=None, actions=None) -> dict:
        """The reference's side: playing ``actions`` (those the program
        drew) and learning with the routes and the selections the program's
        learner used; its forward, with its OWN choices, over the tokens the
        program decodes."""
        self.learner_choices()
        self.decode_through_the_carry()
        _, env_key, shard_keys = fused.seed_keys(
            fused.split_seed(self.seed), self.chips)
        params = self.start_params()
        logits = np.asarray(reference.logits_of(
            params, jnp.asarray(self.decode_tokens()), self.spec, lower))
        out = reference.follow_updates(  # consumes ``params``
            params, env_key, shard_keys, self.n_envs, self.spec, self.hyper,
            self.follow, actions, self.prompt_len, lower,
            routes=self.program["routes"], selected=self.program["selected"],
        )
        return dict(
            out,
            first_grad=check.leaf_norms(out["first_grad"]),
            delta=check.leaf_norms(out["delta"]),
            decode_logits=logits,
        )

    def compare(self, side: dict, reference_side: dict, limits=None,
                limits_sparse=None) -> List[dict]:
        return check_sparse.compare(
            side, reference_side, limits or self.limits,
            limits_sparse or self.limits_sparse, self.loss_floor)

    def check(self) -> List[dict]:
        t0 = time.monotonic()
        reference_side = self.reference_readings(actions=self.program["actions"])
        print(f"reference: followed {self.follow} updates in "
              f"{time.monotonic() - t0:.1f} s")
        return self.compare(self.program, reference_side)


def setup(cell: dict, config: dict, devices, seed: int, control=None) -> Session:
    return Session(cell, config, devices, seed, control)
