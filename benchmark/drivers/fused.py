"""Stand ``--trainer tpu_fused_ba3c`` up and drive its compiled update.

Builds what ``fused/loop.py:run_fused_training`` builds (``make_mesh``,
``create_fused_state``, ``make_fused_step`` from ``cli.py``'s own parser and
config) and calls the step itself, one dispatch an update as ``train.py``
does. No evaluation and no checkpoint fall in the window.

Set-up makes ONE step-and-state object, follows it through its first
updates for the comparison with the reference, and hands that same object to
the window. The weights, the env keys and the per-shard random streams are
the benchmark's own, made from the seed, so that the reference can start
from the same point without taking anything the program made.

``Session(..., control=True)`` is the control of that comparison and nothing
a run uses: the same step with its rollout forward served from the program's
own int8 table (``quantize/``), the precision below the configuration's.
"""

from __future__ import annotations

import contextlib
import queue
import threading
import time
from typing import Dict, List
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check, stats
from benchmark.reference import ba3c as reference

#: updates the dispatcher may run ahead of the last one seen complete: the
#: device never waits for the host, and the window overruns by no more
MAX_IN_FLIGHT = 3
#: how far the runtime's peak may lie from the compiled step's own footprint
#: (read on the chip, PR 23: 0.3 % over it at 256x20, 0.04 % over on four
#: chips, 4.6 % under at 4096x20; counted twice it would be 100 % over)
MEMORY_AGREES_WITHIN = 0.1


def split_seed(seed: int) -> np.ndarray:
    """``--seed`` may need more than 32 signed bits: two int32 halves."""
    return np.asarray([seed & 0x7FFFFFFF, seed >> 31], np.int32)


def seed_keys(seed_halves, n_shards: int):
    """(weights key, env key, per-shard stream keys) from the seed."""
    root = jax.random.fold_in(
        jax.random.PRNGKey(seed_halves[0]), seed_halves[1]
    )
    stream = jax.random.fold_in(root, 2)
    shard_keys = jax.vmap(lambda i: jax.random.fold_in(stream, i))(
        jnp.arange(n_shards)
    )
    return jax.random.fold_in(root, 0), jax.random.fold_in(root, 1), shard_keys


def _adam_mu(opt_state):
    import optax

    return optax.tree_utils.tree_get(opt_state, "mu")


def int8_rollout(model, params, frames):
    """A context in which ``make_fused_step``'s rollout forward is the
    program's int8 one (``--rollout_dtype int8``'s table and apply, the
    activation scales calibrated on ``frames``); the learner is untouched."""
    from distributed_ba3c_tpu.fused import loop
    from distributed_ba3c_tpu.quantize.calibrate import calibrate_offline
    from distributed_ba3c_tpu.quantize.qforward import (
        make_quant_apply,
        quantize_params,
    )

    spec = calibrate_offline(model, params, [frames])
    apply_int8 = make_quant_apply(model)
    real = loop.make_rollout_body

    def lowered(*args, **kw):
        return real(
            *args, **kw,
            apply_fn=lambda p, stack: apply_int8(quantize_params(p, spec), stack),
        )

    return mock.patch.object(loop, "make_rollout_body", lowered)


class Session:
    """One cell's step and state, from set-up through the window."""

    def __init__(self, cell: dict, config: dict, devices, seed: int,
                 control: bool = False):
        from distributed_ba3c_tpu import cli
        from distributed_ba3c_tpu.envs import jaxenv
        from distributed_ba3c_tpu.fused.loop import (
            create_fused_state,
            make_fused_step,
            make_rollout_body,
        )
        from distributed_ba3c_tpu.models.a3c import BA3CNet
        from distributed_ba3c_tpu.ops.gradproc import make_optimizer
        from distributed_ba3c_tpu.parallel.mesh import make_mesh

        args = cli.make_parser().parse_args(
            list(config["argv"]) + list(cell.get("argv", []))
        )
        cfg = cli.build_config(args)
        model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
        optimizer = make_optimizer(
            cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm
        )
        env = jaxenv.get_env(args.env.split(":", 1)[1])
        chips = cell["chips"]
        mesh = make_mesh(num_data=chips, num_model=1, devices=devices[:chips])
        self.devices = list(devices[:chips])
        self.chips = chips
        self.rollout_len = args.rollout_len
        self.n_envs = max(1, cfg.batch_size // args.rollout_len) * chips
        self.beta, self.lr = cfg.entropy_beta, cfg.learning_rate
        self.seed = seed
        self.follow = int(cell["follow_updates"])
        self.limits = cell["limits"]
        self.hyper = dict(config["algorithm"], rollout_len=args.rollout_len)
        self.step = make_fused_step(
            model, optimizer, cfg, mesh, env, args.rollout_len,
            grad_chunk_samples=args.grad_chunk_samples,
            steps_per_dispatch=args.steps_per_dispatch,
        )
        n_envs = self.n_envs

        def build(seed_halves):
            w_key, env_key, shard_keys = seed_keys(seed_halves, chips)
            state = create_fused_state(
                w_key, model, cfg, optimizer, env, n_envs, n_shards=chips
            )
            env_state = jax.vmap(env.reset)(jax.random.split(env_key, n_envs))
            stack = jnp.zeros_like(state.obs_stack).at[..., -1].set(
                jax.vmap(env.render)(env_state)
            )
            params = reference.init_params(w_key, cfg.num_actions)
            return state.replace(
                train=state.train.replace(params=params),
                env_state=env_state, obs_stack=stack, key=shard_keys,
            )

        def rollout_actions(params, env_state, stack, key):
            """The actions one shard's rollout draws from this state: the
            step's own scan body (the trajectory never leaves ``fused.step``,
            so the reference is given the actions this way; PERF.md)."""
            body = make_rollout_body(model, cfg, env, params)
            zeros = jnp.zeros(stack.shape[0], jnp.float32)
            carry = (env_state, stack, key, zeros, zeros.astype(jnp.int32), zeros)
            _, traj = jax.lax.scan(body, carry, None, length=args.rollout_len)
            return traj[1]

        self._rollout_actions = jax.jit(rollout_actions)
        self.state = self.step.put(jax.jit(build)(split_seed(seed)))
        self.counters: Dict[str, float] = {}
        self.program: dict = {}
        lowered = contextlib.nullcontext()
        if control:
            lowered = int8_rollout(
                model, self.state.train.params, np.asarray(self.state.obs_stack[:256])
            )
        with lowered:  # the step is traced at its first call
            self._follow_first_updates()

    def _follow_first_updates(self):
        """The step's first updates, through the window's own call; keeps
        what the comparison needs (host copies: small, but for the frame
        stacks each update leaves, 28 KB an env)."""
        start = jax.device_get(self.state.train.params)
        losses: List[float] = []
        actions: List[np.ndarray] = []
        states: List[tuple] = []
        first_grad = None
        per = self.n_envs // self.chips
        for i in range(self.follow):
            st = self.state
            actions.append(np.stack([
                np.asarray(self._rollout_actions(
                    st.train.params,
                    jax.tree_util.tree_map(
                        lambda x: x[s * per:(s + 1) * per], st.env_state),
                    st.obs_stack[s * per:(s + 1) * per], st.key[s],
                ))
                for s in range(self.chips)
            ]))
            t0 = time.monotonic()
            self.state, metrics = self.step(self.state, self.beta, self.lr)
            losses.append(float(metrics["loss"]))
            first_call_s = time.monotonic() - t0
            # what the timed step itself left: the envs move only by the
            # actions it drew, so this ties the handed-over actions to it
            states.append((
                {k: np.asarray(v) for k, v in self.state.env_state._asdict().items()},
                np.asarray(self.state.obs_stack),
            ))
            if i == 0:
                self.counters["first_dispatch_s"] = first_call_s
                mu = jax.device_get(_adam_mu(self.state.train.opt_state))
                first_grad = jax.tree_util.tree_map(
                    lambda m: np.asarray(m) / (1 - reference.ADAM_B1), mu
                )
        end = jax.device_get(self.state.train.params)
        delta = jax.tree_util.tree_map(lambda a, b: a - b, end, start)
        self.program = {
            "losses": losses,
            "first_grad": check.leaf_norms(first_grad),
            "delta": check.leaf_norms(delta),
            "actions": actions,
            "states": states,
        }

    @property
    def work_per_update(self) -> int:
        """Env-steps one completed update trained on."""
        return self.n_envs * self.rollout_len * self.step.steps_per_dispatch

    def window(self, seconds: float, tracer=None) -> dict:
        """Dispatch updates for ``seconds``; a watcher times completions.

        ``tracer`` (traced runs): an object whose ``tick(elapsed)`` is
        called between dispatches to start and stop the profiler."""
        pending: "queue.Queue" = queue.Queue()
        slots = threading.Semaphore(MAX_IN_FLIGHT)
        completions: List[float] = []
        bad: List[int] = []

        def watch():
            while True:
                item = pending.get()
                if item is None:
                    return
                with jax.profiler.TraceAnnotation("bench_wait_update"):
                    loss = np.asarray(item["loss"])  # blocks until done
                completions.append(time.monotonic())
                if not np.isfinite(loss):
                    bad.append(len(completions))
                slots.release()

        watcher = threading.Thread(target=watch, name="bench-watcher")
        watcher.start()
        attempted = 0
        start = time.monotonic()
        try:
            while time.monotonic() - start < seconds:
                if tracer is not None:
                    tracer.tick(time.monotonic() - start)
                with jax.profiler.TraceAnnotation("bench_wait_slot"):
                    slots.acquire()
                attempted += 1
                with jax.profiler.TraceAnnotation("bench_dispatch"):
                    self.state, metrics = self.step(
                        self.state, self.beta, self.lr
                    )
                pending.put(metrics)
        finally:
            pending.put(None)
            watcher.join()
            if tracer is not None:
                tracer.close()
        out = {"attempted": attempted, "failed": len(bad)}
        self.counters["work_per_update"] = self.work_per_update
        if tracer is None:  # a traced run's clock is the profiler's, not ours
            rate, span = stats.completed_rate(
                start, completions, self.work_per_update, self.chips
            )
            print(f"window: {len(completions)} updates of "
                  f"{self.work_per_update} env-steps completed in {span:.3f} s")
            out["end_to_end"] = {"env_steps_per_s_per_chip": rate}
        return out

    def memory_peak_bytes(self) -> int:
        """Peak bytes held on the fullest chip: the buffers in use plus what
        the runtime reserved for the compiled programs' temporaries (on this
        TPU runtime ``peak_bytes_in_use`` leaves the reservation out: at
        256x20 it read 81 MB beside 7.27 GB reserved, the program's
        ``temp_size_in_bytes``). The two peaks need not fall together, so
        the sum is held against what the compiler says the one step needs
        on a chip; a runtime that counts otherwise fails the run here."""
        peaks = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            print(f"memory chip {d.id}: in use {stats.get('peak_bytes_in_use')} "
                  f"reserved {stats.get('peak_bytes_reserved')} "
                  f"of {stats.get('bytes_limit')}")
            peaks.append(int(stats.get("peak_bytes_in_use", 0))
                         + int(stats.get("peak_bytes_reserved", 0)))
        if max(peaks):  # the CPU's runtime reports nothing
            m = self.step.audit_jit.lower(
                self.state, jnp.float32(self.beta), jnp.float32(self.lr)
            ).compile().memory_analysis()
            compiled = (m.argument_size_in_bytes + m.temp_size_in_bytes
                        + m.output_size_in_bytes - m.alias_size_in_bytes)
            print(f"memory: the runtime's peak {max(peaks)}, the compiled step's "
                  f"arguments + temporaries + outputs - aliased {compiled}")
            if abs(max(peaks) - compiled) > MEMORY_AGREES_WITHIN * compiled:
                raise RuntimeError(
                    "memory_peak_bytes is not the compiled step's footprint: "
                    f"{max(peaks)} against {compiled}"
                )
        return max(peaks)

    def release(self):
        """Free the program's device state (before the reference runs)."""
        for leaf in jax.tree_util.tree_leaves(self.state):
            leaf.delete()
        self.state = None

    def reference_readings(self, lower=None, actions=None) -> dict:
        """The reference's side of the comparison: drawing its own actions,
        or (``actions``) playing those another side took."""
        w_key, env_key, shard_keys = seed_keys(split_seed(self.seed), self.chips)
        out = reference.follow_updates(
            reference.init_params(w_key, self.hyper["num_actions"]), env_key,
            shard_keys, self.n_envs, self.hyper, self.follow, lower, actions,
        )
        return dict(
            out,
            first_grad=check.leaf_norms(jax.device_get(out["first_grad"])),
            delta=check.leaf_norms(jax.device_get(out["delta"])),
        )

    def check(self) -> List[dict]:
        t0 = time.monotonic()
        reference_side = self.reference_readings(actions=self.program["actions"])
        print(f"reference: followed {self.follow} updates in "
              f"{time.monotonic() - t0:.1f} s")
        return check.compare(self.program, reference_side, self.limits)


def setup(cell: dict, config: dict, devices, seed: int,
          control: bool = False) -> Session:
    return Session(cell, config, devices, seed, control)
