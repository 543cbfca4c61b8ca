#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls
(``distributed_ba3c_tpu.cli.main``, i.e. ``train.py``) at the full width of
the one model the repo has — ``BA3CNet`` on 84x84x4 uint8 frames, convs
32/32/64/64, ``fc_units`` 512 — with seeded random weights, and checks what
comes out by the repo's own means. It measures nothing: compile and step
seconds are printed as information, and no rate is published from here.

Phases (each fails the run; nothing is caught):

``fused``     ``--trainer tpu_fused_ba3c --env jax:pong`` at 128 envs x 20 per
              chip: two short epochs, the on-device greedy evaluator, an
              orbax checkpoint per epoch, then a ``--load`` resume that must
              continue the step counter.
``plane``     ``--trainer tpu_vtrace_ba3c --env cpp:pong`` on the default wire
              with staged ingest: C++ env-server children -> BatchedPredictor
              on the chip -> TrajBlocks -> V-trace learner steps -> params
              published back. No child may load libtpu; copies per ingested
              block must be exactly 1; and the staged ingest must give the
              learner bit-identical losses to plain ``device_put`` on the
              same seeded blocks while its slots are being reused.
``forwards``  the rollout forward at all three dtypes (``predict.server``,
              ``_bf16``, ``_int8``) answering batches of 256 inside the
              repo's parity bands of the f32 forward; the int8 arm ``auto``
              resolved to.
``grouped``   the learner's grouped expert product (``ops/grouped_matmul.py``)
              at the language-model cell's shapes, seeded group sizes with
              an empty group: the Pallas kernel's forward and both gradients
              against ``jax.lax.ragged_dot`` on the same chip (tier-1 cannot
              run Mosaic), the largest gaps in the result.
``sparse_attn`` the learner's attention under a selection's mask
              (``ops/sparse_attention.py``) at the sparse-attention cell's
              shapes (2 envs x 4,096 positions, 32 heads over 4 of 128, a
              top-k of half the positions): the four Pallas kernels' output,
              heads' sum and three gradients against the masked-dense form in
              blocks of 512 queries on the same chip, the largest gaps in the
              result.
``select``    the exact top-k as a mask (``ops/topk_select.py``) at the same
              cell's shapes, a decode step's 16 rows of 4,096 keys and a
              block of the learner's: rows no longer than the top-k, rows
              that all fit, rows that do not and a batch of both, each the
              plain searches' mask bit for bit; two Pallas kernels under the
              scope ``op_indexer/select/radix`` in the compiled text (its
              lines, and those in fast memory, in the result); a call's time
              where every row fits under a call's where none does.
``mesh``      only with more than one device: env state and batch sharded
              over every device, and after K updates every param leaf's
              replicas bit-identical — the on-chip form of audit rule T3.

Every phase runs under ``BA3C_AUDIT=1``: a registered entry point that
re-traces after its warm-up kills the run.

One process for each chip: this parent never imports JAX. Each phase is a
child process (``--phase NAME``) that holds the chip while it runs and has
released it before the next one starts; JAX's compile cache is shared
between them through ``utils/backend.py`` (``$JAX_COMPILATION_CACHE_DIR``,
else ``<repo>/.jax_cache``). On anything but a TPU the first child exits
nonzero and no result is printed.

Last line of stdout on success::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import queue
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

#: the contract's limit is 1200 s, compilation included
TOTAL_BUDGET_S = 1150.0
#: the recurrence's kernels against its plain form, of a value's largest:
#: float32 sums in another order (a bfloat16 operand would read 4e-3)
SSD_BAND = 2e-4
#: the same for the gated delta rule's
DELTA_BAND = 2e-4

#: parity bands of the rollout-forward ladder vs the f32 forward — the same
#: numbers tests/test_quantize.py and tests/test_staging.py hold the bf16 and
#: int8 rungs to (log mu(a|s) and V(s), max abs over the batch)
BAND_LOG_MU = 0.1
BAND_VALUE = 0.05


@dataclasses.dataclass(frozen=True)
class Shape:
    """One size of the smoke. ``FULL`` is the model's real width (the chip
    run); ``SMALL`` is what tests/test_chip_smoke.py drives on the CPU mesh
    through the same phase functions."""

    fc_units: int
    # fused trainer (jax:pong renders 84x84 at every size)
    envs_per_chip: int
    rollout_len: int
    fused_steps_per_epoch: int
    nr_eval: int
    eval_max_steps: int       # >= one Pong episode at FULL, or no eval score
    # actor plane
    plane_env: str
    plane_image_size: Optional[int]   # None = the env's own 84x84
    plane_envs: int
    plane_batch: int
    plane_steps_per_epoch: int    # StatPrinter samples the loss every 20 steps
    staging_blocks: int
    # rollout forwards
    serve_batch: int
    # grouped expert product: sorted rows, hidden, expert width, experts held
    grouped_dims: tuple
    # the same at an expert width off whole lanes (1,856 = 14.5 lanes), which
    # the kernels take as one whole tile
    grouped_off_lane_dims: tuple
    # the learner's sparse attention: envs, positions, query heads, K/V
    # heads, head width, queries a block of the masked-dense form
    sparse_dims: tuple
    # the selection: a decode step's rows and keys, the top-k, a block of
    # the learner's (envs, queries, keys)
    select_dims: tuple
    # the Mamba-2 recurrence: envs, positions, heads, channels a head,
    # groups, numbers a state's row, positions a chunk
    ssd_dims: tuple
    # the gated delta rule: envs, positions, heads, keys a head, values a
    # head, positions a chunk
    delta_dims: tuple


FULL = Shape(
    fc_units=512,
    envs_per_chip=128, rollout_len=20, fused_steps_per_epoch=16,
    nr_eval=8, eval_max_steps=3000,
    plane_env="cpp:pong", plane_image_size=None, plane_envs=64,
    plane_batch=128, plane_steps_per_epoch=20, staging_blocks=12,
    serve_batch=256,
    grouped_dims=(5120, 2048, 1792, 8),
    grouped_off_lane_dims=(1024, 2688, 1856, 8),
    sparse_dims=(2, 4096, 32, 4, 128, 512),
    select_dims=(16, 4096, 2048, (2, 512, 2560)),
    ssd_dims=(2, 2048, 64, 64, 8, 128, 128),
    delta_dims=(2, 2048, 10, 96, 192, 64),
)

SMALL = Shape(
    fc_units=16,
    envs_per_chip=4, rollout_len=2, fused_steps_per_epoch=2,
    nr_eval=1, eval_max_steps=8,
    plane_env="fake", plane_image_size=16, plane_envs=4,
    plane_batch=32, plane_steps_per_epoch=20, staging_blocks=5,
    serve_batch=8,
    grouped_dims=(256, 128, 128, 4),
    grouped_off_lane_dims=(256, 128, 192, 4),
    sparse_dims=(2, 64, 4, 2, 16, 16),
    select_dims=(4, 64, 16, (2, 8, 40)),
    ssd_dims=(2, 40, 4, 8, 2, 16, 16),
    delta_dims=(2, 40, 3, 8, 16, 16),
)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _finite(record: dict, keys) -> None:
    import math

    for k in keys:
        _check(
            k in record and math.isfinite(record[k]),
            f"stat {k!r} missing or not finite: {record.get(k)!r}",
        )


def _read_stats(logdir: str) -> List[dict]:
    with open(os.path.join(logdir, "stat.json")) as f:
        return json.load(f)


def _require_device(platform: str) -> Dict[str, object]:
    """The device as JAX reports it; anything but ``platform`` ends the
    phase before it runs (a smoke that fell back to the CPU proves
    nothing about the chip)."""
    from distributed_ba3c_tpu.utils.backend import (
        configure_compile_cache,
        device_info,
    )

    configure_compile_cache()
    device = device_info()
    if device["platform"] != platform:
        raise SystemExit(
            f"chip_smoke: needs platform {platform!r}, jax found {device}"
        )
    return device


def _check_run_device(record: dict, device: dict) -> None:
    _check(
        record.get("device") == device,
        f"stat.json's first record names {record.get('device')!r}, "
        f"the phase ran on {device!r}",
    )


# --------------------------------------------------------------------------
# phase: fused trainer
# --------------------------------------------------------------------------


def phase_fused(shape: Shape, workdir: str, platform: str = "tpu") -> dict:
    device = _require_device(platform)  # before the heavy imports: fail fast

    from distributed_ba3c_tpu.cli import main as cli_main
    from distributed_ba3c_tpu.train.checkpoint import CheckpointManager

    n_chips = int(device["count"])
    logdir = os.path.join(workdir, "fused")
    steps = shape.fused_steps_per_epoch
    argv = [
        "--trainer", "tpu_fused_ba3c", "--env", "jax:pong",
        "--batch_size", str(shape.envs_per_chip * shape.rollout_len),
        "--rollout_len", str(shape.rollout_len),
        "--fc_units", str(shape.fc_units),
        "--steps_per_epoch", str(steps),
        "--nr_eval", str(shape.nr_eval),
        "--eval_max_steps", str(shape.eval_max_steps),
        "--logdir", logdir,
    ]
    if n_chips > 1:
        # one line per epoch: the per-leaf digest of the fetched replica
        os.environ["BA3C_PARAM_DIGEST"] = "1"
    _check(cli_main(argv + ["--max_epoch", "2"]) == 0, "fused run rc != 0")
    stats = _read_stats(logdir)
    _check(len(stats) == 2, f"expected 2 epoch records, got {len(stats)}")
    _check(
        [s["global_step"] for s in stats] == [steps, 2 * steps],
        f"global_step {[s['global_step'] for s in stats]}",
    )
    _check_run_device(stats[0], device)
    for s in stats:
        _finite(s, ("loss", "policy_loss", "value_loss", "entropy",
                    "grad_norm", "fps"))
    if shape.eval_max_steps >= 1000:
        # a whole Pong episode fits the horizon: the greedy evaluator must
        # have scored one (random weights lose, but inside the game's range)
        _check(
            -21.0 <= stats[-1].get("eval_mean_score", float("nan")) <= 21.0,
            f"greedy eval score {stats[-1].get('eval_mean_score')!r}",
        )
    ckpt_dir = os.path.join(logdir, "checkpoints")
    _check(
        CheckpointManager(ckpt_dir).latest_step == 2 * steps,
        "no checkpoint at the last step",
    )

    # resume in the same process, as run_with_resume.sh does in a new one:
    # --max_epoch is the TOTAL budget, so one more epoch continues at 3
    _check(
        cli_main(argv + ["--max_epoch", "3", "--load", ckpt_dir]) == 0,
        "resume rc != 0",
    )
    stats = _read_stats(logdir)
    _check(
        [(s["epoch"], s["global_step"]) for s in stats]
        == [(1, steps), (2, 2 * steps), (3, 3 * steps)],
        f"resume did not continue the counters: "
        f"{[(s['epoch'], s['global_step']) for s in stats]}",
    )
    _check_run_device(stats[2], device)
    _finite(stats[2], ("loss", "entropy", "grad_norm"))
    samples = shape.envs_per_chip * n_chips * shape.rollout_len
    return {
        "device": device,
        "first_dispatch_s": round(stats[0]["first_dispatch_s"], 2),
        "resume_first_dispatch_s": round(stats[2]["first_dispatch_s"], 2),
        "steady_step_s": round(samples / stats[1]["fps"], 5),
        "updates": 3 * steps,
        "eval_mean_score": stats[-1].get("eval_mean_score"),
    }


# --------------------------------------------------------------------------
# phase: actor plane with the device in the loop
# --------------------------------------------------------------------------


class _ChildWatch(threading.Thread):
    """Samples this process's descendants while a phase runs and records any
    that mapped libtpu: a chip belongs to ONE process, and the children of
    the process that holds it (env servers, the resource tracker) must never
    initialise an accelerator back-end."""

    def __init__(self):
        super().__init__(daemon=True, name="smoke-child-watch")
        self.seen: Dict[int, str] = {}
        self.offenders: Dict[int, str] = {}
        self._stop_evt = threading.Event()

    @staticmethod
    def _descendants(root: int) -> List[int]:
        parent: Dict[int, int] = {}
        for name in os.listdir("/proc"):
            if not name.isdigit():
                continue
            try:
                with open(f"/proc/{name}/stat") as f:
                    # "pid (comm) state ppid ...": comm may hold spaces
                    parent[int(name)] = int(
                        f.read().rsplit(")", 1)[1].split()[1]
                    )
            except (OSError, IndexError, ValueError):
                continue  # the process exited while we were reading it
        out, frontier = [], [root]
        while frontier:
            p = frontier.pop()
            kids = [c for c, pp in parent.items() if pp == p]
            out += kids
            frontier += kids
        return out

    def run(self) -> None:
        while not self._stop_evt.wait(0.25):
            for pid in self._descendants(os.getpid()):
                try:
                    with open(f"/proc/{pid}/cmdline", "rb") as f:
                        cmd = f.read().replace(b"\0", b" ").decode()[:120]
                    with open(f"/proc/{pid}/maps") as f:
                        maps = f.read()
                except OSError:
                    continue
                self.seen[pid] = cmd
                if "libtpu" in maps:
                    self.offenders[pid] = cmd

    def stop(self) -> None:
        self._stop_evt.set()
        self.join(timeout=5)


def _staging_equivalence(shape: Shape) -> dict:
    """The staged ingest (data/staging.py) against plain ``device_put`` on
    the same seeded blocks, with TWO slots so every third block rewrites a
    slot whose transfer may still be in flight. On a TPU ``device_put``
    returns before it has read the host buffer (measured PR 21), so the
    ring's ready fence is the only thing between a reused slot and a
    corrupted batch: equal losses show it holds."""
    import jax
    import numpy as np

    from distributed_ba3c_tpu import telemetry
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.data.staging import (
        DeviceIngest, HostStagingRing, ingest_counts)
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh
    from distributed_ba3c_tpu.parallel.train_step import create_train_state
    from distributed_ba3c_tpu.parallel.vtrace_step import make_vtrace_train_step

    size = shape.plane_image_size or 84
    cfg = BA3CConfig(fc_units=shape.fc_units, image_size=(size, size))
    n_actions = cfg.num_actions
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh()
    step = make_vtrace_train_step(model, opt, cfg, mesh)
    n_data = mesh.shape["data"]
    T = cfg.local_time_max
    B = max(n_data, shape.plane_batch // n_data * n_data)
    rng = np.random.default_rng(0)
    blocks = [
        {
            "state": rng.integers(0, 255, (T, B, *cfg.state_shape), np.uint8),
            "action": rng.integers(0, n_actions, (T, B), np.int32),
            "reward": rng.normal(size=(T, B)).astype(np.float32),
            "done": (rng.random((T, B)) < 0.05).astype(np.float32),
            "behavior_log_probs": -rng.random((T, B)).astype(np.float32),
            "bootstrap_state": rng.integers(
                0, 255, (B, *cfg.state_shape), np.uint8
            ),
        }
        for _ in range(shape.staging_blocks)
    ]
    spec = {k: (v.shape, v.dtype) for k, v in blocks[0].items()}

    def fresh_state():
        return jax.device_put(
            create_train_state(jax.random.PRNGKey(0), model, cfg, opt),
            step.state_sharding,
        )

    def run_plain():
        state, losses = fresh_state(), []
        for blk in blocks:
            batch = {
                k: jax.device_put(v, step.batch_sharding[k])
                for k, v in blk.items()
            }
            state, m = step(state, batch, cfg.entropy_beta)
            losses.append(m["loss"])
        return np.asarray(jax.device_get(losses))

    def run_staged():
        ring = HostStagingRing(slots=2)
        pending = iter(blocks)

        class SeededFeed:
            def next_batch(self, timeout=None):
                blk = next(pending, None)
                if blk is None:
                    raise queue.Empty
                slot = ring.acquire(spec, timeout=60.0)
                _check(slot is not None, "staging ring never freed a slot")
                for k, v in blk.items():
                    np.copyto(slot.buffers[k], v)
                ring.count_staged_copy()
                return ring.staged(slot)

        ingest = DeviceIngest(SeededFeed(), step.batch_sharding)
        state, losses = fresh_state(), []
        for _ in blocks:
            batch = ingest.next_batch(timeout=60.0)
            state, m = step(state, batch, cfg.entropy_beta)
            ingest.prefetch()  # the next block's H2D behind this step
            losses.append(m["loss"])
        return np.asarray(jax.device_get(losses))

    tele = telemetry.registry("learner")
    copies0, blocks0 = ingest_counts("learner").values()
    waits0 = tele.counter("staging_waits_total").value()
    staged = run_staged()
    copies, n_blocks = ingest_counts("learner").values()
    copies, n_blocks = copies - copies0, n_blocks - blocks0
    plain = run_plain()
    _check(bool(np.all(np.isfinite(plain))), f"plain losses {plain}")
    _check(
        np.array_equal(staged, plain),
        f"staged ingest changed the learner's losses:\n{staged}\nvs\n{plain}",
    )
    _check(
        copies == n_blocks == len(blocks),
        f"{copies} host copies for {n_blocks} staged blocks",
    )
    return {
        "staging_blocks": len(blocks),
        "staging_block_mb": round(
            sum(v.nbytes for v in blocks[0].values()) / 2**20, 1
        ),
        "staging_fence_waits": int(
            tele.counter("staging_waits_total").value() - waits0
        ),
    }


def phase_plane(shape: Shape, workdir: str, platform: str = "tpu") -> dict:
    device = _require_device(platform)

    from distributed_ba3c_tpu.cli import main as cli_main

    logdir = os.path.join(workdir, "plane")
    steps = shape.plane_steps_per_epoch
    argv = [
        "--trainer", "tpu_vtrace_ba3c", "--env", shape.plane_env,
        "--simulator_procs", str(shape.plane_envs),
        "--batch_size", str(shape.plane_batch),
        "--fc_units", str(shape.fc_units),
        "--steps_per_epoch", str(steps), "--max_epoch", "2",
        "--nr_eval", "0", "--ingest_staging", "on",
        "--logdir", logdir,
    ]
    if shape.plane_image_size:
        argv += ["--image_size", str(shape.plane_image_size)]
    watch = _ChildWatch()
    watch.start()
    try:
        _check(cli_main(argv) == 0, "plane run rc != 0")
    finally:
        watch.stop()
    _check(bool(watch.seen), "the plane ran without one child process")
    _check(
        not watch.offenders,
        f"children of the chip-holding process loaded libtpu: "
        f"{watch.offenders}",
    )
    stats = _read_stats(logdir)
    _check(len(stats) == 2, f"expected 2 epoch records, got {len(stats)}")
    _check_run_device(stats[0], device)
    last = stats[-1]
    _check(last["global_step"] == 2 * steps, f"global_step {last['global_step']}")
    for s in stats:
        _finite(s, ("loss", "policy_loss", "value_loss", "entropy",
                    "grad_norm", "mean_rho"))
    copies = last["tele/learner/ingest_copies_total"]
    n_blocks = last["tele/learner/ingest_blocks_total"]
    _check(
        n_blocks >= 2 * steps and copies == n_blocks,
        f"ingest: {copies} host copies for {n_blocks} blocks (want 1.0 each)",
    )
    _check(
        last["tele/predictor/param_publishes_total"] >= 2 * steps,
        "the learner did not publish params back to the predictor",
    )
    _check(
        last["tele/predictor/rows_total"] > 0
        and last["tele/master/datapoints_total"] > 0,
        "the predictor served no rows / the master assembled no datapoints",
    )
    if shape.plane_env.startswith("cpp:"):
        # the default wire: `auto` resolves to block-shm where /dev/shm is
        _check(
            last.get("tele/master/block_shm_msgs_total", 0) > 0,
            "no block-shm traffic: --wire auto did not resolve to block-shm",
        )
    return {
        "device": device,
        "children": len(watch.seen),
        "predictor_warmup_s": round(last["tele/predictor/warmup_s"], 2),
        "first_dispatch_s": round(stats[0]["first_dispatch_s"], 2),
        "steady_step_s": round(shape.plane_batch / last["fps"], 5),
        "ingest_copies_per_block": copies / n_blocks,
        "param_publishes": int(last["tele/predictor/param_publishes_total"]),
        **_staging_equivalence(shape),
    }


# --------------------------------------------------------------------------
# phase: the rollout forward at three dtypes
# --------------------------------------------------------------------------


def _pong_frames(model, cfg, params, n_frames: int):
    """Real Pong frame stacks through the actor's own scan body — parity
    is measured on the pixels the rollout forward sees, not on noise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import make_rollout_body

    T = 4
    n_envs = max(1, n_frames // T)
    key = jax.random.PRNGKey(7)
    env_state = jax.vmap(pong.reset)(jax.random.split(key, n_envs))
    obs = jax.vmap(pong.render)(env_state)
    stack = jnp.zeros(
        (n_envs, *obs.shape[1:], cfg.frame_history), jnp.uint8
    ).at[..., -1].set(obs)
    carry = (
        env_state, stack, jax.random.fold_in(key, 1),
        jnp.zeros(n_envs, jnp.float32), jnp.zeros(n_envs, jnp.int32),
        jnp.zeros(n_envs, jnp.float32),
    )
    body = make_rollout_body(model, cfg, pong, params)
    _, traj = jax.jit(lambda c: jax.lax.scan(body, c, None, length=T))(carry)
    return np.asarray(traj[0]).reshape(-1, *cfg.state_shape)


def phase_forwards(shape: Shape, workdir: str, platform: str = "tpu") -> dict:
    del workdir
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.predict.server import BatchedPredictor
    from distributed_ba3c_tpu.quantize import (
        calibrate_offline,
        int8_conv_supported,
        make_quant_apply,
        quantize_params,
    )

    device = _require_device(platform)
    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=shape.fc_units)
    model = build_model(DEFAULT_MODEL, cfg)
    params = model.init(
        jax.random.PRNGKey(0), np.zeros((1, *cfg.state_shape), np.uint8)
    )["params"]
    frames = _pong_frames(model, cfg, params, shape.serve_batch)
    _check(
        frames.shape == (shape.serve_batch, *cfg.state_shape),
        f"frames {frames.shape}",
    )
    spec = calibrate_offline(model, params, [frames])

    int8_arm = "int8" if int8_conv_supported() else "folded"
    _check(
        platform != "tpu" or int8_arm == "int8",
        "the chip resolved the `auto` quant arm to the bf16 `folded` "
        "reference, not int8 compute",
    )

    # the reference: the f32-param forward, exactly as the learner runs it
    apply = jax.jit(lambda p, x: model.apply({"params": p}, x))
    ref = apply(params, frames)
    ref_lp = np.asarray(jax.nn.log_softmax(ref.logits, axis=-1))
    ref_v = np.asarray(ref.value)
    _check(bool(np.all(np.isfinite(ref_lp)) and np.all(np.isfinite(ref_v))),
           "the f32 reference forward is not finite")

    # log mu(a|s), the record V-trace corrects against, at the two cheaper
    # tables (predict_batch returns actions and values only)
    bf16_params = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        params,
    )
    qapply = jax.jit(make_quant_apply(model))
    ladder = {
        "bfloat16": apply(bf16_params, frames),
        "int8": qapply(quantize_params(params, spec), frames),
    }
    info: dict = {"device": device, "int8_arm": int8_arm}
    for name, out in ladder.items():
        d_lp = float(np.max(np.abs(
            np.asarray(jax.nn.log_softmax(out.logits, axis=-1)) - ref_lp
        )))
        _check(d_lp < BAND_LOG_MU, f"{name}: log mu off by {d_lp}")
        info[f"{name}_dlogmu"] = round(d_lp, 5)

    for dtype in ("float32", "bfloat16", "int8"):
        pred = BatchedPredictor(  # ba3clint: disable=A14 — sync predict_batch only, owned by this loop
            model, params, batch_size=shape.serve_batch,
            rollout_dtype=dtype,
            quant_spec=spec if dtype == "int8" else None,
            tele_role=f"predictor.{dtype}",
        )
        t0 = time.monotonic()
        pred.warmup(cfg.state_shape)
        warmup_s = time.monotonic() - t0
        _check(pred.serving_dtype == dtype, f"serving {pred.serving_dtype}")
        batch_s = []
        for _ in range(3):
            t0 = time.monotonic()
            actions, values, greedy = pred.predict_batch(frames)
            batch_s.append(time.monotonic() - t0)
            _check(
                actions.shape == values.shape == greedy.shape
                == (shape.serve_batch,),
                f"{dtype}: shapes {actions.shape} {values.shape}",
            )
            _check(
                bool(np.all((actions >= 0) & (actions < cfg.num_actions))),
                f"{dtype}: action out of range",
            )
            d_v = float(np.max(np.abs(values - ref_v)))
            _check(d_v < BAND_VALUE, f"{dtype}: V off by {d_v}")
        info[f"{dtype}_warmup_s"] = round(warmup_s, 2)
        info[f"{dtype}_batch_s"] = round(min(batch_s), 5)
        info[f"{dtype}_dvalue"] = round(d_v, 5)
    return info


# --------------------------------------------------------------------------
# phase: the grouped expert product against ragged_dot
# --------------------------------------------------------------------------


def phase_grouped(shape: Shape, workdir: str, platform: str = "tpu") -> dict:
    """The product at the language-model cell's shapes; then, at a width off
    whole lanes, the up product (the columns off the lane) and the down
    product (the contracted dimension off the lane)."""
    del workdir
    device = _require_device(platform)
    info = _grouped_against_ragged_dot(*shape.grouped_dims, platform)
    m, d, f, groups = shape.grouped_off_lane_dims
    info["off_lane"] = {
        "columns": _grouped_against_ragged_dot(m, d, f, groups, platform),
        "contracted": _grouped_against_ragged_dot(m, f, d, groups, platform),
    }
    return dict(info, device=device)


def _grouped_against_ragged_dot(m, d, f, groups, platform) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_ba3c_tpu.ops.grouped_matmul import grouped_dot

    # a seeded router's rows for the experts held here, one of them empty
    share = np.random.default_rng(0).dirichlet(np.full(groups, 50.0))
    sizes = np.floor(share * 0.8 * m).astype(np.int32)
    sizes[groups // 2] = 0
    held = int(sizes.sum())
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    lhs = jax.random.normal(keys[0], (m, d), jnp.bfloat16)
    rhs = (jax.random.normal(keys[1], (groups, d, f)) / d ** 0.5).astype(
        jnp.bfloat16)
    pull = jax.random.normal(keys[2], (m, f), jnp.bfloat16)
    here = (jnp.arange(m) < held)[:, None]

    def all_three(dot):
        def run(lhs, rhs, sizes):
            out, pull_back = jax.vjp(lambda l, r: dot(l, r, sizes), lhs, rhs)
            d_lhs, d_rhs = pull_back(jnp.where(here, pull, 0))
            # rows outside every group: unspecified from the kernel
            return jnp.where(here, out, 0), jnp.where(here, d_lhs, 0), d_rhs
        return jax.jit(run)

    ours = all_three(grouped_dot)
    text = ours.lower(lhs, rhs, jnp.asarray(sizes)).as_text()
    kernels = text.count("tpu_custom_call")
    _check(kernels == (3 if platform == "tpu" else 0),
           f"{kernels} Pallas kernels lowered on {platform}")
    got = ours(lhs, rhs, jnp.asarray(sizes))
    want = all_three(jax.lax.ragged_dot)(lhs, rhs, jnp.asarray(sizes))
    info = {"rows_held": held, "pallas_kernels": kernels}
    for name, a, b in zip(("forward", "dx", "dw"), got, want):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        _check(bool(np.isfinite(a).all()), f"{name}: not finite")
        gap, scale = float(np.abs(a - b).max()), float(np.abs(b).max())
        # bf16 out of float32 sums on both sides: an ulp of the largest
        _check(gap <= scale / 64, f"{name}: off by {gap} of {scale}")
        info[f"{name}_max_abs_err"] = gap
        info[f"{name}_max_abs"] = scale
    return info


# --------------------------------------------------------------------------
# phase: the learner's attention under a selection against the masked-dense form
# --------------------------------------------------------------------------


def phase_sparse_attn(shape: Shape, workdir: str, platform: str = "tpu") -> dict:
    del workdir
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_ba3c_tpu.ops.sparse_attention import attend_selected
    from distributed_ba3c_tpu.ops.topk_select import select_mask

    device = _require_device(platform)
    B, T, H, KV, D, block = shape.sparse_dims
    scale = D ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(keys[0], (B, T, H, D), jnp.bfloat16)
    k, v = (jax.random.normal(key, (B, T, KV, D), jnp.bfloat16) for key in keys[1:3])
    pull = jax.random.normal(keys[3], (B, T, H * D), jnp.float32)
    # an indexer's selection: the top half of the positions by seeded scores,
    # every past position up to there
    live = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    chosen = select_mask(
        jax.random.normal(keys[4], (B, T, T)), jnp.broadcast_to(live, (B, T, T)),
        T // 2)

    def in_blocks(q, k, v, chosen, scale):
        """The masked-dense form a block of queries at a time, each over the
        keys its mask can reach, its scores recomputed in the backward."""
        @jax.checkpoint
        def one(q, k, v, mask):
            Tq, Tk = q.shape[1], k.shape[1]
            scores = jnp.einsum(
                "bqkgd,bskd->bkgqs", q.reshape(B, Tq, KV, H // KV, D), k,
                preferred_element_type=jnp.float32) * scale
            probs = jax.nn.softmax(
                jnp.where(mask[:, None, None], scores, -jnp.inf), axis=-1)
            out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                             preferred_element_type=jnp.float32)
            shared = jnp.pad(jnp.sum(probs, axis=(1, 2)), ((0, 0), (0, 0), (0, T - Tk)))
            return out.reshape(B, Tq, H * D), shared

        outs = [one(q[:, lo:lo + block], k[:, :lo + block], v[:, :lo + block],
                    chosen[:, lo:lo + block, :lo + block])
                for lo in range(0, T, block)]
        return tuple(jnp.concatenate(x, axis=1) for x in zip(*outs))

    def all_five(attend):
        def run(q, k, v, chosen):
            (out, shared), pull_back = jax.vjp(
                lambda q, k, v: attend(q, k, v, chosen, scale), q, k, v)
            return (out, shared) + pull_back((pull, jnp.zeros_like(shared)))
        return jax.jit(run)

    ours = all_five(attend_selected)
    kernels = ours.lower(q, k, v, chosen).as_text().count("tpu_custom_call")
    _check(kernels == (4 if platform == "tpu" else 0),
           f"{kernels} Pallas kernels lowered on {platform}")
    got = ours(q, k, v, chosen)
    want = all_five(in_blocks)(q, k, v, chosen)
    info = {"device": device, "pallas_kernels": kernels,
            "kept_share": float(jnp.sum(chosen) / (B * jnp.sum(live)))}
    for name, a, b in zip(("out", "heads_sum", "dq", "dk", "dv"), got, want):
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        _check(bool(np.isfinite(a).all()), f"{name}: not finite")
        gap, size = float(np.abs(a - b).max()), float(np.abs(b).max())
        # probabilities rounded to bfloat16 before their division and not
        # after it, and bfloat16 gradients out of float32 sums on both sides
        _check(gap <= size / 32, f"{name}: off by {gap} of {size}")
        info[f"{name}_max_abs_err"] = gap
        info[f"{name}_max_abs"] = size
    return info


# --------------------------------------------------------------------------
# phase: the Mamba-2 recurrence's kernels against its plain form
# --------------------------------------------------------------------------


def phase_ssd(shape: Shape, workdir: str, platform: str = "tpu") -> dict:
    """``ops/ssd.py:ssd_chunked`` (on the chip: the two Pallas kernels, at a
    learner chunk's shapes) against ``ssd_chunked_plain``: ``y``, the last
    state and every gradient leaf. Under the interpreter a float32 product
    is exact whatever it asks for; Mosaic rounds the operands of one that
    does not ask for the highest precision: here is where the two part."""
    del workdir
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.ops import ssd

    device = _require_device(platform)
    b, T, h, P, g, N, chunk = shape.ssd_dims
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    # step sizes as a trained mixer's: most small, some that forget a chunk
    # whole (the mask before the exp), a small one after large ones
    u = jax.random.uniform(keys[1], (b, T, h))
    dt = jnp.where(u < 0.7, 0.05 * u, jnp.where(u < 0.95, u, 30.0 * u))
    # operands and results lie as they do in the layer, a position's heads
    # side by side in one row: handed ``[.., h, P]`` arrays XLA copies each
    # into that layout round the kernels (P = 64 is padded to 128 lanes in
    # HBM), a quarter of a millisecond an array that the layer does not pay
    args = (jax.random.normal(keys[0], (b, T, h * P)), dt,
            -jnp.exp(jax.random.normal(keys[2], (h,))),
            *(jax.random.normal(k, (b, T, g * N)) / N ** 0.25 for k in keys[3:5]),
            jax.random.normal(keys[5], (h,)))
    pull = jax.random.normal(keys[6], (b, T, h * P))
    pull_last = jax.random.normal(keys[7], (b, h, P, N)) / 8

    def flat(form):
        def run(x, dt, A, Bm, Cm, D):
            y, last = form(
                x.reshape(b, T, h, P), dt, A, Bm.reshape(b, T, g, N),
                Cm.reshape(b, T, g, N), D, chunk=chunk)
            return y.reshape(b, T, h * P), last
        return run

    def all_eight(form):
        def run(*args):
            out, pull_back = jax.vjp(flat(form), *args)
            return out + pull_back((pull, pull_last))
        return jax.jit(run)

    ours = all_eight(ssd.ssd_chunked)
    text = ours.lower(*args).as_text()
    kernels = text.count("tpu_custom_call")
    _check(kernels == (2 if platform == "tpu" else 0),
           f"{kernels} Pallas kernels lowered on {platform}")
    if kernels:
        _check(ssd.FORWARD_KERNEL in text and ssd.BACKWARD_KERNEL in text,
               "the kernels' names are not in the lowered program")
    got = ours(*args)
    want = all_eight(ssd.ssd_chunked_plain)(*args)
    info = {"device": device, "pallas_kernels": kernels, **_gaps_within(
        SSD_BAND, ("y", "last_state", "dx", "ddt", "dA", "dB", "dC", "dD"),
        got, want)}

    if platform == "tpu":  # what each form takes alone, forward and both ways
        info["forward_ms"] = _ms_a_call(jax.jit(flat(ssd.ssd_chunked)), args)
        info["forward_plain_ms"] = _ms_a_call(
            jax.jit(flat(ssd.ssd_chunked_plain)), args)
        info["both_ways_ms"] = _ms_a_call(ours, args)
        info["both_ways_plain_ms"] = _ms_a_call(
            all_eight(ssd.ssd_chunked_plain), args)
    return info


def _gaps_within(band, names, got, want, of: str = "") -> dict:
    """``{name}_max_rel_err`` of each array of ``got`` against ``want``'s:
    the largest gap over the largest value, every one printed (under ``of``,
    where a phase holds several cases) before the first out of ``band``
    fails the phase. Float32 at the highest precision on both sides reads
    sums in another order and no rounded operand (a bfloat16 operand would
    read 4e-3)."""
    import numpy as np

    gaps, off = {}, []
    for name, a, w in zip(names, got, want):
        a, w = np.asarray(a), np.asarray(w)
        _check(bool(np.isfinite(a).all()), f"{name}: not finite")
        gap, size = float(np.abs(a - w).max()), float(np.abs(w).max())
        # against ``band * size``, so a reference that is all zeros (a state
        # forgotten whole) holds the other form to zeros and divides nothing
        if not gap <= band * size:
            off.append(f"{of} {name}: off by {gap} of {size}".strip())
        gaps[f"{name}_max_rel_err"] = gap / max(size, float(np.finfo(np.float32).tiny))
    print(json.dumps({of: gaps} if of else gaps), flush=True)
    _check(not off, "; ".join(off))
    return gaps


def _ms_a_call(fn, args, reps=5):
    """The best of ``reps`` calls of ``fn(*args)`` by the wall clock, in ms,
    after one that compiles."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))  # ba3clint: disable=J1 — the wait IS the measurement: a call by the wall clock
        best = min(best, time.perf_counter() - t0)
    return round(best * 1e3, 3)


# --------------------------------------------------------------------------
# phase: the gated delta rule's kernels against its plain form
# --------------------------------------------------------------------------


def phase_delta(shape: Shape, workdir: str, platform: str = "tpu") -> dict:
    """``ops/delta_rule.py:delta_chunked`` (on the chip: the two Pallas
    kernels, at a learner chunk's shapes) against ``delta_chunked_plain``:
    ``o``, the last state and every gradient leaf, as ``phase_ssd`` holds the
    Mamba-2 recurrence's."""
    del workdir
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.ops import delta_rule

    device = _require_device(platform)
    b, T, h, K, V, chunk = shape.delta_dims
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    heads = lambda x: x.reshape(b, T, h, -1)  # noqa: E731
    unit = lambda x: (heads(x) / jnp.linalg.norm(  # noqa: E731
        heads(x), axis=-1, keepdims=True)).reshape(x.shape)
    # The least gates stand seven decimal places above ``LEAST_GATE``: a
    # gate's gradient is a sum of terms that each hold the gate as a factor,
    # over the gate, and within a place or two of the least float32 the chip
    # flushes some of those terms to zero, not the same ones in both forms
    # (PR 46's first run here, at 1e-37: 0.43 off of 9.6 on ``dalpha`` alone;
    # ``tests/test_delta_rule.py`` holds that regime on its own)
    u = jax.random.uniform(keys[3], (b, T, h))
    least = 1e7 * delta_rule.LEAST_GATE * (1 + u)
    regimes = {
        # as a trained mixer's: most near 1, some that forget a chunk whole
        # (the mask before the exp), one near 1 after one near 0
        "trained": jnp.where(u < 0.7, 1.0 - 0.05 * u, jnp.where(u < 0.95, u, least)),
        "near_one": 1.0 - 1e-4 * u,  # a state that forgets nothing
        "near_least": least,         # one that forgets everything, every position
    }
    # operands and results lie as they do in the layer, a position's heads
    # side by side in one row (handed ``[.., h, K]`` arrays XLA copies each
    # into that layout round the kernels, which the layer does not pay);
    # step sizes up to 2 (past 1 the rule mirrors what the state held along k)
    qkv = (unit(jax.random.normal(keys[0], (b, T, h * K))) / K ** 0.5,
           unit(jax.random.normal(keys[1], (b, T, h * K))),
           jax.random.normal(keys[2], (b, T, h * V)))
    beta = 2.0 * jax.random.uniform(keys[4], (b, T, h))
    args = (*qkv, regimes["trained"], beta)
    pull = jax.random.normal(keys[5], (b, T, h * V))
    pull_last = jax.random.normal(keys[6], (b, h, K, V)) / 8

    def flat(form):
        def run(q, k, v, alpha, beta):
            o, last = form(heads(q), heads(k), heads(v), alpha, beta, chunk=chunk)
            return o.reshape(b, T, h * V), last
        return run

    def all_seven(form):
        def run(*args):
            out, pull_back = jax.vjp(flat(form), *args)
            return out + pull_back((pull, pull_last))
        return jax.jit(run)

    ours = all_seven(delta_rule.delta_chunked)
    text = ours.lower(*args).as_text()
    kernels = text.count("tpu_custom_call")
    _check(kernels == (2 if platform == "tpu" else 0),
           f"{kernels} Pallas kernels lowered on {platform}")
    if kernels:
        _check(delta_rule.FORWARD_KERNEL in text
               and delta_rule.BACKWARD_KERNEL in text,
               "the kernels' names are not in the lowered program")
    plain = all_seven(delta_rule.delta_chunked_plain)
    info = {"device": device, "pallas_kernels": kernels, "gaps": {}}
    for regime, alpha in regimes.items():
        gated = (*qkv, alpha, beta)
        info["gaps"][regime] = _gaps_within(
            DELTA_BAND, ("o", "last_state", "dq", "dk", "dv", "dalpha", "dbeta"),
            ours(*gated), plain(*gated), of=regime)

    if platform == "tpu":  # what each form takes alone, forward and both ways
        info["forward_ms"] = _ms_a_call(jax.jit(flat(delta_rule.delta_chunked)), args)
        info["forward_plain_ms"] = _ms_a_call(
            jax.jit(flat(delta_rule.delta_chunked_plain)), args)
        info["both_ways_ms"] = _ms_a_call(ours, args)
        info["both_ways_plain_ms"] = _ms_a_call(plain, args)
    return info


# --------------------------------------------------------------------------
# phase: the exact top-k as a mask, its three regimes against the plain searches
# --------------------------------------------------------------------------


def phase_select(shape: Shape, workdir: str, platform: str = "tpu") -> dict:
    del workdir
    import functools

    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.ops import topk_select
    from distributed_ba3c_tpu.ops.topk_select import select_mask
    from distributed_ba3c_tpu.utils import profiling

    device = _require_device(platform)
    rows, keys, k, (envs, queries, block_keys) = shape.select_dims
    seeds = jax.random.split(jax.random.PRNGKey(0), 2)
    # an indexer's scores: rounded, so that values tie, and a row of one value
    scores = jnp.round(jax.random.normal(seeds[0], (rows, keys)) * 64) / 64
    scores = scores.at[0].set(0.0)
    block = jax.random.normal(seeds[1], (envs, queries, block_keys))
    at = (block_keys - queries) + jnp.arange(queries)[:, None]
    causal = jnp.broadcast_to(jnp.arange(block_keys)[None, :] <= at, block.shape)
    up_to = lambda pos: jnp.arange(keys)[None, :] <= pos[:, None]  # noqa: E731
    spread = jnp.arange(rows)
    decode_live = {
        # every env in an episode's first k positions; every env past them;
        # one env that fits beside others that do not
        "fits": up_to(k - 1 - spread), "overflows": up_to(k + spread * 7),
        "mixed": up_to(jnp.where(spread == 0, k // 2, keys - 1 - spread)),
    }
    ours = jax.jit(lambda s, a: select_mask(s, a, k))
    plain = jax.jit(functools.partial(topk_select._searches, k=k))

    both = jax.jit(lambda s, a, b, c: (select_mask(s, a, k), select_mask(b, c, k)))
    text = both.lower(scores, decode_live["mixed"], block, causal).compile().as_text()
    lines = [line for line in text.splitlines()
             if f"/{profiling.OP_INDEXER_SELECT_RADIX.rsplit('/', 1)[-1]}/" in line]
    kernels = sum("tpu_custom_call" in line for line in lines)
    _check(kernels == (2 if platform == "tpu" else 0),
           f"{kernels} Pallas kernels under the radix scope on {platform}")
    info = {"device": device, "pallas_kernels": kernels,
            "radix_lines": len(lines),
            "radix_lines_in_fast_memory": sum("S(1)" in line for line in lines)}

    head = decode_live["mixed"][:, :k]  # regime 1: keys no longer than k
    _check("while" not in ours.lower(scores[:, :k], head).as_text(),
           "a row no longer than the top-k lowered a loop")
    _check(bool((ours(scores[:, :k], head) == head).all()),
           "a row no longer than the top-k is not its live entries")
    got = {name: ours(scores, live) for name, live in decode_live.items()}
    for name, live in decode_live.items():
        _check(bool((got[name] == plain(scores, live)).all()),
               f"decode rows, {name}: not the plain searches' mask")
        _check(bool((jnp.sum(got[name], -1)
                     == jnp.minimum(jnp.sum(live, -1), k)).all()),
               f"decode rows, {name}: not min(k, live) a row")
        info[f"decode_{name}_selected"] = int(jnp.sum(got[name]))
    _check(bool((got["fits"] == decode_live["fits"]).all()),
           "rows that fit are not their live entries")
    _check(bool(got["overflows"][0, :k].all()),
           "a row of one value does not keep its first k positions")
    _check(bool((ours(block, causal) == plain(block, causal)).all()),
           "a learner's block: not the plain searches' mask")

    def ms_a_call(live, calls=256):
        def body(x, _):
            mask = select_mask(x, live, k)
            return x + mask * 1e-3, None
        run = jax.jit(lambda x: jax.lax.scan(body, x, None, length=calls)[0])
        jax.block_until_ready(run(scores))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(run(scores))  # ba3clint: disable=J1 — the wait IS the measurement: 256 calls by the wall clock
            best = min(best, time.perf_counter() - t0)
        return 1e3 * best / calls

    info["decode_fits_ms"] = ms_a_call(decode_live["fits"])
    info["decode_overflows_ms"] = ms_a_call(decode_live["overflows"])
    if platform == "tpu":  # the skip is a skip: not the searches' time
        _check(info["decode_fits_ms"] < info["decode_overflows_ms"],
               f"rows that fit took {info['decode_fits_ms']} ms a call, rows "
               f"that do not {info['decode_overflows_ms']}")
    return info


# --------------------------------------------------------------------------
# phase: more than one device
# --------------------------------------------------------------------------


def phase_mesh(shape: Shape, workdir: str, platform: str = "tpu") -> dict:
    del workdir
    import jax
    import numpy as np

    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import (
        create_fused_state,
        make_fused_step,
    )
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh

    device = _require_device(platform)
    n = int(device["count"])
    if n < 2:
        return {"device": device, "skipped": "one device: nothing to shard"}
    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=shape.fc_units)
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh()
    step = make_fused_step(
        model, opt, cfg, mesh, pong, rollout_len=shape.rollout_len
    )
    state = step.put(create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong,
        shape.envs_per_chip * n, n_shards=n,
    ))
    everyone = {d.id for d in jax.devices()}
    for name, arr in (
        ("obs_stack", state.obs_stack),
        ("env_state", jax.tree_util.tree_leaves(state.env_state)[0]),
    ):
        held = {s.device.id for s in arr.addressable_shards}
        _check(held == everyone, f"{name} lives on {held}, not {everyone}")
        _check(
            all(s.data.shape[0] == shape.envs_per_chip
                for s in arr.addressable_shards),
            f"{name} shards are not {shape.envs_per_chip} envs each",
        )
    updates = shape.fused_steps_per_epoch
    for _ in range(updates):
        state, metrics = step(state, cfg.entropy_beta)
    _check(np.isfinite(float(metrics["loss"])), "loss not finite")

    # the on-chip form of T3: one all-reduce per leaf means every replica
    # applied the same gradient, so the replicas are the same bits
    digests = [[] for _ in range(n)]
    leaves = jax.tree_util.tree_leaves((state.train.params, state.train.opt_state))
    for leaf in leaves:
        shards = sorted(leaf.addressable_shards, key=lambda s: s.device.id)
        _check(len(shards) == n, f"a leaf has {len(shards)} replicas")
        first = np.asarray(shards[0].data)
        for k, s in enumerate(shards):
            data = np.asarray(s.data)
            _check(
                np.array_equal(data, first, equal_nan=True),
                f"replica {k} of a {first.shape} leaf differs from replica 0",
            )
            digests[k].append(float(np.float64(np.sum(data))))
    _check(all(d == digests[0] for d in digests), "param digests differ")
    return {
        "device": device,
        "updates": updates,
        "replicated_leaves": len(leaves),
        "sharded_over": sorted(everyone),
        # the BA3C_PARAM_DIGEST formula, identical on every replica
        "param_digest_head": [f"{v:.10e}" for v in digests[0][:3]],
    }


PHASES = {
    "fused": phase_fused,
    "plane": phase_plane,
    "forwards": phase_forwards,
    "grouped": phase_grouped,
    "sparse_attn": phase_sparse_attn,
    "select": phase_select,
    "ssd": phase_ssd,
    "delta": phase_delta,
    "mesh": phase_mesh,
}


# --------------------------------------------------------------------------
# child and parent
# --------------------------------------------------------------------------


def run_phase(name: str, workdir: str) -> int:
    """The child: holds the chip for one phase, prints its JSON last."""
    os.environ["BA3C_AUDIT"] = "1"  # a post-warm-up re-trace kills the run
    t0 = time.monotonic()
    info = PHASES[name](FULL, workdir)
    info = {"phase": name, "ok": True,
            "wall_s": round(time.monotonic() - t0, 1), **info}
    print(json.dumps(info), flush=True)
    return 0


def _run_child(name: str, workdir: str, timeout_s: float) -> dict:
    """Run one phase as a child in its own session; everything it started
    is killed with it. Its stdout is passed through; the last line is its
    result."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--phase", name, "--workdir", workdir],
        stdout=subprocess.PIPE, text=True, start_new_session=True,
    )
    last = ""
    timer = threading.Timer(timeout_s, os.killpg, (proc.pid, signal.SIGKILL))
    timer.start()
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.strip():
                last = line
        rc = proc.wait()
    finally:
        timer.cancel()
        try:
            os.killpg(proc.pid, signal.SIGKILL)  # stragglers of the session
        except ProcessLookupError:
            pass
    if rc != 0:
        raise SystemExit(f"chip_smoke: phase {name!r} failed (rc {rc})")
    result = json.loads(last)
    if result.get("phase") != name or result.get("ok") is not True:
        raise SystemExit(f"chip_smoke: phase {name!r} printed no result")
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        return run_phase(args.phase, args.workdir)

    t0 = time.monotonic()
    workdir = tempfile.mkdtemp(prefix="chip_smoke-")
    results = []
    try:
        for name in PHASES:
            left = TOTAL_BUDGET_S - (time.monotonic() - t0)
            print(f"[chip_smoke] phase {name} ({left:.0f}s left)", flush=True)
            results.append(_run_child(name, workdir, max(left, 1.0)))
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    devices = [r["device"] for r in results]
    if any(d != devices[0] for d in devices):
        raise SystemExit(f"chip_smoke: phases disagree on the device: {devices}")
    summary = {
        "wall_s": round(time.monotonic() - t0, 1),
        "phases": results,
    }
    for r in results:
        print("[chip_smoke] " + json.dumps(
            {k: v for k, v in r.items() if k not in ("device", "ok")}
        ))
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
