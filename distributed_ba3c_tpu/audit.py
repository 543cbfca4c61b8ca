"""Compiled-program audit plane: entry-point registry + retrace tripwire.

``ba3clint`` (tools/ba3clint) checks what the *source* promises; this module
is the other half — the registry of what the *compiled program* must do.
Each hot-path jit site registers a named entry point with canonical abstract
shapes, and ``tools/ba3caudit`` traces them (``.trace()`` → jaxpr → lowered
HLO → compiled cost analysis) and checks IR-level invariants the north-star
number lives on:

    T1  no f32 compute leaking into the bf16 conv stack
    T2  donation materialized as input→output buffer aliasing
    T3  exactly one gradient all-reduce per step on the data axis
    T4  no host callbacks / debug prints in hot paths
    T5  FLOPs + HBM bytes pinned by the checked-in audit_manifest.json

The registered entry points (one per hot-path jit site):

    parallel.train_step   the sync DP step      (parallel/train_step.py)
    parallel.train_macro_step
                          the multi-fleet macro step: K fleet sub-batches
                          (fleet axis sharded over data), one update
    parallel.vtrace_step  the V-trace step      (parallel/vtrace_step.py)
    parallel.vtrace_macro_step
                          the V-trace macro step (same fleet-major layout)
    fused.step            the fused rollout+update step (fused/loop.py)
    fused.macro_learner   the overlap macro learner: K trajectory blocks
                          accumulated into one update (fused/overlap.py)
    fused.actor           the overlap rollout program (fused/overlap.py) —
                          donation-aliased env carry, collective-free
    fused.learner         the overlap V-trace learner (fused/overlap.py)
    fused.greedy_eval     the on-device greedy Evaluator (fused/loop.py)
    predict.server        the batched action-server forward (predict/server.py)
    predict.server_greedy the greedy (eval/play) server variant — [3, B]
                          packed fetch (the duplicated argmax row dropped)
    predict.server_bf16   the quantized serving forward: bf16 param storage
                          (--rollout_dtype bfloat16), f32 heads — the
                          cheaper program the actor plane serves from,
                          structurally pinned so it cannot silently revert
    fused.actor_bf16      the overlap rollout program at the bf16 params
                          snapshot (fused.prep's cast output) — same pin
    predict.server_int8   the int8 serving forward (--rollout_dtype int8):
                          per-channel symmetric int8 weights + calibrated
                          per-tensor activation scales (quantize/), int8
                          conv accumulate-to-int32, f32 epilogue + heads —
                          the quarter-bandwidth rung, structurally pinned
    fused.actor_int8      the overlap rollout program at the int8 qparams
                          snapshot (fused.prep quantizes on snapshot) —
                          same donation/collective-free contract
    pod.learner           the pod's bounded-staleness V-trace learner
                          (pod/learner.py) — the fused.learner gradient
                          body compiled standalone for host-fed blocks

Canonical shapes are deliberately SMALL (the invariants are shape-class
properties, not magnitude properties) and the canonical mesh is always the
first :data:`CANONICAL_MESH_DEVICES` devices, so the manifest numbers are
identical under the 8-device pytest harness and the standalone CLI.

Runtime tripwire (``BA3C_AUDIT=1``, mirroring ``BA3C_SANITIZE=1``): the same
jit sites route through :func:`tripwire_jit`, which counts trace events per
entry point and raises :class:`AuditError` if a registered program re-traces
after warmup — a silent recompile mid-run is exactly the "bench below 64k
triggers re-investigation" regression (VERDICT.md), now a machine check.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

AUDIT_ENV = "BA3C_AUDIT"

#: The canonical audit mesh is ALWAYS the first two devices — fixed so the
#: committed manifest does not depend on how many CPU devices the harness
#: happens to force (pytest forces 8; the CLI forces 2).
CANONICAL_MESH_DEVICES = 2


def audit_enabled() -> bool:
    return os.environ.get(AUDIT_ENV, "") not in ("", "0")


class AuditError(RuntimeError):
    """A compiled-program invariant was violated at runtime (tripwire)."""


# --------------------------------------------------------------------------
# runtime retrace tripwire
# --------------------------------------------------------------------------

#: live tripwires by entry-point name (inspection/testing; latest wins)
_LIVE_TRIPWIRES: Dict[str, "RetraceTripwire"] = {}


class RetraceTripwire:
    """Wrap a to-be-jitted function and refuse post-warmup retraces.

    Trace events are counted by instrumenting the *python function itself*
    (its body runs exactly once per cache miss), not a private jit API, so
    the counter is exact on every jax version. By default the tripwire arms
    itself after the first call — the first call IS the warmup compile; any
    later cache miss means an input changed shape/dtype/sharding and the
    entry point silently recompiled. Sites with a legitimate multi-shape
    warmup (the predictor's pow-2 buckets) pass ``auto_arm=False`` and call
    :meth:`arm` when their warmup completes.

    Attribute access falls through to the underlying jitted callable, so
    ``.trace()``/``.lower()`` (the static auditor) keep working.
    """

    def __init__(self, name: str, fn: Callable, jit_kwargs: dict,
                 auto_arm: bool = True):
        import threading

        import jax

        self.name = name
        self.traces = 0
        self.armed = False
        self._auto_arm = auto_arm
        self._lock = threading.Lock()
        # jit traces run synchronously in the CALLING thread, so a
        # thread-local flag attributes each trace to exactly the call that
        # caused it — the predictor shares one tripwire across worker
        # threads, and blaming worker A for worker B's retrace would send
        # the operator debugging the wrong shape
        self._tls = threading.local()

        @functools.wraps(fn)
        def counted(*args, **kwargs):
            with self._lock:
                self.traces += 1
            self._tls.traced = True
            return fn(*args, **kwargs)

        self._jitted = jax.jit(counted, **jit_kwargs)

    def arm(self) -> None:
        """Declare warmup complete: any further trace raises AuditError."""
        self.armed = True

    def __call__(self, *args, **kwargs):
        self._tls.traced = False
        out = self._jitted(*args, **kwargs)
        if self.armed and getattr(self._tls, "traced", False):
            try:
                # leave postmortem evidence before raising: the retrace is
                # exactly the mid-run stall class the flight recorder exists
                # for (telemetry/recorder.py)
                from distributed_ba3c_tpu import telemetry

                telemetry.record(
                    "retrace", entry=self.name, trace=self.traces
                )
                telemetry.dump("AuditError")
            except Exception:
                pass  # telemetry must never mask the audit finding
            raise AuditError(
                f"[audit] entry point {self.name!r} re-traced after warmup "
                f"(trace #{self.traces}) — an input changed "
                "shape/dtype/sharding and the program silently recompiled. "
                "Every recompile stalls the step for the full XLA compile; "
                "fix the unstable input or re-warm explicitly."
            )
        if self._auto_arm and not self.armed and self.traces:
            self.armed = True
        return out

    def __getattr__(self, item):
        return getattr(self._jitted, item)


def tripwire_jit(name: str, fn: Callable, *, auto_arm: bool = True,
                 **jit_kwargs):
    """``jax.jit`` with the BA3C_AUDIT=1 retrace tripwire.

    The single wrapper every registered hot-path jit site uses: a plain
    ``jax.jit(fn, **jit_kwargs)`` when auditing is off (zero overhead), a
    :class:`RetraceTripwire` when ``BA3C_AUDIT=1``.
    """
    import jax

    if not audit_enabled():
        return jax.jit(fn, **jit_kwargs)
    tw = RetraceTripwire(name, fn, jit_kwargs, auto_arm=auto_arm)
    _LIVE_TRIPWIRES[name] = tw
    return tw


def live_tripwires() -> Dict[str, RetraceTripwire]:
    return dict(_LIVE_TRIPWIRES)


# --------------------------------------------------------------------------
# static entry-point registry
# --------------------------------------------------------------------------


@dataclasses.dataclass
class TraceTarget:
    """One registered entry point, built at canonical abstract shapes.

    ``jit_fn`` is the REAL jitted callable from the hot-path module (exposed
    as ``step.audit_jit``), so the auditor sees exactly the program training
    runs — not a re-derivation of it.
    """

    name: str
    jit_fn: Any                      # jitted callable exposing .trace()
    args: Tuple[Any, ...]            # ShapeDtypeStruct pytrees
    #: shapes of the non-scalar param leaves whose gradients must each be
    #: all-reduced EXACTLY once on the data axis; None = entry computes no
    #: gradients (any non-scalar psum is a violation)
    grad_shapes: Optional[List[Tuple[int, ...]]]
    #: flattened input indices of the donated argument's NON-SCALAR leaves:
    #: each must materialize as an input→output alias in the compiled
    #: module (empty = no donation, so no alias may appear at all). Scalar
    #: leaves are excluded — XLA occasionally declines a 4-byte alias (CSE
    #: on identical scalar updates) and nothing rides on it.
    donated_nonscalar_indices: List[int]
    #: False = the program must contain NO collectives at all (predictor)
    allow_collectives: bool = True
    #: required operand dtype for every conv eqn in the program
    conv_dtype: str = "bfloat16"


ENTRY_POINTS: Dict[str, Callable[[], TraceTarget]] = {}


def register_entry(name: str):
    def deco(builder: Callable[[], TraceTarget]):
        ENTRY_POINTS[name] = builder
        return builder

    return deco


def entry_names() -> List[str]:
    return sorted(ENTRY_POINTS)


def build_entry(name: str) -> TraceTarget:
    if name not in ENTRY_POINTS:
        raise KeyError(
            f"unknown audit entry point {name!r}; registered: {entry_names()}"
        )
    return ENTRY_POINTS[name]()


# -- canonical construction helpers ----------------------------------------


def _canonical_parts():
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.models.policy import DEFAULT_MODEL, build_model
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer

    cfg = BA3CConfig(num_actions=6)
    model = build_model(DEFAULT_MODEL, cfg)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    return cfg, model, opt


def canonical_mesh():
    import jax

    from distributed_ba3c_tpu.parallel.mesh import make_mesh

    devs = jax.devices()
    if len(devs) < CANONICAL_MESH_DEVICES:
        raise AuditError(
            f"the audit needs {CANONICAL_MESH_DEVICES} devices for its "
            f"canonical mesh, found {len(devs)} — run via "
            "`python -m tools.ba3caudit` (which forces a 2-device CPU "
            "platform) or set --xla_force_host_platform_device_count"
        )
    return make_mesh(
        num_data=CANONICAL_MESH_DEVICES,
        num_model=1,
        devices=devs[:CANONICAL_MESH_DEVICES],
    )


def _key_aval():
    import jax

    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def _scalar(dtype):
    import jax

    return jax.ShapeDtypeStruct((), dtype)


def _state_avals(model, cfg, opt):
    import jax

    from distributed_ba3c_tpu.parallel.train_step import create_train_state

    return jax.eval_shape(
        lambda k: create_train_state(k, model, cfg, opt), _key_aval()
    )


def _grad_shapes(params_avals) -> List[Tuple[int, ...]]:
    import jax

    return [
        tuple(l.shape)
        for l in jax.tree_util.tree_leaves(params_avals)
        if l.ndim >= 1
    ]


def _donated_indices(state_avals, exempt: Tuple[str, ...] = (),
                     offset: int = 0) -> List[int]:
    """Flattened input indices of the donated arg's non-scalar leaves.

    The donated state is usually positional arg 0, so its leaves occupy the
    first positions of the jit's flattened input list — which is the HLO
    parameter numbering the compiled module's alias table uses. When the
    donated arg comes AFTER others (the overlap actor donates arg 1, its
    env carry, while arg 0 is the params snapshot), ``offset`` is the leaf
    count of the preceding args. ``exempt`` names leaf-path fragments
    excluded from the T2 requirement; every exemption must carry a
    justification comment at the registration site (the manifest's exact
    ``aliased_inputs`` count still pins the total).
    """
    import jax

    out = []
    for i, (path, leaf) in enumerate(
        jax.tree_util.tree_flatten_with_path(state_avals)[0]
    ):
        if leaf.ndim < 1:
            continue
        key = jax.tree_util.keystr(path)
        if any(frag in key for frag in exempt):
            continue
        out.append(offset + i)
    return out


# -- the five entry points --------------------------------------------------


@register_entry("parallel.train_step")
def _build_train_step() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.parallel.train_step import make_train_step

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    step = make_train_step(model, opt, cfg, mesh)
    state = _state_avals(model, cfg, opt)
    B = 32  # canonical global batch: 16 samples per canonical shard
    batch = {
        "state": jax.ShapeDtypeStruct((B, *cfg.state_shape), jnp.uint8),
        "action": jax.ShapeDtypeStruct((B,), jnp.int32),
        "return": jax.ShapeDtypeStruct((B,), jnp.float32),
    }
    return TraceTarget(
        name="parallel.train_step",
        jit_fn=step.audit_jit,
        args=(state, batch, _scalar(jnp.float32), _scalar(jnp.float32)),
        grad_shapes=_grad_shapes(state.params),
        donated_nonscalar_indices=_donated_indices(state),
    )


@register_entry("parallel.vtrace_step")
def _build_vtrace_step() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.parallel.vtrace_step import make_vtrace_train_step

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    step = make_vtrace_train_step(model, opt, cfg, mesh)
    state = _state_avals(model, cfg, opt)
    T, B = 4, 8  # canonical unroll: 4 samples per canonical shard
    sds = jax.ShapeDtypeStruct
    batch = {
        "state": sds((T, B, *cfg.state_shape), jnp.uint8),
        "action": sds((T, B), jnp.int32),
        "reward": sds((T, B), jnp.float32),
        "done": sds((T, B), jnp.float32),
        "behavior_log_probs": sds((T, B), jnp.float32),
        "bootstrap_state": sds((B, *cfg.state_shape), jnp.uint8),
    }
    return TraceTarget(
        name="parallel.vtrace_step",
        jit_fn=step.audit_jit,
        args=(state, batch, _scalar(jnp.float32), _scalar(jnp.float32)),
        grad_shapes=_grad_shapes(state.params),
        donated_nonscalar_indices=_donated_indices(state),
    )


@register_entry("parallel.train_macro_step")
def _build_train_macro_step() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.parallel.train_step import make_macro_train_step

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    # canonical macro shape: K=4 fleets over the 2-device mesh — 2 fleets
    # per shard, so the sequential accumulation scan is IN the program
    # (K == D would compile the scan away and pin the wrong structure)
    K, B = 4, 16
    step = make_macro_train_step(model, opt, cfg, mesh, n_fleets=K)
    state = _state_avals(model, cfg, opt)
    batch = {
        "state": jax.ShapeDtypeStruct((K, B, *cfg.state_shape), jnp.uint8),
        "action": jax.ShapeDtypeStruct((K, B), jnp.int32),
        "return": jax.ShapeDtypeStruct((K, B), jnp.float32),
    }
    return TraceTarget(
        name="parallel.train_macro_step",
        jit_fn=step.audit_jit,
        args=(state, batch, _scalar(jnp.float32), _scalar(jnp.float32)),
        grad_shapes=_grad_shapes(state.params),
        donated_nonscalar_indices=_donated_indices(state),
    )


@register_entry("parallel.vtrace_macro_step")
def _build_vtrace_macro_step() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.parallel.vtrace_step import make_vtrace_macro_step

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    # K=4 over D=2 for the same in-program-scan reason as the BA3C macro
    K, T, B = 4, 4, 8
    step = make_vtrace_macro_step(model, opt, cfg, mesh, n_fleets=K)
    state = _state_avals(model, cfg, opt)
    sds = jax.ShapeDtypeStruct
    batch = {
        "state": sds((K, T, B, *cfg.state_shape), jnp.uint8),
        "action": sds((K, T, B), jnp.int32),
        "reward": sds((K, T, B), jnp.float32),
        "done": sds((K, T, B), jnp.float32),
        "behavior_log_probs": sds((K, T, B), jnp.float32),
        "bootstrap_state": sds((K, B, *cfg.state_shape), jnp.uint8),
    }
    return TraceTarget(
        name="parallel.vtrace_macro_step",
        jit_fn=step.audit_jit,
        args=(state, batch, _scalar(jnp.float32), _scalar(jnp.float32)),
        grad_shapes=_grad_shapes(state.params),
        donated_nonscalar_indices=_donated_indices(state),
    )


@register_entry("fused.step")
def _build_fused_step() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import (
        create_fused_state,
        make_fused_step,
    )

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    n_envs = 2 * CANONICAL_MESH_DEVICES  # 2 envs per canonical shard
    step = make_fused_step(model, opt, cfg, mesh, pong, rollout_len=4)
    state = jax.eval_shape(
        lambda k: create_fused_state(
            k, model, cfg, opt, pong, n_envs,
            n_shards=CANONICAL_MESH_DEVICES,
        ),
        _key_aval(),
    )
    return TraceTarget(
        name="fused.step",
        jit_fn=step.audit_jit,
        args=(state, _scalar(jnp.float32), _scalar(jnp.float32)),
        grad_shapes=_grad_shapes(state.train.params),
        # ep_return_sum: XLA's buffer assignment declines this one alias
        # (the new value feeds both the carried state and the episode
        # metrics psum) — [n_envs] f32, a few KB at real scale, nothing
        # rides on it. Pinned by the manifest's aliased_inputs count.
        donated_nonscalar_indices=_donated_indices(
            state, exempt=("ep_return_sum",)
        ),
    )


@register_entry("fused.actor")
def _build_overlap_actor() -> TraceTarget:
    import jax

    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import create_fused_state
    from distributed_ba3c_tpu.fused.overlap import ActorState, make_overlap_step

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    n_envs = 2 * CANONICAL_MESH_DEVICES  # 2 envs per canonical shard
    step = make_overlap_step(model, opt, cfg, mesh, pong, rollout_len=4)
    state = jax.eval_shape(
        lambda k: create_fused_state(
            k, model, cfg, opt, pong, n_envs,
            n_shards=CANONICAL_MESH_DEVICES,
        ),
        _key_aval(),
    )
    astate = ActorState(
        env_state=state.env_state,
        obs_stack=state.obs_stack,
        key=state.key,
        ep_return=state.ep_return,
        ep_count=state.ep_count,
        ep_return_sum=state.ep_return_sum,
    )
    params = state.train.params
    return TraceTarget(
        name="fused.actor",
        jit_fn=step.actor_jit,
        # arg 0 is the params SNAPSHOT (fused.prep's output), arg 1 the
        # donated env carry — its leaves sit after every params leaf in
        # the HLO parameter numbering
        args=(params, astate),
        grad_shapes=None,
        donated_nonscalar_indices=_donated_indices(
            astate,
            offset=len(jax.tree_util.tree_leaves(params)),
        ),
        # the overlap schedule's whole premise: the rollout program has
        # nothing to wait on — single-chip form must be collective-free
        allow_collectives=False,
    )


@register_entry("fused.learner")
def _build_overlap_learner() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.overlap import TrajBlock, make_overlap_step

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    step = make_overlap_step(model, opt, cfg, mesh, pong, rollout_len=4)
    train = _state_avals(model, cfg, opt)
    T, B = 4, 2 * CANONICAL_MESH_DEVICES  # one canonical actor block
    sds = jax.ShapeDtypeStruct
    block = TrajBlock(
        states=sds((T, B, *cfg.state_shape), jnp.uint8),
        actions=sds((T, B), jnp.int32),
        rewards=sds((T, B), jnp.float32),
        dones=sds((T, B), jnp.float32),
        behavior_log_probs=sds((T, B), jnp.float32),
        behavior_values=sds((T, B), jnp.float32),
        bootstrap_state=sds((B, *cfg.state_shape), jnp.uint8),
    )
    return TraceTarget(
        name="fused.learner",
        jit_fn=step.learner_jit,
        args=(train, block, _scalar(jnp.float32), _scalar(jnp.float32)),
        grad_shapes=_grad_shapes(train.params),
        # only the train state is donated — the block must stay live (it
        # is the double-buffer slot the actor wrote; no learner output
        # matches its shapes, so an alias is impossible anyway)
        donated_nonscalar_indices=_donated_indices(train),
    )


@register_entry("fused.macro_learner")
def _build_overlap_macro_learner() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.overlap import TrajBlock, make_overlap_step

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    K = 2  # canonical macro window count (the accumulation scan is per-shard)
    step = make_overlap_step(
        model, opt, cfg, mesh, pong, rollout_len=4, macro_fleets=K
    )
    train = _state_avals(model, cfg, opt)
    T, B = 4, 2 * CANONICAL_MESH_DEVICES  # one canonical actor block each
    sds = jax.ShapeDtypeStruct
    block = TrajBlock(
        states=sds((T, B, *cfg.state_shape), jnp.uint8),
        actions=sds((T, B), jnp.int32),
        rewards=sds((T, B), jnp.float32),
        dones=sds((T, B), jnp.float32),
        behavior_log_probs=sds((T, B), jnp.float32),
        behavior_values=sds((T, B), jnp.float32),
        bootstrap_state=sds((B, *cfg.state_shape), jnp.uint8),
    )
    return TraceTarget(
        name="fused.macro_learner",
        jit_fn=step.macro_learner_jit,
        args=(train, (block,) * K, _scalar(jnp.float32), _scalar(jnp.float32)),
        grad_shapes=_grad_shapes(train.params),
        # only the train state is donated — the K blocks are the actor's
        # double-buffer slots, same non-donation contract as fused.learner
        donated_nonscalar_indices=_donated_indices(train),
    )


@register_entry("fused.greedy_eval")
def _build_greedy_eval() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import make_greedy_eval

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    evaluate = make_greedy_eval(
        model, cfg, mesh, pong, n_envs=CANONICAL_MESH_DEVICES, max_steps=8
    )
    params = _state_avals(model, cfg, opt).params
    return TraceTarget(
        name="fused.greedy_eval",
        jit_fn=evaluate.audit_jit,
        args=(params, _scalar(jnp.uint32)),
        grad_shapes=None,  # pure inference: a param-shaped psum is a bug
        donated_nonscalar_indices=[],
    )


@register_entry("predict.server")
def _build_predict_server() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.predict.server import make_fwd_sample

    cfg, model, opt = _canonical_parts()
    params = _state_avals(model, cfg, opt).params
    B = 16  # canonical serving bucket (cfg.predict_batch_size)
    states = jax.ShapeDtypeStruct((B, *cfg.state_shape), jnp.uint8)
    return TraceTarget(
        name="predict.server",
        jit_fn=jax.jit(make_fwd_sample(model, greedy=False)),
        args=(params, states, _key_aval()),
        grad_shapes=None,
        donated_nonscalar_indices=[],
        # single-device serving path: any collective here means a mesh
        # sharding leaked into the action server
        allow_collectives=False,
    )


def _bf16_params(params_avals):
    """f32 param leaves → bf16 avals (what fused.prep's cast / the
    predictor's publish-cast hands the rollout-side programs)."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
        if l.dtype == jnp.float32 else l,
        params_avals,
    )


@register_entry("predict.server_bf16")
def _build_predict_server_bf16() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.predict.server import make_fwd_sample

    cfg, model, opt = _canonical_parts()
    params = _bf16_params(_state_avals(model, cfg, opt).params)
    B = 16  # same canonical bucket as predict.server
    states = jax.ShapeDtypeStruct((B, *cfg.state_shape), jnp.uint8)
    return TraceTarget(
        # the quantized serving/actor forward (--rollout_dtype bfloat16):
        # same fwd_sample body, bf16 param STORAGE — a distinct compiled
        # program whose halved param reads T5 pins separately (the f32
        # entry must not silently absorb the cheap program's cost profile,
        # nor vice versa); T1 still requires the bf16 conv stack and the
        # log-prob heads stay f32 (models/a3c.py)
        name="predict.server_bf16",
        jit_fn=jax.jit(make_fwd_sample(model, greedy=False)),
        args=(params, states, _key_aval()),
        grad_shapes=None,
        donated_nonscalar_indices=[],
        allow_collectives=False,
    )


@register_entry("fused.actor_bf16")
def _build_overlap_actor_bf16() -> TraceTarget:
    import jax

    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import create_fused_state
    from distributed_ba3c_tpu.fused.overlap import ActorState, make_overlap_step

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    n_envs = 2 * CANONICAL_MESH_DEVICES  # 2 envs per canonical shard
    step = make_overlap_step(
        model, opt, cfg, mesh, pong, rollout_len=4,
        rollout_dtype="bfloat16",
    )
    state = jax.eval_shape(
        lambda k: create_fused_state(
            k, model, cfg, opt, pong, n_envs,
            n_shards=CANONICAL_MESH_DEVICES,
        ),
        _key_aval(),
    )
    astate = ActorState(
        env_state=state.env_state,
        obs_stack=state.obs_stack,
        key=state.key,
        ep_return=state.ep_return,
        ep_count=state.ep_count,
        ep_return_sum=state.ep_return_sum,
    )
    params = _bf16_params(state.train.params)
    return TraceTarget(
        # the overlap rollout at the bf16 snapshot (fused.prep's cast):
        # same donation-aliased env carry and collective-free contract as
        # fused.actor, traced at the bf16 param avals the bf16 schedule
        # actually feeds it — its halved param-read bytes get their own
        # T5 row instead of hiding behind the f32 entry
        name="fused.actor_bf16",
        jit_fn=step.actor_jit,
        args=(params, astate),
        grad_shapes=None,
        donated_nonscalar_indices=_donated_indices(
            astate,
            offset=len(jax.tree_util.tree_leaves(params)),
        ),
        allow_collectives=False,
    )


def _int8_qparams(model, params_avals):
    """f32 param avals → quantized-table avals (what the predictor's
    publish-quantize / fused.prep's snapshot-quantize hands the int8
    programs). The spec's SCALE VALUES never shape the program — one
    compiled forward per shape class serves every calibration — so a
    placeholder all-1.0 spec yields the exact avals the live table has."""
    import jax

    from distributed_ba3c_tpu.quantize import QuantSpec, quant_layer_names, quantize_params

    spec = QuantSpec(
        act_scales={n: 1.0 for n in quant_layer_names(model)}
    )
    return jax.eval_shape(lambda p: quantize_params(p, spec), params_avals)


@register_entry("predict.server_int8")
def _build_predict_server_int8() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.quantize import make_quant_fwd_sample

    cfg, model, opt = _canonical_parts()
    qparams = _int8_qparams(model, _state_avals(model, cfg, opt).params)
    B = 16  # same canonical bucket as predict.server
    states = jax.ShapeDtypeStruct((B, *cfg.state_shape), jnp.uint8)
    return TraceTarget(
        # the int8 serving forward (--rollout_dtype int8): same packed-fetch
        # contract as predict.server, int8 param STORAGE with per-channel
        # weight scales riding in the table — T1 here requires every conv
        # to run int8×int8 (accumulate-to-int32 via preferred_element_type;
        # a dequantize-first regression shows up as f32 operands), and T5
        # pins the quartered param reads on their own row
        name="predict.server_int8",
        jit_fn=jax.jit(make_quant_fwd_sample(model, greedy=False)),
        args=(qparams, states, _key_aval()),
        grad_shapes=None,
        donated_nonscalar_indices=[],
        allow_collectives=False,
        conv_dtype="int8",
    )


@register_entry("fused.actor_int8")
def _build_overlap_actor_int8() -> TraceTarget:
    import jax

    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.fused.loop import create_fused_state
    from distributed_ba3c_tpu.fused.overlap import ActorState, make_overlap_step
    from distributed_ba3c_tpu.quantize import QuantSpec, quant_layer_names

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    n_envs = 2 * CANONICAL_MESH_DEVICES  # 2 envs per canonical shard
    spec = QuantSpec(
        act_scales={n: 1.0 for n in quant_layer_names(model)}
    )
    step = make_overlap_step(
        model, opt, cfg, mesh, pong, rollout_len=4,
        rollout_dtype="int8", quant_spec=spec,
    )
    state = jax.eval_shape(
        lambda k: create_fused_state(
            k, model, cfg, opt, pong, n_envs,
            n_shards=CANONICAL_MESH_DEVICES,
        ),
        _key_aval(),
    )
    astate = ActorState(
        env_state=state.env_state,
        obs_stack=state.obs_stack,
        key=state.key,
        ep_return=state.ep_return,
        ep_count=state.ep_count,
        ep_return_sum=state.ep_return_sum,
    )
    qparams = _int8_qparams(model, state.train.params)
    return TraceTarget(
        # the overlap rollout at the int8 qparams snapshot (fused.prep
        # quantizes on snapshot): same donation-aliased env carry and
        # collective-free contract as fused.actor/_bf16, traced at the
        # quantized-table avals the int8 schedule actually feeds it
        name="fused.actor_int8",
        jit_fn=step.actor_jit,
        args=(qparams, astate),
        grad_shapes=None,
        donated_nonscalar_indices=_donated_indices(
            astate,
            offset=len(jax.tree_util.tree_leaves(qparams)),
        ),
        allow_collectives=False,
        conv_dtype="int8",
    )


@register_entry("pod.learner")
def _build_pod_learner() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.fused.overlap import TrajBlock
    from distributed_ba3c_tpu.pod.learner import make_pod_learner_step

    cfg, model, opt = _canonical_parts()
    mesh = canonical_mesh()
    step = make_pod_learner_step(model, opt, cfg, mesh)
    train = _state_avals(model, cfg, opt)
    T, B = 4, 2 * CANONICAL_MESH_DEVICES  # one canonical host-fed block
    sds = jax.ShapeDtypeStruct
    block = TrajBlock(
        states=sds((T, B, *cfg.state_shape), jnp.uint8),
        actions=sds((T, B), jnp.int32),
        rewards=sds((T, B), jnp.float32),
        dones=sds((T, B), jnp.float32),
        behavior_log_probs=sds((T, B), jnp.float32),
        behavior_values=sds((T, B), jnp.float32),
        bootstrap_state=sds((B, *cfg.state_shape), jnp.uint8),
    )
    return TraceTarget(
        name="pod.learner",
        jit_fn=step.audit_jit,
        args=(train, block, _scalar(jnp.float32), _scalar(jnp.float32)),
        grad_shapes=_grad_shapes(train.params),
        # same donation contract as fused.learner: only the train state —
        # the block stays live for the LaggedBlockDriver's double buffer
        donated_nonscalar_indices=_donated_indices(train),
    )


@register_entry("predict.server_greedy")
def _build_predict_server_greedy() -> TraceTarget:
    import jax
    import jax.numpy as jnp

    from distributed_ba3c_tpu.predict.server import make_fwd_sample

    cfg, model, opt = _canonical_parts()
    params = _state_avals(model, cfg, opt).params
    B = 16  # same canonical bucket as predict.server
    states = jax.ShapeDtypeStruct((B, *cfg.state_shape), jnp.uint8)
    return TraceTarget(
        name="predict.server_greedy",
        # the eval/play servers' program: greedy=True drops the duplicated
        # argmax row, shrinking the packed fetch to [3, B] — registering
        # BOTH shapes keeps T5 pinned on each (the sampling entry must not
        # silently absorb the greedy server's cost profile)
        jit_fn=jax.jit(make_fwd_sample(model, greedy=True)),
        args=(params, states, _key_aval()),
        grad_shapes=None,
        donated_nonscalar_indices=[],
        allow_collectives=False,
    )
