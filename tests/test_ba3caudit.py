"""tools/ba3caudit: per-rule toys, the real registry end-to-end, tripwire.

Layout mirrors test_ba3clint.py: every T-rule must (a) fire on a seeded
IR-level violation and (b) stay quiet on the clean construction, so a rule
regression that would spam (or blind) the real audit fails here first. The
end-to-end test runs the registry against the COMMITTED manifest — the same
check CI's audit job gates on.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ba3c_tpu import audit as audit_mod
from distributed_ba3c_tpu.audit import AuditError, RetraceTripwire, TraceTarget
from tools import ba3caudit
from tools.ba3caudit import ir, rules

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sds = jax.ShapeDtypeStruct


def _toy_target(fn, args, donate_argnums=None, **kwargs):
    fields = dict(
        name="toy",
        jit_fn=None,
        args=args,
        grad_shapes=None,
        donated_nonscalar_indices=[],
    )
    fields.update(kwargs)
    if fn is not None:
        fields["jit_fn"] = (
            jax.jit(fn, donate_argnums=donate_argnums)
            if donate_argnums is not None else jax.jit(fn)
        )
    return TraceTarget(**fields)


def _measure(target):
    return rules.measure(target)


# --------------------------------------------------------------------------
# T1: conv dtype policy
# --------------------------------------------------------------------------


def _conv_fn(dtype):
    def f(x, w):
        return jax.lax.conv_general_dilated(
            x.astype(dtype), w.astype(dtype),
            window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
    return f


_CONV_ARGS = (sds((1, 8, 8, 4), jnp.float32), sds((3, 3, 4, 8), jnp.float32))


def test_t1_flags_f32_conv():
    t = _toy_target(_conv_fn(jnp.float32), _CONV_ARGS)
    findings = rules.check_t1(t, _measure(t))
    assert findings and findings[0].rule == "T1"


def test_t1_clean_on_bf16_conv():
    t = _toy_target(_conv_fn(jnp.bfloat16), _CONV_ARGS)
    assert rules.check_t1(t, _measure(t)) == []


# --------------------------------------------------------------------------
# T2: donation materialized
# --------------------------------------------------------------------------


def test_t2_clean_when_donation_aliases():
    t = _toy_target(
        lambda x: x + 1.0, (sds((64, 64), jnp.float32),),
        donate_argnums=(0,), donated_nonscalar_indices=[0],
    )
    assert rules.check_t2(t, _measure(t)) == []


def test_t2_flags_dropped_donation():
    # donated arg has no same-shape output -> XLA cannot alias it
    t = _toy_target(
        lambda x: jnp.sum(x), (sds((64, 64), jnp.float32),),
        donate_argnums=(0,), donated_nonscalar_indices=[0],
    )
    findings = rules.check_t2(t, _measure(t))
    assert findings and findings[0].rule == "T2"


# --------------------------------------------------------------------------
# T3: exactly one gradient all-reduce
# --------------------------------------------------------------------------

_GRAD_SHAPE = (4, 4)


def _psum_step(extra_psums=0, check_vma=True):
    """A toy sharded grad step. With ``check_vma`` (the step builders'
    mode) the gradient of the replicated ``params`` is summed over the data
    axis by the reduction the shard_map transpose inserts — ONE all-reduce
    with no psum in the source; every ``extra_psums`` is the double-pmean
    bug (the grad scaled by the axis size again). ``check_vma=False`` turns
    the inserted reduction off: with no explicit psum each device applies
    its shard-local gradient."""
    from jax.sharding import PartitionSpec as P

    from distributed_ba3c_tpu.parallel.mesh import DATA_AXIS

    mesh = audit_mod.canonical_mesh()

    def body(params, x):
        g = jax.grad(lambda p: jnp.sum((x @ p) ** 2))(params)
        for _ in range(extra_psums):
            g = jax.lax.psum(g, DATA_AXIS)
        return params - g

    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P("data")), out_specs=P(),
        check_vma=check_vma,
    ))


_T3_ARGS = (sds(_GRAD_SHAPE, jnp.float32), sds((8, 4), jnp.float32))


def test_t3_clean_on_single_grad_psum():
    t = _toy_target(None, _T3_ARGS, grad_shapes=[_GRAD_SHAPE])
    t.jit_fn = _psum_step()
    assert rules.check_t3(t, _measure(t)) == []


def test_t3_counts_explicit_psum_without_check_vma():
    # the pre-check_vma spelling: no inserted reduction, one explicit psum
    t = _toy_target(None, _T3_ARGS, grad_shapes=[_GRAD_SHAPE])
    t.jit_fn = _psum_step(extra_psums=1, check_vma=False)
    assert rules.check_t3(t, _measure(t)) == []


def test_t3_flags_double_psum():
    t = _toy_target(None, _T3_ARGS, grad_shapes=[_GRAD_SHAPE])
    t.jit_fn = _psum_step(extra_psums=1)
    findings = rules.check_t3(t, _measure(t))
    assert findings and "extra" in findings[0].message


def test_t3_flags_missing_psum():
    t = _toy_target(None, _T3_ARGS, grad_shapes=[_GRAD_SHAPE])
    t.jit_fn = _psum_step(check_vma=False)
    findings = rules.check_t3(t, _measure(t))
    assert findings and "NEVER all-reduced" in findings[0].message


def test_t3_flags_collectives_in_collective_free_entry():
    t = _toy_target(None, _T3_ARGS, allow_collectives=False)
    t.jit_fn = _psum_step()
    findings = rules.check_t3(t, _measure(t))
    assert findings and "single-device" in findings[0].message


# --------------------------------------------------------------------------
# T4: host callbacks
# --------------------------------------------------------------------------


def test_t4_flags_debug_print():
    def f(x):
        jax.debug.print("x={x}", x=x)
        return x * 2

    t = _toy_target(f, (sds((4,), jnp.float32),))
    findings = rules.check_t4(t, _measure(t))
    assert findings and findings[0].rule == "T4"


def test_t4_flags_pure_callback():
    def f(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )
        return y + 1

    t = _toy_target(f, (sds((4,), jnp.float32),))
    assert rules.check_t4(t, _measure(t))


def test_t4_clean_without_callbacks():
    t = _toy_target(lambda x: x * 2, (sds((4,), jnp.float32),))
    assert rules.check_t4(t, _measure(t)) == []


# --------------------------------------------------------------------------
# T5: manifest drift (pure logic — no tracing)
# --------------------------------------------------------------------------


def _fake_measurement(**overrides):
    base = dict(
        entry="toy", collectives={"psum": 3}, host_callbacks={},
        conv_dtypes=[], dot_dtypes={"bfloat16": 2},
        nonscalar_psum_shapes=[(4, 4)], aliased_inputs=[0, 1],
        flops=1000.0, bytes_accessed=2000.0,
    )
    base.update(overrides)
    return rules.Measurement(**base)


def test_t5_missing_manifest_entry_is_a_finding():
    findings = rules.check_t5(_fake_measurement(), None, tolerance=0.25)
    assert findings and "missing from audit_manifest" in findings[0].message


def test_t5_within_tolerance_is_clean():
    m = _fake_measurement()
    entry = m.manifest_entry()
    entry["flops"] *= 1.2  # 20% < 25%
    assert rules.check_t5(m, entry, tolerance=0.25) == []


def test_t5_flags_cost_drift_beyond_tolerance():
    m = _fake_measurement()
    entry = m.manifest_entry()
    entry["bytes_accessed"] *= 2.0
    findings = rules.check_t5(m, entry, tolerance=0.25)
    assert findings and "bytes_accessed drifted" in findings[0].message


def test_t5_flags_exact_structure_drift():
    m = _fake_measurement()
    entry = m.manifest_entry()
    entry["collectives"] = {"psum": 4}
    findings = rules.check_t5(m, entry, tolerance=0.25)
    assert findings and "collectives drifted" in findings[0].message


# --------------------------------------------------------------------------
# IR plumbing
# --------------------------------------------------------------------------


def test_input_aliases_parses_tuple_and_bare_forms():
    s = ("HloModule jit_f, is_scheduled=true, input_output_alias="
         "{ {0}: (0, {}, may-alias), {12}: (7, {}, may-alias) }, entry=x")
    assert ir.input_aliases(s) == [0, 7]
    s2 = "HloModule j, input_output_alias={ {}: (3, {}, may-alias) }, e={y}"
    assert ir.input_aliases(s2) == [3]
    assert ir.input_aliases("HloModule j, no aliases here") == []


def test_iter_eqns_descends_into_scan():
    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "i") if False else c * 2, None
        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    jaxpr = jax.make_jaxpr(f)(jnp.ones((4,)))
    names = [e.primitive.name for e in ir.iter_eqns(jaxpr)]
    assert "scan" in names and "mul" in names  # mul only inside the body


# --------------------------------------------------------------------------
# the real registry, end to end
# --------------------------------------------------------------------------


def test_registry_names():
    assert audit_mod.entry_names() == [
        "fused.actor",
        "fused.actor_bf16",
        "fused.actor_int8",
        "fused.greedy_eval",
        "fused.learner",
        "fused.macro_learner",
        "fused.step",
        "parallel.train_macro_step",
        "parallel.train_step",
        "parallel.vtrace_macro_step",
        "parallel.vtrace_step",
        "pod.learner",
        "predict.server",
        "predict.server_bf16",
        "predict.server_greedy",
        "predict.server_int8",
    ]


def test_real_entry_points_pass_against_committed_manifest():
    """The acceptance check: every registered hot-path program satisfies
    T1–T4 and matches the committed audit_manifest.json (T5)."""
    measurements, findings = ba3caudit.run_audit()
    assert sorted(measurements) == audit_mod.entry_names()
    assert findings == [], [f"{f.entry} [{f.rule}] {f.message}" for f in findings]


@pytest.mark.slow
def test_cli_end_to_end_json():
    out = subprocess.run(
        [sys.executable, "-m", "tools.ba3caudit", "--json"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=560,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["findings"] == []
    assert sorted(payload["entries"]) == audit_mod.entry_names()


def test_cli_rejects_unknown_entry():
    out = subprocess.run(
        [sys.executable, "-m", "tools.ba3caudit", "--entries", "nope"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=240,
    )
    assert out.returncode == 2
    assert "unknown entry point" in out.stderr


def test_stale_manifest_entry_is_a_finding(tmp_path):
    """A manifest key with no registered entry point (rename/delete) must
    surface instead of silently pinning nothing."""
    from tools.ba3caudit import manifest as manifest_mod

    stored = dict(manifest_mod.load() or {})
    stored["fused.step_OLD_NAME"] = stored["fused.step"]
    path = str(tmp_path / "m.json")
    manifest_mod.save(stored, path)
    _, findings = ba3caudit.run_audit(
        entries=["predict.server"], manifest_path=path
    )
    assert [f.entry for f in findings] == ["fused.step_OLD_NAME"]
    assert "no registered entry point" in findings[0].message


def test_update_manifest_prunes_stale_and_records_toolchain(tmp_path):
    from tools.ba3caudit import manifest as manifest_mod

    stored = dict(manifest_mod.load() or {})
    stored["fused.step_OLD_NAME"] = stored["fused.step"]
    path = str(tmp_path / "m.json")
    manifest_mod.save(stored, path)
    _, findings = ba3caudit.run_audit(
        entries=["predict.server"], manifest_path=path, update_manifest=True
    )
    assert findings == []
    rewritten = manifest_mod.load(path)
    assert "fused.step_OLD_NAME" not in rewritten
    # pins for entries NOT re-measured in this subset run are preserved
    assert "fused.step" in rewritten and "parallel.train_step" in rewritten


def test_subset_update_preserves_old_toolchain_stamp(tmp_path):
    """A subset --update-manifest must NOT re-stamp _meta: the preserved
    entries still hold the old toolchain's numbers, and re-stamping would
    suppress the CLI's toolchain-mismatch hint."""
    from tools.ba3caudit import manifest as manifest_mod

    stored = dict(manifest_mod.load() or {})
    stored[manifest_mod.META_KEY] = {"jax": "0.0.0-test"}
    path = str(tmp_path / "m.json")
    manifest_mod.save(stored, path)
    ba3caudit.run_audit(
        entries=["predict.server"], manifest_path=path, update_manifest=True
    )
    assert manifest_mod.load(path)[manifest_mod.META_KEY] == {
        "jax": "0.0.0-test"
    }
    # a FULL update re-stamps to the running toolchain
    ba3caudit.run_audit(manifest_path=path, update_manifest=True)
    assert manifest_mod.load(path)[manifest_mod.META_KEY]["jax"] == jax.__version__


# --------------------------------------------------------------------------
# the BA3C_AUDIT=1 runtime tripwire
# --------------------------------------------------------------------------


def test_tripwire_off_by_default(monkeypatch):
    monkeypatch.delenv("BA3C_AUDIT", raising=False)
    fn = audit_mod.tripwire_jit("test.off", lambda x: x * 2)
    assert not isinstance(fn, RetraceTripwire)
    assert float(fn(jnp.float32(2.0))) == 4.0


def test_tripwire_fires_on_injected_recompile(monkeypatch):
    monkeypatch.setenv("BA3C_AUDIT", "1")
    tw = audit_mod.tripwire_jit("test.unstable", lambda x: x * 2)
    assert isinstance(tw, RetraceTripwire)
    tw(jnp.zeros((4,)))   # warmup compile; auto-arms
    tw(jnp.zeros((4,)))   # cache hit: fine
    assert tw.traces == 1
    with pytest.raises(AuditError, match="re-traced after warmup"):
        tw(jnp.zeros((8,)))  # deliberately shape-unstable


def test_tripwire_manual_arm_allows_bucketed_warmup(monkeypatch):
    monkeypatch.setenv("BA3C_AUDIT", "1")
    tw = audit_mod.tripwire_jit("test.buckets", lambda x: x + 1, auto_arm=False)
    for b in (1, 2, 4):  # the predictor's pow-2 warmup sequence
        tw(jnp.zeros((b,)))
    tw.arm()
    tw(jnp.zeros((2,)))  # warm bucket: fine
    with pytest.raises(AuditError):
        tw(jnp.zeros((8,)))  # a NEW bucket mid-serving


def test_predictor_chunks_oversized_eval_batch_after_arm(monkeypatch):
    """An Evaluator batch larger than the serving bucket must be chunked to
    warmed buckets, not compile a new one — with BA3C_AUDIT=1 armed, a new
    bucket mid-serving would raise AuditError and kill the run."""
    from distributed_ba3c_tpu.models.a3c import BA3CNet
    from distributed_ba3c_tpu.predict.server import BatchedPredictor

    monkeypatch.setenv("BA3C_AUDIT", "1")
    state_shape = (8, 8, 2)
    model = BA3CNet(num_actions=3, fc_units=8)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *state_shape), jnp.uint8)
    )["params"]
    pred = BatchedPredictor(model, params, batch_size=2)
    assert isinstance(pred._fwd, RetraceTripwire)
    pred.warmup(state_shape)
    assert pred._fwd.armed
    # 5 states > the pow-2 serving cap of 2: three chunks (2, 2, 1), zero
    # new compiles
    actions, values, greedy = pred.predict_batch(
        np.zeros((5, *state_shape), np.uint8)
    )
    assert actions.shape == values.shape == greedy.shape == (5,)


def test_predictor_publish_from_mesh_does_not_retrace(monkeypatch):
    """The learner publishes params REPLICATED over its mesh; the buckets
    were warmed with a freshly initialised, unplaced table. jit keys its
    trace on the sharding, so the predictor must commit every table to its
    own device or the first publish recompiles every bucket mid-serving."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_ba3c_tpu.models.a3c import BA3CNet
    from distributed_ba3c_tpu.parallel.mesh import make_mesh
    from distributed_ba3c_tpu.predict.server import BatchedPredictor

    monkeypatch.setenv("BA3C_AUDIT", "1")
    state_shape = (8, 8, 2)
    model = BA3CNet(num_actions=3, fc_units=8)
    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, *state_shape), jnp.uint8)
    )["params"]
    pred = BatchedPredictor(model, params, batch_size=2)
    pred.warmup(state_shape)
    pred.update_params(
        jax.device_put(params, NamedSharding(make_mesh(), P()))
    )
    actions, _, _ = pred.predict_batch(np.zeros((2, *state_shape), np.uint8))
    assert actions.shape == (2,)


def test_tripwire_fires_on_real_train_step(monkeypatch):
    """Integration: the registered sync-step site detects a batch-shape
    change after warmup (the silent-recompile regression, as a machine
    check)."""
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.models.a3c import BA3CNet
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh
    from distributed_ba3c_tpu.parallel.train_step import (
        create_train_state,
        make_train_step,
    )

    monkeypatch.setenv("BA3C_AUDIT", "1")
    cfg = BA3CConfig(num_actions=4, fc_units=16)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh()
    step = make_train_step(model, opt, cfg, mesh)
    # placed as the Trainer places it: an unplaced first state differs in
    # sharding from the state the step returns, which is a second compile
    state = jax.device_put(
        create_train_state(jax.random.PRNGKey(0), model, cfg, opt),
        step.state_sharding,
    )

    def batch(n):
        return {
            "state": np.zeros((n, *cfg.state_shape), np.uint8),
            "action": np.zeros((n,), np.int32),
            "return": np.zeros((n,), np.float32),
        }

    n = 2 * mesh.shape["data"]
    state, _ = step(state, batch(n), cfg.entropy_beta)   # warmup
    state, _ = step(state, batch(n), cfg.entropy_beta)   # steady state
    with pytest.raises(AuditError, match="parallel.train_step"):
        step(state, batch(2 * n), cfg.entropy_beta)      # injected recompile
