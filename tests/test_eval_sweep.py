"""The north-star verification tool, tested before it is trusted (VERDICT
r4 weak #2): `scripts/eval_sweep.py` + `train/eval_tools.py` are what the
headline "independently verified >= threshold at step N" claim rests on.

Coverage:
- make_checkpoint_evaluator's n_eval rounding (load-bearing: envs shard
  over the mesh data axis; a non-multiple silently drops envs and makes
  completion gates unsatisfiable);
- a REAL sweep over a real tiny fused run's kept checkpoints, where no
  episode can finish inside the horizon — the 0.95-completion gate must
  refuse to certify a crossing (incomplete evals cannot make claims);
- earliest-crossing selection + JSON contract over real checkpoint
  enumeration with a scripted evaluator (step-indexed means);
- --steps subset narrowing.
"""

import importlib.util
import json
import os

import pytest

_SWEEP_PATH = os.path.join(
    os.path.dirname(os.path.dirname(__file__)), "scripts", "eval_sweep.py"
)


def _load_sweep_module():
    spec = importlib.util.spec_from_file_location("eval_sweep", _SWEEP_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_run(tmp_path_factory):
    """A real fused run with 3 kept checkpoints (steps 2, 4, 6)."""
    from distributed_ba3c_tpu.cli import main

    logdir = str(tmp_path_factory.mktemp("sweep") / "run")
    rc = main([
        "--trainer", "tpu_fused_ba3c",
        "--env", "jax:pong",
        "--batch_size", "8",
        "--rollout_len", "2",
        "--fc_units", "16",
        "--steps_per_epoch", "2",
        "--max_epoch", "3",
        "--nr_eval", "1",
        "--eval_max_steps", "8",
        "--max_to_keep", "64",
        "--logdir", logdir,
    ])
    assert not rc
    return logdir


def test_n_eval_rounds_up_to_data_axis_multiple(tmp_path):
    from distributed_ba3c_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from distributed_ba3c_tpu.train.eval_tools import make_checkpoint_evaluator

    n_data = make_mesh().shape[DATA_AXIS]
    assert n_data == 8  # the conftest's forced 8-device CPU mesh
    for requested, expected in [
        (1, 8), (7, 8), (8, 8), (9, 16), (128, 128), (0, 8),
    ]:
        _, _, _, n_eval = make_checkpoint_evaluator(
            "jax:pong", str(tmp_path / "ckpts"), requested, 16, fc_units=16
        )
        assert n_eval == expected, (requested, n_eval)
        assert n_eval % n_data == 0


def _run_sweep(monkeypatch, tmp_path, argv_tail, mod=None):
    """Drive the sweep script's main() with argv; return the written JSON.

    Pass ``mod`` to run a module whose evaluator factory was patched
    beforehand (the scripted-evaluator tests)."""
    mod = mod or _load_sweep_module()
    out = str(tmp_path / "sweep.json")
    monkeypatch.setattr(
        "sys.argv", ["eval_sweep.py", "--out", out] + argv_tail
    )
    mod.rc = mod.main()
    return json.load(open(out)), mod


def test_incomplete_evals_cannot_certify_crossing(
    monkeypatch, tiny_run, tmp_path
):
    """Real checkpoints, real restores, real on-device eval — but no Pong
    episode can finish in an 8-step horizon, so n==0 for every step and
    the completion gate must report earliest_at_threshold=None even with a
    trivially low threshold."""
    summary, _ = _run_sweep(monkeypatch, tmp_path, [
        "--env", "jax:pong",
        "--load", os.path.join(tiny_run, "checkpoints"),
        "--nr_eval", "8", "--max_steps", "8",
        "--threshold", "-1000", "--fc_units", "16",
    ])
    assert [r["step"] for r in summary["results"]] == [2, 4, 6]
    for r in summary["results"]:
        assert r["episodes"] == 0
        assert r["eval_mean"] is None
    assert summary["earliest_at_threshold"] is None


def _scripted_evaluator(mod, means_by_step):
    """Patch the sweep's evaluator factory: real CheckpointManager + real
    restore target, scripted eval results keyed by the restored step."""
    import distributed_ba3c_tpu.train.eval_tools as et

    real = et.make_checkpoint_evaluator

    def fake(env_spec, load, nr_eval, max_steps, fc_units=512):
        mgr, target, _evaluate, n_eval = real(
            env_spec, load, nr_eval, max_steps, fc_units
        )
        calls = {"step": None}

        real_restore = mgr.restore

        def restore(t, step=None):
            state = real_restore(t, step)
            calls["step"] = int(state.step)
            return state

        mgr.restore = restore

        def evaluate(_params, _seed):
            mean = means_by_step[calls["step"]]
            return mean, mean + 1.0, n_eval  # full completion

        return mgr, target, evaluate, n_eval

    mod.make_checkpoint_evaluator = fake


def test_earliest_crossing_selected(monkeypatch, tiny_run, tmp_path):
    mod = _load_sweep_module()
    _scripted_evaluator(mod, {2: 10.0, 4: 19.0, 6: 20.0})
    summary, _ = _run_sweep(monkeypatch, tmp_path, [
        "--env", "jax:pong",
        "--load", os.path.join(tiny_run, "checkpoints"),
        "--nr_eval", "8", "--max_steps", "8",
        "--threshold", "18", "--fc_units", "16",
    ], mod=mod)
    assert mod.rc == 0
    # earliest step clearing 18 is 4 — NOT the higher-scoring 6
    assert summary["earliest_at_threshold"]["step"] == 4
    assert summary["earliest_at_threshold"]["eval_mean"] == 19.0
    assert [r["step"] for r in summary["results"]] == [2, 4, 6]
    assert summary["threshold"] == 18


def test_steps_subset_narrows_sweep(monkeypatch, tiny_run, tmp_path):
    mod = _load_sweep_module()
    _scripted_evaluator(mod, {2: 10.0, 4: 19.0, 6: 20.0})
    summary, _ = _run_sweep(monkeypatch, tmp_path, [
        "--env", "jax:pong",
        "--load", os.path.join(tiny_run, "checkpoints"),
        "--steps", "6",
        "--nr_eval", "8", "--max_steps", "8",
        "--threshold", "18", "--fc_units", "16",
    ], mod=mod)
    assert [r["step"] for r in summary["results"]] == [6]
    assert summary["earliest_at_threshold"]["step"] == 6


def test_midsweep_failure_keeps_prior_results_and_continues(
    monkeypatch, tiny_run, tmp_path
):
    """One bad checkpoint (corrupt save, a device error) must not discard
    the evals already done — the sweep IS the verification artifact. The
    failed step gets an error record, the sweep continues, the summary
    marks itself incomplete, and the exit code is nonzero."""
    mod = _load_sweep_module()
    import distributed_ba3c_tpu.train.eval_tools as et

    real = et.make_checkpoint_evaluator

    def fake(env_spec, load, nr_eval, max_steps, fc_units=512):
        mgr, target, _e, n_eval = real(
            env_spec, load, nr_eval, max_steps, fc_units
        )
        calls = {"step": None}
        real_restore = mgr.restore

        def restore(t, step=None):
            if step == 4:
                raise RuntimeError("corrupt checkpoint")
            state = real_restore(t, step)
            calls["step"] = int(state.step)
            return state

        mgr.restore = restore
        means = {2: 10.0, 6: 20.0}
        return (
            mgr, target,
            (lambda p, s: (means[calls["step"]], 21.0, n_eval)), n_eval,
        )

    mod.make_checkpoint_evaluator = fake
    summary, mod = _run_sweep(monkeypatch, tmp_path, [
        "--env", "jax:pong",
        "--load", os.path.join(tiny_run, "checkpoints"),
        "--nr_eval", "8", "--max_steps", "8",
        "--threshold", "18", "--fc_units", "16",
    ], mod=mod)
    assert mod.rc == 1
    assert [r["step"] for r in summary["results"]] == [2, 4, 6]
    assert "corrupt checkpoint" in summary["results"][1]["error"]
    assert summary["results"][2]["eval_mean"] == 20.0  # continued past it
    assert summary["earliest_at_threshold"]["step"] == 6
    assert summary["sweep_complete"] is False


def test_partial_completion_below_gate_is_not_certified(
    monkeypatch, tiny_run, tmp_path
):
    """n under the 0.95 gate: a high mean over too few episodes must not
    certify (the round-3 lesson: long rallies leave envs unfinished —
    int(0.95*8)=7, so 7/8 still passes but 6/8 must not)."""
    mod = _load_sweep_module()
    import distributed_ba3c_tpu.train.eval_tools as et

    real = et.make_checkpoint_evaluator

    def fake(env_spec, load, nr_eval, max_steps, fc_units=512):
        mgr, target, _e, n_eval = real(
            env_spec, load, nr_eval, max_steps, fc_units
        )
        return mgr, target, (lambda p, s: (99.0, 99.0, int(0.75 * n_eval))), n_eval

    mod.make_checkpoint_evaluator = fake
    summary, _ = _run_sweep(monkeypatch, tmp_path, [
        "--env", "jax:pong",
        "--load", os.path.join(tiny_run, "checkpoints"),
        "--nr_eval", "8", "--max_steps", "8",
        "--threshold", "18", "--fc_units", "16",
    ], mod=mod)
    assert summary["earliest_at_threshold"] is None
    assert all(r["eval_mean"] == 99.0 for r in summary["results"])
