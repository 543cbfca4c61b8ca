"""What decides ``correct``: the reference against the program at a size a
test run holds, the two controls (the program with its rollout forward from
its int8 table; the reference in float8) failing the same comparison, and a
whole run driven on the CPU (the look for a chip skipped) with the timed
path broken underneath coming out not correct."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, run  # noqa: E402
from benchmark.reference import ba3c as reference, pong as ref_pong  # noqa: E402
from benchmark.spec import Benchmark  # noqa: E402

#: a fused cell small enough for a CPU test: 16 envs x 4 steps, full widths
TINY_ARGV = ["--batch_size", "64", "--rollout_len", "4"]
SEED = 2**31 + 77
#: limits for that size and that seed, set as the cells' are, between what
#: the program and the float8 control read here on the CPU (PR 23): loss gap
#: 0.00055 against 0.00315, first-gradient gap 0.0091 against 0.0408,
#: parameter-change gap 0.0024 against 0.0142. So few actions are drawn that
#: none comes near a tie for the float8 reference: no action differs on
#: either side. The int8 rollout draws one of its 192 differently, and the
#: env it moved is told from the one the reference reaches, exactly.
TINY_LIMITS = {"loss_gap": 0.0015, "first_grad_norm_gap": 0.02,
               "param_delta_norm_gap": 0.007, "state_mismatch_share": 0.0,
               "action_flip_share": 0.02}


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


@pytest.fixture(scope="module")
def tiny(bench):
    cell = dict(bench.cell("fused-pong-256x20"), argv=TINY_ARGV,
                follow_updates=3, limits=TINY_LIMITS, trace_seconds=1)
    return cell, bench.config(cell["config"])


def _frames(n, seed=0):
    """Real Pong frame stacks from the reference env, a few steps in."""
    env_state, stack = reference.initial_env(jax.random.PRNGKey(seed), n)
    for t in range(4):
        actions = jnp.full((n,), 2 + t % 2, jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(100 + t), n)
        env_state, frame, _, _ = jax.vmap(ref_pong.step)(env_state, actions, keys)
        stack = jnp.concatenate([stack[..., 1:], frame[..., None]], -1)
    return stack


def test_reference_env_is_the_programs_env():
    from distributed_ba3c_tpu.envs.jaxenv import pong

    keys = jax.random.split(jax.random.PRNGKey(5), 16)
    ours = jax.vmap(ref_pong.reset)(keys)
    theirs = jax.vmap(pong.reset)(keys)
    for t in range(40):
        actions = jax.random.randint(jax.random.PRNGKey(t), (16,), 0, 6)
        step_keys = jax.random.split(jax.random.PRNGKey(1000 + t), 16)
        ours, f1, r1, d1 = jax.vmap(ref_pong.step)(ours, actions, step_keys)
        theirs, f2, r2, d2 = jax.vmap(pong.step)(theirs, actions, step_keys)
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    assert np.asarray(f1).max() == 255 and np.asarray(f1).dtype == np.uint8


def test_forward_agrees_with_ba3cnet_and_float8_does_not():
    from distributed_ba3c_tpu.models.a3c import BA3CNet

    params = reference.init_params(jax.random.PRNGKey(3))
    frames = _frames(8)
    with jax.default_matmul_precision("highest"):
        ref_logits, ref_value = reference.forward(params, frames)
        low_logits, low_value = reference.forward(params, frames, "fp8")
    out = BA3CNet(num_actions=6).apply({"params": params}, frames)
    scale = float(jnp.max(jnp.abs(ref_logits)))
    sound = float(jnp.max(jnp.abs(out.logits - ref_logits))) / scale
    control = float(jnp.max(jnp.abs(low_logits - ref_logits))) / scale
    # bf16 compute against float32: under 2 % of the logits' range; the
    # float8 forward is several times further off
    assert sound < 0.02, sound
    assert control > 3 * sound, (control, sound)
    f32 = BA3CNet(num_actions=6, compute_dtype=jnp.float32).apply(
        {"params": params}, frames)
    np.testing.assert_allclose(f32.logits, ref_logits, atol=2e-4 * scale)
    np.testing.assert_allclose(f32.value, ref_value, atol=2e-4)


def test_reference_pieces_by_hand():
    rewards = jnp.array([[0.0, 1.0], [1.0, 0.0], [0.0, 0.0]])
    dones = jnp.array([[0.0, 0.0], [1.0, 0.0], [0.0, 0.0]])
    got = reference.n_step_returns(rewards, dones, jnp.array([10.0, 10.0]), 0.5)
    # env 0: the episode ends at t=1, so nothing reaches back across it
    np.testing.assert_allclose(got[:, 0], [0.5, 1.0, 5.0])
    np.testing.assert_allclose(got[:, 1], [1.0 + 0.25 * 5.0, 2.5, 5.0])
    g = {"a": {"w": jnp.array([3.0, 4.0])}}
    np.testing.assert_allclose(
        reference.clip_by_global_norm(g, 0.5)["a"]["w"], [0.3, 0.4], rtol=1e-6)
    np.testing.assert_allclose(
        reference.clip_by_global_norm(g, 50.0)["a"]["w"], [3.0, 4.0])
    p, mu, nu = reference.adam_update(
        {"a": {"w": jnp.array([1.0])}}, {"a": {"w": jnp.array([0.1])}},
        {"a": {"w": jnp.zeros(1)}}, {"a": {"w": jnp.zeros(1)}}, 1, 1e-3, 1e-3)
    np.testing.assert_allclose(mu["a"]["w"], [0.01], rtol=1e-6)
    np.testing.assert_allclose(p["a"]["w"], [1.0 - 1e-3 * 0.1 / (0.1 + 1e-3)], rtol=1e-6)


def test_worst_leaf_gap_and_limits():
    ref = {"a/w": 1.0, "b/w": 2.0, "c/alpha": 1e-9}
    gap, leaf = check.worst_leaf_gap({"a/w": 1.1, "b/w": 2.0, "c/alpha": 5e-9}, ref)
    # the all-but-zero leaf is measured against the median leaf's norm
    assert leaf == "a/w" and abs(gap - 0.1) < 1e-9
    assert check.worst_leaf_gap({"a/w": float("nan"), "b/w": 2, "c/alpha": 0}, ref)[0] == float("inf")
    with pytest.raises(ValueError):
        check.worst_leaf_gap({"a/w": 1.0}, ref)
    assert abs(check.loss_gap([1.1, -2.0], [1.0, -2.0]) - 0.1) < 1e-9
    states = [({"y": np.zeros(4)}, np.zeros((4, 2, 2), np.uint8))]
    side = {"losses": [1.0], "first_grad": ref, "delta": ref, "states": states,
            "action_margin": 0.0, "action_flips": 0.0}
    rows = check.compare(side, side, dict.fromkeys(TINY_LIMITS, 0))
    assert [r["number"] for r in rows] == list(TINY_LIMITS)
    assert all(r["ok"] and r["value"] == 0 for r in rows)


def test_envs_that_part_are_counted_once_whatever_leaf_shows_it():
    def side(y, pixel):
        frames = np.zeros((4, 2, 2), np.uint8)
        frames[3, 0, 0] = pixel
        return ({"y": np.asarray(y, np.float32), "score": np.zeros(4, np.int32)}, frames)

    same = side([0, 0, 0, 0], 0)
    share, where = check.state_mismatch([same, same], [same, same])
    assert share == 0.0 and where == {}
    # update 2: env 1 differs in y, env 3 in y and in a pixel -> 2 of 8 envs
    share, where = check.state_mismatch(
        [same, side([0, 1e-7, 0, 1], 255)], [same, same])
    assert share == 2 / 8 and where == {"y": 2, "frames": 1}
    # a leaf the program does not give, or of another shape, counts every env
    share, where = check.state_mismatch([({"y": same[0]["y"]}, same[1])], [same])
    assert share == 1.0 and where == {"score": 4}
    with pytest.raises(ValueError):
        check.state_mismatch([same], [same, same])


def _measure(bench, tiny, seed):
    cell, config = tiny
    return run.measure(bench, cell, config, jax.devices()[:1],
                       {"platform": "cpu", "kind": "cpu", "count": 1},
                       seed, 1.0, False)


@pytest.mark.timeout(600)
def test_a_run_on_the_cpu_is_correct_and_the_control_is_not(bench, tiny, capsys):
    """The look for a chip skipped, the rest of a run as the chip runs it."""
    result = _measure(bench, tiny, SEED)
    out = capsys.readouterr().out
    assert result["correct"] is True, out
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"env_steps_per_s_per_chip", "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    for number in TINY_LIMITS:  # each number printed beside its limit
        assert f"compare {number}:" in out and "limit" in out
    cell, config = tiny
    driver = bench.driver(config["driver"])

    def failed(side, session):
        rows = check.compare(
            side, session.reference_readings(actions=side["actions"]), TINY_LIMITS)
        return [r["number"] for r in rows if not r["ok"]], rows

    # one control: the program itself, its rollout forward from the int8
    # table, while the reference is still told the unchanged rollout's actions
    session = driver.setup(cell, config, jax.devices()[:1], SEED, control=True)
    session.release()
    numbers, rows = failed(session.program, session)
    assert "state_mismatch_share" in numbers, rows
    # the other: the reference in float8 in the program's place
    numbers, rows = failed(session.reference_readings(lower="fp8"), session)
    assert "first_grad_norm_gap" in numbers and "loss_gap" in numbers, rows
    assert "state_mismatch_share" not in numbers, rows


@pytest.mark.timeout(600)
def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        bench, tiny, monkeypatch, capsys):
    cell, config = tiny
    driver = bench.driver(config["driver"])

    class Broken(driver.Session):
        """The timed step keeps its metrics but hands its state back."""

        def __init__(self, *a, **kw):
            self._wrapped = False
            super().__init__(*a, **kw)

        def __setattr__(self, name, value):
            if name == "step" and not getattr(self, "_wrapped", True):
                real = value

                def step(state, beta, lr):
                    copy = jax.tree_util.tree_map(jnp.copy, state)
                    _, metrics = real(state, beta, lr)
                    return copy, metrics

                for attr in ("put", "steps_per_dispatch"):
                    setattr(step, attr, getattr(real, attr))
                value = step
                object.__setattr__(self, "_wrapped", True)
            object.__setattr__(self, name, value)

    monkeypatch.setattr(driver, "Session", Broken)
    monkeypatch.setattr(bench, "driver", lambda name: driver)
    result = _measure(bench, tiny, SEED)
    out = capsys.readouterr().out
    assert result["correct"] is False
    assert "param_delta_norm_gap: 1 " in out and "FAIL" in out


def test_gate_refuses_anything_but_a_tpu_in_the_peaks_table(bench):
    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    with pytest.raises(run.NoChip):
        run.gate(jax.devices(), 1, bench)  # the CPU
    with pytest.raises(run.NoChip):
        run.gate([Dev("tpu", "TPU v5 lite")], 4, bench)  # too few chips
    with pytest.raises(run.NoChip):
        run.gate([Dev("tpu", "TPU v9 imaginary")], 1, bench)  # no peaks row
    assert run.gate([Dev("tpu", "TPU v5 lite")] * 4, 4, bench) == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}


def test_the_command_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload",
         "fused-pong-256x20", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240,
    )
    assert done.returncode != 0
    assert "no result" in done.stderr
    for line in done.stdout.splitlines():
        assert not line.startswith("{"), line


def test_seed_wider_than_31_bits_gives_its_own_inputs(bench, tiny):
    cell, config = tiny
    driver = bench.driver(config["driver"])
    a = driver.seed_keys(driver.split_seed(2**31 + 5), 2)
    b = driver.seed_keys(driver.split_seed(5), 2)
    again = driver.seed_keys(driver.split_seed(2**31 + 5), 2)
    assert not np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(again)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a[2].shape[0] == 2


def test_reference_plays_the_actions_it_is_given():
    """Given its own draws back, the reference repeats itself exactly with a
    margin of 0; given other actions, it says how far below its best each
    lay."""
    params = reference.init_params(jax.random.PRNGKey(1))
    hyper = {"rollout_len": 3, "gamma": 0.99, "entropy_beta": 0.01,
             "value_loss_coef": 0.5, "grad_clip_norm": 0.5,
             "learning_rate": 1e-3, "adam_epsilon": 1e-3}
    keys = jax.random.split(jax.random.PRNGKey(2), 2)
    own = reference.follow_updates(params, jax.random.PRNGKey(3), keys, 8, hyper, 2)
    assert own["action_margin"] == 0.0 and own["action_flips"] == 0.0
    assert len(own["actions"]) == 2 and own["actions"][0].shape == (2, 3, 4)
    again = reference.follow_updates(
        params, jax.random.PRNGKey(3), keys, 8, hyper, 2, actions=own["actions"])
    assert again["losses"] == own["losses"] and again["action_margin"] == 0.0
    assert check.state_mismatch(again["states"], own["states"]) == (0.0, {})
    other = [(a + 1) % 6 for a in own["actions"]]
    forced = reference.follow_updates(
        params, jax.random.PRNGKey(3), keys, 8, hyper, 2, actions=other)
    assert forced["action_flips"] == 1.0 and forced["action_margin"] > 0
    np.testing.assert_array_equal(forced["actions"][1], other[1])
    assert check.state_mismatch(forced["states"], own["states"])[0] > 0.5
