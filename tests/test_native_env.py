"""C++ env core: binding, batched stepping, semantic parity with jaxenv."""

import numpy as np
import pytest

from distributed_ba3c_tpu.envs import native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="cpp/libba3c_env.so not built (make -C cpp)"
)


def test_stale_library_is_rebuilt_and_a_failed_build_says_why(monkeypatch):
    """The .so is git-ignored, so whatever copy sits in the tree may predate
    env_core.cc: the loader runs make, which rebuilds a library older than
    its sources — and a build that fails raises with the compiler's words
    instead of loading the stale copy."""
    import os

    os.utime(native._LIB_PATH, (0, 0))
    monkeypatch.setenv("CXX", "false")  # the Makefile's `CXX ?= g++`
    with pytest.raises(ImportError, match=r"make -C .*failed \(rc"):
        native._build()
    monkeypatch.delenv("CXX")
    native._build()
    assert os.path.getmtime(native._LIB_PATH) > 0
    assert native.CppBatchedEnv("pong", 1).num_actions == 6


def test_create_and_metadata():
    env = native.CppBatchedEnv("pong", 4, seed=1)
    assert env.num_actions == 6 and env.n == 4
    assert env.h == 84 and env.w == 84
    b = native.CppBatchedEnv("breakout", 2)
    assert b.num_actions == 4
    s = native.CppBatchedEnv("seaquest", 2)
    assert s.num_actions == 6
    q = native.CppBatchedEnv("qbert", 2)
    assert q.num_actions == 5
    with pytest.raises(ValueError):
        native.CppBatchedEnv("doom", 1)


def test_action_space_parity_with_jaxenv():
    """Full-gameset parity: the C++ core and the on-device JAX envs must
    agree on the action maps so policies transfer between planes."""
    jaxenv = pytest.importorskip("distributed_ba3c_tpu.envs.jaxenv")
    for name in (
        "pong", "breakout", "seaquest", "qbert",
        "space_invaders", "boxing", "assault",
    ):
        assert (
            native.CppBatchedEnv(name, 1).num_actions
            == jaxenv.get_env(name).num_actions
        ), name


def test_gameset_cpp_semantics():
    """Space Invaders / Boxing / Assault C++ mirrors: reward structure
    invariants matching their jaxenv counterparts."""
    rng = np.random.default_rng(0)
    # space invaders: fire-heavy play scores in row-point quanta (5..30)
    env = native.CppBatchedEnv("space_invaders", 4, seed=7)
    env.reset()
    total = 0.0
    for _ in range(300):
        a = rng.choice([1, 1, 2, 3, 4, 5], size=4).astype(np.int32)
        _, rew, _ = env.step(a)
        total += float(rew.sum())
    assert total > 0.0 and total % 5.0 == 0.0

    # assault: 21-point quanta
    env = native.CppBatchedEnv("assault", 4, seed=8)
    env.reset()
    total = 0.0
    for _ in range(400):
        a = rng.choice([1, 1, 3, 4, 5, 6, 2], size=4).astype(np.int32)
        _, rew, _ = env.step(a)
        total += float(rew.sum())
    assert total > 0.0 and total % 21.0 == 0.0

    # boxing: rewards are per-punch units in [-4, 4] per agent step, and the
    # tuned opponent keeps aggressive random play near break-even
    env = native.CppBatchedEnv("boxing", 4, seed=9)
    env.reset()
    total = 0.0
    for _ in range(500):
        a = rng.integers(0, 18, size=4).astype(np.int32)
        _, rew, _ = env.step(a)
        assert (np.abs(rew) <= 4.0).all()
        total += float(rew.sum())
    assert abs(total) / (4 * 500) < 0.5  # near break-even per step


def test_seaquest_oxygen_and_lives():
    """No-op agent never surfaces or shoots: oxygen runs out every 50 agent
    steps (200 substeps / frameskip 4), 3 lives -> episode ends, zero reward
    (mirrors jaxenv/seaquest.py oxygen/lives semantics)."""
    env = native.CppBatchedEnv("seaquest", 1, seed=11)
    obs = env.reset()
    assert obs.max() == 255  # submarine drawn
    total, done_at = 0.0, None
    for t in range(400):
        _, rew, done = env.step(np.zeros(1, np.int32))
        total += float(rew[0])
        if done[0]:
            done_at = t + 1
            break
    # 3 suffocations x ~50 steps each (collisions can only end it sooner)
    assert done_at is not None and done_at <= 160
    assert total == 0.0


def test_seaquest_torpedo_scores():
    """Fire torpedoes while sitting on a lane: fish kills must score +20
    multiples; surfacing by holding 'up' must outlive the no-op baseline."""
    env = native.CppBatchedEnv("seaquest", 1, seed=5)
    env.reset()
    total = 0.0
    for t in range(300):
        act = 1 if t % 3 == 0 else (2 if t % 50 > 44 else 0)  # fire + surface
        _, rew, done = env.step(np.array([act], np.int32))
        assert float(rew[0]) % 20.0 == 0.0
        total += float(rew[0])
        if done[0]:
            break
    assert total >= 20.0, "firing torpedoes into lanes never hit a fish"


def test_qbert_diagonal_descent_scores_then_falls():
    """Deterministic parity walk (mirrors jaxenv/qbert.py): hopping
    down-right flips (1,1)..(5,5) for 5x25 points, the 6th hop leaves the
    pyramid and costs a life; 3 lives of the same path end the episode with
    no new flips after the first pass."""
    env = native.CppBatchedEnv("qbert", 1, seed=3)
    env.reset()
    total, steps, done_seen = 0.0, 0, False
    for t in range(40):
        _, rew, done = env.step(np.array([2], np.int32))  # down-right
        total += float(rew[0])
        steps += 1
        if done[0]:
            done_seen = True
            break
    assert done_seen and steps == 18  # 3 lives x 6 hops
    assert total == pytest.approx(125.0)  # 5 new cubes x 25, once


def test_qbert_render_shows_pyramid():
    env = native.CppBatchedEnv("qbert", 1, seed=0)
    obs = env.reset()
    frame = obs[0]
    # unflipped cubes (100), agent (255) present; no flipped cubes yet
    assert (frame == 100).sum() > 200
    assert (frame == 255).sum() > 0
    assert (frame == 200).sum() == 0
    env.step(np.array([2], np.int32))  # flip (1,1)
    frame = env._obs[0]
    assert (frame == 200).sum() > 0


def test_reset_renders_scene():
    env = native.CppBatchedEnv("pong", 2)
    obs = env.reset()
    assert obs.shape == (2, 84, 84) and obs.dtype == np.uint8
    assert obs.max() == 255  # ball/paddles
    # paddles at fixed columns: agent at x=0.95 -> col ~79, opp at ~4
    assert obs[0][:, 78:82].max() == 255
    assert obs[0][:, 2:6].max() == 255


def test_batched_step_shapes_and_bounds():
    env = native.CppBatchedEnv("pong", 8, seed=3)
    env.reset()
    rng = np.random.default_rng(0)
    total_done = 0
    for _ in range(200):
        acts = rng.integers(0, env.num_actions, 8).astype(np.int32)
        obs, rew, done = env.step(acts)
        assert obs.shape == (8, 84, 84)
        assert np.isin(rew, [-1.0, 0.0, 1.0]).all() or np.abs(rew).max() <= 2
        total_done += int(done.sum())
    assert total_done >= 0  # matches are long; dones rare in 200 steps


def test_pong_still_agent_loses_match():
    """Semantic parity with jaxenv pong: a still agent loses to the tracking
    opponent and the match terminates at 21."""
    env = native.CppBatchedEnv("pong", 1, seed=7)
    env.reset()
    total, done_seen = 0.0, False
    for i in range(6000):
        _, rew, done = env.step(np.zeros(1, np.int32))
        total += float(rew[0])
        if done[0]:
            done_seen = True
            break
    assert done_seen and total <= -1


def test_breakout_semantics():
    """Fire + track the rendered ball with the paddle: bricks MUST break."""
    env = native.CppBatchedEnv("breakout", 1, seed=2)
    obs = env.reset()
    total = 0.0
    for i in range(1500):
        frame = obs[0]
        # ball = 255 pixels in the free-play band (below bricks ~row 45,
        # above the paddle ~row 77); paddle = 255 pixels near row 77
        ball_px = np.argwhere(frame[4:70] == 255)
        paddle_px = np.argwhere(frame[75:80] == 255)
        if len(ball_px) and len(paddle_px):
            ball_col = ball_px[:, 1].mean()
            paddle_col = paddle_px[:, 1].mean()
            act = 2 if ball_col > paddle_col + 1 else 3 if ball_col < paddle_col - 1 else 0
        else:
            act = 1  # serve
        obs, rew, done = env.step(np.array([act], np.int32))
        total += float(rew[0])
        if done[0]:
            break
    assert total > 0.0, "tracking paddle never broke a brick"


def test_cpp_player_protocol():
    p = native.build_cpp_player(0, "pong", frame_history=4)
    s = p.current_state()
    assert s.shape == (84, 84, 4) and s.dtype == np.uint8
    r, over = p.action(2)
    assert isinstance(r, float) and isinstance(over, bool)
    assert p.get_action_space_size() == 6


@pytest.mark.timeout(600)
def test_cpp_env_server_speaks_wire_protocol(tmp_path):
    """The server process is indistinguishable from B SimulatorProcesses.

    Generous timeouts: under a fully loaded suite the spawned server can
    take minutes to start (process spawn + import contention)."""
    import zmq

    from distributed_ba3c_tpu.utils.serialize import dumps, loads

    import time

    c2s = f"ipc://{tmp_path}/c2s"
    s2c = f"ipc://{tmp_path}/s2c"
    ctx = zmq.Context()
    pull = ctx.socket(zmq.PULL)
    pull.setsockopt(zmq.RCVTIMEO, 10_000)
    pull.bind(c2s)
    router = ctx.socket(zmq.ROUTER)
    router.bind(s2c)

    # this test pins the PER-ENV reference protocol (SimulatorProcess
    # compatibility); the block wires have their own live e2e coverage in
    # test_block_wire.py
    proc = native.CppEnvServerProcess(
        0, c2s, s2c, game="pong", n_envs=3, wire="per-env"
    )
    proc.start()

    def recv_with_liveness(deadline):
        """Poll-recv so a dead/stuck server fails with a DIAGNOSIS, not a
        bare timeout (this test has flaked under full-suite load)."""
        while True:
            try:
                return loads(pull.recv())
            except zmq.Again:
                assert proc.is_alive(), (
                    f"env server died, exitcode={proc.exitcode}"
                )
                assert time.time() < deadline, (
                    "env server alive but silent past the deadline"
                )

    try:
        deadline = time.time() + 550  # startup under load can take minutes
        seen = {}
        for round_ in range(3):
            for _ in range(3):
                ident, state, reward, is_over = recv_with_liveness(deadline)
                assert state.shape == (84, 84, 4) and state.dtype == np.uint8
                seen[ident] = seen.get(ident, 0) + 1
                router.send_multipart([ident, dumps(0)])
        assert len(seen) == 3  # three distinct env idents
        assert all(v == 3 for v in seen.values())
    finally:
        proc.terminate()
        proc.join(timeout=5)
        ctx.destroy(0)
