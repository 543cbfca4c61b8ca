"""The pod parameter plane (distributed_ba3c_tpu/pod/, docs/pod.md).

The contracts this suite pins (ISSUE 11 acceptance):

- wire: endpoint derivation from the fleet port map, version-stamp
  round-trips for both channels.
- params plane: publisher broadcast + late-joiner fetch with retry, the
  cache's immediate-callback contract, rejoin at the CURRENT version
  after a (simulated) host respawn.
- ingest: stamped delivery, drop-oldest under the depth bound (actor
  hosts never backpressured by a slow learner), the per-host
  ``pod.host<k>`` telemetry mirror.
- measured-lag V-trace: lag-0 through the pod path stays BIT-EXACT vs
  the fused step (the overlap parity contract, extended); lag-k updates
  equal an oracle recomputation from the recorded block alone (the
  correction reads measured behavior data, never an assumed lag); the
  recorded behavior log-probs ARE the stale policy's (recomputation from
  the old snapshot matches).
- bounded staleness: the learner gate rejects past ``max_staleness`` with
  the typed counter and KEEPS CONSUMING; the host-side
  VersionGatedPredictor sheds with the masters' uniform fallback so a
  lockstep server always gets its action reply (never wedges in recv).
- e2e (slow): a real 2-host localhost pod trains, survives a host-loss
  SIGKILL without a learner restart, and the killed host rejoins at the
  current version.
"""

import queue
import tempfile
import time

import jax
import numpy as np
import pytest

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.envs.jaxenv import pong
from distributed_ba3c_tpu.fused.loop import create_fused_state, make_fused_step
from distributed_ba3c_tpu.fused.overlap import make_overlap_step
from distributed_ba3c_tpu.models.a3c import BA3CNet
from distributed_ba3c_tpu.ops.gradproc import make_optimizer
from distributed_ba3c_tpu.parallel.mesh import make_mesh
from distributed_ba3c_tpu.pod import (
    LaggedBlockDriver,
    ParamsPublisher,
    PodIngest,
    PodLearner,
    StaleParamsCache,
    StalenessGate,
    VersionGatedPredictor,
    batch_to_block,
    make_pod_learner_step,
    pack_experience,
    pack_params,
    pod_endpoints,
    pod_role,
    unpack_experience,
    unpack_params,
)
from distributed_ba3c_tpu.pod.ingest import StampedBatch


# ---------------------------------------------------------------------------
# wire
# ---------------------------------------------------------------------------

def test_pod_endpoints_tcp_derivation():
    eps = pod_endpoints("tcp://10.0.0.1:5555", "tcp://10.0.0.1:5556")
    assert eps.params_pub == "tcp://10.0.0.1:5655"
    assert eps.params_fetch == "tcp://10.0.0.1:5656"
    assert eps.experience == "tcp://10.0.0.1:5657"


def test_pod_endpoints_ipc_suffixes():
    eps = pod_endpoints("ipc:///tmp/x/c2s", "ipc:///tmp/x/s2c")
    assert eps.params_pub.endswith("-pod-pub")
    assert eps.params_fetch.endswith("-pod-fetch")
    assert eps.experience.endswith("-pod-exp")
    assert len({eps.params_pub, eps.params_fetch, eps.experience}) == 3


def test_pod_endpoints_fleet_collision_refused():
    # 50+ fleets would stride into the pod port band — fail at derivation
    with pytest.raises(ValueError):
        pod_endpoints("tcp://h:5555", "tcp://h:5556", n_fleets=64)


def test_pod_role_formula():
    assert pod_role(0) == "pod.host0"
    assert pod_role(3) == "pod.host3"


def test_params_roundtrip_preserves_tree_and_version():
    params = {
        "conv": {"kernel": np.arange(12, dtype=np.float32).reshape(3, 4)},
        "head": {"bias": np.ones(5, np.float32)},
    }
    epoch, v, step, out = unpack_params(
        pack_params(7, params, step=42, epoch=99)
    )
    assert (epoch, v, step) == (99, 7, 42)
    np.testing.assert_array_equal(out["conv"]["kernel"], params["conv"]["kernel"])
    np.testing.assert_array_equal(out["head"]["bias"], params["head"]["bias"])
    # the unpacked arrays OWN their memory (they outlive the zmq frame)
    assert out["conv"]["kernel"].flags["OWNDATA"]


def _batch(T=3, B=4, H=8):
    return {
        "state": np.random.randint(0, 255, (T, B, H, H, 4), dtype=np.uint8),
        "action": np.random.randint(0, 4, (T, B)).astype(np.int32),
        "reward": np.random.randn(T, B).astype(np.float32),
        "done": np.zeros((T, B), np.float32),
        "behavior_log_probs": np.random.randn(T, B).astype(np.float32),
        "behavior_values": np.random.randn(T, B).astype(np.float32),
        "bootstrap_state": np.random.randint(
            0, 255, (B, H, H, 4), dtype=np.uint8
        ),
    }


def test_experience_roundtrip_stamp_and_arrays():
    batch = _batch()
    frames = pack_experience(2, 9, batch, {"env_steps_total": 11.0}, epoch=5)
    # simulate the wire: frames arrive as bytes
    host, epoch, version, scalars, out = unpack_experience(
        [bytes(f) for f in frames]
    )
    assert (host, epoch, version) == (2, 5, 9)
    assert scalars == {"env_steps_total": 11.0}
    for k, v in batch.items():
        np.testing.assert_array_equal(out[k], v)


def test_experience_missing_key_refused():
    batch = _batch()
    del batch["behavior_values"]
    with pytest.raises(ValueError):
        pack_experience(0, 0, batch)


# ---------------------------------------------------------------------------
# params plane: publisher <-> cache
# ---------------------------------------------------------------------------

@pytest.fixture
def ipc_endpoints(tmp_path):
    return pod_endpoints(f"ipc://{tmp_path}/c2s", f"ipc://{tmp_path}/s2c")


def _wait(cond, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return False


def test_cache_fetches_before_any_broadcast(ipc_endpoints):
    """The late-joiner path: a cache started while the publisher holds
    nothing retries with backoff and lands on the first publish."""
    pub = ParamsPublisher(ipc_endpoints)
    pub.start()
    cache = StaleParamsCache(
        ipc_endpoints, host=0, fetch_backoff_s=0.05, fetch_backoff_max_s=0.2
    )
    cache.start()
    try:
        assert not cache.wait_first(0.3)  # nothing published yet
        pub.publish(0, {"w": np.zeros(2, np.float32)})
        assert cache.wait_first(10)
        assert cache.version == 0
    finally:
        cache.close()
        pub.close()


def test_cache_applies_broadcasts_and_fires_callbacks(ipc_endpoints):
    pub = ParamsPublisher(ipc_endpoints)
    pub.start()
    pub.publish(0, {"w": np.zeros(2, np.float32)})
    cache = StaleParamsCache(ipc_endpoints, host=0)
    cache.start()
    try:
        assert cache.wait_first(10)
        seen = []
        cache.on_update(lambda p, v: seen.append(v))
        # registered after the first version: fires immediately with it
        assert seen == [0]
        pub.publish(1, {"w": np.ones(2, np.float32)})
        assert _wait(lambda: cache.version == 1)
        assert seen == [0, 1]
        np.testing.assert_array_equal(cache.params["w"], np.ones(2, np.float32))
        assert cache.behind() == 0
    finally:
        cache.close()
        pub.close()


def test_cache_adopts_new_epoch_despite_lower_version(ipc_endpoints):
    """A restarted learner's versions regress to 0 under a FRESH epoch:
    surviving caches must adopt the new lineage instead of silently
    dropping every 'older' broadcast forever (the wedge a version-only
    stamp cannot detect)."""
    pub1 = ParamsPublisher(ipc_endpoints, epoch=111)
    pub1.start()
    for v in range(4):
        pub1.publish(v, {"w": np.full(2, float(v), np.float32)})
    cache = StaleParamsCache(ipc_endpoints, host=0, fetch_backoff_s=0.05)
    cache.start()
    try:
        assert cache.wait_first(10)
        assert (cache.epoch, cache.version) == (111, 3)
        # the learner restarts: same endpoints, NEW epoch, version 0
        pub1.close()
        pub2 = ParamsPublisher(ipc_endpoints, epoch=222)
        pub2.start()
        try:
            # publish REPEATEDLY, like a live learner: the cache's SUB
            # needs a reconnect interval to find the rebound endpoint,
            # and PUB drops broadcasts sent before a subscriber attaches
            deadline = time.monotonic() + 10
            while cache.epoch != 222 and time.monotonic() < deadline:
                pub2.publish(0, {"w": np.full(2, 42.0, np.float32)})
                time.sleep(0.1)
            assert cache.epoch == 222
            assert cache.version == 0
            np.testing.assert_array_equal(
                cache.params["w"], np.full(2, 42.0, np.float32)
            )
        finally:
            pub2.close()
    finally:
        cache.close()


def test_cache_retry_backoff_ceiling_against_unreachable_publisher(
    ipc_endpoints,
):
    """ISSUE-13 satellite: the PR-12 retry path, partition-shaped. Against
    an endpoint where NOTHING answers, the fetch retries with backoff up
    to the ceiling and no further — bounded probing, not hammering — and
    nothing on the serving surface ever blocks."""
    cache = StaleParamsCache(
        ipc_endpoints, host=0,
        fetch_backoff_s=0.05, fetch_backoff_max_s=0.2,
    )
    cache.start()
    try:
        time.sleep(1.3)
        retries = telemetry.registry("pod.host0").scalars()[
            "params_fetch_retries_total"
        ]
        # doubling 0.05 -> cap 0.2 gives ~8 attempts in 1.3 s; a flat
        # 0.05 cadence (no backoff) would give ~26, a stuck loop 0. The
        # band proves BOTH halves: it keeps retrying AND the ceiling is
        # respected.
        assert 3 <= retries <= 14, retries
        # rollout-facing surface never blocks on the dead publisher
        t0 = time.monotonic()
        assert cache.params is None
        assert cache.behind() == 0  # nothing seen -> no measurable lag
        assert not cache.wait_first(0.05)
        assert time.monotonic() - t0 < 0.5
    finally:
        cache.close()


def test_cache_rejoins_current_epoch_when_publisher_heals(ipc_endpoints):
    """Unreachable-then-healed: the publisher that finally appears is a
    NEW lifetime (fresh epoch, versions from 0) — the rejoining cache
    must adopt it through the retrying fetch path."""
    cache = StaleParamsCache(
        ipc_endpoints, host=0,
        fetch_backoff_s=0.05, fetch_backoff_max_s=0.2,
    )
    cache.start()
    try:
        assert not cache.wait_first(0.5)  # provably unreachable first
        pub = ParamsPublisher(ipc_endpoints, epoch=333)
        pub.start()
        pub.publish(7, {"w": np.full(2, 7.0, np.float32)})
        try:
            assert cache.wait_first(10)  # the RETRY landed, no restart
            assert (cache.epoch, cache.version) == (333, 7)
            np.testing.assert_array_equal(
                cache.params["w"], np.full(2, 7.0, np.float32)
            )
        finally:
            pub.close()
    finally:
        cache.close()


def test_cache_degraded_broadcast_channel_probes_fetch(ipc_endpoints):
    """Asymmetric-partition self-heal: when the SUB channel goes silent
    past its degraded threshold, the cache re-arms the bounded-backoff
    fetch even though it HOLDS params — and catches up to versions it
    never saw broadcast."""
    pub = ParamsPublisher(ipc_endpoints)
    pub.start()
    cache = StaleParamsCache(
        ipc_endpoints, host=0,
        fetch_backoff_s=0.05, fetch_backoff_max_s=0.2,
        heartbeat_s=0.1, degraded_after_s=0.3, partitioned_after_s=2.0,
    )
    cache.start()
    try:
        pub.publish(1, {"w": np.zeros(2, np.float32)})
        assert cache.wait_first(10)
        assert _wait(lambda: cache.version == 1)
        # "lose" the broadcast: arm the fetch channel's latest WITHOUT a
        # PUB send — exactly a dead broadcast path with a live ROUTER
        pub._latest = None
        from distributed_ba3c_tpu.pod.wire import pack_params

        pub._latest = pack_params(2, {"w": np.ones(2, np.float32)}, epoch=pub.epoch)
        # past degraded_after_s the cache must probe the fetch channel and
        # adopt the version the broadcast never delivered
        assert _wait(lambda: cache.version == 2, timeout=10)
        from distributed_ba3c_tpu.pod.linkstate import UP

        assert cache.fetch_link.poll() == UP  # side-channel alive
    finally:
        cache.close()
        pub.close()


def test_learner_rejects_foreign_epoch_blocks(pod_parts, ipc_endpoints):
    """A block stamped under a publisher lifetime the learner does not
    own carries a version from the wrong lineage — typed rejection (the
    clamped lag would otherwise read 0 and admit it silently)."""
    cfg, model, opt, mesh, pstep = pod_parts
    pub = ParamsPublisher(ipc_endpoints, epoch=7)
    try:
        learner = PodLearner(
            pstep, _fresh_train(cfg, model, opt), cfg, publisher=pub,
            max_staleness=4,
        )
        foreign = StampedBatch(0, 0, _pong_batch(cfg), epoch=1234)
        assert learner.consume(foreign) is None
        assert (
            telemetry.registry("learner")
            .counter("epoch_mismatch_blocks_total").value() >= 1
        )
        ours = StampedBatch(0, 0, _pong_batch(cfg), epoch=7)
        assert learner.consume(ours) is not None
    finally:
        pub.close()


def test_respawned_cache_rejoins_at_current_version(ipc_endpoints):
    """The host-loss recovery contract: a brand-new cache (the respawned
    host) fetches the CURRENT version, not a replay from zero."""
    pub = ParamsPublisher(ipc_endpoints)
    pub.start()
    for v in range(5):
        pub.publish(v, {"w": np.full(2, float(v), np.float32)})
    rejoined = StaleParamsCache(ipc_endpoints, host=1, fetch_backoff_s=0.05)
    rejoined.start()
    try:
        assert rejoined.wait_first(10)
        assert rejoined.version == 4
        np.testing.assert_array_equal(
            rejoined.params["w"], np.full(2, 4.0, np.float32)
        )
    finally:
        rejoined.close()
        pub.close()


# ---------------------------------------------------------------------------
# ingest
# ---------------------------------------------------------------------------

class _Pusher:
    """One persistent PUSH socket: ordering across sends is guaranteed
    (separate sockets would interleave arbitrarily at the PULL side),
    and the default linger flushes every message before close."""

    def __init__(self, eps):
        import zmq

        self._ctx = zmq.Context()
        self.sock = self._ctx.socket(zmq.PUSH)
        self.sock.connect(eps.experience)

    def send(self, host, version, batch, scalars=None):
        self.sock.send_multipart(pack_experience(host, version, batch, scalars))

    def close(self):
        self.sock.close()
        self._ctx.term()


def test_ingest_stamped_delivery_and_host_mirror(ipc_endpoints):
    telemetry.reset_all()
    ing = PodIngest(ipc_endpoints, depth=4)
    ing.start()
    push = _Pusher(ipc_endpoints)
    try:
        push.send(
            3, 17, _batch(),
            {"env_steps_total": 99.0, "params_version": 17.0},
        )
        sb = ing.next_batch(timeout=10)
        assert sb is not None and (sb.host, sb.version) == (3, 17)
        assert sb.batch["state"].shape[0] == 3  # time-major [T, B]
        mirror = telemetry.registry(pod_role(3)).scalars()
        assert mirror["env_steps_total"] == 99.0
        assert mirror["params_version"] == 17.0
    finally:
        push.close()
        ing.close()


def test_ingest_drop_oldest_never_blocks_hosts(ipc_endpoints):
    telemetry.reset_all()
    ing = PodIngest(ipc_endpoints, depth=2)
    ing.start()
    push = _Pusher(ipc_endpoints)
    try:
        for v in range(5):
            push.send(0, v, _batch())
        assert _wait(
            lambda: telemetry.registry("learner")
            .counter("pod_ingest_blocks_total").value() == 5
        )
        assert _wait(lambda: ing.qsize() == 2)
        dropped = telemetry.registry("learner").counter(
            "pod_ingest_dropped_total"
        ).value()
        assert dropped == 3
        # the survivors are the NEWEST stamps
        versions = [ing.next_batch(timeout=2).version for _ in range(2)]
        assert versions == [3, 4]
    finally:
        push.close()
        ing.close()


def test_export_scalars_carries_pod_host_roles():
    telemetry.reset_all()
    telemetry.registry(pod_role(0)).gauge("params_version").set(5)
    telemetry.registry(pod_role(1)).counter("env_steps_total").inc(7)
    out = telemetry.export_scalars()
    assert out["tele/pod.host0/params_version"] == 5.0
    assert out["tele/pod.host1/env_steps_total"] == 7.0


def test_role_scalars_sums_pod_hosts():
    from distributed_ba3c_tpu.telemetry.attribution import role_scalars

    telemetry.reset_all()
    telemetry.registry(pod_role(0)).counter("env_steps_total").inc(3)
    telemetry.registry(pod_role(1)).counter("env_steps_total").inc(4)
    assert role_scalars("pod")["env_steps_total"] == 7.0


# ---------------------------------------------------------------------------
# the staleness gate
# ---------------------------------------------------------------------------

def test_gate_measures_and_bounds():
    telemetry.reset_all()
    gate = StalenessGate(max_staleness=2)
    assert gate.admit(5, 5) == 0
    assert gate.admit(3, 5) == 2
    assert gate.admit(2, 5) is None  # lag 3 > bound 2: typed rejection
    s = telemetry.registry("learner").scalars()
    assert s["stale_blocks_rejected_total"] == 1
    assert s["params_lag_count"] == 3  # rejected blocks are still measured
    assert s["pod_max_staleness"] == 2


def test_gate_unbounded_measures_only():
    telemetry.reset_all()
    gate = StalenessGate(max_staleness=None)
    assert gate.admit(0, 1000) == 1000
    assert (
        telemetry.registry("learner")
        .counter("stale_blocks_rejected_total").value() == 0
    )


def test_learner_rejection_keeps_consuming(pod_parts):
    """A burst of over-stale blocks must not wedge the consuming loop:
    rejects return None (counted) and the next fresh block still trains."""
    cfg, model, opt, mesh, pstep = pod_parts
    learner = PodLearner(pstep, _fresh_train(cfg, model, opt), cfg,
                         max_staleness=1)
    learner.version = 10
    stale = StampedBatch(0, 2, _pong_batch(cfg))  # lag 8 >> 1
    assert learner.consume(stale) is None
    assert learner.version == 10  # rejected: no update happened
    fresh = StampedBatch(0, 10, _pong_batch(cfg))
    assert learner.consume(fresh) is not None
    assert learner.version == 11


# ---------------------------------------------------------------------------
# the pod learner step: parity + oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def pod_parts():
    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=16)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon,
                         cfg.grad_clip_norm)
    mesh = make_mesh()
    pstep = make_pod_learner_step(model, opt, cfg, mesh)
    return cfg, model, opt, mesh, pstep


def _fresh_train(cfg, model, opt, seed=0):
    from distributed_ba3c_tpu.parallel.train_step import create_train_state

    return create_train_state(jax.random.PRNGKey(seed), model, cfg, opt)


def _pong_batch(cfg, T=3, B=16, seed=0):
    """A host-shaped random batch at pong's action space (collate layout)."""
    rng = np.random.default_rng(seed)
    H, W, C = cfg.state_shape
    return {
        "state": rng.integers(0, 255, (T, B, H, W, C), dtype=np.uint8),
        "action": rng.integers(0, cfg.num_actions, (T, B)).astype(np.int32),
        "reward": rng.standard_normal((T, B)).astype(np.float32),
        "done": (rng.random((T, B)) < 0.05).astype(np.float32),
        "behavior_log_probs": -np.abs(
            rng.standard_normal((T, B))
        ).astype(np.float32),
        "behavior_values": rng.standard_normal((T, B)).astype(np.float32),
        "bootstrap_state": rng.integers(
            0, 255, (B, H, W, C), dtype=np.uint8
        ),
    }


def test_batch_to_block_coerces_dtypes(pod_parts):
    cfg, _, _, _, pstep = pod_parts
    b = _pong_batch(cfg)
    b["action"] = b["action"].astype(np.int64)
    b["reward"] = b["reward"].astype(np.float64)
    block = batch_to_block(b, pstep.block_sharding)
    assert block.actions.dtype == np.int32
    assert block.rewards.dtype == np.float32
    assert block.states.dtype == np.uint8


@pytest.fixture(scope="module")
def overlap_parts(pod_parts):
    cfg, model, opt, mesh, _ = pod_parts
    n_envs = 2 * mesh.shape["data"]
    ostep = make_overlap_step(model, opt, cfg, mesh, pong, rollout_len=3,
                              lag=0)

    def fresh_state(putter):
        return putter(
            create_fused_state(
                jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs,
                n_shards=mesh.shape["data"],
            )
        )

    return ostep, fresh_state, n_envs


def test_lag0_pod_path_bitexact_with_fused(pod_parts, overlap_parts):
    """THE acceptance parity: the pod path at lag 0 with frozen params is
    bit-exact with the fused step over a K-window — same trajectories,
    frame stacks, env carries (the overlap parity contract, driven
    through LaggedBlockDriver + the pod.learner program)."""
    cfg, model, opt, mesh, pstep = pod_parts
    ostep, fresh_state, n_envs = overlap_parts
    K = 4
    fstep = make_fused_step(model, opt, cfg, mesh, pong, rollout_len=3)
    f = fresh_state(fstep.put)
    learner = PodLearner(pstep, _fresh_train(cfg, model, opt), cfg)
    learner.learning_rate = 0.0
    drv = LaggedBlockDriver(ostep, learner, lag=0)
    drv.prime(fresh_state(ostep.put))
    for _ in range(K):
        f, _ = fstep(f, cfg.entropy_beta, learning_rate=0.0)
        m = drv.iterate()
        assert m is not None
    assert learner.version == K
    np.testing.assert_array_equal(
        np.asarray(f.obs_stack), np.asarray(drv.astate.obs_stack)
    )
    for fl, ol in zip(
        jax.tree_util.tree_leaves(f.env_state),
        jax.tree_util.tree_leaves(drv.astate.env_state),
    ):
        np.testing.assert_array_equal(np.asarray(fl), np.asarray(ol))
    np.testing.assert_array_equal(
        np.asarray(f.ep_count), np.asarray(drv.astate.ep_count)
    )
    # at lag 0 the correction is the identity
    assert abs(float(m["mean_rho"]) - 1.0) < 1e-5


def test_lag0_pod_update_matches_fused_math(pod_parts, overlap_parts):
    """One LIVE update from identical state lands on the fused step's
    params up to float reassociation (the learning-math half)."""
    cfg, model, opt, mesh, pstep = pod_parts
    ostep, fresh_state, _ = overlap_parts
    fstep = make_fused_step(model, opt, cfg, mesh, pong, rollout_len=3)
    f, mf = fstep(fresh_state(fstep.put), cfg.entropy_beta)
    learner = PodLearner(pstep, _fresh_train(cfg, model, opt), cfg)
    drv = LaggedBlockDriver(ostep, learner, lag=0)
    drv.prime(fresh_state(ostep.put))
    mo = drv.iterate()
    for fl, ol in zip(
        jax.tree_util.tree_leaves(f.train.params),
        jax.tree_util.tree_leaves(learner.state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(fl), np.asarray(ol), rtol=2e-4, atol=2e-5
        )
    for k in ("loss", "policy_loss", "value_loss", "entropy"):
        assert abs(float(mf[k]) - float(mo[k])) < 5e-4, k


def test_recorded_behavior_log_probs_are_the_stale_policys(pod_parts,
                                                           overlap_parts):
    """At measured lag k the correction inputs are EXACT: the block's
    recorded behavior log-probs equal a recomputation under the stale
    snapshot that served the rollout (nothing is approximated away by
    growing lag — the property that makes the correction exact at any k).
    """
    cfg, model, opt, mesh, pstep = pod_parts
    ostep, fresh_state, _ = overlap_parts
    learner = PodLearner(pstep, _fresh_train(cfg, model, opt), cfg)
    drv = LaggedBlockDriver(ostep, learner, lag=2)
    drv.prime(fresh_state(ostep.put))
    for _ in range(4):  # fill the snapshot ring past the warmup ramp
        drv.iterate()
    stale_version, stale_params = drv._snaps[0]
    # genuinely stale: the ring's oldest snapshot trails the learner by
    # the configured lag (plus one — version advanced after its last use)
    assert learner.version - stale_version >= 2
    astate, block = drv.actor_jit(stale_params, drv.astate)
    drv.astate = astate
    T, B = block.actions.shape
    states = np.asarray(block.states).reshape(T * B, *cfg.state_shape)
    out = model.apply({"params": stale_params}, states)
    lp = jax.nn.log_softmax(out.logits, axis=-1)
    recomputed = np.take_along_axis(
        np.asarray(lp), np.asarray(block.actions).reshape(T * B, 1), axis=1
    ).reshape(T, B)
    np.testing.assert_allclose(
        recomputed, np.asarray(block.behavior_log_probs),
        rtol=1e-5, atol=1e-5,
    )


def test_lagk_update_matches_oracle_recomputation():
    """The lag-k correction equals an oracle that recomputes V-trace +
    Adam directly from the recorded block (plain jax, no shard_map): the
    pod update is a pure function of (current params, recorded data) —
    measured behavior probs, not an assumed lag."""
    from distributed_ba3c_tpu.ops.gradproc import inject_learning_rate
    from distributed_ba3c_tpu.ops.vtrace import vtrace_returns
    import jax.numpy as jnp
    import optax

    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=16)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon,
                         cfg.grad_clip_norm)
    mesh1 = make_mesh(num_data=1, devices=jax.devices()[:1])
    pstep = make_pod_learner_step(model, opt, cfg, mesh1)
    train = _fresh_train(cfg, model, opt)
    batch = _pong_batch(cfg, T=4, B=6, seed=3)  # "collected 3 versions ago"
    block = batch_to_block(batch, pstep.block_sharding)

    learner = PodLearner(pstep, train, cfg, max_staleness=8)
    learner.version = 3
    m = learner.consume(StampedBatch(0, 0, batch))
    assert m is not None and learner.gate is not None

    # oracle: the same math, written independently of the pod program
    def oracle_loss(params):
        T, B = batch["action"].shape
        flat = block.states.reshape((T * B, *cfg.state_shape))
        all_states = jnp.concatenate([flat, block.bootstrap_state], axis=0)
        out = model.apply({"params": params}, all_states)
        logits = out.logits[: T * B].reshape((T, B, -1))
        values = out.value[: T * B].reshape((T, B))
        boot = out.value[T * B:]
        lp = jax.nn.log_softmax(logits, axis=-1)
        probs = jax.nn.softmax(logits, axis=-1)
        target_lp = jnp.take_along_axis(
            lp, block.actions[..., None].astype(jnp.int32), axis=-1
        )[..., 0]
        vt = vtrace_returns(
            behaviour_log_probs=block.behavior_log_probs,
            target_log_probs=jax.lax.stop_gradient(target_lp),
            rewards=block.rewards,
            dones=block.dones,
            values=jax.lax.stop_gradient(values),
            bootstrap_value=jax.lax.stop_gradient(boot),
            gamma=cfg.gamma,
        )
        policy_loss = -jnp.mean(target_lp * vt.pg_advantages)
        value_loss = 0.5 * jnp.mean(jnp.square(values - vt.vs))
        entropy = -jnp.mean(jnp.sum(probs * lp, axis=-1))
        return (
            policy_loss + cfg.value_loss_coef * value_loss
            - cfg.entropy_beta * entropy
        )

    train0 = _fresh_train(cfg, model, opt)
    grads = jax.grad(oracle_loss)(train0.params)
    opt_state = inject_learning_rate(train0.opt_state, cfg.learning_rate)
    updates, _ = opt.update(grads, opt_state, train0.params)
    oracle_params = optax.apply_updates(train0.params, updates)
    for a, b in zip(
        jax.tree_util.tree_leaves(oracle_params),
        jax.tree_util.tree_leaves(learner.state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


def test_staleness_curve_value_lag_mae_grows_with_lag(pod_parts,
                                                      overlap_parts):
    """The curve the bench measures, in miniature: training at a larger
    measured lag yields a larger (or equal) value drift signal. Smoke of
    monotone direction, not magnitudes — CPU, tiny model, few steps."""
    cfg, model, opt, mesh, pstep = pod_parts
    ostep, fresh_state, _ = overlap_parts

    def run(lag, iters=6):
        telemetry.reset_all()
        learner = PodLearner(pstep, _fresh_train(cfg, model, opt), cfg)
        learner.learning_rate = 1e-2  # move the value net so lag shows
        drv = LaggedBlockDriver(ostep, learner, lag=lag)
        drv.prime(fresh_state(ostep.put))
        maes = []
        for _ in range(iters):
            m = drv.iterate()
            maes.append(float(m["value_lag_mae"]))
        # skip the ring-fill ramp: only full-lag iterations count
        return np.mean(maes[lag:])

    mae0, mae4 = run(0), run(4)
    assert mae4 >= mae0


# ---------------------------------------------------------------------------
# host-side shed: the uniform fallback keeps lockstep servers stepping
# ---------------------------------------------------------------------------

class _NeverServePredictor:
    """A predictor stand-in that must never be reached past the gate."""

    num_actions = 4

    def put_block_task(self, *a, **k):  # pragma: no cover
        raise AssertionError("gate must shed before the predictor")

    def put_task(self, *a, **k):  # pragma: no cover
        raise AssertionError("gate must shed before the predictor")


def test_version_gate_sheds_with_typed_reject():
    telemetry.reset_all()
    gated = VersionGatedPredictor(
        _NeverServePredictor(), behind_fn=lambda: 5, max_staleness=2,
        tele_role=pod_role(0),
    )
    rejects = []
    ok = gated.put_block_task(
        np.zeros((4, 8, 8, 4), np.uint8), lambda *a: None,
        shed_callback=rejects.append,
    )
    assert ok is False and len(rejects) == 1
    assert rejects[0].reason == "stale_params"
    assert (
        telemetry.registry(pod_role(0))
        .counter("stale_params_sheds_total").value() == 4
    )


def test_stale_shed_answers_with_uniform_fallback(tmp_path):
    """Compose the host gate with a real master's shed fallback: the
    lockstep server's action reply is produced IMMEDIATELY (uniform
    policy, exact log-prob) — the server steps on instead of parking in
    recv, and V-trace stays exact on the fallback experience."""
    from distributed_ba3c_tpu.pod.host import PodSimulatorMaster

    telemetry.reset_all()
    gated = VersionGatedPredictor(
        _NeverServePredictor(), behind_fn=lambda: 9, max_staleness=3,
        tele_role=pod_role(0),
    )
    master = PodSimulatorMaster(
        f"ipc://{tmp_path}/c2s", f"ipc://{tmp_path}/s2c", gated,
        unroll_len=3,
    )
    try:
        replies = []

        def cb(actions, values, logps):
            replies.append((actions, values, logps))

        k = 6
        ok = gated.put_block_task(
            np.zeros((k, 8, 8, 4), np.uint8), cb,
            shed_callback=master._shed_fallback_block(cb, k),
        )
        assert ok is False
        assert len(replies) == 1  # the reply exists: no wedge possible
        actions, values, logps = replies[0]
        assert actions.shape == (k,) and actions.dtype == np.int32
        assert np.all((actions >= 0) & (actions < 4))
        # the recorded behavior log-prob IS the fallback policy's
        np.testing.assert_allclose(logps, np.full(k, -np.log(4)), rtol=1e-6)
        np.testing.assert_array_equal(values, np.zeros(k, np.float32))
    finally:
        master.close()


def test_pod_master_segments_carry_behavior_values(tmp_path):
    """PodSimulatorMaster's per-env path emits behavior_values, and
    collate_rollout stacks them into the [T, B] layout the wire ships."""
    from distributed_ba3c_tpu.data.dataflow import collate_rollout
    from distributed_ba3c_tpu.pod.host import PodSimulatorMaster

    class _InstantPredictor:
        num_actions = 4

        def put_task(self, state, cb, *, shed_callback=None):
            cb(1, 0.5, -1.25)
            return True

    master = PodSimulatorMaster(
        f"ipc://{tmp_path}/c2s", f"ipc://{tmp_path}/s2c",
        _InstantPredictor(), unroll_len=2,
    )
    try:
        ident = b"simulator-0"
        state = np.zeros((8, 8, 4), np.uint8)
        for _ in range(4):  # 3 completed transitions -> one T=2 segment
            master._on_message(ident, state, reward=1.0, is_over=False)
        seg = master.queue.get_nowait()
        assert seg["behavior_values"].shape == (2,)
        np.testing.assert_allclose(seg["behavior_values"], [0.5, 0.5])
        np.testing.assert_allclose(seg["behavior_log_probs"], [-1.25, -1.25])
        batch = collate_rollout([seg, seg])
        assert batch["behavior_values"].shape == (2, 2)  # [T, B]
    finally:
        master.close()


def test_vtrace_master_segments_unchanged(tmp_path):
    """The V-trace plane's segments must NOT grow the key (its learner
    feed has no spec for it) — only the pod master records values."""
    from distributed_ba3c_tpu.actors.vtrace_master import VTraceSimulatorMaster

    class _InstantPredictor:
        num_actions = 4

        def put_task(self, state, cb, *, shed_callback=None):
            cb(1, 0.5, -1.25)
            return True

    master = VTraceSimulatorMaster(
        f"ipc://{tmp_path}/c2s", f"ipc://{tmp_path}/s2c",
        _InstantPredictor(), unroll_len=2,
    )
    try:
        ident = b"simulator-0"
        state = np.zeros((8, 8, 4), np.uint8)
        for _ in range(4):
            master._on_message(ident, state, reward=1.0, is_over=False)
        seg = master.queue.get_nowait()
        assert "behavior_values" not in seg
    finally:
        master.close()


# ---------------------------------------------------------------------------
# e2e: a real 2-host localhost pod (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.timeout(600)
def test_pod_e2e_two_hosts_train_and_survive_host_loss(tmp_path):
    """The whole pod on localhost ipc-derived tcp: two supervised actor
    hosts feed one bounded-staleness learner; a SIGKILLed host's blocks
    keep flowing from the survivor (no learner restart), the supervisor
    respawns it, and its cache rejoins at the current version."""
    import socket

    from distributed_ba3c_tpu.orchestrate.pod import (
        PodLearnerPlane,
        PodSupervisor,
        host_argv,
    )

    telemetry.reset_all()
    # pick a free tcp port band (the pod channels derive +100..+102)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    base = s.getsockname()[1]
    s.close()
    c2s = f"tcp://127.0.0.1:{base}"
    s2c = f"tcp://127.0.0.1:{base + 1}"

    cfg = BA3CConfig(
        image_size=(16, 16), frame_history=4, num_actions=4, fc_units=16,
        local_time_max=3, predict_batch_size=16,
    )
    plane = PodLearnerPlane(cfg, c2s, s2c, max_staleness=64)
    plane.start()
    sup = PodSupervisor(
        2,
        lambda i: host_argv(
            i, c2s, s2c, env="fake", n_sims=2, unroll_len=3,
            segments_per_block=8, image_size=16, frame_history=4,
            num_actions=4, fc_units=16,
        ),
        backoff_base_s=0.2,
    )
    sup.start()
    try:
        def train_until(n, timeout):
            deadline = time.monotonic() + timeout
            while plane.learner.version < n and time.monotonic() < deadline:
                plane.step_once(timeout=1.0)
            return plane.learner.version >= n

        assert train_until(5, 240), "pod never produced 5 updates"
        # both hosts reported in (registry ROLES persist process-wide
        # across reset_all, so read live mirrored series, not role names)
        hosts_seen = {
            r for r, reg in telemetry.all_registries().items()
            if r.startswith("pod.host") and reg.scalars()
        }
        assert hosts_seen == {"pod.host0", "pod.host1"}

        # host-loss chaos: SIGKILL host 0's whole process group
        v_kill = plane.learner.version
        assert sup.sigkill_slot(0)
        # the learner keeps training on the survivor — no restart of
        # anything learner-side
        assert train_until(v_kill + 3, 240), "learner stalled after host loss"
        # the supervisor respawns the host and its cache rejoins at the
        # CURRENT version (not zero): its mirrored params_version catches
        # back up to the learner's publish frontier
        def rejoined():
            g = telemetry.registry("pod.host0").scalars()
            return g.get("params_version", -1) >= v_kill
        deadline = time.monotonic() + 240
        ok = False
        while time.monotonic() < deadline:
            plane.step_once(timeout=0.5)
            if rejoined():
                ok = True
                break
        assert ok, "killed host never rejoined at the current version"
        assert (
            telemetry.registry("orchestrator")
            .counter("server_respawns_total").value() >= 1
        )
    finally:
        sup.stop()
        sup.join(timeout=5)
        sup.close()
        plane.close()
