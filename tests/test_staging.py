"""Device-ingest staging (data/staging.py) + the quantized rollout forward.

The contracts this suite pins (ISSUE 14 acceptance):

- **in-place collate parity**: ``collate_train_into``/``collate_rollout_into``
  produce byte-exact the same batches as the legacy collates — including
  lazy ``SegStates`` columns over block-shm ring windows with young envs
  (the zeroed-history path).
- **slot-reuse safety under backpressure**: a ring whose slots are all
  queued/unfenced blocks the producer (bounded, stop-responsive) — the
  staging mirror of the shm-ring cap contract: backpressure, never
  overwrite.
- **read-after-donate regression**: a slot is not writable until every
  device array produced from it reports ready; bytes staged and
  dispatched must survive the slot's reuse byte-for-byte.
- **copy budget**: the staged path's ``ingest_copies_total /
  ingest_blocks_total`` is EXACTLY 1; the legacy collates self-report
  more (the before/after ``plane_bench --ingest`` gates on this).
- **bf16 rollout forward**: parity band vs f32 on real jax-Pong
  observations (policy log-probs + values), the predictor's bf16 serving
  table, and lag-0 overlap learning staying healthy at bf16 rollout.
"""

import queue
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu import telemetry
from distributed_ba3c_tpu.actors.simulator import BlockStatesView, SegStates
from distributed_ba3c_tpu.data import staging
from distributed_ba3c_tpu.data.dataflow import (
    FleetMergeFeed,
    RolloutFeed,
    TrainFeed,
    collate_rollout,
    collate_train,
)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset_all()
    telemetry.set_enabled(True)
    yield
    telemetry.reset_all()


def _train_holder(rng, n=4, shape=(8, 8, 4)):
    return [
        [
            rng.integers(0, 255, shape).astype(np.uint8),
            int(rng.integers(0, 6)),
            np.float32(rng.normal()),
        ]
        for _ in range(n)
    ]


def _rollout_holder(rng, n=3, t=4, shape=(8, 8, 4), values=False):
    holder = []
    for _ in range(n):
        seg = {
            "state": rng.integers(0, 255, (t, *shape)).astype(np.uint8),
            "action": rng.integers(0, 6, t).astype(np.int32),
            "reward": rng.normal(size=t).astype(np.float32),
            "done": (rng.random(t) < 0.1).astype(np.float32),
            "behavior_log_probs": rng.normal(size=t).astype(np.float32),
            "bootstrap_state": rng.integers(0, 255, shape).astype(np.uint8),
        }
        if values:
            seg["behavior_values"] = rng.normal(size=t).astype(np.float32)
        holder.append(seg)
    return holder


def _ring_windows(rng, t=4, b=3, h=8, w=8, hist=4):
    """T consecutive BlockStatesViews over a fake ring, with env 0 young
    at every step (the zeroed-history path) and the rest mature."""
    views = []
    for step in range(t):
        window = rng.integers(0, 255, (hist, b, h, w)).astype(np.uint8)
        ages = np.array([step] + [hist + step] * (b - 1), np.int64)
        views.append(BlockStatesView(window, ages))
    return views


# -- in-place collate parity ------------------------------------------------


def test_collate_train_into_parity():
    rng = np.random.default_rng(0)
    holder = _train_holder(rng)
    ref = collate_train(holder)
    out = {
        k: np.zeros(shape, dtype)
        for k, (shape, dtype) in staging.train_spec(holder).items()
    }
    staging.collate_train_into(holder, out)
    assert set(out) == set(ref)
    for k in ref:
        assert out[k].dtype == ref[k].dtype, k
        np.testing.assert_array_equal(out[k], ref[k])


@pytest.mark.parametrize("values", [False, True])
def test_collate_rollout_into_parity(values):
    rng = np.random.default_rng(1)
    holder = _rollout_holder(rng, values=values)
    ref = collate_rollout(holder)
    out = {
        k: np.zeros(shape, dtype)
        for k, (shape, dtype) in staging.rollout_spec(holder).items()
    }
    staging.collate_rollout_into(holder, out)
    assert set(out) == set(ref)
    for k in ref:
        assert out[k].dtype == ref[k].dtype, k
        np.testing.assert_array_equal(out[k], ref[k], err_msg=k)


def test_collate_rollout_into_parity_segstates():
    """Lazy SegStates columns over ring windows (young env included):
    staged write == legacy coerce-then-stack, byte for byte."""
    rng = np.random.default_rng(2)
    t, b = 4, 3
    views = _ring_windows(rng, t=t, b=b)
    holder = []
    for j in range(b):
        holder.append({
            "state": SegStates(views, j),
            "action": rng.integers(0, 6, t).astype(np.int32),
            "reward": rng.normal(size=t).astype(np.float32),
            "done": np.zeros(t, np.float32),
            "behavior_log_probs": rng.normal(size=t).astype(np.float32),
            "bootstrap_state": views[-1][j],
        })
    ref = collate_rollout(holder)
    out = {
        k: np.zeros(shape, dtype)
        for k, (shape, dtype) in staging.rollout_spec(holder).items()
    }
    staging.collate_rollout_into(holder, out)
    for k in ref:
        np.testing.assert_array_equal(out[k], ref[k], err_msg=k)


def test_blockstatesview_materialize_into_matches_array():
    rng = np.random.default_rng(3)
    hist, b, h, w = 4, 5, 8, 8
    window = rng.integers(0, 255, (hist, b, h, w)).astype(np.uint8)
    ages = np.array([0, 1, 2, 3, 9], np.int64)  # three young, two mature
    v = BlockStatesView(window, ages)
    out = np.empty((b, h, w, hist), np.uint8)
    v.materialize_into(out)
    np.testing.assert_array_equal(out, np.asarray(v))


def test_segstates_shape_dtype_and_array():
    rng = np.random.default_rng(4)
    views = _ring_windows(rng, t=3, b=2)
    col = SegStates(views, 1)
    assert col.shape == (3, 8, 8, 4)
    assert col.dtype == np.uint8
    ref = np.stack([v[1] for v in views])
    np.testing.assert_array_equal(np.asarray(col), ref)


# -- the staging ring's safety contracts ------------------------------------


def test_staging_ring_backpressure_blocks_producer():
    """Every slot held downstream: acquire blocks (bounded) instead of
    overwriting — the shm-ring cap contract, staged edition."""
    rng = np.random.default_rng(5)
    holder = _train_holder(rng)
    spec = staging.train_spec(holder)
    ring = staging.HostStagingRing(slots=2)
    s1 = ring.acquire(spec, timeout=1.0)
    s2 = ring.acquire(spec, timeout=1.0)
    assert s1 is not None and s2 is not None and s1 is not s2
    t0 = time.monotonic()
    assert ring.acquire(spec, timeout=0.2) is None  # full: bounded refusal
    assert time.monotonic() - t0 >= 0.15
    ring.release(s1)
    s3 = ring.acquire(spec, timeout=1.0)
    assert s3 is s1  # the released slot came back into rotation
    # stop-responsiveness: a stopped producer escapes the wait quickly
    t0 = time.monotonic()
    assert ring.acquire(spec, timeout=30.0, stop=lambda: True) is None
    assert time.monotonic() - t0 < 5.0


def test_read_after_donate_fence_on_reused_slot():
    """Bytes staged + dispatched must survive the slot's reuse: the fence
    admits the writer only after the device arrays are ready, and the
    device copy must keep the ORIGINAL bytes when the slot is refilled."""
    rng = np.random.default_rng(6)
    holder = _train_holder(rng)
    spec = staging.train_spec(holder)
    ring = staging.HostStagingRing(slots=2)
    slot = ring.acquire(spec, timeout=1.0)
    staging.collate_train_into(holder, slot.buffers)
    expect = {k: v.copy() for k, v in slot.buffers.items()}
    # the SANCTIONED put: raw jax.device_put may zero-copy ALIAS the host
    # buffer on the CPU backend (this very test caught it), so readiness
    # would not mean consumption — device_put_staged's fence handles do
    device = {
        k: staging.device_put_staged(v) for k, v in slot.buffers.items()
    }
    ring.dispatched(slot, list(device.values()))
    # churn the ring until the SAME slot comes back (fence must open)
    other = ring.acquire(spec, timeout=1.0)
    ring.release(other)
    again = ring.acquire(spec, timeout=2.0)
    while again is not slot:
        ring.release(again)
        again = ring.acquire(spec, timeout=2.0)
        assert again is not None
    for k in again.buffers:  # overwrite the staging bytes in place
        again.buffers[k][...] = 0
    for k, d in device.items():
        np.testing.assert_array_equal(np.asarray(d), expect[k], err_msg=k)


def test_staged_feed_copy_budget_is_exactly_one():
    """TrainFeed with a staging ring: copies/blocks == 1.0 exactly, and
    the staged batches match the legacy collate's values."""
    rng = np.random.default_rng(7)
    q: "queue.Queue" = queue.Queue()
    items = [_train_holder(rng, n=1)[0] for _ in range(8)]
    for it in items:
        q.put([it[0], it[1], it[2]])
    ring = staging.HostStagingRing()
    feed = TrainFeed(q, batch_size=4, staging=ring)
    feed.start()
    try:
        b1 = feed.next_batch(timeout=10)
        ref1 = collate_train([list(it) for it in items[:4]])
        for k in ref1:
            np.testing.assert_array_equal(b1[k], ref1[k], err_msg=k)
        assert isinstance(b1, staging.StagedBatch)
        b1.release()
        b2 = feed.next_batch(timeout=10)
        b2.release()
    finally:
        feed.stop()
        feed.join(timeout=2)
    snap = telemetry.registry("learner").scalars()
    # legacy collate never ran (the reference above resets the counters)
    telemetry.reset_all()
    telemetry.set_enabled(True)
    assert snap["ingest_blocks_total"] >= 2
    # the reference collate_train call above also counted (1 pass/block);
    # staged blocks counted 1.0 each — the ratio stays exactly 1
    assert snap["ingest_copies_total"] == snap["ingest_blocks_total"]


def test_device_ingest_pipeline_prefetch_and_claim():
    """DeviceIngest: claim k, prefetch dispatches k+1 behind the step,
    and the next claim returns the prefetched device arrays."""
    rng = np.random.default_rng(8)
    q: "queue.Queue" = queue.Queue()
    for _ in range(12):
        it = _train_holder(rng, n=1)[0]
        q.put([it[0], it[1], it[2]])
    ring = staging.HostStagingRing()
    feed = TrainFeed(q, batch_size=4, staging=ring)
    ingest = staging.DeviceIngest(feed, sharding=None)
    ingest.start()
    try:
        b1 = ingest.next_batch(timeout=10)
        assert set(b1) == {"state", "action", "return"}
        assert all(isinstance(v, jax.Array) for v in b1.values())
        # "the learner step runs here": prefetch must land batch 2
        deadline = time.monotonic() + 10
        while not ingest.prefetch() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ingest.prefetch()  # idempotent: already staged
        b2 = ingest.next_batch(timeout=1)  # instant: pre-dispatched
        assert all(isinstance(v, jax.Array) for v in b2.values())
        scal = telemetry.registry("learner").scalars()
        assert scal["ingest_prefetched_total"] >= 1
        assert scal["ingest_dispatch_now_total"] >= 1
    finally:
        ingest.stop()
        ingest.join(timeout=2)


def test_fleet_merge_staged_stacked_parity():
    """FleetMergeFeed stacked macro batches: staged == legacy, and the
    fleet-axis stack collapses into stripe writes (one copy pass)."""
    rng = np.random.default_rng(9)
    K, B = 2, 3

    def fill():
        qs = [queue.Queue() for _ in range(K)]
        rng2 = np.random.default_rng(9)
        for qk in qs:
            for _ in range(B):
                it = _train_holder(rng2, n=1)[0]
                qk.put([it[0], it[1], it[2]])
        return qs

    def drain(feed):
        feed.start()
        try:
            return feed.next_batch(timeout=10)
        finally:
            feed.stop()
            feed.join(timeout=2)

    legacy = drain(FleetMergeFeed(fill(), B))
    staged = drain(
        FleetMergeFeed(fill(), B, staging=staging.HostStagingRing())
    )
    legacy.pop("_trace", None)
    assert isinstance(staged, staging.StagedBatch)
    for k in legacy:
        np.testing.assert_array_equal(staged[k], legacy[k], err_msg=k)
    staged.release()


# -- the pod block stager ---------------------------------------------------


def _wire_batch(rng, t=3, b=2, shape=(8, 8, 4)):
    return {
        "state": rng.integers(0, 255, (t, b, *shape)).astype(np.uint8),
        "action": rng.integers(0, 6, (t, b)).astype(np.int32),
        "reward": rng.normal(size=(t, b)).astype(np.float32),
        "done": np.zeros((t, b), np.float32),
        "behavior_log_probs": rng.normal(size=(t, b)).astype(np.float32),
        "behavior_values": rng.normal(size=(t, b)).astype(np.float32),
        "bootstrap_state": rng.integers(0, 255, (b, *shape)).astype(np.uint8),
    }


def test_block_stager_reuses_buffers_and_counts_one_copy():
    from distributed_ba3c_tpu.pod.learner import batch_to_block

    rng = np.random.default_rng(10)
    stager = staging.BlockStager()
    for i in range(4):
        batch = _wire_batch(rng)
        ref = batch_to_block(batch)  # the compat path: parity oracle
        stg = stager.copy_in(batch)
        block = stager.to_device(stg)
        for name in (
            "states", "actions", "rewards", "dones",
            "behavior_log_probs", "behavior_values", "bootstrap_state",
        ):
            np.testing.assert_array_equal(
                np.asarray(getattr(block, name)),
                np.asarray(getattr(ref, name)),
                err_msg=name,
            )
    scal = telemetry.registry("learner").scalars()
    # 4 staged + 4 compat oracle calls, every one exactly one copy pass
    assert scal["ingest_copies_total"] == scal["ingest_blocks_total"] == 8
    # buffers were REUSED: at most the 2-slot ring was ever allocated
    assert scal["staging_alloc_total"] <= 2


def test_block_stager_cancel_frees_slot():
    rng = np.random.default_rng(11)
    stager = staging.BlockStager()
    a = stager.copy_in(_wire_batch(rng))
    b = stager.copy_in(_wire_batch(rng))
    stager.cancel(a)
    stager.cancel(b)
    # both slots free again: the next two stage without a fallback
    stager.copy_in(_wire_batch(rng))
    stager.copy_in(_wire_batch(rng))
    scal = telemetry.registry("learner").scalars()
    assert scal.get("staging_fallback_total", 0.0) == 0.0
    assert scal["staging_alloc_total"] == 2


def test_pod_ingest_drop_oldest_cancels_staged_slot():
    """The receive-thread staging + drop-oldest liveness: a shed block's
    slot goes back in rotation (no ring starvation, no fallback growth)."""
    rng = np.random.default_rng(12)
    stager = staging.BlockStager()
    staged = [stager.copy_in(_wire_batch(rng)) for _ in range(2)]
    # buffer full: the ingest drops the oldest and cancels its slot
    stager.cancel(staged.pop(0))
    third = stager.copy_in(_wire_batch(rng))
    assert third.slot_idx is not None  # ring slot, not a transient
    scal = telemetry.registry("learner").scalars()
    assert scal.get("staging_fallback_total", 0.0) == 0.0


# -- the quantized rollout forward ------------------------------------------


@pytest.fixture(scope="module")
def pong_parts():
    from distributed_ba3c_tpu.config import BA3CConfig
    from distributed_ba3c_tpu.envs.jaxenv import pong
    from distributed_ba3c_tpu.models.a3c import BA3CNet
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh

    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=16)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(
        cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm
    )
    return cfg, model, opt, make_mesh(), pong


def _bf16_cast(params):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16) if x.dtype == jnp.float32 else x,
        params,
    )


def test_bf16_forward_parity_band_on_pong(pong_parts):
    """The quantization claim itself: on REAL jax-Pong observations the
    bf16-param forward stays inside a tight band of the f32 forward —
    log mu(a|s) within 0.1, V(s) within 0.05 (V-trace clips rho at 1, so
    a 0.1 logp band is far inside the correction's tolerance)."""
    from distributed_ba3c_tpu.fused.loop import create_fused_state

    cfg, model, opt, mesh, pong = pong_parts
    n_data = mesh.shape["data"]
    state = create_fused_state(
        jax.random.PRNGKey(0), model, cfg, opt, pong, 2 * n_data,
        n_shards=n_data,
    )
    # advance a few frames so the stacks are real game pixels, not resets
    env_state = state.env_state
    stack = np.asarray(state.obs_stack)
    obs = jnp.asarray(stack)
    params = state.train.params
    out32 = model.apply({"params": params}, obs)
    outbf = model.apply({"params": _bf16_cast(params)}, obs)
    lp32 = jax.nn.log_softmax(out32.logits, axis=-1)
    lpbf = jax.nn.log_softmax(outbf.logits, axis=-1)
    assert float(jnp.max(jnp.abs(lp32 - lpbf))) < 0.1
    assert float(jnp.max(jnp.abs(out32.value - outbf.value))) < 0.05
    del env_state


def test_bf16_lag0_learning_parity_on_pong(pong_parts):
    """Lag-0 overlap at bf16 rollout vs f32: the first update (identical
    initial state, identical keys) optimizes the same objective inside a
    band, and both keep training finitely."""
    from distributed_ba3c_tpu.fused.loop import create_fused_state
    from distributed_ba3c_tpu.fused.overlap import make_overlap_step

    cfg, model, opt, mesh, pong = pong_parts
    n_data = mesh.shape["data"]
    n_envs = 2 * n_data

    def run(dtype):
        step = make_overlap_step(
            model, opt, cfg, mesh, pong, rollout_len=3, lag=0,
            rollout_dtype=dtype,
        )
        state = step.put(
            create_fused_state(
                jax.random.PRNGKey(0), model, cfg, opt, pong, n_envs,
                n_shards=n_data,
            )
        )
        ms = []
        for _ in range(2):
            state, m = step(state, cfg.entropy_beta)
            ms.append({k: float(v) for k, v in m.items()})
        return ms

    f32 = run("float32")
    bf16 = run("bfloat16")
    for ms in (f32, bf16):
        for m in ms:
            for k, v in m.items():
                assert np.isfinite(v), k
    # first update: same initial state + keys, only the rollout params
    # precision differs — the losses must sit in one band
    assert abs(f32[0]["loss"] - bf16[0]["loss"]) < 0.05
    assert abs(f32[0]["pred_value"] - bf16[0]["pred_value"]) < 0.05
    assert abs(f32[0]["entropy"] - bf16[0]["entropy"]) < 0.05


def test_predictor_bf16_table_and_band(pong_parts):
    """BatchedPredictor(rollout_dtype=bfloat16): the whole policy table
    stores bf16, serving works, values inside the band of the f32 server
    on identical states, and publishes stay castable."""
    from distributed_ba3c_tpu.predict.server import BatchedPredictor

    cfg, model, opt, mesh, pong = pong_parts
    rng = np.random.default_rng(13)
    params = model.init(
        jax.random.PRNGKey(0),
        np.zeros((1, *cfg.state_shape), np.uint8),
    )["params"]
    states = rng.integers(0, 255, (4, *cfg.state_shape)).astype(np.uint8)
    p32 = BatchedPredictor(model, params, batch_size=4, greedy=True)
    pbf = BatchedPredictor(
        model, params, batch_size=4, greedy=True,
        rollout_dtype="bfloat16", tele_role="predictor.bf16",
    )
    leaves = jax.tree_util.tree_leaves(pbf._policies["default"])
    assert all(
        l.dtype in (jnp.bfloat16, jnp.float32) for l in leaves
    ) and any(l.dtype == jnp.bfloat16 for l in leaves)
    a32, v32, _ = p32.predict_batch(states)
    abf, vbf, _ = pbf.predict_batch(states)
    assert np.max(np.abs(v32 - vbf)) < 0.05
    # publish path: a fresh f32 publish lands cast, and still serves
    pbf.update_params(jax.device_put(params))
    leaves = jax.tree_util.tree_leaves(pbf._policies["default"])
    assert any(l.dtype == jnp.bfloat16 for l in leaves)
    abf2, _, _ = pbf.predict_batch(states)
    assert abf2.shape == (4,)


def test_predictor_block_staging_parity_and_reuse(pong_parts):
    """A BlockStatesView block served through the staging pool: same
    actions as the materialized array, one stage copy per dispatch, and
    the pool buffer is REUSED across batches."""
    from distributed_ba3c_tpu.predict.server import BatchedPredictor

    cfg, model, opt, mesh, pong = pong_parts
    rng = np.random.default_rng(14)
    params = model.init(
        jax.random.PRNGKey(0),
        np.zeros((1, *cfg.state_shape), np.uint8),
    )["params"]
    pred = BatchedPredictor(
        model, params, batch_size=8, greedy=True, coalesce_ms=0.0,
        tele_role="predictor.stage",
    )
    pred.warmup(cfg.state_shape)
    pred.start()
    h, w = cfg.image_size
    hist = cfg.frame_history
    try:
        for _ in range(3):
            window = rng.integers(0, 255, (hist, 5, h, w)).astype(np.uint8)
            view = BlockStatesView(
                window, np.full(5, hist + 3, np.int64)
            )
            got = []
            evt = threading.Event()
            pred.put_block_task(
                view, lambda a, v, lp: (got.append(a), evt.set())
            )
            assert evt.wait(60)
            ref, _, _ = pred.predict_batch(np.asarray(view))
            np.testing.assert_array_equal(got[0], ref)
    finally:
        pred.stop()
        pred.join(timeout=5)
    scal = telemetry.registry("predictor.stage").scalars()
    assert scal["stage_copies_total"] >= 3
    # the pow-2-8 bucket buffer allocated ONCE and recycled
    assert scal["stage_alloc_total"] == 1


def test_snapshot_beside_the_writer_never_splits_a_block():
    """A reader snapshotting in a tight loop beside the ingest thread never
    sees a block's copy without the block (ROADMAP D18: two counters showed
    an epoch's record one copy ahead; the pair is one increment, one read)."""
    ring = staging.HostStagingRing(slots=2)
    reg = telemetry.registry("learner")
    done = threading.Event()

    def ingest():
        for _ in range(100_000):
            ring.count_staged_copy()
        done.set()

    writer = threading.Thread(target=ingest)
    writer.start()
    split, reads = [], 0
    while not done.is_set() and not split:
        snap = reg.scalars()
        pair = (snap.get("ingest_copies_total", 0.0),
                snap.get("ingest_blocks_total", 0.0))
        if pair[0] != pair[1]:
            split.append(pair)
        reads += 1
    writer.join()
    assert not split, f"copies, blocks = {split[0]} after {reads} snapshots"
    assert reads > 0 and reg.scalars()["ingest_blocks_total"] == 100_000
