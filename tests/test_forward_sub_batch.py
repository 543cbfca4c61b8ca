"""The shape-dependent sub-batched inference forward (fused/loop.py).

The helper's verdict by shape; that a rollout and a whole fused step come
out the same whether the forward ran in sub-batches or whole (float32, so
that only reassociation can tell them apart); that the new nested scope is
in the compiled step exactly when the mechanism ran, and that the capture
reader charges it to the scopes it nests in. No chip, no time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.envs.jaxenv import pong
from distributed_ba3c_tpu.fused import loop
from distributed_ba3c_tpu.models import a3c as a3c_model
from distributed_ba3c_tpu.models.a3c import BA3CNet
from distributed_ba3c_tpu.ops.gradproc import make_optimizer
from distributed_ba3c_tpu.parallel.mesh import make_mesh
from distributed_ba3c_tpu.utils import profiling

N_ENVS = 8
ROLLOUT_LEN = 3


@pytest.mark.parametrize("n_envs,size", [
    (4, None), (255, None), (256, None), (511, None),  # under two sub-batches
    (512, 256), (4096, 256), (1024, 256), (768, 256),
    (1000, 250), (600, 200), (516, 172), (640, 160), (1280, 256),
    (514, None),   # 2 x 257: no divisor in [128, 256]
    (1021, None),  # a prime
])
def test_the_helpers_verdict_by_shape(n_envs, size):
    assert a3c_model.FORWARD_SUB_BATCH == 256
    assert loop.forward_sub_batch(n_envs) == size
    if size is not None:
        assert n_envs % size == 0 and 128 <= size <= 256


def test_the_verdict_follows_the_module_constant(monkeypatch):
    monkeypatch.setattr(a3c_model, "FORWARD_SUB_BATCH", 2)
    assert [loop.forward_sub_batch(n) for n in (2, 3, 4, 5, 6, 8)] == [
        None, None, 2, 1, 2, 2]  # [1, 2] holds a divisor of anything
    monkeypatch.setattr(a3c_model, "FORWARD_SUB_BATCH", 3)
    assert [loop.forward_sub_batch(n) for n in (6, 8, 9)] == [3, 2, 3]


@pytest.fixture(scope="module")
def f32_parts():
    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=16)
    model = BA3CNet(
        num_actions=cfg.num_actions, fc_units=cfg.fc_units,
        compute_dtype=jnp.float32,
    )
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    state = loop.create_fused_state(
        jax.random.PRNGKey(3), model, cfg, opt, pong, N_ENVS, n_shards=2
    )
    return cfg, model, opt, state


def _rollout(parts, record_log_probs, apply_fn):
    """A jitted rollout of the shared scan body from the fixture's state."""
    cfg, model, _, state = parts

    def run(params, env_state, stack, key):
        body = loop.make_rollout_body(
            model, cfg, pong, params, record_log_probs, apply_fn=apply_fn
        )
        zeros = jnp.zeros(N_ENVS, jnp.float32)
        carry = (env_state, stack, key, zeros, zeros.astype(jnp.int32), zeros)
        return jax.lax.scan(body, carry, None, length=ROLLOUT_LEN)

    return jax.jit(run)(
        state.train.params, state.env_state, state.obs_stack, state.key[0]
    )


def _scaled_apply(model):
    """A passed ``apply_fn`` that is not the default one."""
    def apply(p, stack):
        out = model.apply({"params": p}, stack)
        return out._replace(logits=out.logits * 3.0)
    return apply


@pytest.mark.parametrize("record_log_probs", [False, True])
@pytest.mark.parametrize("passed", [False, True])
def test_a_sub_batched_rollout_is_the_whole_batch_rollout(
        f32_parts, monkeypatch, record_log_probs, passed):
    apply_fn = _scaled_apply(f32_parts[1]) if passed else None
    whole = _rollout(f32_parts, record_log_probs, apply_fn)
    monkeypatch.setattr(a3c_model, "FORWARD_SUB_BATCH", 2)
    assert loop.forward_sub_batch(N_ENVS) == 2
    split = _rollout(f32_parts, record_log_probs, apply_fn)
    (w_carry, w_traj), (s_carry, s_traj) = whole, split
    assert len(w_traj) == len(s_traj) == (6 if record_log_probs else 4)
    # frames, actions, rewards, dones and the whole final carry: bit for bit
    for a, b in zip(jax.tree_util.tree_leaves((w_carry, w_traj[:4])),
                    jax.tree_util.tree_leaves((s_carry, s_traj[:4]))):
        if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(w_traj[4:], s_traj[4:]):  # log mu and value, float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def _one_update(parts, mesh):
    cfg, model, opt, state = parts
    step = loop.make_fused_step(
        model, opt, cfg, mesh, pong, rollout_len=ROLLOUT_LEN, grad_chunk_samples=6
    )
    start = jax.device_get(state.train.params)
    # the step donates its state: hand it copies, the fixture's is shared
    fresh = lambda: step.put(jax.tree_util.tree_map(jnp.copy, state))  # noqa: E731
    new_state, metrics = step(fresh(), cfg.entropy_beta, cfg.learning_rate)
    hlo = step.audit_jit.lower(
        fresh(), jnp.float32(0.01), jnp.float32(1e-3)
    ).compile().as_text()
    return step, start, jax.device_get(new_state), jax.device_get(metrics), hlo


@pytest.fixture(scope="module")
def both_updates(f32_parts):
    """One update of fused.step from one state (4 envs on each of two
    devices, two gradient chunks a device), whole and with sub-batches of 2."""
    mesh = make_mesh(num_data=2, num_model=1, devices=jax.devices()[:2])
    whole = _one_update(f32_parts, mesh)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(a3c_model, "FORWARD_SUB_BATCH", 2)
        split = _one_update(f32_parts, mesh)
    return whole, split


def test_a_sub_batched_fused_step_is_the_whole_batch_step(both_updates):
    (_, start, w_state, w_metrics, _), (_, _, s_state, s_metrics, _) = both_updates
    assert set(w_metrics) == set(s_metrics)
    for k in w_metrics:  # loss, its parts, the gradient's norm, the counters
        np.testing.assert_allclose(
            w_metrics[k], s_metrics[k], rtol=1e-4, atol=1e-6, err_msg=k)
    assert float(w_metrics["grad_norm"]) > 0
    # the envs went the same way: same actions drawn
    for a, b in zip(
        jax.tree_util.tree_leaves((w_state.env_state, w_state.obs_stack, w_state.ep_return)),
        jax.tree_util.tree_leaves((s_state.env_state, s_state.obs_stack, s_state.ep_return)),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        jax.random.key_data(w_state.key), jax.random.key_data(s_state.key))
    # the update itself (Adam's step is lr-sized whatever the gradient, so
    # hold the moments, which are the gradient, as well as the parameters)
    moved = 0.0
    for a, b, a0 in zip(jax.tree_util.tree_leaves(w_state.train.params),
                        jax.tree_util.tree_leaves(s_state.train.params),
                        jax.tree_util.tree_leaves(start)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
        moved += float(np.abs(np.asarray(a) - np.asarray(a0)).sum())
    assert moved > 0
    for a, b in zip(jax.tree_util.tree_leaves(w_state.train.opt_state),
                    jax.tree_util.tree_leaves(s_state.train.opt_state)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=1e-7)


def _scopes_in(hlo):
    import re

    return {
        profiling.scope_of(name)
        for name in re.findall(r'op_name="([^"]*)"', hlo)
    }


@pytest.mark.parametrize("scope", [
    profiling.ROLLOUT_POLICY_SUB_BATCH, profiling.RETURNS_SUB_BATCH])
def test_the_nested_scope_is_in_the_step_exactly_when_it_ran_in_sub_batches(
        both_updates, scope):
    (w_step, *_, w_hlo), (s_step, *_, s_hlo) = both_updates
    assert scope not in _scopes_in(w_hlo)
    assert scope in _scopes_in(s_hlo)
    assert scope in profiling.SCOPES and scope.rsplit("/", 1)[0] in profiling.SCOPES


def test_every_convolution_of_a_sub_batched_step_has_its_home(both_updates):
    _, (*_, s_hlo) = both_updates
    import re

    homes = [
        profiling.scope_of(re.search(r'op_name="([^"]*)"', line).group(1))
        for line in s_hlo.splitlines()
        if " convolution(" in line and "op_name=" in line
    ]
    assert homes.count(profiling.ROLLOUT_POLICY_SUB_BATCH) == 4
    assert homes.count(profiling.RETURNS_SUB_BATCH) == 4
    assert profiling.ROLLOUT_POLICY not in homes and profiling.RETURNS not in homes
    assert profiling.LEARNER in homes


def test_the_built_step_tells_its_sub_batch(monkeypatch):
    cfg = BA3CConfig(num_actions=pong.num_actions, fc_units=16)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh(num_data=2, num_model=1, devices=jax.devices()[:2])
    step = loop.make_fused_step(model, opt, cfg, mesh, pong, rollout_len=2)
    assert step.rollout_sub_batch(512) is None       # 256 envs a device
    assert step.rollout_sub_batch(8192) == 256       # 4,096 a device
    from distributed_ba3c_tpu.fused.overlap import make_overlap_step

    overlap = make_overlap_step(model, opt, cfg, mesh, pong, rollout_len=2)
    assert overlap.rollout_sub_batch(2000) == 250


# -- the reader: a nested scope's time goes to every scope it lies in --------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number, payload):
    """A length-delimited protobuf field, or a varint one for an int."""
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _synthetic_xplane(path, ops):
    """An XSpace with one TPU plane whose ``XLA Ops`` line has one event an
    op: ``ops`` is [(HLO text, op_name, duration ps)]. Written field by
    field as ``utils/profiling.py`` documents the wire format (XPlane:
    name=2, lines=3, event_metadata=4, stat_metadata=5; XLine: name=2,
    timestamp_ns=3, events=4; XEvent: metadata_id=1, offset_ps=2,
    duration_ps=3; XEventMetadata: id=1, name=2, stats=5; XStat:
    metadata_id=1, str_value=5)."""
    tf_op_stat = 1
    plane = _field(2, b"/device:TPU:0")
    plane += _field(5, _field(1, tf_op_stat) + _field(
        2, _field(1, tf_op_stat) + _field(2, profiling.OP_NAME_STAT.encode())))
    events, offset = b"", 0
    for i, (text, op_name, dur_ps) in enumerate(ops, start=1):
        meta = _field(1, i) + _field(2, text.encode())
        if op_name:
            meta += _field(5, _field(1, tf_op_stat) + _field(5, op_name.encode()))
        plane += _field(4, _field(1, i) + _field(2, meta))
        events += _field(4, _field(1, i) + _field(2, offset) + _field(3, dur_ps))
        offset += dur_ps
    plane += _field(3, _field(2, profiling.OPS_LINE.encode()) + _field(3, 1000) + events)
    with open(path, "wb") as f:
        f.write(_field(1, plane))


def test_the_reader_charges_the_nested_scope_to_policy_and_to_rollout(tmp_path):
    us = 1_000_000  # ps
    path = str(tmp_path / "synthetic.xplane.pb")
    _synthetic_xplane(path, [
        ("%fusion.1 = bf16[256,84,84,32] fusion(...)",
         "jit(multi_step)/rollout/while/body/closed_call/policy/sub_batch/"
         "while/body/closed_call/BA3CNet/Conv_0/conv_general_dilated", 7 * us),
        ("%fusion.2 = f32[4096,6] fusion(...)",
         "jit(multi_step)/rollout/while/body/closed_call/policy/reshape", 1 * us),
        ("%fusion.3 = s32[4096] fusion(...)",
         "jit(multi_step)/rollout/while/body/closed_call/sample/argmax", 2 * us),
        ("%fusion.4 = bf16[256,84,84,32] fusion(...)",
         "jit(multi_step)/returns/sub_batch/while/body/closed_call/BA3CNet/"
         "Conv_0/conv_general_dilated", 3 * us),
        ("%fusion.5 = f32[20,4096] fusion(...)",
         "jit(multi_step)/returns/while/body/add", 5 * us),
        ("%fusion.6 = bf16[4096,84,84,32] fusion(...)",
         "jit(multi_step)/learner/jvp(BA3CNet)/Conv_0/conv_general_dilated", 11 * us),
        ("%copy.7 = u8[81920,84,84,4] copy(...)", "", 13 * us),
        ("%while.8 = (s32[]) while(...)", "jit(multi_step)/rollout/while", 100 * us),
    ])
    got = profiling.op_time_by_scope(path)
    s = {k: round(v * 1e6, 6) for k, v in got["seconds"].items()}  # us
    assert s[profiling.ROLLOUT_POLICY_SUB_BATCH] == 7
    assert s[profiling.ROLLOUT_POLICY] == 7 + 1
    assert s[profiling.ROLLOUT] == 7 + 1 + 2
    assert s[profiling.RETURNS_SUB_BATCH] == 3
    assert s[profiling.RETURNS] == 3 + 5
    assert s[profiling.LEARNER] == s[profiling.LEARNER_FWD] == 11
    assert s[profiling.UNSCOPED] == 13
    # the while spans its body and is not summed; the phases and the
    # unscoped rest are all of the op time
    assert round(got["total_s"] * 1e6, 6) == 42
    assert sum(s[p] for p in profiling.PHASES) + s[profiling.UNSCOPED] == 42
    assert got["events"] == {"/device:TPU:0": [8, 1000]}
