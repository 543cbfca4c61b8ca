"""Phi-4-mini-flash-reasoning as a token-sequence policy, at a size the CPU
runs (hidden 64, the five kinds of layer, window 8, d_inner 128 x 4 states,
vocabulary 64, episodes of 24 = three windows): the model against the
benchmark's plain reference, decoding through the carry against the unroll
past the window and across a reset, the ring against a banded mask, the side
channels between layers, ``ops/ssm.py`` against the one-position recurrence,
the fused step's gradient, the scopes, the refusals.
"""

import dataclasses
import functools
import json
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import opcount_phi4flash  # noqa: E402
from benchmark.reference import phi4_flash as reference, recall as ref_recall  # noqa: E402
from distributed_ba3c_tpu.config import BA3CConfig  # noqa: E402
from distributed_ba3c_tpu.envs.jaxenv.recall import RecallEnv  # noqa: E402
from distributed_ba3c_tpu.fused.loop import (  # noqa: E402
    create_fused_state,
    make_fused_step,
)
from distributed_ba3c_tpu.models import phi4_flash, policy  # noqa: E402
from distributed_ba3c_tpu.models.phi4_flash import (  # noqa: E402
    CROSS, CUTS, FULL, GMU, MAMBA, WINDOW, Phi4Flash)
from distributed_ba3c_tpu.ops import decode_attention, ssm  # noqa: E402
from distributed_ba3c_tpu.ops.gradproc import make_optimizer  # noqa: E402
from distributed_ba3c_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

IDS, PROMPT, EPISODE = 64, 4, 24
WINDOW_LEN = 8
#: the configuration's keys at the small cut, as the reference reads them
TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 8,
    "num_key_value_heads": 4, "sliding_window": WINDOW_LEN,
    "layer_norm_eps": 1e-5, "vocab_size": IDS,
    "published": {"num_hidden_layers": 8},
    "held": {"layers": [2, 3, 4, 5, 6, 7]},
    "state_space": {"d_inner": 128, "d_state": 4, "d_conv": 4, "dt_rank": 4},
}
SPEC = reference.spec_of(TINY_CONFIG)
HYPER = {"gamma": 0.99, "entropy_beta": 0.01, "value_loss_coef": 0.5,
         "grad_clip_norm": 0.5, "learning_rate": 1e-3, "adam_epsilon": 1e-3}
KINDS = (MAMBA, WINDOW, MAMBA, FULL, GMU, CROSS)


def tiny(compute_dtype=jnp.float32, **kw) -> Phi4Flash:
    fields = dict(CUTS["tiny"], num_actions=IDS, max_positions=EPISODE,
                  compute_dtype=compute_dtype)
    return Phi4Flash(**dict(fields, **kw))


def params_of(seed, spec=SPEC):
    """The reference's seeded weights with every vector moved off its start
    (unit gains, zero biases and equal ``A_log`` rows hide a wrong reading)."""
    params = reference.init_params(jax.random.PRNGKey(seed), spec)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1000), 256))
    return {layer: {leaf: x + 0.1 * jax.random.normal(next(keys), x.shape)
                    if leaf not in ("table", "kernel") and x.ndim <= 2
                    and x.size <= 1024 else x
                    for leaf, x in leaves.items()}
            for layer, leaves in params.items()}


def tokens_of(seed, batch=3, length=EPISODE):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length), 0, IDS)


def decode(model, params, tokens, fresh_at=()):
    """Token by token through the carry -> logits, value [B, T, ...]; the
    positions in ``fresh_at`` open a new episode."""
    B, T = tokens.shape
    fresh = jnp.zeros((T, B), bool).at[0].set(True)
    for t in fresh_at:  # a position: every env; (position, env): that env
        fresh = fresh.at[t].set(True)

    def one(carry, x):
        out, carry = model.step(params, x[0], carry, x[1])
        return carry, (out.logits, out.value)

    _, (logits, value) = jax.lax.scan(
        one, model.init_carry(B), (jnp.swapaxes(tokens, 0, 1), fresh))
    return jnp.swapaxes(logits, 0, 1), jnp.swapaxes(value, 0, 1)


# -- the architecture as the configuration states it ----------------------------
#: the issue's table for n = 32
PUBLISHED_KINDS = {
    **{i: MAMBA for i in range(0, 17, 2)}, **{i: WINDOW for i in range(1, 16, 2)},
    17: FULL, **{i: GMU for i in range(18, 32, 2)},
    **{i: CROSS for i in range(19, 32, 2)},
}


@pytest.mark.parametrize("i", range(32))
def test_a_published_layers_kind(i):
    assert phi4_flash.kind_of(i, 32) == PUBLISHED_KINDS[i]
    assert reference.kind_of(i, 32) == PUBLISHED_KINDS[i]


def test_the_published_ratio_of_kinds():
    kinds = [phi4_flash.kind_of(i, 32) for i in range(32)]
    assert [kinds.count(k) for k in (MAMBA, WINDOW, FULL, GMU, CROSS)] == [9, 8, 1, 7, 7]
    assert Phi4Flash().layer_kinds == KINDS and tiny().layer_kinds == KINDS
    assert Phi4Flash().memory_layer == tiny().memory_layer == 2  # published 16


def test_the_programs_parameters_are_the_references():
    ours = tiny().init_params(jax.random.PRNGKey(3))
    theirs = reference.init_params(jax.random.PRNGKey(3), SPEC)
    assert jax.tree_util.tree_map(jnp.shape, ours) == jax.tree_util.tree_map(
        jnp.shape, theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs), strict=True):
        np.testing.assert_array_equal(a, b)


def test_the_held_parameter_count_is_the_operation_counts():
    with open(os.path.join(
            ROOT, "benchmark/configs/phi4-mini-flash-recall-fused-a2c.json")) as f:
        config = json.load(f)
    shapes = jax.eval_shape(Phi4Flash().init_params, jax.random.PRNGKey(0))
    held = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert held == opcount_phi4flash.params_held(config) == 697_096_833
    small = jax.eval_shape(tiny().init_params, jax.random.PRNGKey(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(small)) == (
        opcount_phi4flash.params_held(TINY_CONFIG))


@pytest.mark.parametrize("missing,kept", [
    ((6, 7), "memory unit"), ((7,), "cross layer")])
def test_a_reader_without_its_source_is_refused(missing, kept):
    with pytest.raises(ValueError, match=kept):
        tiny(layer_ids=missing)


# -- the program against the reference --------------------------------------------
#: float32 against float32 at ``highest``: one function in another order of
#: sums (read 2.4e-6 of the largest logit); bfloat16 operands against
#: float32: rounding through six layers (read 0.01-0.02 at this size)
TOLERANCE = [(jnp.float32, 2e-5), (jnp.bfloat16, 0.05)]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype,tol", TOLERANCE)
def test_unroll_agrees_with_the_reference(seed, dtype, tol):
    params, tokens = params_of(seed), tokens_of(seed + 10)
    with jax.default_matmul_precision("highest"):
        out, aux = jax.jit(tiny(dtype).unroll)(params, tokens)
        logits, value = jax.jit(
            lambda p, t: reference.forward(p, t, SPEC))(params, tokens)
    assert aux == {}
    scale = float(jnp.abs(logits).max())
    assert float(jnp.abs(out.logits - logits).max()) < tol * scale
    assert float(jnp.abs(out.value - value).max()) < tol * max(
        float(jnp.abs(value).max()), 0.1)


def _loss(forward):
    def fn(params, tokens, actions, returns):
        logits, value = forward(params, tokens)
        logp = jax.nn.log_softmax(logits)
        logp_a = jnp.take_along_axis(logp, actions[..., None], -1)[..., 0]
        adv = returns - jax.lax.stop_gradient(value)
        return (-jnp.sum(logp_a * adv) + 0.25 * jnp.sum(jnp.square(value - returns))
                + 0.01 * jnp.sum(jnp.exp(logp) * logp))
    return fn


@pytest.fixture(scope="module")
def both_gradients():
    params, tokens = params_of(4), tokens_of(14)
    actions = tokens_of(15)
    returns = jax.random.uniform(jax.random.PRNGKey(16), tokens.shape)
    model = tiny()
    with jax.default_matmul_precision("highest"):
        ours = jax.jit(jax.value_and_grad(_loss(
            lambda p, t: tuple(model.unroll(p, t)[0]))))(
                params, tokens, actions, returns)
        theirs = jax.jit(jax.value_and_grad(_loss(
            lambda p, t: reference.forward(p, t, SPEC))))(
                params, tokens, actions, returns)
    assert abs(float(ours[0]) - float(theirs[0])) < 1e-4 * abs(float(theirs[0]))
    return ours[1], theirs[1]


_LEAVES = sorted(
    f"{layer}/{leaf}" for layer, leaves in jax.eval_shape(
        tiny().init_params, jax.random.PRNGKey(0)).items() for leaf in leaves)


@pytest.mark.parametrize("name", _LEAVES)
def test_a_leafs_gradient_of_the_loss_is_the_references(both_gradients, name):
    """Every leaf: the program's scan is chunked and checkpointed, its
    attention runs in blocks of queries over lane-wide pairs; the
    reference's is the recurrence and a written-out mask. Float32 both, so
    the gap is the order of the sums: 2e-3 of the leaf's largest entry
    (read up to 3e-5; ``A_log`` and the ``lam`` vectors sum thousands of
    terms of both signs)."""
    layer, leaf = name.split("/")
    got, want = both_gradients[0][layer][leaf], both_gradients[1][layer][leaf]
    scale = max(float(jnp.abs(want).max()), 1e-6)
    assert float(jnp.abs(want).max()) > 0, "a leaf nothing reads"
    np.testing.assert_allclose(got, want, atol=2e-3 * scale)


# -- decoding through the carry -----------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 1e-5)])
def test_step_through_the_carry_is_the_unroll_position_by_position(dtype, tol):
    """24 positions = three windows: the ring has forgotten twice over, the
    shared K/V has grown to 24 rows. The two forms round alike at equal
    shapes, so even bfloat16 agrees to float32's order of sums."""
    model, params, tokens = tiny(dtype), params_of(5), tokens_of(6)
    logits, value = jax.jit(lambda p, t: decode(model, p, t))(params, tokens)
    out, _ = jax.jit(model.unroll)(params, tokens)
    scale = float(jnp.abs(out.logits).max())
    gap = jnp.abs(logits - out.logits).max(axis=(0, 2))
    assert float(gap.max()) < max(tol, 0.02 * (dtype == jnp.bfloat16)) * scale, gap
    assert float(gap[WINDOW_LEN:].max()) > 0 or dtype == jnp.float32
    assert float(jnp.abs(value - out.value).max()) < 1e-3


@pytest.mark.parametrize("at", [1, 7, 13])
def test_a_fresh_token_forgets_the_episode_before(at):
    """Across a reset inside the sequence: the positions from ``at`` on are
    those of an episode that starts there (state-space state and conv tail
    zeroed, ring and shared K/V masked by the position)."""
    model, params, tokens = tiny(), params_of(7), tokens_of(8)
    logits, _ = jax.jit(lambda p, t: decode(model, p, t, fresh_at=(at,)))(
        params, tokens)
    first, _ = jax.jit(model.unroll)(params, tokens[:, :at])
    second, _ = jax.jit(model.unroll)(params, tokens[:, at:])
    want = jnp.concatenate([first.logits, second.logits], axis=1)
    assert float(jnp.abs(logits - want).max()) < 1e-5 * float(jnp.abs(want).max())


def test_fresh_resets_the_position_and_the_state_space_state_alone():
    model, params = tiny(), params_of(9)
    carry = model.init_carry(2)
    for t in range(5):
        _, carry = model.step(params, jnp.array([3, 4]), carry,
                              jnp.array([t == 0, t == 0]))
    assert carry.pos.tolist() == [5, 5]
    _, after = model.step(params, jnp.array([1, 2]), carry, jnp.array([True, False]))
    assert after.pos.tolist() == [1, 6]
    lone = model.step(params, jnp.array([1, 2]), model.init_carry(2),
                      jnp.array([True, True]))[1]
    for (state, tail), (state1, tail1) in zip(after.ssm, lone.ssm, strict=True):
        np.testing.assert_allclose(state[0], state1[0], atol=1e-6)
        np.testing.assert_allclose(tail[0], tail1[0], atol=1e-6)
        assert float(jnp.abs(state[1] - state1[1]).max()) > 1e-4
    # the rings and the shared K/V keep the last episode's rows (masked)
    assert float(jnp.abs(after.shared_kv[0][0, 1:5]).max()) > 0


def test_the_carrys_bytes_by_kind_are_its_shapes():
    model = tiny()
    carry = jax.eval_shape(lambda: model.init_carry(1))
    size = lambda tree: sum(x.size * x.dtype.itemsize  # noqa: E731
                            for x in jax.tree_util.tree_leaves(tree))
    assert model.carry_bytes() == (
        size(carry.ssm), size(carry.ring), size(carry.shared_kv), 4)
    assert len(carry.ssm) == 2 and len(carry.ring) == 1 and len(carry.shared_kv) == 2
    assert carry.ssm[0][0].shape == (1, 4, 128) and carry.ssm[0][1].shape == (1, 3, 128)
    assert carry.ring[0][0].shape == (1, WINDOW_LEN, 2 * 16)  # a slot's two pairs
    assert carry.shared_kv[0].shape == (1, EPISODE, 2 * 16)
    # at the published widths: 16 x 5120 float32 a state, 1,280 bfloat16 a row
    full = Phi4Flash().carry_bytes()
    assert full == (2 * (16 * 5120 + 3 * 5120) * 4, 2 * 512 * 1280 * 2,
                    2 * 1024 * 1280 * 2, 4)
    gauges = model.carry_gauges(model.init_carry(3))
    assert gauges["carry_bytes_per_env"].tolist() == list(model.carry_bytes())
    assert float(gauges["ssm_state_absmax"]) == 0.0


# -- the ring is a banded mask ----------------------------------------------------
@pytest.mark.parametrize("window", [1, 3, 8, EPISODE, 2 * EPISODE])
def test_the_ring_equals_a_banded_mask(window):
    """The window layer alone (published layer 3 of 8), decoded through a
    ring of ``window`` slots, against the reference's ``T x T`` mask ``0 <=
    t - s < window`` over the whole episode."""
    model = tiny(layer_ids=(3,), sliding_window=window)
    spec = dict(SPEC, layers=((3, WINDOW),), window=window)
    params, tokens = params_of(11, spec), tokens_of(12)
    logits, _ = jax.jit(lambda p, t: decode(model, p, t))(params, tokens)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.forward(params, tokens, spec)
        unrolled, _ = jax.jit(model.unroll)(params, tokens)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(logits - want).max()) < 2e-5 * scale
    assert float(jnp.abs(unrolled.logits - want).max()) < 2e-5 * scale
    if window < EPISODE:  # and a mask one wider is another function
        wider, _ = reference.forward(params, tokens, dict(spec, window=window + 1))
        assert float(jnp.abs(wider - want).max()) > 1e-3 * scale


# -- the same through the kernel (ops/decode_attention.py) --------------------------
# A whole-lane cut the CPU runs under Pallas's interpreter: pairs of 2 x 64 =
# 128 lanes, 4 queries a pair, a ring of 128 slots = one block, 256 positions
# = two blocks of the shared K/V, so the ring wraps and the shared buffer is
# read one block, then two.
LANES_EPISODE, LANES_WINDOW = 256, 128
LANES_CONFIG = dict(
    TINY_CONFIG, hidden_size=256, num_attention_heads=4, num_key_value_heads=2,
    sliding_window=LANES_WINDOW)
LANES_SPEC = reference.spec_of(LANES_CONFIG)


def lanes(compute_dtype=jnp.float32, **kw) -> Phi4Flash:
    fields = dict(CUTS["tiny"], hidden_size=256, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=64,
                  sliding_window=LANES_WINDOW, max_positions=LANES_EPISODE)
    return tiny(compute_dtype, **dict(fields, **kw))


@pytest.fixture
def kernel_path(monkeypatch):
    """The decode's attention in the Pallas kernel, interpreted, in blocks
    of 128 rows."""
    monkeypatch.setattr(decode_attention, "INTERPRET", True)
    monkeypatch.setattr(decode_attention, "block_rows", lambda rows, row_bytes: 128)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 0.03)])
def test_step_through_the_carry_is_the_unroll_with_the_kernel(kernel_path, dtype, tol):
    """256 positions = two windows: the ring has wrapped, the shared K/V has
    grown past its first block."""
    model = lanes(dtype)
    params, tokens = params_of(5, LANES_SPEC), tokens_of(6, 3, LANES_EPISODE)
    carry = model.init_carry(3)
    assert carry.ring[0][0].shape == (3, LANES_WINDOW, 128)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda p: model.step(p, tokens[:, 0], carry, jnp.ones(3, bool)))(params))
    logits, value = jax.jit(lambda p, t: decode(model, p, t))(params, tokens)
    out, _ = jax.jit(model.unroll)(params, tokens)
    scale = float(jnp.abs(out.logits).max())
    gap = jnp.abs(logits - out.logits).max(axis=(0, 2))
    assert float(gap.max()) < tol * scale, gap
    assert float(jnp.abs(value - out.value).max()) < 1e-3 + tol


@pytest.mark.parametrize("window", [128, 256])
def test_the_ring_equals_a_banded_mask_with_the_kernel(kernel_path, window):
    """The window layer alone, decoded through a ring of ``window`` slots
    read by the kernel up to ``min(pos + 1, window)``, against the
    reference's ``T x T`` mask: a ring that wraps at half the episode, and
    one that the episode just fills."""
    model = lanes(layer_ids=(3,), sliding_window=window)
    spec = dict(LANES_SPEC, layers=((3, WINDOW),), window=window)
    params, tokens = params_of(11, spec), tokens_of(12, 3, LANES_EPISODE)
    logits, _ = jax.jit(lambda p, t: decode(model, p, t))(params, tokens)
    with jax.default_matmul_precision("highest"):
        want, _ = reference.forward(params, tokens, spec)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(logits - want).max()) < 2e-5 * scale
    if window < LANES_EPISODE:  # and a mask one wider is another function
        wider, _ = reference.forward(params, tokens, dict(spec, window=window + 1))
        assert float(jnp.abs(wider - want).max()) > 1e-3 * scale


@pytest.mark.parametrize("at", [(1, 100, 200), (129, 128, 127), (255, 3, 130)])
def test_a_fresh_token_forgets_the_episode_before_with_the_kernel(kernel_path, at):
    """Every env reset at a position of its own, so the envs stand at
    different positions (and lengths, and live blocks) from there on: each
    env's logits are those of two episodes, the second from its reset."""
    model = lanes()
    params, tokens = params_of(7, LANES_SPEC), tokens_of(8, 3, LANES_EPISODE)
    logits, _ = jax.jit(lambda p, t: decode(
        model, p, t, fresh_at=tuple(zip(at, range(3)))))(params, tokens)
    unroll = jax.jit(model.unroll)
    for env, a in enumerate(at):
        first, _ = unroll(params, tokens[env:env + 1, :a])
        second, _ = unroll(params, tokens[env:env + 1, a:])
        want = jnp.concatenate([first.logits, second.logits], axis=1)[0]
        assert float(jnp.abs(logits[env] - want).max()) < 1e-5 * float(
            jnp.abs(want).max()), env


# -- the side channels between layers ---------------------------------------------
@pytest.fixture(scope="module")
def layer_by_layer():
    """The unroll's layer loop by hand at float32: after each held layer the
    residual stream and the two side channels, the program's and the
    reference's."""
    model, params, tokens = tiny(), params_of(13), tokens_of(14)
    x = model._embed(params, tokens)
    rx = params["embed"]["table"][tokens]
    memory, shared, rmemory, rshared = None, (), None, None
    rows = []
    with jax.default_matmul_precision("highest"):
        for i, (layer_id, kind) in enumerate(SPEC["layers"]):
            p = params[f"layer_{layer_id}"]
            x, (memory, shared) = model._layer_unroll(i, p, x, memory, shared)
            rx, rmemory, rshared = reference._layer(
                layer_id, kind, SPEC, None, p, rx, rmemory, rshared)
            rows.append(dict(kind=kind, x=x, memory=memory, shared=shared,
                             rx=rx, rmemory=rmemory, rshared=rshared))
    return model, params, rows


@pytest.mark.parametrize("i", range(6))
def test_a_layers_output_is_the_references(layer_by_layer, i):
    row = layer_by_layer[2][i]
    assert row["kind"] == KINDS[i]
    np.testing.assert_allclose(row["x"], row["rx"], atol=2e-5 * float(
        jnp.abs(row["rx"]).max()))


def test_the_memory_units_read_exactly_layer_16s_y(layer_by_layer):
    model, params, rows = layer_by_layer
    # nothing is handed on before the layer at n / 2 (held index 2), whose y
    # (before the gate) it is from then on, unchanged by the layers after
    assert rows[0]["memory"] is None and rows[1]["memory"] is None
    for row in rows[2:]:
        np.testing.assert_allclose(row["memory"], rows[2]["rmemory"], atol=1e-5)
        assert row["memory"] is rows[2]["memory"]
    # the unit's output moves with that y and with nothing else of the side
    p, before = params["layer_6"], rows[3]
    out = lambda m, kv: model._layer_unroll(4, p, before["x"], m, kv)[0]  # noqa: E731
    base = out(before["memory"], before["shared"])
    assert float(jnp.abs(out(before["memory"] * 1.5, before["shared"]) - base).max()) > 1e-3
    np.testing.assert_array_equal(
        out(before["memory"], tuple(2.0 * x for x in before["shared"])), base)
    # and it is not the first Mamba layer's y
    assert float(jnp.abs(rows[2]["memory"] - reference._mamba(
        params["layer_2"], reference._ln(
            params["embed"]["table"][tokens_of(14)],
            params["layer_2"]["mix_norm"], params["layer_2"]["mix_norm_b"], 1e-5),
        SPEC, lambda x: x)[1]).max()) > 1e-3


def test_the_cross_layers_read_exactly_the_full_layers_kv(layer_by_layer):
    model, params, rows = layer_by_layer
    assert rows[2]["shared"] == ()
    k1, k2, v = rows[3]["rshared"]  # the reference's, of published layer 5
    for row in rows[3:]:
        k, vv = row["shared"]
        np.testing.assert_allclose(k, jnp.concatenate([k1, k2], -1), atol=1e-5)
        np.testing.assert_allclose(vv, v, atol=1e-5)
        assert row["shared"] is rows[3]["shared"] or row is rows[3]
    p, before = params["layer_7"], rows[4]
    out = lambda m, kv: model._layer_unroll(5, p, before["x"], m, kv)[0]  # noqa: E731
    base = out(before["memory"], before["shared"])
    k, vv = before["shared"]
    # (a value scaled everywhere would vanish in the RMSNorm over the pair)
    assert float(jnp.abs(out(before["memory"], (k, vv.at[:, 0].multiply(3.0)))
                         - base).max()) > 1e-3
    assert float(jnp.abs(out(before["memory"], (1.5 * k, vv)) - base).max()) > 1e-4
    np.testing.assert_array_equal(out(2.0 * before["memory"], before["shared"]), base)


# -- ops/ssm.py ---------------------------------------------------------------------
def _scan_inputs(seed, b=2, T=EPISODE, c=128, n=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (b, T, c))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, T, c)) - 1.0)
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (n, c)))
    B, C = jax.random.normal(ks[3], (b, T, n)), jax.random.normal(ks[4], (b, T, n))
    return u, dt, A, B, C, 1.0 + 0.1 * jax.random.normal(ks[5], (c,))


def _recurrence(u, dt, A, B, C, D):
    """The one-position recurrence written out, a Python loop over T."""
    s = jnp.zeros((u.shape[0], A.shape[0], u.shape[2]))
    ys = []
    for t in range(u.shape[1]):
        s = (jnp.exp(dt[:, t, None, :] * A) * s
             + (dt[:, t] * u[:, t])[:, None, :] * B[:, t, :, None])
        ys.append(jnp.einsum("bnc,bn->bc", s, C[:, t]) + D * u[:, t])
    return jnp.stack(ys, axis=1), s


@pytest.mark.parametrize("T,chunk", [(24, 8), (24, 64), (24, 1), (13, 4), (1, 64)])
def test_the_sequence_form_is_the_recurrence(T, chunk):
    args = _scan_inputs(T, T=T)
    y, last = jax.jit(lambda *a: ssm.selective_scan(*a, chunk=chunk))(*args)
    want_y, want_last = _recurrence(*args)
    np.testing.assert_allclose(y, want_y, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(last, want_last, atol=1e-5, rtol=1e-5)


def test_the_one_token_form_is_the_recurrence():
    u, dt, A, B, C, D = _scan_inputs(21)
    want_y, want_last = _recurrence(u, dt, A, B, C, D)
    s = jnp.zeros((2, 4, 128))
    for t in range(EPISODE):
        s, y = ssm.scan_step(s, u[:, t], dt[:, t], A, B[:, t], C[:, t], D)
        np.testing.assert_allclose(y, want_y[:, t], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(s, want_last, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("arg", range(6), ids=["u", "dt", "A", "B", "C", "D"])
@pytest.mark.parametrize("chunk", [8, 5])
def test_the_sequence_forms_gradient_is_the_recurrences(arg, chunk):
    """Through the checkpointed chunks (5 does not divide 24: chunks of 4)."""
    args = _scan_inputs(22)
    w = jax.random.normal(jax.random.PRNGKey(23), (2, EPISODE, 128))
    w_last = jax.random.normal(jax.random.PRNGKey(24), (2, 4, 128))

    def value(fn):
        def of(*a):
            y, last = fn(*a)
            return jnp.sum(y * w) + jnp.sum(last * w_last)
        return of

    got = jax.jit(jax.grad(value(
        lambda *a: ssm.selective_scan(*a, chunk=chunk)), argnums=arg))(*args)
    want = jax.grad(value(_recurrence), argnums=arg)(*args)
    np.testing.assert_allclose(
        got, want, atol=1e-4 * float(jnp.abs(want).max()), rtol=1e-4)


def test_the_sequence_forms_backward_keeps_no_state_a_position():
    """What the backward keeps between the forward and itself: the chunk
    boundaries' states, not ``[T, n, c]``."""
    b, T, c, n, chunk = 2, 64, 128, 4, 8
    args = _scan_inputs(25, b=b, T=T)
    _, vjp = jax.vjp(lambda *a: ssm.selective_scan(*a, chunk=chunk)[0], *args)
    kept = sum(x.size for x in jax.tree_util.tree_leaves(vjp)
               if hasattr(x, "size"))
    inputs = sum(x.size for x in args)
    assert ssm.chunk_length(T, chunk) == chunk
    assert kept < 3 * inputs + 2 * (T // chunk) * b * n * c
    assert kept < T * b * n * c  # one state a position alone would be more


# -- the fused step -----------------------------------------------------------------
def _fused(n_shards, n_envs=8, dtype=jnp.float32, grad_chunk_samples=48, seed=11,
           model=None, spec=SPEC):
    model = model or tiny(dtype)
    episode = model.max_positions
    env = RecallEnv(IDS, PROMPT, episode)
    cfg = BA3CConfig(num_actions=IDS, batch_size=n_envs * episode // n_shards)
    opt = make_optimizer(HYPER["learning_rate"], HYPER["adam_epsilon"],
                         HYPER["grad_clip_norm"])
    mesh = make_mesh(num_data=n_shards, num_model=1,
                     devices=jax.devices()[:n_shards])
    step = make_fused_step(model, opt, cfg, mesh, env, episode,
                           grad_chunk_samples=grad_chunk_samples)
    state = create_fused_state(jax.random.PRNGKey(seed), model, cfg, opt, env,
                               n_envs, n_shards=n_shards)
    params = params_of(seed, spec)
    state = state.replace(train=state.train.replace(params=params))
    return env, cfg, model, step, state, jax.device_get(params)


@pytest.fixture(scope="module", params=[1, 2], ids=["one-device", "two-shards"])
def one_update(request):
    """One fused update in float32 (chunks of 2 envs) and what the reference
    makes of the same start and the same actions."""
    import optax

    n_shards = request.param
    env, cfg, model, step, state, params = _fused(n_shards)
    per = 8 // n_shards
    env_state0 = jax.device_get(state.env_state)
    keys = [np.asarray(jax.random.key_data(k)) if jnp.issubdtype(
        k.dtype, jax.dtypes.prng_key) else np.asarray(k) for k in state.key]
    new, metrics = step(step.put(state), HYPER["entropy_beta"],
                        HYPER["learning_rate"])
    actions = np.stack([np.asarray(metrics["actions"])[:, s * per:(s + 1) * per]
                        for s in range(n_shards)])
    mu = optax.tree_utils.tree_get(new.train.opt_state, "mu")
    grad = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - 0.9), mu)
    numbers = {k: float(v) for k, v in HYPER.items()}
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for s in range(n_shards):
            env_state = {k: v[s * per:(s + 1) * per]
                         for k, v in env_state0._asdict().items()}
            l, g, *_ = reference._shard_pass(
                params, env_state, jax.vmap(ref_recall.shown)(env_state),
                jnp.asarray(keys[s]), jnp.asarray(actions[s]), numbers,
                reference._spec_key(SPEC), None, 2)
            loss = loss + l
            grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
        n = 8.0 * EPISODE
        clipped = reference.clip_by_global_norm(
            jax.tree_util.tree_map(lambda g: g / n, grads), HYPER["grad_clip_norm"])
    return dict(n_shards=n_shards, params=params, new=new, metrics=metrics,
                grad=grad, reference=(float(loss) / n, clipped), model=model)


def test_the_fused_steps_gradient_is_the_references(one_update):
    loss, want = one_update["reference"]
    assert abs(float(one_update["metrics"]["loss"]) - loss) < 2e-4
    for layer, leaves in want.items():
        for leaf, g in leaves.items():
            got = one_update["grad"][layer][leaf]
            scale = max(float(jnp.abs(g).max()), 1e-4)
            np.testing.assert_allclose(
                got, g, atol=2e-3 * scale, err_msg=f"{layer}/{leaf}")


def test_a_fused_update_moves_the_state_and_reports_its_carry(one_update):
    new, metrics, n_shards, model = (
        one_update[k] for k in ("new", "metrics", "n_shards", "model"))
    assert int(metrics["episodes"]) == 8  # every env ended its episode
    tokens, actions = (np.asarray(metrics[k]) for k in ("tokens", "actions"))
    assert tokens.shape == actions.shape == (EPISODE, 8)
    np.testing.assert_array_equal(tokens[PROMPT + 1:], actions[PROMPT:-1])
    # the gauges: the carry's bytes by kind (a constant of the shapes) and
    # the largest |s| the rollout left, whichever shard holds it
    assert np.asarray(metrics["carry_bytes_per_env"]).tolist() == list(
        model.carry_bytes())
    held, fresh = new.policy_carry
    largest = max(float(jnp.abs(state).max()) for state, _ in held.ssm)
    assert float(metrics["ssm_state_absmax"]) == pytest.approx(largest) and largest > 0
    assert np.asarray(fresh).all() and held.pos.tolist() == [EPISODE] * 8
    assert len(held.pos.sharding.device_set) == n_shards
    stats = model.epoch_stats({k: np.asarray(v) for k, v in metrics.items()})
    assert stats == {"ssm_state_absmax": pytest.approx(largest),
                     "carry_bytes_per_env": float(sum(model.carry_bytes()))}
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), new.train.params,
        one_update["params"])
    for layer, leaf in (("layer_2", "A_log"), ("layer_4", "x_proj"),
                        ("layer_5", "wqkv"), ("layer_6", "gmu_in"),
                        ("layer_7", "lam_q1"), ("embed", "table")):
        assert moved[layer][leaf] > 0, (layer, leaf)


def test_the_trainer_names_no_policys_counter():
    import inspect

    from distributed_ba3c_tpu.fused import loop

    source = inspect.getsource(loop)
    for name in ("moe_", "ssm_", "carry_bytes"):
        assert name not in source, name


@pytest.fixture(scope="module")
def compiled_op_names():
    _, _, _, step, state, _ = _fused(1, dtype=jnp.bfloat16)
    hlo = step.audit_jit.lower(
        step.put(state), jnp.float32(0.01), jnp.float32(1e-3)
    ).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', hlo))


#: open only round the Pallas kernel of the decode's attention, which this
#: small step (pairs of 16 lanes, on the CPU) does not reach
_BY_KERNEL = tuple(profiling.policy_scope(under, layer)
                   for under in (profiling.ROLLOUT_POLICY, profiling.LEARNER)
                   for layer in profiling.OP_ATTN_DECODE)


@pytest.mark.parametrize("scope", profiling.SEQUENCE_SCOPES)
def test_a_sequence_scope_is_in_the_compiled_step_if_it_is_this_policys(
        compiled_op_names, scope):
    found = {profiling.scope_of(name) for name in compiled_op_names}
    there = any(s is not None and (s == scope or s.startswith(scope + "/"))
                for s in found)
    mine = scope == profiling.ROLLOUT_WEIGHTS_BF16 or any(
        scope == profiling.policy_scope(under, layer)
        for under in (profiling.ROLLOUT_POLICY, profiling.LEARNER)
        for layer in profiling.PHI4_FLASH_LAYERS)
    assert there == (mine and scope not in _BY_KERNEL), scope


def test_the_kernels_scope_is_in_the_rollout_where_the_kernel_is_lowered(kernel_path):
    """``rollout/policy/op_attn_{window,full,cross}/decode_attend`` holds the
    Pallas call of a decode whose buffers are whole lanes wide (time there
    says the rows up to the position were read); the bootstrap's one decode
    step has it under ``returns``; the learner unrolls whole episodes and
    never reaches it. (Pallas's interpreter binds primitives on operands of
    mixed varying axes, which ``shard_map``'s typing refuses: off here, as
    tests/test_lfm2_moe.py has it.)"""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "shard_map", functools.partial(
            jax.shard_map, check_vma=False))
        _, _, _, step, state, _ = _fused(
            1, n_envs=2, grad_chunk_samples=LANES_WINDOW,
            model=lanes(max_positions=LANES_WINDOW), spec=LANES_SPEC)
        hlo = step.audit_jit.lower(
            step.put(state), jnp.float32(0.01), jnp.float32(1e-3)
        ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    found = {profiling.scope_of(n) for n in names}
    for scope in _BY_KERNEL:
        assert (scope in found) == scope.startswith(profiling.ROLLOUT_POLICY), scope
    assert any(profiling.scope_of(n) == profiling.RETURNS
               and profiling.DECODE_ATTEND in n for n in names)


def test_the_learners_scan_is_marked_forward_and_backward(compiled_op_names):
    scan = {n for n in compiled_op_names if profiling.scope_of(n) ==
            profiling.policy_scope(profiling.LEARNER, profiling.OP_SSM_SCAN)}
    assert any(profiling.is_backward(n) for n in scan)
    assert any(not profiling.is_backward(n) for n in scan)


# -- the refusals and the registry ------------------------------------------------
def test_a_segment_that_starts_mid_episode_is_refused():
    env = RecallEnv(IDS, PROMPT, EPISODE)
    cfg = BA3CConfig(num_actions=IDS, batch_size=64)
    opt = make_optimizer(1e-3, 1e-3, 0.5)
    mesh = make_mesh(num_data=1, num_model=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="episode length"):
        make_fused_step(tiny(), opt, cfg, mesh, env, rollout_len=8)


@pytest.mark.parametrize("argv", [
    ["--task", "train", "--trainer", "tpu_sync_ba3c", "--env", "fake"],
    ["--task", "eval", "--env", "jax:recall"],
])
def test_the_cli_refuses_the_policy_off_the_fused_trainer(argv, capsys):
    from distributed_ba3c_tpu import cli

    with pytest.raises(SystemExit) as e:
        cli.main(argv + ["--model", "phi4-flash", "--model_cut", "tiny"])
    assert e.value.code == 2
    assert "carries state" in capsys.readouterr().err


def test_the_registry_builds_by_name():
    cfg = BA3CConfig(num_actions=IDS)
    model = policy.build_model("phi4-flash", cfg, "tiny")
    assert isinstance(model, Phi4Flash) and policy.carries_state(model)
    assert model.hidden_size == 64 and model.num_actions == IDS
    whole = policy.build_model("phi4-flash", cfg)
    assert whole.hidden_size == 2560 and whole.layer_ids == (14, 15, 16, 17, 18, 19)
    assert policy.build_model("phi4-flash", cfg, "stage-14-19") == whole
    env = RecallEnv(IDS, PROMPT, EPISODE)
    assert whole.for_env(env) == dataclasses.replace(
        whole, num_actions=IDS, max_positions=EPISODE)
    with pytest.raises(ValueError, match="model_cut"):
        policy.build_model("phi4-flash", cfg, "chip-share-4")
