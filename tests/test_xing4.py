"""Xing4.0-29B-A4B as a token-sequence policy, at a size the CPU runs (hidden
32 in 4 residual streams, 2 of 8 heads of 8 + 8 over a latent of 16, 2 of 16
experts of width 24 beside a shared expert, vocabulary 32, episodes of 24,
three times YaRN's original length): the model against the benchmark's
plain reference (logits, value, loss, every gradient leaf) on both paths of
the expert layer, the decode's absorbed attention through the carry against
the reference's expanded one, the hyper-connections alone, the shares of
heads, experts and vocabulary adding up to the uncut layer and head, the
fused step through ``cli.py``'s parser, the scopes, the refusals.
"""

import dataclasses
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

from benchmark.reference import recall as ref_recall, xing4 as reference  # noqa: E402
from benchmark.reference.ba3c import clip_by_global_norm  # noqa: E402
from distributed_ba3c_tpu import cli  # noqa: E402
from distributed_ba3c_tpu.config import BA3CConfig  # noqa: E402
from distributed_ba3c_tpu.envs import jaxenv  # noqa: E402
from distributed_ba3c_tpu.envs.jaxenv.recall import RecallEnv  # noqa: E402
from distributed_ba3c_tpu.fused.loop import (  # noqa: E402
    create_fused_state,
    make_fused_step,
)
from distributed_ba3c_tpu.models import layers, policy, xing4  # noqa: E402
from distributed_ba3c_tpu.models.xing4 import (  # noqa: E402
    ATTN, CUTS, DENSE, EXPERTS, FFN, Xing4)
from distributed_ba3c_tpu.ops import hyper_connection as hc, moe  # noqa: E402
from distributed_ba3c_tpu.ops.gradproc import make_optimizer  # noqa: E402
from distributed_ba3c_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

IDS, PROMPT, EPISODE = 32, 4, 24
EXPERT_SHARES, HEAD_SHARES = 8, 4  # chips that share a layer's experts, heads
#: the configuration's keys at the small cut, as the reference reads them
TINY_CONFIG = {
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 24,
    "num_attention_heads": 2, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8,
    "n_routed_experts": 2, "num_experts_per_tok": 3, "n_shared_experts": 1,
    "first_k_dense_replace": 2, "routed_scaling_factor": 2,
    "rms_norm_eps": 1e-6, "rope_theta": 100.0,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 4.0,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 8, "type": "yarn"},
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "hidden_act": "silu", "attention_bias": False, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "tie_word_embeddings": False, "moe_layer_freq": 1,
    "vocab_size": IDS,
    "published": {"n_routed_experts": 16},
    "held": {"layers": [0, 2, 3], "expert_offset": 0},
}
SPEC = reference.spec_of(TINY_CONFIG)
#: the same layers with every expert and every head: what the shares add up to
UNCUT_SPEC = dict(SPEC, experts=EXPERT_SHARES * SPEC["experts"],
                  heads=HEAD_SHARES * SPEC["heads"])
HYPER = {"gamma": 0.99, "entropy_beta": 0.01, "value_loss_coef": 0.5,
         "grad_clip_norm": 0.5, "learning_rate": 1e-3, "adam_epsilon": 1e-3}
#: bfloat16 at a hidden size of 32 is coarse: a fifth of the largest logit
TOLERANCE = [(jnp.float32, 2e-4), (jnp.bfloat16, 0.2)]
#: envs whose whole episodes are few enough tokens for ``DENSE_ROWS`` (every
#: held expert computes every token) and enough for the sorted rows
ENVS = {"every-token": 3, "sorted-rows": 12}


def tiny(compute_dtype=jnp.float32, **kw) -> Xing4:
    fields = dict(CUTS["tiny"], num_actions=IDS, max_positions=EPISODE,
                  compute_dtype=compute_dtype)
    return Xing4(**dict(fields, **kw))


def params_of(seed, spec=SPEC):
    """The reference's seeded weights with every vector moved off its start
    (unit gains and zero biases hide a wrong reading)."""
    params = reference.init_params(jax.random.PRNGKey(seed), spec)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1000), 256))
    return {layer: {leaf: x + 0.1 * jax.random.normal(next(keys), x.shape)
                    if x.ndim == 1 and leaf not in ("bias", "expert_bias") else x
                    for leaf, x in leaves.items()}
            for layer, leaves in params.items()}


def tokens_of(seed, batch=3, length=EPISODE):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length), 0, IDS)


def decode(model, params, tokens, fresh_at=(), carry=None):
    """Token by token through the carry -> logits, value [B, T, ...]; the
    positions in ``fresh_at`` open a new episode."""
    B, T = tokens.shape
    fresh = jnp.zeros((T, B), bool).at[0].set(True)
    for t in fresh_at:
        fresh = fresh.at[t].set(True)

    def one(carry, x):
        out, carry = model.step(params, x[0], carry, x[1])
        return carry, (out.logits, out.value)

    carry, (logits, value) = jax.lax.scan(
        one, model.init_carry(B) if carry is None else carry,
        (jnp.swapaxes(tokens, 0, 1), fresh))
    return jnp.swapaxes(logits, 0, 1), jnp.swapaxes(value, 0, 1), carry


# -- the architecture as the configuration states it ----------------------------
def test_the_published_share_and_the_cuts():
    whole = Xing4()
    assert whole.layer_ids == (0, 2, 3, 4, 5)
    assert whole.layer_kinds == (DENSE, EXPERTS, EXPERTS, EXPERTS, EXPERTS)
    assert (whole.hidden_size, whole.hc_mult, whole.hc_sinkhorn_iters) == (3584, 4, 20)
    assert (whole.q_lora_rank, whole.kv_lora_rank, whole.qk_nope_head_dim,
            whole.qk_rope_head_dim, whole.v_head_dim) == (768, 512, 128, 64, 128)
    assert (whole.heads_held, whole.num_attention_heads) == (4, 32)
    assert (whole.experts_held, whole.n_routed_experts) == (8, 64)
    # a cache row: 512 + 64 numbers in 5 whole lanes of 128
    assert whole.row_width == 640
    m = 0.1 * np.log(64.0) + 1.0
    assert whole.attention_scale == pytest.approx(m * m / np.sqrt(192.0))
    assert list(CUTS) == ["ep8-heads8-vocab8", "tiny"] and not CUTS["ep8-heads8-vocab8"]
    assert xing4.cut_fields(None) == {} and xing4.cut_fields("tiny") == CUTS["tiny"]
    small = tiny()
    assert small.layer_kinds == (DENSE, EXPERTS, EXPERTS) and small.row_width == 128
    # the carry: 5 layers x 2,048 rows x 640 lanes of bfloat16, and the position
    assert whole.carry_bytes() == (5 * 2048 * 640 * 2, 4)


def test_yarn_keeps_the_fast_frequencies_and_slows_the_rest():
    """The published rule at the published numbers: dimensions 0-10 turn
    more than 32 times in 4,096 positions and keep ``theta``'s frequency,
    23-31 turn less than once and are slowed 64 times, a ramp between; the
    reference computes the same; the small cut's episode crosses its
    original length, with one frequency kept and three slowed."""
    freq = np.asarray(layers.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0))
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freq[23:], plain[23:] / 64.0, rtol=1e-6)
    assert np.all(freq[11:23] < plain[11:23]) and np.all(
        freq[11:23] > plain[11:23] / 64.0)
    published = dict(SPEC, rope=64, theta=10000.0, factor=64.0, original=4096)
    np.testing.assert_allclose(
        freq, reference.yarn_frequencies(published), rtol=1e-6)
    small = np.asarray(tiny().rope_frequencies())
    np.testing.assert_allclose(small, reference.yarn_frequencies(SPEC), rtol=1e-6)
    base = 100.0 ** (-np.arange(4) / 4.0)
    np.testing.assert_allclose(small, base / np.array([1, 4, 4, 4]), rtol=1e-6)
    assert EPISODE > SPEC["original"]
    assert layers.yarn_attention_scale(1.0, 1.0) == 1.0


def test_the_programs_parameters_are_the_references():
    ours = tiny().init_params(jax.random.PRNGKey(3))
    theirs = reference.init_params(jax.random.PRNGKey(3), SPEC)
    assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs), strict=True):
        np.testing.assert_array_equal(a, b)
    layer = ours["layer_2"]
    assert layer["attn_hc_phi"].shape == (24, 4 * 32)
    assert layer["wq_b"].shape == (24, 2 * 16) and layer["wkv_b"].shape == (16, 2 * 16)
    assert layer["wkv_a"].shape == (32, 16 + 8) and layer["wo"].shape == (16, 32)
    assert layer["router"].shape == (32, 16) and layer["w1"].shape == (2, 32, 24)
    assert ours["layer_0"]["w1"].shape == (32, 48) and "router" not in ours["layer_0"]


def test_the_held_parameter_count_at_the_published_share():
    shapes = jax.eval_shape(Xing4().init_params, jax.random.PRNGKey(0))
    per_layer = {name: sum(int(np.prod(x.shape)) for x in leaves.values())
                 for name, leaves in shapes.items()}
    mla = 3584 * 768 + 768 + 768 * 4 * 192 + 3584 * 576 + 512 + 512 * 4 * 256 + 512 * 3584
    mhc = 2 * (24 * 4 * 3584 + 3 + 4 + 4 + 16)
    dense = 3 * 3584 * 9216
    experts = 3584 * 64 + 64 + 9 * 3 * 3584 * 1024
    assert per_layer["layer_0"] == mla + mhc + 2 * 3584 + dense
    assert per_layer["layer_2"] == mla + mhc + 2 * 3584 + experts
    assert sum(per_layer.values()) == 656_130_831


# -- against the reference ------------------------------------------------------------
@pytest.mark.parametrize("path", sorted(ENVS))
@pytest.mark.parametrize("dtype,tol", TOLERANCE)
def test_unroll_agrees_with_the_reference(path, dtype, tol):
    params, tokens = params_of(1), tokens_of(2, ENVS[path])
    assert (tokens.size > moe.DENSE_ROWS) == (path == "sorted-rows")
    out, aux = jax.jit(lambda p, t: tiny(dtype).unroll(p, t, with_routes=True))(
        params, tokens)
    with jax.default_matmul_precision("highest"):
        # with the routes the program chose: in bfloat16 a near-tie may flip
        logits, value, routes, off = reference.forward(
            params, tokens, SPEC, forced_routes=aux["routes"])
    scale = float(jnp.abs(logits).max())
    np.testing.assert_allclose(out.logits, logits, atol=tol * scale)
    np.testing.assert_allclose(out.value, value, atol=tol * max(
        float(jnp.abs(value).max()), 1e-3))
    if dtype == jnp.float32:
        np.testing.assert_array_equal(
            jnp.sort(aux["routes"], -1), jnp.sort(routes, -1))
    assert aux["moe_tokens_per_expert"].shape == (2, 2)
    assert int(aux["moe_overflow_blocks"].sum()) == 0
    gap_sum, mappings = (float(x) for x in aux["mhc_doubly_stochastic_gap"])
    assert mappings == 2 * 3 * tokens.size and 0 < gap_sum / mappings < 0.05
    if dtype == jnp.float32:  # the reference's own count of the same
        assert gap_sum == pytest.approx(float(off), rel=1e-2)


def _loss(forward):
    def loss(params, tokens, actions, returns):
        logits, value = forward(params, tokens)
        logp = jax.nn.log_softmax(logits)
        logp_a = jnp.take_along_axis(logp, actions[..., None], -1)[..., 0]
        adv = returns - jax.lax.stop_gradient(value)
        return (-jnp.sum(logp_a * adv) + 0.25 * jnp.sum(jnp.square(value - returns))
                + 0.01 * jnp.sum(jnp.exp(logp) * logp))
    return loss


@pytest.fixture(scope="module", params=sorted(ENVS))
def both_gradients(request):
    """(the program's loss and gradient, the reference's) in float32."""
    params, tokens = params_of(4), tokens_of(5, ENVS[request.param])
    actions = tokens_of(6, ENVS[request.param])
    returns = jax.random.normal(jax.random.PRNGKey(7), tokens.shape)
    model = tiny()
    ours = jax.jit(jax.value_and_grad(_loss(
        lambda p, t: model.unroll(p, t)[0])))(params, tokens, actions, returns)
    with jax.default_matmul_precision("highest"):
        theirs = jax.value_and_grad(_loss(
            lambda p, t: reference.forward(p, t, SPEC)[:2]))(
                params, tokens, actions, returns)
    return ours, theirs


_LEAVES = sorted(
    f"{layer}/{leaf}" for layer, leaves in jax.eval_shape(
        lambda: reference.init_params(jax.random.PRNGKey(0), SPEC)).items()
    if layer in ("embed", "layer_0", "layer_3", "final", "head", "value")
    for leaf in leaves)


def test_the_loss_is_the_references(both_gradients):
    (ours, _), (theirs, _) = both_gradients
    assert float(ours) == pytest.approx(float(theirs), rel=1e-5, abs=1e-4)


@pytest.mark.parametrize("name", _LEAVES)
def test_a_leafs_gradient_of_the_loss_is_the_references(both_gradients, name):
    (_, ours), (_, theirs) = both_gradients
    layer, leaf = name.split("/")
    want = theirs[layer][leaf]
    if leaf == "expert_bias":  # it only chooses
        assert not np.asarray(ours[layer][leaf]).any() and not np.asarray(want).any()
        return
    assert float(jnp.abs(want).max()) > 0, "nothing to compare"
    # (a leaf whose whole gradient is 1e-5 is compared on float32's floor of
    # a loss whose other leaves' are 1: layer 0 reads four equal streams)
    np.testing.assert_allclose(
        ours[layer][leaf], want,
        atol=5e-4 * max(float(jnp.abs(want).max()), 1e-2))


@pytest.mark.parametrize("dtype,tol", TOLERANCE)
def test_the_absorbed_decode_is_the_references_expanded_forward(dtype, tol):
    """Every position of whole episodes through the carry (the latent rows,
    ``W_kvb`` taken into the query and out of the attended latent), a fresh
    episode opened in the middle, against the reference's forward, which
    expands every key and value: YaRN past its original length included."""
    model = tiny(dtype)
    params, tokens = params_of(8), tokens_of(9)
    logits, value, carry = jax.jit(
        lambda p, t: decode(model, model.rollout_params(p), t, fresh_at=(10,)))(
            params, tokens)
    with jax.default_matmul_precision("highest"):
        head, head_v, *_ = reference.forward(params, tokens[:, :10], SPEC)
        tail, tail_v, *_ = reference.forward(params, tokens[:, 10:], SPEC)
    want = jnp.concatenate([head, tail], 1)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(logits, want, atol=tol * scale)
    np.testing.assert_allclose(
        value, jnp.concatenate([head_v, tail_v], 1),
        atol=tol * max(float(jnp.abs(tail_v).max()), 1e-3))
    assert carry.pos.tolist() == [EPISODE - 10] * 3
    assert all(c.dtype == dtype and c.shape == (3, EPISODE, 128)
               for c in carry.latent)
    # a row is [c | k_r | zeros]: the padding is never written
    assert not np.asarray(carry.latent[0][..., 24:], np.float32).any()
    assert np.asarray(carry.latent[0][:, :14, :24], np.float32).any()


def test_the_decode_forms_no_key_and_no_value_of_a_head():
    """The step's program has no array of a head's keys or values over the
    cache's positions: it attends over ``[B, P, row_width]`` as it lies."""
    model = tiny()
    params = model.init_params(jax.random.PRNGKey(0))
    text = str(jax.make_jaxpr(model.step)(
        params, jnp.zeros(3, jnp.int32), model.init_carry(3),
        jnp.zeros(3, bool)))
    H, nope, v = model.heads_held, model.qk_nope_head_dim, model.v_head_dim
    for width in (nope, v, nope + v, nope + model.qk_rope_head_dim):
        assert f"[3,{EPISODE},{H},{width}]" not in text
        assert f"[3,{H},{EPISODE},{width}]" not in text
    assert f"[3,{EPISODE},128]" in text


@pytest.mark.parametrize("at", [1, 9, 17])
def test_a_fresh_token_forgets_the_episode_before(at):
    """``fresh`` resets the position; the latent rows an older episode left
    at and past it are under the mask and move no logit."""
    model = tiny()
    params = params_of(10)
    tokens = tokens_of(11)
    alone, _, _ = decode(model, params, tokens[:, at:])
    after, _, _ = decode(model, params, tokens, fresh_at=(at,))
    np.testing.assert_allclose(after[:, at:], alone, atol=1e-5)
    # rows of garbage everywhere: a reset episode reads none of them
    dirty = model.init_carry(3)
    dirty = dirty._replace(
        pos=jnp.full((3,), 7, jnp.int32),
        latent=tuple(jnp.full_like(c, 3.0) for c in dirty.latent))
    over, _, _ = decode(model, params, tokens[:, at:], carry=dirty)
    np.testing.assert_allclose(over, alone, atol=1e-5)


# -- the hyper-connections alone ------------------------------------------------------
def _hc_leaves(seed, n=4, d=32, gates=(1.0, 1.0, 1.0), diagonal=0.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {
        "phi": jax.random.normal(k[0], (2 * n + n * n, n * d)) / np.sqrt(n * d),
        "alpha": jnp.asarray(gates, jnp.float32),
        "b_pre": 0.3 * jax.random.normal(k[1], (n,)),
        "b_post": 0.3 * jax.random.normal(k[2], (n,)),
        "b_res": diagonal * jnp.eye(n) + 0.3 * jax.random.normal(k[3], (n, n)),
    }


def _streams(X):
    """[..., n, d] -> the program's n arrays [N, d]."""
    return tuple(X[..., j, :].reshape(-1, X.shape[-1]) for j in range(X.shape[-2]))


def test_the_mixing_matrix_is_doubly_stochastic_after_twenty_iterations():
    x = _streams(jax.random.normal(jax.random.PRNGKey(20), (64, 4, 32)))
    h = hc.mappings(x, _hc_leaves(21), 20, 1e-6, (-30.0, 30.0))
    assert h.pre.shape == h.post.shape == (4, 64) and h.res.shape == (4, 4, 64)
    np.testing.assert_allclose(jnp.sum(h.res, axis=1), 1.0, atol=1e-4)  # rows
    np.testing.assert_allclose(jnp.sum(h.res, axis=0), 1.0, atol=1e-4)  # columns
    assert float(hc.doubly_stochastic_gap(h.res).max()) < 1e-4
    assert float(h.res.min()) > 0
    assert float(h.pre.min()) > 0 and float(h.pre.max()) < 1
    assert float(h.post.min()) > 0 and float(h.post.max()) < 2
    # five iterations leave a gap twenty close
    few = hc.mappings(x, _hc_leaves(21), 5, 1e-6, (-30.0, 30.0))
    assert float(hc.doubly_stochastic_gap(few.res).max()) > 10 * float(
        hc.doubly_stochastic_gap(h.res).max())
    # the mappings vary by token: the dynamic part is not a constant
    assert float(jnp.std(h.pre, axis=1).min()) > 0.01


def test_the_clamp_bounds_the_logits_before_the_exponential():
    x = _streams(jax.random.normal(jax.random.PRNGKey(22), (8, 4, 32)))
    leaves = dict(_hc_leaves(23), b_res=1e4 * (jnp.eye(4) - 0.5))
    h = hc.mappings(x, leaves, 20, 1e-6, (-30.0, 30.0))
    assert np.isfinite(np.asarray(h.res)).all()
    np.testing.assert_allclose(h.res[jnp.arange(4), jnp.arange(4)], 1.0, atol=1e-6)


def test_the_mappings_and_the_mix_are_the_references():
    model, p = tiny(), params_of(24)["layer_2"]
    X = jax.random.normal(jax.random.PRNGKey(25), (2, EPISODE, 4, 32))
    y = jax.random.normal(jax.random.PRNGKey(26), (2, EPISODE, 32))
    with jax.default_matmul_precision("highest"):
        pre, post, res = reference.mappings(p, FFN, X, SPEC)
        want, _, _ = reference.sub_block(p, FFN, X, lambda z: (y, None), SPEC)
    got, _, ours = model._hyper(
        p, FFN, _streams(X), lambda u: (y.reshape(-1, 32), None))
    np.testing.assert_allclose(
        jnp.moveaxis(ours, -1, 0).reshape(res.shape), res, atol=1e-5)
    np.testing.assert_allclose(
        jnp.stack(got, 1).reshape(X.shape), want, atol=1e-4)


def test_with_no_gates_and_a_large_diagonal_a_sub_block_is_the_plain_residual():
    """``a = 0`` and ``b_res`` large on the diagonal: ``H_res`` is the
    identity and a sub-block is ``X[i] + H_post[i] y``."""
    n, d = 4, 32
    X = jax.random.normal(jax.random.PRNGKey(27), (16, n, d))
    x = _streams(X)
    y = jax.random.normal(jax.random.PRNGKey(28), (16, d))
    leaves = dict(_hc_leaves(29, gates=(0.0, 0.0, 0.0)), b_res=40.0 * jnp.eye(n))
    h = hc.mappings(x, leaves, 20, 1e-6, (-30.0, 30.0))
    np.testing.assert_allclose(
        h.res, jnp.broadcast_to(jnp.eye(n)[:, :, None], h.res.shape), atol=1e-6)
    post = 2.0 * jax.nn.sigmoid(leaves["b_post"])
    want = X + post[None, :, None] * y[:, None, :]
    np.testing.assert_allclose(jnp.stack(hc.write(x, h, y), 1), want, atol=1e-5)
    pre = jax.nn.sigmoid(leaves["b_pre"])
    np.testing.assert_allclose(
        hc.read(x, h), jnp.einsum("j,njd->nd", pre, X), atol=1e-5)


def test_the_streams_go_in_as_copies_and_come_out_as_their_sum():
    model = tiny()
    x = jax.random.normal(jax.random.PRNGKey(30), (5, 32))
    streams = model.streams_in(x)
    assert len(streams) == 4 and streams[0].dtype == jnp.float32
    for s in streams:
        np.testing.assert_array_equal(s, x)
    np.testing.assert_allclose(model.streams_out(streams), 4 * x, rtol=1e-6)
    # the five policies of one stream take the scaffold's, which adds nothing
    other = policy.build_model("lfm2-moe", BA3CConfig(num_actions=IDS), "tiny")
    assert other.streams_in(x) is x and other.streams_out(x) is x


def test_streams_kept_in_bfloat16_decode_another_answer():
    """The benchmark's control ``streams_bf16`` is a different program."""
    params, tokens = params_of(31), tokens_of(32)
    sound, _, _ = decode(tiny(), params, tokens)
    lower, _, _ = decode(tiny(stream_dtype=jnp.bfloat16), params, tokens)
    gap = float(jnp.abs(sound - lower).max()) / float(jnp.abs(sound).max())
    assert 1e-4 < gap < 0.3


# -- the shares add up --------------------------------------------------------------
@pytest.fixture(scope="module")
def uncut():
    """Layer 2 (an expert layer) with all 8 heads and all 16 experts, its
    streams and a sub-block's normed input."""
    p = params_of(40, UNCUT_SPEC)["layer_2"]
    X = jax.random.normal(jax.random.PRNGKey(41), (2, EPISODE, 4, 32))
    return p, X, jax.random.normal(jax.random.PRNGKey(42), (2, EPISODE, 32))


def _heads_of(p, s, model):
    """Share ``s``'s columns of ``W_qb`` and ``W_kvb`` and rows of ``W_o``."""
    h = model.heads_held
    take = lambda w, axis: jnp.take(  # noqa: E731
        w, jnp.arange(s * h, (s + 1) * h), axis=axis)
    cols = lambda w: take(w.reshape(w.shape[0], HEAD_SHARES * h, -1), 1).reshape(  # noqa: E731
        w.shape[0], -1)
    return dict(p, wq_b=cols(p["wq_b"]), wkv_b=cols(p["wkv_b"]),
                wo=take(p["wo"].reshape(HEAD_SHARES * h, -1, p["wo"].shape[1]),
                        0).reshape(-1, p["wo"].shape[1]))


def test_four_head_shares_attention_outputs_are_the_uncut_attention(uncut):
    p, _, u = uncut
    with jax.default_matmul_precision("highest"):
        whole = reference.attention(
            p, reference._rms(u, p["attn_norm"], SPEC["eps"]), UNCUT_SPEC)
    model = tiny()
    total = sum(model.attention(_heads_of(p, s, model), u)
                for s in range(HEAD_SHARES))
    np.testing.assert_allclose(total, whole, atol=2e-5 * float(jnp.abs(whole).max()))
    # and the uncut policy's own attention is the reference's
    np.testing.assert_allclose(
        tiny(heads_held=8).attention(p, u), whole,
        atol=2e-5 * float(jnp.abs(whole).max()))


@pytest.mark.parametrize("path", sorted(ENVS))
def test_eight_expert_shares_with_the_shared_expert_once_are_the_uncut_layer(
        uncut, path):
    p, _, u = uncut
    u = jnp.concatenate([u] * (ENVS[path] // 2 + 1))[:ENVS[path]]
    rows = u.reshape(-1, 32)
    with jax.default_matmul_precision("highest"):
        z = reference._rms(u, p["ffn_norm"], SPEC["eps"])
        whole, routes = reference.feed_forward(EXPERTS, p, z, UNCUT_SPEC)
        shared = reference._swiglu(
            z, p["shared_w1"], p["shared_w3"], p["shared_w2"], lambda a: a)
    total, counted = 0.0, 0
    for s in range(EXPERT_SHARES):
        held = dict(p, **{w: p[w][2 * s:2 * s + 2] for w in ("w1", "w3", "w2")})
        mixed, (counts, chosen, _) = tiny(expert_offset=2 * s).feed_forward(
            EXPERTS, held, rows)
        total = total + (mixed.reshape(u.shape) - shared)
        counted += int(counts.sum())
        np.testing.assert_array_equal(
            jnp.sort(chosen, -1), jnp.sort(routes.reshape(-1, 3), -1))
    assert counted == rows.shape[0] * 3  # every assignment is some chip's
    np.testing.assert_allclose(
        total + shared, whole, atol=2e-5 * float(jnp.abs(whole).max()))


def test_a_whole_sub_block_is_rebuilt_from_its_shares_with_the_mix_once(uncut):
    """Every chip computes the mappings and ``H_res X`` alike; a chip's
    sub-block adds ``H_post`` times ITS part of ``y``. The shares' streams
    minus the mixed streams counted ``shares - 1`` times are the uncut
    sub-block's."""
    p, X, _ = uncut
    with jax.default_matmul_precision("highest"):
        whole, _, _ = reference.sub_block(
            p, ATTN, X,
            lambda z: (reference.attention(p, z, UNCUT_SPEC), None), SPEC)
    model = tiny()
    flat = _streams(X)
    attend = lambda held: lambda u: (model.attention(  # noqa: E731
        held, u.reshape(2, EPISODE, -1)).reshape(-1, 32), None)
    stacked = lambda out: jnp.stack(out[0], 1).reshape(X.shape)  # noqa: E731
    mixed = stacked(model._hyper(
        p, ATTN, flat, lambda u: (jnp.zeros_like(u), None)))
    total = sum(stacked(model._hyper(p, ATTN, flat, attend(_heads_of(p, s, model))))
                for s in range(HEAD_SHARES)) - (HEAD_SHARES - 1) * mixed
    np.testing.assert_allclose(
        total, whole, atol=5e-5 * float(jnp.abs(whole).max()))


def test_eight_vocabulary_slices_logits_are_the_uncut_heads():
    params = params_of(43)
    x = jax.random.normal(jax.random.PRNGKey(44), (5, 32))
    whole = tiny()._head(params, x)
    per = IDS // 8
    for s in range(8):
        table = params["head"]["table"][s * per:(s + 1) * per]
        part = tiny(num_actions=per)._head(
            dict(params, head={"table": table}), x)
        np.testing.assert_allclose(
            part.logits, whole.logits[:, s * per:(s + 1) * per], atol=1e-6)
        np.testing.assert_allclose(part.value, whole.value, atol=1e-6)


def test_an_expert_layer_has_room_for_twice_the_even_share():
    assert xing4.EXPERT_ROWS_MARGIN == 1.0
    # a learner chunk of 2 envs at the cell: 2,048 expected rows, 4,096 held
    assert moe.block_rows(4096, 4, 8, 64, xing4.EXPERT_ROWS_MARGIN) == 4096
    # a margin handed over is the policy's alone: the other cells' blocks stay
    assert moe.block_rows(4096, 4, 8, 32) == 5120
    assert moe.block_rows(8192, 8, 16, 128) == 10240


# -- the fused step, built from ``cli.py``'s parser -----------------------------------
N_SHARDS, N_ENVS = 2, 24
ARGV = ["--trainer", "tpu_fused_ba3c", "--model", "xing4", "--model_cut",
        "tiny", "--env", f"jax:recall:{IDS}:{PROMPT}:{EPISODE}", "--rollout_len",
        str(EPISODE), "--batch_size", str(N_ENVS // N_SHARDS * EPISODE),
        "--grad_chunk_samples", str(N_ENVS // N_SHARDS * EPISODE),
        "--learning_rate", "0.001", "--adam_epsilon", "0.001",
        "--grad_clip_norm", "0.5", "--entropy_beta", "0.01"]


@pytest.fixture(scope="module")
def two_updates():
    """Two fused updates on two shards in float32 (one chunk of 12 envs a
    shard: 288 tokens, the expert layer's sorted rows), built as ``cli.py``
    builds them, and what the reference makes of the first from the same
    start, the same actions and the learner's own routes."""
    import optax

    args = cli.make_parser().parse_args(ARGV)
    cfg = cli.build_config(args)
    env = jaxenv.get_env(args.env.split(":", 1)[1])
    served = policy.build_model(args.model, cfg, args.model_cut).for_env(env)
    model = dataclasses.replace(served, compute_dtype=jnp.float32)
    assert isinstance(model, Xing4) and model == tiny()
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    mesh = make_mesh(num_data=N_SHARDS, num_model=1,
                     devices=jax.devices()[:N_SHARDS])
    step = make_fused_step(model, opt, cfg, mesh, env, args.rollout_len,
                           grad_chunk_samples=args.grad_chunk_samples)
    state = create_fused_state(jax.random.PRNGKey(11), model, cfg, opt, env,
                               N_ENVS, n_shards=N_SHARDS)
    params = params_of(11)
    state = state.replace(train=state.train.replace(params=params))
    params = jax.device_get(params)
    per = N_ENVS // N_SHARDS
    assert per * EPISODE > moe.DENSE_ROWS
    env_state0 = jax.device_get(state.env_state)
    keys = [np.asarray(jax.random.key_data(k)) if jnp.issubdtype(
        k.dtype, jax.dtypes.prng_key) else np.asarray(k) for k in state.key]
    hlo = step.audit_jit.lower(
        step.put(state), jnp.float32(0.01), jnp.float32(1e-3)).compile().as_text()
    first, metrics = step(step.put(state), cfg.entropy_beta, cfg.learning_rate)
    mu = optax.tree_utils.tree_get(first.train.opt_state, "mu")
    grad = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - 0.9), mu)
    first_params = jax.device_get(first.train.params)
    first_carry = jax.device_get(first.policy_carry)
    metrics = jax.device_get(metrics)
    second, metrics_2 = step(first, cfg.entropy_beta, cfg.learning_rate)
    actions = np.stack([np.asarray(metrics["actions"])[:, s * per:(s + 1) * per]
                        for s in range(N_SHARDS)])
    tokens = np.stack([np.asarray(metrics["tokens"])[:, s * per:(s + 1) * per]
                       for s in range(N_SHARDS)])
    numbers = {k: float(v) for k, v in HYPER.items()}
    loss, grads, flips = 0.0, None, 0
    with jax.default_matmul_precision("highest"):
        for s in range(N_SHARDS):
            env_state = {k: v[s * per:(s + 1) * per]
                         for k, v in env_state0._asdict().items()}
            routes = model.unroll(
                params, jnp.asarray(tokens[s]).T, with_routes=True)[1]["routes"]
            l, g, *_, flipped, _ = reference._shard_pass(
                params, env_state, jax.vmap(ref_recall.shown)(env_state),
                jnp.asarray(keys[s]), jnp.asarray(actions[s]), routes, numbers,
                reference._spec_key(SPEC), None, 4)
            loss = loss + l
            flips += int(flipped.sum())
            grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
        n = float(N_ENVS * EPISODE)
        clipped = clip_by_global_norm(
            jax.tree_util.tree_map(lambda g: g / n, grads), HYPER["grad_clip_norm"])
    return dict(params=params, first_params=first_params, carry=first_carry,
                metrics=metrics, metrics_2=jax.device_get(metrics_2),
                second_params=jax.device_get(second.train.params), grad=grad,
                reference=(float(loss) / n, clipped), flips=flips, model=model,
                served=served,
                op_names=set(re.findall(r'op_name="([^"]*)"', hlo)))


def test_the_fused_steps_gradient_is_the_references(two_updates):
    loss, want = two_updates["reference"]
    assert two_updates["flips"] == 0  # in float32 both sides choose alike
    assert abs(float(two_updates["metrics"]["loss"]) - loss) < 2e-4
    for layer, leaves in want.items():
        for leaf, g in leaves.items():
            got = two_updates["grad"][layer][leaf]
            scale = max(float(jnp.abs(g).max()), 1e-4)
            np.testing.assert_allclose(
                got, g, atol=2e-3 * scale, err_msg=f"{layer}/{leaf}")


def test_two_fused_updates_move_the_state_and_report_the_carry(two_updates):
    metrics, model = two_updates["metrics"], two_updates["model"]
    assert int(metrics["episodes"]) == N_ENVS  # every env ended its episode
    tokens, actions = (np.asarray(metrics[k]) for k in ("tokens", "actions"))
    assert tokens.shape == actions.shape == (EPISODE, N_ENVS)
    np.testing.assert_array_equal(tokens[PROMPT + 1:], actions[PROMPT:-1])
    assert np.asarray(metrics["carry_bytes_per_env"]).tolist() == list(
        model.carry_bytes()) == [3 * EPISODE * 128 * 4, 4]
    held, fresh = two_updates["carry"]
    assert np.asarray(fresh).all() and held.pos.tolist() == [EPISODE] * N_ENVS
    assert len(held.latent) == 3 and all(
        np.abs(np.asarray(c)).max() > 0 for c in held.latent)
    # the experts' counters: every token of the update, summed over the shards
    routed = np.asarray(metrics["moe_tokens_per_expert"])
    assert routed.shape == (2, 2) and 0 < routed.sum() <= 2 * 3 * N_ENVS * EPISODE
    assert np.asarray(metrics["moe_overflow_blocks"]).shape == (2,)
    # the projection's error: summed over both shards' tokens and sub-blocks
    gap_sum, mappings = (float(x) for x in metrics["mhc_doubly_stochastic_gap"])
    assert mappings == 2 * 3 * N_ENVS * EPISODE and 0 < gap_sum / mappings < 0.05
    stats = model.epoch_stats(metrics)
    assert set(stats) == {"carry_bytes_per_env", "mhc_doubly_stochastic_gap",
                          "moe_load_max_over_mean", "moe_overflow_blocks"}
    assert stats["mhc_doubly_stochastic_gap"] == pytest.approx(gap_sum / mappings)
    assert np.isfinite(two_updates["metrics_2"]["loss"])
    for before, after in (("params", "first_params"),
                          ("first_params", "second_params")):
        moved = jax.tree_util.tree_map(
            lambda a, b: float(np.abs(a - b).max()), two_updates[after],
            two_updates[before])
        for layer, leaf in (("layer_0", "attn_hc_phi"), ("layer_0", "attn_hc_alpha"),
                            ("layer_0", "ffn_hc_b_res"), ("layer_2", "ffn_hc_b_pre"),
                            ("layer_2", "attn_hc_b_post"), ("layer_0", "wq_a"),
                            ("layer_0", "q_norm"), ("layer_2", "wq_b"),
                            ("layer_2", "wkv_a"), ("layer_3", "kv_norm"),
                            ("layer_3", "wkv_b"), ("layer_3", "wo"),
                            ("layer_0", "w2"), ("layer_2", "router"),
                            ("layer_2", "w3"), ("layer_3", "shared_w3"),
                            ("embed", "table"), ("head", "table")):
            assert moved[layer][leaf] > 0, (before, layer, leaf)
        assert moved["layer_2"]["expert_bias"] == 0  # it only chooses


def test_the_rollouts_snapshot_leaves_the_float32_leaves_float32(two_updates):
    served = two_updates["served"]
    assert served.compute_dtype == jnp.bfloat16
    shapes = jax.eval_shape(served.init_params, jax.random.PRNGKey(0))
    snapshot = jax.eval_shape(served.rollout_params, shapes)
    layer = snapshot["layer_2"]
    for leaf in ("router", "expert_bias", "attn_hc_phi", "ffn_hc_phi",
                 "attn_hc_b_res", "ffn_hc_b_res", "attn_hc_alpha", "attn_hc_b_pre",
                 "ffn_hc_b_post", "attn_norm", "ffn_norm", "q_norm", "kv_norm"):
        assert layer[leaf].dtype == jnp.float32, leaf
    for leaf in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w1", "w3", "w2",
                 "shared_w1", "shared_w3", "shared_w2"):
        assert layer[leaf].dtype == jnp.bfloat16, leaf
    assert snapshot["embed"]["table"].dtype == jnp.bfloat16
    assert snapshot["value"]["kernel"].dtype == jnp.float32


def test_the_fused_loop_names_no_model():
    import inspect

    from distributed_ba3c_tpu.fused import loop

    source = inspect.getsource(loop)
    for name in ("xing", "Xing4", "mla", "hyper_conn", "sinkhorn"):
        assert name not in source, name


# -- the scopes ----------------------------------------------------------------------
#: open only round a Pallas kernel (the grouped products, the decode's
#: attention), which this small step on the CPU does not reach
_BY_KERNEL = (profiling.MOE_EXPERTS_GMM, profiling.OP_MLA_ATTEND_DECODE)
#: in the decode alone; in the unroll alone
_ONE_SIDED = {profiling.OP_MLA_ABSORB: profiling.ROLLOUT_POLICY,
              profiling.OP_MLA_EXPAND: profiling.LEARNER}
_NEW = (profiling.OP_MLA, profiling.OP_MLA_Q, profiling.OP_MLA_KV_LATENT,
        profiling.OP_MLA_EXPAND, profiling.OP_MLA_ABSORB, profiling.OP_MLA_ATTEND,
        profiling.OP_MLA_ATTEND_DECODE, profiling.OP_MLA_OUT, profiling.HYPER_CONN,
        profiling.HYPER_CONN_MAPPINGS, profiling.HYPER_CONN_MIX)


def test_this_policys_layers_are_among_the_policies_layers():
    assert set(profiling.XING4_LAYERS) <= set(profiling.POLICY_LAYERS)
    assert len(set(profiling.POLICY_LAYERS)) == len(profiling.POLICY_LAYERS)
    assert set(_NEW) | {profiling.MOE_SHARED, profiling.FFN_DENSE} <= set(
        profiling.XING4_LAYERS)
    assert profiling.scope_of(
        "jit(multi_step)/rollout/while/body/policy/op_mla/attend/decode_attend/"
        "jit(_kernel_attend)/decode_attend/pallas_call"
    ) == "rollout/policy/op_mla/attend/decode_attend"
    assert profiling.scope_of(
        "jit(multi_step)/learner/transpose(jvp(learner))/checkpoint/"
        "rematted_computation/hyper_conn/mappings/div"
    ) == "learner/hyper_conn/mappings"
    assert profiling.scope_of(
        "jit(multi_step)/rollout/while/body/policy/op_mla/absorb/dot_general"
    ) == "rollout/policy/op_mla/absorb"
    # what was there keeps its place: the new layers come after
    assert profiling.POLICY_LAYERS[-len(_NEW):] == _NEW


@pytest.mark.parametrize("scope", profiling.SEQUENCE_SCOPES)
def test_a_sequence_scope_is_in_the_compiled_step_if_it_is_this_policys(
        two_updates, scope):
    found = {profiling.scope_of(name) for name in two_updates["op_names"]}
    there = any(s is not None and (s == scope or s.startswith(scope + "/"))
                for s in found)
    # (in float32 the rollout's snapshot is the parameters: no op under it)
    mine = any(
        scope == profiling.policy_scope(under, layer)
        for under in (profiling.ROLLOUT_POLICY, profiling.LEARNER)
        for layer in profiling.XING4_LAYERS
        if _ONE_SIDED.get(layer, under) == under)
    assert there == (mine and not scope.endswith(_BY_KERNEL)), scope


def test_the_learners_mappings_are_marked_forward_and_backward(two_updates):
    for layer in (profiling.HYPER_CONN_MAPPINGS, profiling.OP_MLA_ATTEND):
        learner = {n for n in two_updates["op_names"] if profiling.scope_of(n) ==
                   profiling.policy_scope(profiling.LEARNER, layer)}
        assert any(profiling.is_backward(n) for n in learner), layer
        assert any(not profiling.is_backward(n) for n in learner), layer
        rollout = {n for n in two_updates["op_names"] if profiling.scope_of(n) ==
                   profiling.policy_scope(profiling.ROLLOUT_POLICY, layer)}
        assert rollout and not any(profiling.is_backward(n) for n in rollout)


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
    ["--task", "train", "--trainer", "tpu_vtrace_ba3c", "--env", "fake"],
    ["--task", "eval", "--env", "jax:recall"],
])
def test_the_cli_refuses_the_policy_off_the_fused_trainer(argv, capsys):
    with pytest.raises(SystemExit) as e:
        cli.main(argv + ["--model", "xing4", "--model_cut", "tiny"])
    assert e.value.code == 2
    assert "carries state" in capsys.readouterr().err


def test_every_other_path_refuses_it_through_refuse_carry():
    with pytest.raises(ValueError, match="carries state.*Xing4"):
        policy.refuse_carry(tiny(), "the greedy on-device evaluator")
    assert policy.carries_state(tiny())


def test_the_registry_builds_by_name():
    cfg = BA3CConfig(num_actions=IDS)
    model = policy.build_model("xing4", cfg, "tiny")
    assert isinstance(model, Xing4) and policy.carries_state(model)
    assert model.hidden_size == 32 and model.num_actions == IDS
    whole = policy.build_model("xing4", cfg)
    assert whole.hidden_size == 3584 and whole.layer_ids == (0, 2, 3, 4, 5)
    assert policy.build_model("xing4", cfg, "ep8-heads8-vocab8") == whole
    env = RecallEnv(IDS, PROMPT, EPISODE)
    assert whole.for_env(env) == dataclasses.replace(
        whole, num_actions=IDS, max_positions=EPISODE)
    with pytest.raises(ValueError, match="model_cut"):
        policy.build_model("xing4", cfg, "chip-share-16")
    help_text = cli.make_parser().format_help()
    assert "xing4" in help_text and "ep8-heads8-vocab8" in help_text
