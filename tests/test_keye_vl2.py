"""Keye-VL-2.0-30B-A3B's language model as a token-sequence policy, at a size
the CPU runs (hidden 64, 4 query heads over 2 K/V heads of 16, an indexer of
2 heads of 8 that keeps 8 keys, 16 experts top-2 with 2 held, vocabulary 64,
episodes of 32 = four times the top-k in blocks of 8 queries): the model
against the benchmark's plain reference, decoding through the carry against
the unroll past the top-k and across a reset (and the same through the
decode's Pallas kernel, interpreted, at a whole-lane cut), the exact top-k,
the two gradient paths kept apart, the eight shares' expert parts, the policy's own
loss term through the fused step, the scopes, the refusals.
"""

import dataclasses
import inspect
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

from benchmark.reference import keye_vl2 as reference, recall as ref_recall  # noqa: E402
from distributed_ba3c_tpu.config import BA3CConfig  # noqa: E402
from distributed_ba3c_tpu.envs.jaxenv.recall import RecallEnv  # noqa: E402
from distributed_ba3c_tpu.fused.loop import (  # noqa: E402
    create_fused_state,
    make_fused_step,
)
from distributed_ba3c_tpu.models import keye_vl2, layers, policy  # noqa: E402
from distributed_ba3c_tpu.models.keye_vl2 import CUTS, INDEXER_LEAVES, KeyeVL2  # noqa: E402
from distributed_ba3c_tpu.ops import decode_attention, moe, sparse_attention  # noqa: E402
from distributed_ba3c_tpu.ops.gradproc import make_optimizer  # noqa: E402
from distributed_ba3c_tpu.ops.topk_select import select_mask  # noqa: E402
from distributed_ba3c_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

IDS, PROMPT, EPISODE = 64, 4, 32
TOPK = 8
HYPER = {"gamma": 0.99, "entropy_beta": 0.01, "value_loss_coef": 0.5,
         "grad_clip_norm": 0.5, "learning_rate": 1e-3, "adam_epsilon": 1e-3}
#: the configuration's keys at the small cut, as the reference reads them
TINY_CONFIG = {
    "hidden_size": 64, "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rms_norm_eps": 1e-6,
    "rope_theta": 1e7, "num_experts": 2, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "vocab_size": IDS,
    "sa_config": {"indexer_num_heads": 2, "indexer_head_dim": 8, "topk": TOPK},
    "published": {"num_experts": 16},
    "held": {"layers": [0, 1], "expert_offset": 0},
    "algorithm": dict(HYPER, indexer_loss_coef=1.0),
}
SPEC = reference.spec_of(TINY_CONFIG)


def tiny(compute_dtype=jnp.float32, **kw) -> KeyeVL2:
    fields = dict(CUTS["tiny"], num_actions=IDS, max_positions=EPISODE,
                  compute_dtype=compute_dtype)
    return KeyeVL2(**dict(fields, **kw))


def params_of(seed, spec=SPEC):
    """The reference's seeded weights with every vector moved off its start
    (unit gains and a zero bias hide a wrong reading)."""
    params = reference.init_params(jax.random.PRNGKey(seed), spec)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1000), 64))
    return {layer: {leaf: x + 0.1 * jax.random.normal(next(keys), x.shape)
                    if x.ndim == 1 and layer != "value" else x
                    for leaf, x in leaves.items()}
            for layer, leaves in params.items()}


def tokens_of(seed, batch=3, length=EPISODE):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length), 0, IDS)


def decode(model, params, tokens, fresh_at=()):
    """Token by token through the carry -> logits, value [B, T, ...]; the
    positions in ``fresh_at`` open a new episode."""
    B, T = tokens.shape
    fresh = jnp.zeros((T, B), bool).at[0].set(True)
    for t in fresh_at:
        fresh = fresh.at[t].set(True)

    def one(carry, x):
        out, carry = model.step(params, x[0], carry, x[1])
        return carry, (out.logits, out.value)

    _, (logits, value) = jax.lax.scan(
        one, model.init_carry(B), (jnp.swapaxes(tokens, 0, 1), fresh))
    return jnp.swapaxes(logits, 0, 1), jnp.swapaxes(value, 0, 1)


def unpacked(selected, T=EPISODE):
    return np.unpackbits(np.asarray(selected), axis=-1, count=T).astype(bool)


def reference_forward(params, tokens, spec=SPEC, **kw):
    with jax.default_matmul_precision("highest"):
        return reference.forward(params, tokens, spec, **kw)


# -- the architecture as the configuration states it ----------------------------
def test_the_programs_parameters_are_the_references():
    ours = jax.eval_shape(tiny().init_params, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(
        lambda k: reference.init_params(k, SPEC), jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda s: s.shape, ours) == \
        jax.tree_util.tree_map(lambda s: s.shape, theirs)
    layer = ours["layer_0"]
    assert set(INDEXER_LEAVES) == {k for k in layer if k.startswith("idx_")}
    assert set(INDEXER_LEAVES) == set(reference.INDEXER_LEAVES)
    assert "expert_bias" not in layer and "head" in ours  # no bias; untied


def test_the_defaults_are_the_published_widths():
    m = KeyeVL2()
    assert (m.hidden_size, m.num_attention_heads, m.num_key_value_heads,
            m.head_dim, m.moe_intermediate_size) == (2048, 32, 4, 128, 768)
    assert (m.num_experts, m.num_experts_per_tok, m.norm_topk_prob) == (128, 8, True)
    assert (m.indexer_num_heads, m.indexer_head_dim, m.index_topk,
            m.q_chunk_size) == (16, 64, 2048, 512)
    assert (m.rms_norm_eps, m.rope_theta) == (1e-6, 1e7)
    assert (m.layer_ids, m.experts_held, m.expert_offset, m.num_actions) == (
        (0, 1, 2, 3), 16, 0, 18992)
    n = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(m.init_params, jax.random.PRNGKey(0))))
    assert n == 465_393_153


def test_the_snapshot_keeps_the_router_and_the_vectors_float32():
    served = jax.eval_shape(
        lambda k: tiny(jnp.bfloat16).rollout_params(tiny().init_params(k)),
        jax.random.PRNGKey(0))
    layer = served["layer_0"]
    for leaf in ("wq", "wo", "idx_wq", "idx_wk", "idx_ww", "w1", "w2"):
        assert layer[leaf].dtype == jnp.bfloat16, leaf
    for leaf in ("router", "attn_norm", "q_norm", "idx_k_norm", "idx_k_norm_b"):
        assert layer[leaf].dtype == jnp.float32, leaf
    assert served["head"]["table"].dtype == jnp.bfloat16
    assert served["value"]["kernel"].dtype == jnp.float32


# -- the unroll against the reference -----------------------------------------------
TOLERANCE = [(jnp.float32, 2e-5), (jnp.bfloat16, 0.06)]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype,tol", TOLERANCE)
def test_unroll_agrees_with_the_reference(seed, dtype, tol):
    """Float32: the two sides choose alike and agree tightly. bfloat16: the
    reference computes with the program's routes and selections (a near-tie
    that flips is a different function) and the gap is rounding's."""
    params, tokens = params_of(seed), tokens_of(seed + 10)
    out, aux = jax.jit(lambda p, t: tiny(dtype).unroll(p, t, with_routes=True))(
        params, tokens)
    ours = unpacked(aux["selected"])
    forced = {} if dtype == jnp.float32 else dict(
        forced_routes=aux["routes"], forced_selected=jnp.asarray(ours))
    logits, value, kls, routes, selected = reference_forward(params, tokens, **forced)
    scale = float(jnp.abs(logits).max())
    assert float(jnp.abs(out.logits - logits).max()) < tol * scale
    assert float(jnp.abs(out.value - value).max()) < tol
    kl = np.asarray(aux[policy.LOSS_TERMS]["indexer_kl"])
    np.testing.assert_allclose(
        kl, np.asarray(kls) / tokens.size, rtol=50 * tol, atol=1e-6)
    assert (kl > 0).all()
    if dtype == jnp.float32:
        np.testing.assert_array_equal(
            np.sort(aux["routes"], -1), np.sort(routes, -1))
        np.testing.assert_array_equal(ours, np.asarray(selected))
    # position t sees min(t + 1, top-k) keys, in both
    kept = np.minimum(np.arange(EPISODE) + 1, TOPK)
    assert (ours.sum(-1) == kept).all() and (np.asarray(selected).sum(-1) == kept).all()
    assert aux["dsa_keys_selected"].tolist() == [int(kept.sum()) * 3] * 2
    assert aux["dsa_keys_live"].tolist() == [EPISODE * (EPISODE + 1) // 2 * 3] * 2


def _total(forward):
    """The differentiated total: an A2C-shaped loss and the indexer's term."""
    def total(params, tokens):
        logits, value, kl = forward(params, tokens)
        logp = jax.nn.log_softmax(logits)
        picked = jnp.take_along_axis(logp, (tokens % IDS)[..., None], -1)[..., 0]
        return (-jnp.mean(picked) + 0.5 * jnp.mean(jnp.square(value - 0.3))
                + 0.01 * jnp.mean(jnp.sum(jnp.exp(logp) * logp, -1)) + kl)
    return total


@pytest.fixture(scope="module")
def both_gradients():
    params, tokens = params_of(5), tokens_of(15)
    model = tiny()

    def ours(p, t):
        out, aux = model.unroll(p, t)
        return out.logits, out.value, jnp.sum(aux[policy.LOSS_TERMS]["indexer_kl"])

    def theirs(p, t):
        logits, value, kls, _, _ = reference.forward(p, t, SPEC)
        return logits, value, jnp.sum(kls) / t.size

    got = jax.jit(jax.grad(_total(ours)))(params, tokens)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(_total(theirs)))(params, tokens)
    return got, want


_LEAVES = [(layer, leaf) for layer, leaves in sorted(jax.eval_shape(
    lambda k: reference.init_params(k, SPEC), jax.random.PRNGKey(0)).items())
    for leaf in sorted(leaves)]


@pytest.mark.parametrize("layer,leaf", _LEAVES)
def test_a_leafs_gradient_of_the_total_is_the_references(both_gradients, layer, leaf):
    got, want = (np.asarray(g[layer][leaf]) for g in both_gradients)
    scale = max(float(np.abs(want).max()), 1e-6)
    assert float(np.abs(want).max()) > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got, want, atol=2e-4 * scale)


def test_the_two_gradient_paths_are_kept_apart():
    """The A2C side's gradient is exactly zero on the indexer's leaves and
    the indexer's term's exactly zero on every other leaf: the selection
    passes no gradient, the indexer reads a stopped ``z`` and its target is
    stopped."""
    params, tokens = params_of(6), tokens_of(16)
    model = tiny()
    of_policy = jax.grad(lambda p: jnp.sum(jnp.square(
        model.unroll(p, tokens)[0].logits)) + jnp.sum(
        model.unroll(p, tokens)[0].value))(params)
    of_term = jax.grad(lambda p: jnp.sum(
        model.unroll(p, tokens)[1][policy.LOSS_TERMS]["indexer_kl"]))(params)
    for layer, leaves in params.items():
        for leaf in leaves:
            a = float(jnp.abs(of_policy[layer][leaf]).max())
            b = float(jnp.abs(of_term[layer][leaf]).max())
            if leaf in INDEXER_LEAVES:
                assert a == 0.0 and b > 0.0, (layer, leaf)
            else:
                assert b == 0.0, (layer, leaf)
                assert a > 0.0 or layer == "embed", (layer, leaf)


# -- decoding through the carry -----------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-5)])
def test_step_through_the_carry_is_the_unroll_position_by_position(dtype, tol):
    """Every position, the 24 past the top-k included: the decode's gathered
    rows and the unroll's mask are one selection."""
    model = tiny(dtype)
    params, tokens = params_of(3), tokens_of(13)
    served = model.rollout_params(params)
    want, _ = jax.jit(model.unroll)(served if dtype == jnp.bfloat16 else params, tokens)
    logits, value = jax.jit(lambda p, t: decode(model, p, t))(served, tokens)
    scale = float(jnp.abs(want.logits).max())
    if dtype == jnp.bfloat16:
        # the same bfloat16 operands both ways; sums in another order
        tol = 0.02
    assert float(jnp.abs(logits - want.logits).max()) < tol * scale
    assert float(jnp.abs(value - want.value).max()) < tol


@pytest.mark.parametrize("at", [1, 9, 20])
def test_a_fresh_token_forgets_the_episode_before(at):
    model = tiny()
    params, tokens = params_of(4), tokens_of(14)
    logits, _ = jax.jit(lambda p, t: decode(model, p, t, fresh_at=(at,)))(params, tokens)
    want, _ = jax.jit(model.unroll)(params, tokens[:, at:])
    assert float(jnp.abs(logits[:, at:] - want.logits).max()) < 2e-5 * float(
        jnp.abs(want.logits).max())


def test_the_carrys_bytes_by_kind_are_its_shapes():
    model = tiny(jnp.bfloat16)
    kv, index_keys, pos = model.carry_bytes()
    assert kv == 2 * 2 * EPISODE * 2 * 16 * 2      # layers, K and V, rows, heads x 16, bf16
    assert index_keys == 2 * EPISODE * 8 * 2
    assert pos == 4
    whole = KeyeVL2()
    assert whole.carry_bytes() == (4 * 2 * 4096 * 512 * 2, 4 * 4096 * 64 * 2, 4)
    carry = whole.for_env(RecallEnv(18992, 1024, 4096))
    assert jax.eval_shape(lambda: carry.init_carry(16)).kv[0][0].shape == (16, 4096, 512)


# -- the selection is the exact top-k ------------------------------------------------
def _by_sort(scores, live, k):
    want = np.zeros(scores.shape, bool)
    for r, (row, alive) in enumerate(zip(scores, live, strict=True)):
        # the live entries first, by descending score, a tie by position
        order = np.lexsort((np.arange(len(row)), -(row + 0.0), ~alive))
        want[r, order[:min(k, int(alive.sum()))]] = True
    return want & live


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("n,k", [(37, 8), (64, 1), (16, 16), (5, 8), (128, 100)])
def test_select_mask_is_the_stable_sorts_top_k(seed, n, k):
    rng = np.random.default_rng(seed)
    scores = rng.normal(size=(6, n)).astype(np.float32)
    scores[0] = 0.0                                  # every entry ties
    scores[1, 2:9] = scores[1].max() + 1.0           # a tie above the k-th place
    scores[2] = np.round(scores[2])                  # ties everywhere
    scores[3, ::2], scores[3, 1::2] = -0.0, 0.0      # the two zeros are one
    scores[4, : n // 2] = -np.inf
    live = rng.random((6, n)) < 0.7
    live[5] = np.arange(n) < 3                       # fewer live than k
    got = np.asarray(jax.jit(lambda s, a: select_mask(s, a, k))(scores, live))
    np.testing.assert_array_equal(got, _by_sort(scores, live, k))


def test_a_planted_tie_goes_to_the_lower_position_in_all_three():
    """Program's unroll, program's decode and the reference, on an indexer
    whose every score is exactly zero (``idx_ww`` zero): each query keeps its
    first ``top-k`` positions."""
    params, tokens = params_of(7), tokens_of(17, batch=2)
    for layer in ("layer_0", "layer_1"):
        params[layer]["idx_ww"] = jnp.zeros_like(params[layer]["idx_ww"])
    model = tiny()
    out, aux = jax.jit(lambda p, t: model.unroll(p, t, with_routes=True))(params, tokens)
    first = np.arange(EPISODE)[None, :] < np.minimum(
        np.arange(EPISODE) + 1, TOPK)[:, None]
    ours = unpacked(aux["selected"])
    assert (ours == first).all()
    logits, _, _, _, selected = reference_forward(params, tokens)
    assert (np.asarray(selected) == first).all()
    stepped, _ = jax.jit(lambda p, t: decode(model, p, t))(params, tokens)
    scale = float(jnp.abs(logits).max())
    assert float(jnp.abs(out.logits - logits).max()) < 2e-5 * scale
    assert float(jnp.abs(stepped - logits).max()) < 2e-5 * scale


def test_a_planted_key_only_the_indexer_can_find():
    """One early position's indexer key is made every later query's best
    match. A recency rule drops it ``top-k`` positions on; the indexer keeps
    it to the episode's end, the reference's full sort of the same scores
    agrees, and the attention's output is the masked product's under that
    selection and not under the recency window."""
    model = tiny()
    rng = np.random.default_rng(0)
    T, planted = EPISODE, 2
    normal = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    q, k, v = normal(1, T, 4, 16), normal(1, T, 2, 16), normal(1, T, 2, 16)
    qi, ki, w = 0.1 * normal(1, T, 2, 8), 0.1 * normal(1, T, 8), np.ones((1, T, 2), np.float32)
    qi[..., 0] += 1.0
    ki[:, planted, 0] = 50.0
    _, chosen, n_sel, n_live = jax.jit(
        lambda *a: model._select(*a, 0))(qi, ki, w)
    out, _ = jax.jit(lambda *a: sparse_attention.attend_selected(*a, 0.25))(
        q, k, v, chosen)
    chosen = np.asarray(chosen)[0]
    assert chosen[planted:, planted].all()
    at = np.arange(T)
    causal = at[None, :] <= at[:, None]
    recency = causal & (at[None, :] > at[:, None] - TOPK)
    assert not recency[planted + TOPK:, planted].any()
    assert (chosen.sum(-1) == np.minimum(at + 1, TOPK)).all()
    assert int(n_sel) == chosen.sum() and int(n_live) == causal.sum()
    index = model._index_scores(jnp.asarray(qi), jnp.asarray(ki), jnp.asarray(w))
    np.testing.assert_array_equal(
        chosen, np.asarray(reference.select_by_sort(index, TOPK))[0])
    under = lambda mask: layers.attend(  # noqa: E731
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(mask)[None],
        jnp.float32)
    np.testing.assert_allclose(out, under(chosen), atol=1e-5)
    assert float(jnp.abs(out - under(recency)).max()) > 0.1


def test_the_decodes_attention_is_the_masked_attention_and_the_gathered_rows():
    """A decode step's attention over the buffers as the carry holds them
    (``decode_attend`` under the position and the selection; off the TPU
    ``layers.attend`` under ``live & kept``) against ``layers.attend`` over
    the rows under the selection's mask alone (it keeps live rows only),
    and against the selected rows gathered out: one set of keys, three ways
    of reading it."""
    rng = np.random.default_rng(0)
    B, P, H, KV, D, K = 3, 32, 4, 2, 16, 8
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, P, KV * D)), jnp.float32) for _ in range(2))
    scores = jnp.asarray(rng.normal(size=(B, P)), jnp.float32)
    pos = jnp.asarray([3, 20, 31])
    live = jnp.arange(P)[None, :] <= pos[:, None]
    mask = select_mask(scores, live, K)
    got = decode_attention.decode_attend(
        q, k, v, pos + 1, 1.0 / np.sqrt(D), mask).reshape(B, H * D)
    heads = lambda c: c.reshape(B, -1, KV, D)  # noqa: E731
    want = layers.attend(q[:, None], heads(k), heads(v), mask[:, None, :], jnp.float32)
    np.testing.assert_allclose(got, want[:, 0], atol=1e-5)
    _, chosen = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), K)
    take = lambda c: jnp.take_along_axis(c, chosen[:, :, None], axis=1)  # noqa: E731
    length = jnp.minimum(pos + 1, K)[:, None, None]
    rows = layers.attend(q[:, None], heads(take(k)), heads(take(v)),
                         jnp.arange(K)[None, None, :] < length, jnp.float32)
    np.testing.assert_allclose(got, rows[:, 0], atol=1e-5)


# -- the same through the kernel (ops/decode_attention.py) ---------------------------
# A whole-lane cut the CPU runs under Pallas's interpreter: 4 query heads over
# 2 K/V heads of 128 lanes (a row of 256), 256 positions = two blocks of 128
# rows, a top-k of 8: from position 8 on the indexer decides, and late in the
# episode it may keep nothing of the first block.
LANES_EPISODE = 256
LANES_SPEC = reference.spec_of(dict(TINY_CONFIG, head_dim=128))


def lanes(compute_dtype=jnp.float32, **kw) -> KeyeVL2:
    return tiny(compute_dtype, **dict(
        dict(head_dim=128, q_chunk_size=64, max_positions=LANES_EPISODE), **kw))


@pytest.fixture
def kernel_path(monkeypatch):
    """The decode's attention in the Pallas kernel, interpreted, in blocks
    of 128 rows."""
    monkeypatch.setattr(decode_attention, "INTERPRET", True)
    monkeypatch.setattr(decode_attention, "block_rows", lambda rows, row_bytes: 128)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 0.02)])
def test_step_through_the_carry_is_the_unroll_with_the_kernel(kernel_path, dtype, tol):
    """Every position of two blocks of rows, the 248 past the top-k
    included: the kernel under the position and the selection against the
    unroll's masked-dense blocks. In bfloat16 a rounding that differs flips
    a near-tie of a selection or of a router at a token here and there (of
    768 tokens 1-2 % decoding through ``layers.attend``, whose rounding is
    the unroll's own, 6-7 % through the kernel, which rounds the
    probabilities before their division): nine tokens in ten are held to
    the tolerance there, and every token in float32."""
    model = lanes(dtype)
    params, tokens = params_of(3, LANES_SPEC), tokens_of(13, 3, LANES_EPISODE)
    served = model.rollout_params(params)
    carry = model.init_carry(3)
    assert carry.kv[0][0].shape == (3, LANES_EPISODE, 256)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda p: model.step(p, tokens[:, 0], carry, jnp.ones(3, bool)))(served))
    assert model.decode_rows_read_share() == 0.75  # one block, then two
    want, _ = jax.jit(model.unroll)(served if dtype == jnp.bfloat16 else params, tokens)
    logits, value = jax.jit(lambda p, t: decode(model, p, t))(served, tokens)
    scale = float(jnp.abs(want.logits).max())
    gap = np.asarray(jnp.abs(logits - want.logits).max(axis=2))
    held = gap.max() if dtype == jnp.float32 else np.quantile(gap, 0.9)
    assert held < tol * scale, np.sort(gap.ravel())[-8:]
    assert np.isfinite(gap).all()
    if dtype == jnp.float32:
        assert float(jnp.abs(value - want.value).max()) < tol


@pytest.mark.parametrize("at", [(1, 100, 200), (130, 128, 126), (255, 56, 156)])
def test_a_fresh_token_forgets_the_episode_before_with_the_kernel(kernel_path, at):
    """Every env reset at a position of its own, mid-buffer: the rows of the
    episode before lie past ``pos`` (in the live block and in blocks past
    it) and each env stands at a length of its own from there on. (The
    positions leave both parts a length the unroll takes in few blocks.)"""
    model = lanes()
    params, tokens = params_of(4, LANES_SPEC), tokens_of(14, 3, LANES_EPISODE)
    logits, _ = jax.jit(lambda p, t: decode(
        model, p, t, fresh_at=tuple(zip(at, range(3)))))(params, tokens)
    unroll = jax.jit(model.unroll)
    for env, a in enumerate(at):
        first, _ = unroll(params, tokens[env:env + 1, :a])
        second, _ = unroll(params, tokens[env:env + 1, a:])
        want = jnp.concatenate([first.logits, second.logits], axis=1)[0]
        assert float(jnp.abs(logits[env] - want).max()) < 2e-5 * float(
            jnp.abs(want).max()), env


# -- the learner's attention through its kernels (ops/sparse_attention.py) -------------
@pytest.fixture(scope="module")
def unroll_both_ways():
    """The whole-lane cut's unroll and the gradient of a differentiated
    total, through the masked-dense form and through the learner's Pallas
    kernels (interpreted, in tiles of 128 positions: two a side), in float32
    and in bfloat16."""
    def run(dtype):
        model = lanes(dtype)
        params, tokens = params_of(7, LANES_SPEC), tokens_of(17, 2, LANES_EPISODE)

        def forward(p, t):
            out, aux = model.unroll(p, t, with_routes=True)
            return out.logits, out.value, jnp.sum(
                aux[policy.LOSS_TERMS]["indexer_kl"]), aux

        def total(p, t):
            logits, value, kl, aux = forward(p, t)
            return _total(lambda p, t: (logits, value, kl))(p, t), (logits, value, aux)

        (_, (logits, value, aux)), grads = jax.jit(
            jax.value_and_grad(total, has_aux=True))(params, tokens)
        return logits, value, aux, grads, model.learner_tiles_visited_share()

    found = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        dense = run(dtype)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(sparse_attention, "INTERPRET", True)
            patch.setattr(sparse_attention, "TILE", 128)
            found[dtype] = (dense, run(dtype))
    return found


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 0.02)])
def test_unroll_through_the_kernels_is_the_masked_dense_unroll(
        unroll_both_ways, dtype, tol):
    """Logits, value, ``L_I`` and the counts; the selections bit for bit in
    float32 (in bfloat16 a rounding that differs flips a near-tie of a
    selection or of a router at a token here and there, as in the decode's
    test above: nine tokens in ten are held to the tolerance there)."""
    (logits, value, aux, _, share), (k_logits, k_value, k_aux, _, k_share) = \
        unroll_both_ways[dtype]
    assert share == 1.0 and k_share == 3 / 4  # of 2 x 2 tiles, the causal 3
    scale = float(jnp.abs(logits).max())
    gap = np.asarray(jnp.abs(k_logits - logits).max(axis=2))
    held = gap.max() if dtype == jnp.float32 else np.quantile(gap, 0.9)
    assert np.isfinite(gap).all() and held < tol * scale, np.sort(gap.ravel())[-8:]
    kl, k_kl = (np.asarray(a[policy.LOSS_TERMS]["indexer_kl"]) for a in (aux, k_aux))
    np.testing.assert_allclose(k_kl, kl, rtol=50 * tol)
    for count in ("dsa_keys_selected", "dsa_keys_live"):
        np.testing.assert_array_equal(k_aux[count], aux[count])
    assert aux["selected"].dtype == jnp.uint8
    assert aux["selected"].shape == (2, 2, LANES_EPISODE, LANES_EPISODE // 8)
    if dtype == jnp.float32:
        assert float(jnp.abs(k_value - value).max()) < tol
        np.testing.assert_array_equal(k_aux["selected"], aux["selected"])
        np.testing.assert_array_equal(k_aux["routes"], aux["routes"])
    else:
        assert float(np.mean(unpacked(k_aux["selected"], LANES_EPISODE)
                             != unpacked(aux["selected"], LANES_EPISODE))) < 0.01


_LANES_LEAVES = [(layer, leaf) for layer, leaves in sorted(jax.eval_shape(
    lambda k: reference.init_params(k, LANES_SPEC), jax.random.PRNGKey(0)).items())
    for leaf in sorted(leaves)]


@pytest.mark.parametrize("layer,leaf", _LANES_LEAVES)
def test_a_leafs_gradient_through_the_kernels_is_the_masked_dense_forms(
        unroll_both_ways, layer, leaf):
    """Every leaf of the differentiated total (A2C-shaped loss + ``L_I``):
    float32 tightly. In bfloat16 the two roundings flip 0.2 % of the
    selections' bits and 1.5 % of the routes, which at this size (two held
    experts, a top-k of 8) moves a leaf's gradient by 5-25 % of its norm:
    held there to finite and to under a third (the kernels' own bfloat16
    gradients are held to rounding in tests/test_sparse_attention.py)."""
    (*_, want, _), (*_, got, _) = unroll_both_ways[jnp.float32]
    want, got = np.asarray(want[layer][leaf]), np.asarray(got[layer][leaf])
    assert np.abs(want).max() > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())
    (*_, want, _), (*_, got, _) = unroll_both_ways[jnp.bfloat16]
    want, got = np.asarray(want[layer][leaf]), np.asarray(got[layer][leaf])
    assert np.isfinite(got).all()
    assert np.linalg.norm(got - want) < 0.33 * np.linalg.norm(want)


# -- the experts: softmax scoring, and the shares add up -------------------------------
def test_softmax_routing_is_the_published_rule():
    rng = np.random.default_rng(1)
    z = jnp.asarray(rng.normal(size=(9, 16)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(16, 12)), jnp.float32)
    routing = moe.route(z, w, None, 3, True, scoring="softmax")
    p = jax.nn.softmax(z @ w, -1)
    order = np.argsort(-np.asarray(p), -1)[:, :3]
    np.testing.assert_array_equal(np.sort(routing.experts, -1), np.sort(order, -1))
    picked = np.take_along_axis(np.asarray(p), np.asarray(routing.experts), -1)
    np.testing.assert_allclose(
        routing.weights, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(routing.weights).sum(-1), 1.0, rtol=1e-6)
    with pytest.raises(AssertionError):
        moe.route(z, w, jnp.zeros(12), 3, scoring="softmax")
    # the default is the sigmoid scoring, as LFM2 calls it
    assert inspect.signature(moe.route).parameters["scoring"].default == "sigmoid"


def test_the_eight_shares_expert_parts_add_up_to_the_whole_layer():
    """One expert layer: the program's part on each of the 8 chips that
    share it (2 of 16 experts each) summed, against the uncut reference's
    layer (all 16 held)."""
    whole_spec = dict(SPEC, experts=16, layers=(0,))
    whole = reference.init_params(jax.random.PRNGKey(2), whole_spec)["layer_0"]
    h = jax.random.normal(jax.random.PRNGKey(3), (2, EPISODE, 64))
    with jax.default_matmul_precision("highest"):
        z = reference._rms(h, whole["ffn_norm"], SPEC["eps"])
        want, _ = reference._experts_ffn(whole, z, whole_spec, lambda x: x)
    parts = 0.0
    for share in range(8):
        model = tiny(expert_offset=2 * share)
        held = slice(2 * share, 2 * share + 2)
        p = dict(whole, w1=whole["w1"][held], w3=whole["w3"][held], w2=whole["w2"][held])
        flat = h.reshape(-1, 64)
        out, (counts, _, _) = model._ffn(p, flat)
        parts = parts + (out - flat)
        assert int(counts.sum()) > 0 or share > 0
    assert float(jnp.abs(parts.reshape(want.shape) - want).max()) < 1e-5


# -- the fused step: the policy's own loss term -----------------------------------------
def _fused(n_shards, n_envs=8, dtype=jnp.float32, grad_chunk_samples=64, seed=11):
    model = tiny(dtype)
    env = RecallEnv(IDS, PROMPT, EPISODE)
    cfg = BA3CConfig(num_actions=IDS, batch_size=n_envs * EPISODE // n_shards)
    opt = make_optimizer(HYPER["learning_rate"], HYPER["adam_epsilon"],
                         HYPER["grad_clip_norm"])
    mesh = make_mesh(num_data=n_shards, num_model=1,
                     devices=jax.devices()[:n_shards])
    step = make_fused_step(model, opt, cfg, mesh, env, EPISODE,
                           grad_chunk_samples=grad_chunk_samples)
    state = create_fused_state(jax.random.PRNGKey(seed), model, cfg, opt, env,
                               n_envs, n_shards=n_shards)
    params = params_of(seed)
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
    total, a2c, kls, grads = 0.0, 0.0, 0.0, None
    with jax.default_matmul_precision("highest"):
        for s in range(n_shards):
            env_state = {k: v[s * per:(s + 1) * per]
                         for k, v in env_state0._asdict().items()}
            l, a, k, g, *_ = reference._shard_pass(
                params, env_state, jax.vmap(ref_recall.shown)(env_state),
                jnp.asarray(keys[s]), jnp.asarray(actions[s]), None, None, numbers,
                reference._spec_key(SPEC), None, 2)
            total, a2c, kls = total + l, a2c + a, kls + k
            grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
        n = 8.0 * EPISODE
        clipped = reference.clip_by_global_norm(
            jax.tree_util.tree_map(lambda g: g / n, grads), HYPER["grad_clip_norm"])
    return dict(n_shards=n_shards, params=params, new=new, metrics=metrics,
                grad=grad, model=model,
                reference=(float(a2c) / n, np.asarray(kls) / n, clipped))


def test_the_fused_steps_gradient_is_the_references(one_update):
    """The step differentiates the A2C loss PLUS the policy's own terms: its
    clipped gradient, the indexer's leaves included, is the reference's of
    that total; ``loss`` stays the A2C loss and the term is reported under
    the policy's name, averaged over chunks and shards."""
    a2c, kls, want = one_update["reference"]
    metrics = one_update["metrics"]
    assert abs(float(metrics["loss"]) - a2c) < 2e-4
    np.testing.assert_allclose(np.asarray(metrics["indexer_kl"]), kls, rtol=2e-4)
    for layer, leaves in want.items():
        for leaf, g in leaves.items():
            got = one_update["grad"][layer][leaf]
            scale = max(float(jnp.abs(g).max()), 1e-4)
            np.testing.assert_allclose(
                got, g, atol=2e-3 * scale, err_msg=f"{layer}/{leaf}")
    for leaf in INDEXER_LEAVES:  # reached, through the term alone
        assert float(np.abs(one_update["grad"]["layer_0"][leaf]).max()) > 0


def test_a_fused_update_moves_the_state_and_reports_its_counters(one_update):
    new, metrics, n_shards, model = (
        one_update[k] for k in ("new", "metrics", "n_shards", "model"))
    assert int(metrics["episodes"]) == 8
    tokens, actions = (np.asarray(metrics[k]) for k in ("tokens", "actions"))
    assert tokens.shape == actions.shape == (EPISODE, 8)
    np.testing.assert_array_equal(tokens[PROMPT + 1:], actions[PROMPT:-1])
    assert np.asarray(metrics["carry_bytes_per_env"]).tolist() == list(
        model.carry_bytes())
    kept = int(np.minimum(np.arange(EPISODE) + 1, TOPK).sum()) * 8
    assert np.asarray(metrics["dsa_keys_selected"]).tolist() == [kept] * 2
    assert np.asarray(metrics["dsa_keys_live"]).tolist() == [
        EPISODE * (EPISODE + 1) // 2 * 8] * 2
    assert np.asarray(metrics["moe_tokens_per_expert"]).shape == (2, 2)
    assert np.asarray(metrics["moe_overflow_blocks"]).tolist() == [0, 0]
    held, fresh = new.policy_carry
    assert np.asarray(fresh).all() and held.pos.tolist() == [EPISODE] * 8
    assert len(held.pos.sharding.device_set) == n_shards
    stats = model.epoch_stats({k: np.asarray(v) for k, v in metrics.items()})
    assert stats["dsa_kept_share"] == pytest.approx(
        kept / (EPISODE * (EPISODE + 1) // 2 * 8))
    assert stats["indexer_kl"] == pytest.approx(float(np.sum(metrics["indexer_kl"])))
    # no kernel at this cut on the CPU: the decode read whole buffers
    assert stats["dsa_decode_rows_read_share"] == 1.0
    # nor for the learner: the masked-dense form weighed every tile
    assert stats["dsa_learner_tiles_visited_share"] == 1.0
    # an episode of four times this cut's top-k: the selection's searches run
    # in three decode steps of four and three of the learner's four blocks
    assert stats["dsa_decode_selects_run_share"] == 1 - TOPK / EPISODE
    assert stats["dsa_learner_selects_run_share"] == 1 - TOPK / EPISODE
    assert stats["carry_bytes_per_env"] == float(sum(model.carry_bytes()))
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), new.train.params,
        one_update["params"])
    for layer, leaf in (("layer_0", "idx_wq"), ("layer_1", "idx_ww"),
                        ("layer_0", "wq"), ("layer_1", "router"),
                        ("head", "table"), ("embed", "table")):
        assert moved[layer][leaf] > 0, (layer, leaf)


def test_the_trainer_names_no_policys_counter_or_term():
    from distributed_ba3c_tpu.fused import loop

    source = inspect.getsource(loop)
    for name in ("moe_", "dsa_", "indexer", "carry_bytes"):
        assert name not in source, name
    assert "policy.LOSS_TERMS" in source


@pytest.mark.parametrize("name", ["ba3cnet", "lfm2-moe", "phi4-flash"])
def test_the_other_policies_hand_the_trainer_no_term(name):
    cfg = BA3CConfig(num_actions=IDS)
    model = policy.build_model(name, cfg, None if name == "ba3cnet" else "tiny")
    if not policy.carries_state(model):
        return
    model = model.for_env(RecallEnv(IDS, PROMPT, 16))
    aux = jax.eval_shape(
        lambda k: model.unroll(model.init_params(k), jnp.zeros((2, 16), jnp.int32))[1],
        jax.random.PRNGKey(0))
    assert policy.LOSS_TERMS not in aux


# -- the scopes ------------------------------------------------------------------------
@pytest.fixture(scope="module")
def compiled_op_names():
    _, _, _, step, state, _ = _fused(1, dtype=jnp.bfloat16)
    hlo = step.audit_jit.lower(
        step.put(state), jnp.float32(0.01), jnp.float32(1e-3)
    ).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', hlo))


#: open only round the Pallas kernels (the grouped products, the decode's
#: attention), which this small step on the CPU does not reach; and the
#: indexer's loss, which the rollout has none of
_NOT_HERE = tuple(
    profiling.policy_scope(under, layer)
    for under in (profiling.ROLLOUT_POLICY, profiling.LEARNER)
    for layer in (profiling.MOE_EXPERTS_GMM, profiling.OP_ATTN_SPARSE_DECODE)
) + (profiling.policy_scope(profiling.ROLLOUT_POLICY, profiling.OP_INDEXER_LOSS),)


@pytest.mark.parametrize("scope", profiling.SEQUENCE_SCOPES)
def test_a_sequence_scope_is_in_the_compiled_step_if_it_is_this_policys(
        compiled_op_names, scope):
    assert scope in profiling.ALL_SCOPES and scope not in profiling.SCOPES
    found = {profiling.scope_of(name) for name in compiled_op_names}
    there = any(s is not None and (s == scope or s.startswith(scope + "/"))
                for s in found)
    mine = scope == profiling.ROLLOUT_WEIGHTS_BF16 or any(
        scope == profiling.policy_scope(under, layer)
        for under in (profiling.ROLLOUT_POLICY, profiling.LEARNER)
        for layer in profiling.KEYE_VL2_LAYERS)
    assert there == (mine and scope not in _NOT_HERE), scope


def test_the_indexer_is_a_layer_of_its_own_beside_the_attention():
    assert profiling.OP_INDEXER in profiling.KEYE_VL2_LAYERS
    assert not profiling.OP_INDEXER.startswith(profiling.OP_ATTN_SPARSE)
    assert set(profiling.KEYE_VL2_LAYERS) <= set(profiling.POLICY_LAYERS)
    assert len(set(profiling.POLICY_LAYERS)) == len(profiling.POLICY_LAYERS)
    name = "jit(multi_step)/learner/checkpoint/op_indexer/select/while/body/ge"
    assert profiling.scope_of(name) == "learner/op_indexer/select"
    name = "jit(multi_step)/rollout/while/body/policy/op_attn_sparse/dot_general"
    assert profiling.scope_of(name) == "rollout/policy/op_attn_sparse"


# -- the refusals and the registry -------------------------------------------------------
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
        cli.main(argv + ["--model", "keye-vl2", "--model_cut", "tiny"])
    assert e.value.code == 2
    assert "carries state" in capsys.readouterr().err


def test_the_registry_builds_by_name():
    cfg = BA3CConfig(num_actions=IDS)
    model = policy.build_model("keye-vl2", cfg, "tiny")
    assert isinstance(model, KeyeVL2) and policy.carries_state(model)
    assert model.hidden_size == 64 and model.num_actions == IDS
    whole = policy.build_model("keye-vl2", cfg)
    assert whole.hidden_size == 2048 and whole.layer_ids == (0, 1, 2, 3)
    assert policy.build_model("keye-vl2", cfg, "chip-share-8") == whole
    env = RecallEnv(IDS, PROMPT, EPISODE)
    assert whole.for_env(env) == dataclasses.replace(
        whole, num_actions=IDS, max_positions=EPISODE)
    with pytest.raises(ValueError, match="model_cut"):
        policy.build_model("keye-vl2", cfg, "stage-14-19")
    assert keye_vl2.cut_fields(None) == {}
