"""LFM2-8B-A1B as a token-sequence policy, at a size the CPU runs (hidden
64, 8 experts top-2, a 2-expert share, vocabulary 256, T 16): the model
against the benchmark's plain reference, decoding through the carry against
the unroll, the expert layer's shares against the whole layer, the recall
game against its reference, the fused step's gradient, the refusals.
"""

import contextlib
import dataclasses
import functools
import inspect
import math
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

from benchmark.reference import lfm2_moe as reference, recall as ref_recall  # noqa: E402
from distributed_ba3c_tpu.config import BA3CConfig  # noqa: E402
from distributed_ba3c_tpu.envs import jaxenv  # noqa: E402
from distributed_ba3c_tpu.envs.jaxenv.recall import RecallEnv  # noqa: E402
from distributed_ba3c_tpu.fused.loop import (  # noqa: E402
    create_fused_state,
    learner_chunks,
    make_fused_step,
    make_greedy_eval,
)
from distributed_ba3c_tpu.models import policy  # noqa: E402
from distributed_ba3c_tpu.models.lfm2_moe import CUTS, LFM2MoE  # noqa: E402
from distributed_ba3c_tpu.ops import grouped_matmul, moe  # noqa: E402
from distributed_ba3c_tpu.ops.gradproc import make_optimizer  # noqa: E402
from distributed_ba3c_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

IDS, PROMPT, EPISODE = 256, 4, 16
#: the configuration's keys at the small cut, as the reference reads them
TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "norm_eps": 1e-5, "rope_theta": 1000000, "num_experts": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "vocab_size": IDS, "num_dense_layers": 2,
    "layer_types": ["conv", "conv", "full_attention", "conv"],
    "published": {"num_experts": 8},
    "held": {"layers": [0, 2, 3], "expert_offset": 0},
}
SPEC = reference.spec_of(TINY_CONFIG)
HYPER = {"gamma": 0.99, "entropy_beta": 0.01, "value_loss_coef": 0.5,
         "grad_clip_norm": 0.5, "learning_rate": 1e-3, "adam_epsilon": 1e-3}


def tiny(compute_dtype=jnp.float32, **kw) -> LFM2MoE:
    return LFM2MoE(num_actions=IDS, max_positions=EPISODE,
                   compute_dtype=compute_dtype, **dict(CUTS["tiny"], **kw))


def params_of(seed):
    return reference.init_params(jax.random.PRNGKey(seed), SPEC)


BATCH = 3  # envs of a test's token batch


def tokens_of(seed, batch=None, length=EPISODE):
    return jax.random.randint(
        jax.random.PRNGKey(seed), (batch or BATCH, length), 0, IDS)


@contextlib.contextmanager
def through_the_kernel():
    """The grouped products in the Pallas kernel (ops/grouped_matmul.py)
    under its interpreter, which needs whole lanes and whole tiles of rows:
    the small cut with hidden and expert widths of 128, blocks in tiles of
    128 sorted rows, and 4 envs x 16 tokens x top-2 = 128 rows a batch."""
    wide = dict(hidden_size=128, moe_intermediate_size=128)
    config = dict(TINY_CONFIG, **wide)
    here = sys.modules[__name__]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(grouped_matmul, "INTERPRET", True)
        patch.setattr(moe, "ROW_TILE", 128)
        patch.setitem(CUTS, "tiny", dict(CUTS["tiny"], head_dim=32, **wide))
        patch.setattr(here, "TINY_CONFIG", config)
        patch.setattr(here, "SPEC", reference.spec_of(config))
        patch.setattr(here, "BATCH", 4)
        moe._sorted_rows.clear_cache()  # traced by shape, whatever the path
        yield
    moe._sorted_rows.clear_cache()


@pytest.fixture(params=["grouped", "blocked", "every-token", "kernel"])
def moe_path(request, monkeypatch):
    """The forms of the expert layer (ops/moe.py): the sorted, grouped
    products in one block (at these sizes the bound is all the rows), the
    same with a bound so small that the rows held here need two blocks or
    more, every held expert computing every token (few tokens), and the one
    block with its products in the Pallas kernel."""
    monkeypatch.setattr(
        moe, "DENSE_ROWS", 10**9 if request.param == "every-token" else 0)
    if request.param == "blocked":
        # half the rows an even router sends here, in tiles of 8
        monkeypatch.setattr(moe, "ROW_TILE", 8)
        monkeypatch.setattr(moe, "HELD_ROWS_MARGIN", -0.5)
    with through_the_kernel() if request.param == "kernel" else \
            contextlib.nullcontext():
        yield request.param


def _overflow(held_rows, n, k=2, held=2, num_experts=8):
    """Blocks beyond the first that ``held_rows`` sorted rows need."""
    block = moe.block_rows(n, k, held, num_experts)
    return max(0, math.ceil(held_rows / block) - 1)


def test_the_form_is_chosen_by_the_number_of_tokens():
    assert moe.DENSE_ROWS == 256  # a decode step's 128 under, a chunk's 4,096 over


def test_the_programs_parameters_are_the_references():
    ours = jax.eval_shape(tiny().init_params, jax.random.PRNGKey(0))
    theirs = jax.eval_shape(lambda k: reference.init_params(k, SPEC),
                            jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda x: x.shape, ours) == \
        jax.tree_util.tree_map(lambda x: x.shape, theirs)


# -- (a) the unroll against the reference --------------------------------------
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 0.03)])
def test_unroll_agrees_with_the_reference(seed, dtype, tol, moe_path):
    params, tokens = params_of(seed), tokens_of(100 + seed)
    out, aux = jax.jit(tiny(dtype).unroll, static_argnames="with_routes")(
        params, tokens, with_routes=True)
    with jax.default_matmul_precision("highest"):
        # the reference computes with the routes the program chose, and says
        # what it would have chosen at each of those points
        logits, value, routes = reference.forward(
            params, tokens, SPEC, forced_routes=aux["routes"])
    scale = float(jnp.abs(logits).max())
    same = np.asarray(jnp.sort(aux["routes"], -1) == jnp.sort(routes, -1)).all(-1)
    assert same.mean() > (0.999 if dtype == jnp.float32 else 0.9)
    gap = np.abs(np.asarray(out.logits - logits)).max()
    assert gap < tol * scale, (gap, scale)
    assert np.abs(np.asarray(out.value - value)).max() < 5 * tol
    # what the counters count: the assignments that land on a held expert
    held = np.asarray(aux["moe_tokens_per_expert"])
    for layer in range(2):
        want = [(np.asarray(aux["routes"][layer]) == e).sum() for e in range(2)]
        assert held[layer].tolist() == want
    # and the blocks each layer ran beyond its first
    ran = np.asarray(aux["moe_overflow_blocks"]).tolist()
    assert ran == [0 if moe_path == "every-token" else _overflow(
        rows, tokens.size) for rows in held.sum(-1)]
    assert (min(ran) >= 1) == (moe_path == "blocked"), ran


# -- (b) decoding through the carry equals the unroll ---------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 0.03)])
def test_decode_through_the_carry_equals_the_unroll_with_a_reset_inside(dtype, tol):
    model, params = tiny(dtype), params_of(3)
    first, second = tokens_of(7, length=7), tokens_of(8, length=9)
    unroll = jax.jit(model.unroll)
    want = jnp.concatenate(
        [unroll(params, first)[0].logits, unroll(params, second)[0].logits], 1)
    want_v = jnp.concatenate(
        [unroll(params, first)[0].value, unroll(params, second)[0].value], 1)
    step = jax.jit(model.step)
    served = model.rollout_params(params)
    carry, got, got_v = model.init_carry(3), [], []
    tokens = jnp.concatenate([first, second], 1)
    for t in range(EPISODE):
        # a new episode opens at 0 and, inside the sequence, at 7
        fresh = jnp.full((3,), t in (0, 7))
        out, carry = step(served, tokens[:, t], carry, fresh)
        got.append(out.logits)
        got_v.append(out.value)
    scale = float(jnp.abs(want).max())
    gaps = np.abs(np.asarray(jnp.stack(got, 1) - want)).max(2) / scale
    if dtype == jnp.float32:
        assert gaps.max() < tol, gaps  # at every position of every env
    else:
        # under bfloat16 the two forwards round differently, and a token
        # whose 2nd and 3rd router scores all but tie takes another expert
        # in one of them (then it, and the next few, differ by an expert):
        # rounding at the median token, and few tokens beyond it
        assert np.median(gaps) < tol / 2 and (gaps > tol).mean() < 0.1, gaps
    assert np.abs(np.asarray(jnp.stack(got_v, 1) - want_v)).max() < 5 * tol
    assert np.asarray(carry.pos).tolist() == [9, 9, 9]


# -- (c) the shares of one expert layer add up to the whole ---------------------
def _layer_inputs(seed=5, n=None):
    whole = reference.spec_of(dict(TINY_CONFIG, num_experts=8))
    p = reference.init_params(jax.random.PRNGKey(seed), whole)["layer_2"]
    z = jax.random.normal(jax.random.PRNGKey(seed + 1), (
        1, n or BATCH * EPISODE, TINY_CONFIG["hidden_size"]))
    return whole, p, z


def _jit_share(*static):
    """``_share`` traced anew: JAX keeps a function's traces by its shapes,
    and a test's paths are patched in underneath it (under one shared
    ``jax.jit(_share)`` a test's second path ran its first one's trace)."""
    return jax.jit(lambda p, z: _share(p, z, *static))


def _share(p, z, offset, held=2, dtype=jnp.float32):
    """-> (this share's output, tokens routed to each of its experts)."""
    routing = moe.route(z[0], p["router"], p["expert_bias"], 2)
    cut = lambda w: w[offset:offset + held].astype(dtype)  # noqa: E731
    return moe.expert_ffn(z[0].astype(dtype), routing, cut(p["w1"]),
                          cut(p["w3"]), cut(p["w2"]), offset, 8)[:2]


@pytest.mark.parametrize("offset", [0, 2, 4, 6])
def test_a_share_is_the_references_part_for_that_share(offset, moe_path):
    whole, p, z = _layer_inputs()
    out, counts = _jit_share(offset)(p, z)
    spec = dict(whole, experts=2, expert_offset=offset)
    cut = {k: (v[offset:offset + 2] if k in ("w1", "w2", "w3") else v)
           for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want, chosen = reference._experts_ffn(cut, z, spec, lambda x: x)
    np.testing.assert_allclose(out, want[0], atol=2e-5)
    assert int(counts.sum()) == int(
        ((chosen >= offset) & (chosen < offset + 2)).sum())


def test_the_four_shares_add_up_to_the_uncut_layer(moe_path):
    whole, p, z = _layer_inputs()
    parts = [_jit_share(o)(p, z) for o in (0, 2, 4, 6)]
    with jax.default_matmul_precision("highest"):
        want, _ = reference._experts_ffn(p, z, whole, lambda x: x)
    np.testing.assert_allclose(sum(out for out, _ in parts), want[0], atol=5e-5)
    # every assignment lands on exactly one share
    assert sum(int(c.sum()) for _, c in parts) == 2 * z.shape[1]


def test_a_path_runs_the_products_it_names(moe_path):
    whole, p, z = _layer_inputs()
    text = str(jax.make_jaxpr(lambda p, z: _share(p, z, 0))(p, z))
    assert ("pallas_call" in text) == (moe_path == "kernel")
    assert ("ragged_dot" in text) == (moe_path in ("grouped", "blocked"))


# -- (d) a router pushed onto one expert drops no token -------------------------
@pytest.mark.parametrize("held", [2, 8])
def test_a_router_pushed_onto_one_expert_drops_no_token(held, moe_path):
    whole, p, z = _layer_inputs(n=64)
    p = dict(p, expert_bias=p["expert_bias"].at[1].set(50.0))  # always chosen
    out, counts = _jit_share(0, held)(p, z)
    n = z.shape[1]
    assert int(counts[1]) == n  # every token, no capacity
    if held == 8:
        assert int(counts.sum()) == 2 * n  # k x tokens, none lost
    spec = dict(whole, experts=held)
    cut = {k: (v[:held] if k in ("w1", "w2", "w3") else v) for k, v in p.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = reference._experts_ffn(cut, z, spec, lambda x: x)
    np.testing.assert_allclose(out, want[0], atol=5e-5)
    assert np.isfinite(np.asarray(out)).all()


def test_the_expert_layers_gradient_is_the_references(moe_path):
    whole, p, z = _layer_inputs()

    def ours(p, z):
        return jnp.sum(_share(p, z, 2)[0] ** 2)

    def theirs(p, z):
        cut = {k: (v[2:4] if k in ("w1", "w2", "w3") else v) for k, v in p.items()}
        spec = dict(whole, experts=2, expert_offset=2)
        return jnp.sum(reference._experts_ffn(cut, z, spec, lambda x: x)[0] ** 2)

    with jax.default_matmul_precision("highest"):
        g_ours = jax.jit(jax.grad(ours, argnums=(0, 1)))(p, z)
        g_theirs = jax.jit(jax.grad(theirs, argnums=(0, 1)))(p, z)
    for leaf in ("router", "w1", "w2", "w3"):
        # both differentiate the whole layer's tree: nothing outside [2:4]
        a, b = g_ours[0][leaf], g_theirs[0][leaf]
        np.testing.assert_allclose(a, b, atol=2e-4 * float(jnp.abs(b).max()),
                                   err_msg=leaf)
        if leaf != "router":
            assert float(jnp.abs(a[:2]).max()) == 0.0 == float(jnp.abs(a[4:]).max())
    np.testing.assert_allclose(
        g_ours[1], g_theirs[1], atol=2e-4 * float(jnp.abs(g_theirs[1]).max()))
    assert float(jnp.abs(g_ours[0]["expert_bias"]).max()) == 0.0  # a buffer


# -- (d') the bound of a block, and the rows that land on it exactly ------------
@pytest.mark.parametrize("n,k,held,num_experts,rows", [
    (4096, 4, 8, 32, 5120),    # the cell's chunk: 4,096 expected + a quarter
    (4096, 4, 32, 32, 16384),  # a chip that holds every expert: all N * k
    (8192, 4, 8, 32, 10240),
    (300, 4, 8, 32, 512),      # 375 rows in whole tiles
    (300, 4, 1, 32, 512),      # never under a tile
    (64, 2, 2, 8, 128),        # this file's layers: a tile is over N * k
    (64, 2, 8, 8, 128),
])
def test_a_blocks_rows_are_a_function_of_the_shapes(n, k, held, num_experts, rows):
    assert (moe.ROW_TILE, moe.HELD_ROWS_MARGIN) == (512, 0.25)
    assert moe.block_rows(n, k, held, num_experts) == rows
    assert rows == min(n * k, 512 * math.ceil(
        n * k * held / num_experts * 1.25 / 512))


def _plain_share(z, experts, weights, w1, w3, w2):
    """Experts 0..held-1 of the layer, every token through every one of
    them, written out: out[n] = sum_j weights[n, j] FFN_experts[n, j](z[n])."""
    out = jnp.zeros_like(z)
    for e in range(w1.shape[0]):
        share = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        y = (jax.nn.silu(z @ w1[e]) * (z @ w3[e])) @ w2[e]
        out = out + share[:, None] * y
    return out


@pytest.mark.parametrize("over", [-1, 0, 1, 41], ids=lambda o: f"R{o:+d}")
@pytest.mark.parametrize("n,d,f,tile,block,kernel", [
    (64, 64, 32, 8, 40, False), (256, 128, 128, 128, 256, True),
], ids=["ragged-dot", "kernel"])
def test_rows_that_fill_a_block_exactly_and_one_more(
        over, n, d, f, tile, block, kernel, monkeypatch):
    """64 tokens, 2 of 8 experts held, tiles of 8: a block is 40 rows (the
    Pallas kernel under its interpreter: 256 tokens, tiles of 128, 256
    rows). The first ``block / 2`` tokens take both held experts (``block``
    rows), ``over`` more or fewer assignments land here: value, every
    gradient and the count of blocks."""
    monkeypatch.setattr(moe, "DENSE_ROWS", 0)
    monkeypatch.setattr(moe, "ROW_TILE", tile)
    monkeypatch.setattr(grouped_matmul, "INTERPRET", kernel)
    assert moe.block_rows(n, 2, 2, 8) == block
    experts = np.stack([2 + np.arange(n) % 3, 5 + np.arange(n) % 3], 1)
    experts[:block // 2] = (0, 1)
    if over < 0:
        experts[block // 2 - 1] = (0, 6)
    for i in range(max(over, 0)):
        experts[block // 2 + i] = (i % 2, 7)
    assert (experts < 2).sum() == block + over
    assert (experts[:, 0] != experts[:, 1]).all()  # as a top-k's are
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    z = jax.random.normal(keys[0], (n, d))
    weights = jax.nn.softmax(jax.random.normal(keys[1], (n, 2)))
    w1, w3 = (jax.random.normal(k, (2, d, f)) / 8 for k in keys[2:4])
    w2 = jax.random.normal(keys[4], (2, f, d)) / 6
    pull = jax.random.normal(keys[5], (n, d))
    experts = jnp.asarray(experts, jnp.int32)

    def ours(z, weights, w1, w3, w2):
        out, counts, ran = moe.expert_ffn(
            z, moe.Routing(experts, weights), w1, w3, w2, 0, 8)
        return jnp.sum(out * pull), (out, counts, ran)

    def theirs(z, weights, w1, w3, w2):
        out = _plain_share(z, experts, weights, w1, w3, w2)
        return jnp.sum(out * pull), out

    args = (z, weights, w1, w3, w2)
    moe._sorted_rows.clear_cache()  # traced by shape, whatever the path
    with jax.default_matmul_precision("highest"):
        (_, (out, counts, ran)), got = jax.jit(jax.value_and_grad(
            ours, argnums=range(5), has_aux=True))(*args)
        (_, want_out), want = jax.jit(jax.value_and_grad(
            theirs, argnums=range(5), has_aux=True))(*args)
    moe._sorted_rows.clear_cache()
    assert int(counts.sum()) == block + over
    assert int(ran) == {-1: 0, 0: 0, 1: 1, 41: 1 + (41 > block)}[over]
    np.testing.assert_allclose(out, want_out, atol=2e-5 * d / 64)
    for name, a, b in zip(("z", "weights", "w1", "w3", "w2"), got, want,
                          strict=True):
        np.testing.assert_allclose(
            a, b, atol=1e-4 * float(jnp.abs(b).max()), err_msg=name)


def _count(jaxpr, primitive):
    """Equations of ``primitive`` in the program (a kernel's own body, whose
    ``pl.when`` is a ``cond`` on the chip's scalar core, is not the
    program's)."""
    return sum((e.primitive.name == primitive) + sum(
        _count(sub, primitive) for sub in jax.core.jaxprs_in_params(e.params)
        if e.primitive.name != "pallas_call") for e in jaxpr.eqns)


@pytest.mark.parametrize("product", ["ragged_dot_general", "pallas_call"])
def test_the_learners_program_holds_one_copy_of_the_block(product, monkeypatch):
    """What PR 27 was refused for (a second, whole-batch copy of the expert
    layer beside the bounded one grew the compiled step by a third, and its
    set-up with it), caught before the chip: the gradient of a loss over
    ``model.unroll`` holds the grouped product (``jax.lax.ragged_dot``, or
    the Pallas kernel where it runs) as often with a block smaller than the
    rows (overflow possible) as with one block of all of them, the blocks
    are a loop, and no branch holds a grouped product."""
    monkeypatch.setattr(moe, "DENSE_ROWS", 0)
    tile, batch = 8, 3
    if product == "pallas_call":
        # 8 envs: 256 rows, so a block of one 128-row tile leaves overflow
        ctx, tile, batch = through_the_kernel(), 128, 8
    else:
        ctx = contextlib.nullcontext()
    with ctx:
        model, tokens = tiny(), tokens_of(1, batch)
        params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))

        def loss(p):
            out, _ = model.unroll(p, tokens)
            return jnp.sum(out.logits) + jnp.sum(out.value)

        def census():
            moe._sorted_rows.clear_cache()
            jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr
            return {name: _count(jaxpr, name) for name in (
                "ragged_dot_general", "pallas_call", "while", "cond")}

        monkeypatch.setattr(moe, "ROW_TILE", 512)
        assert moe.block_rows(tokens.size, 2, 2, 8) == 2 * tokens.size
        whole = census()
        monkeypatch.setattr(moe, "ROW_TILE", tile)
        assert moe.block_rows(tokens.size, 2, 2, 8) < 2 * tokens.size
        blocked = census()
    other = ({"ragged_dot_general", "pallas_call"} - {product}).pop()
    # an expert layer: 3 products forward, and in the backward's loop the 3
    # again (nothing of a block is kept) with the 2 transposes of each
    assert whole == blocked == {
        product: 2 * 12, other: 0, "while": 2 * 2, "cond": 0}


# -- (f) the recall game -------------------------------------------------------
@pytest.mark.parametrize("ids,prompt,episode", [(256, 4, 16), (16384, 64, 256), (7, 3, 5)])
def test_recall_is_its_reference_bit_for_bit(ids, prompt, episode):
    env = RecallEnv(ids, prompt, episode)
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    ours = jax.vmap(env.reset)(keys)
    theirs = jax.vmap(lambda k: ref_recall.reset(k, ids, prompt))(keys)
    step_ref = jax.jit(jax.vmap(
        lambda s, a, k: ref_recall.step(s, a, k, ids, episode)))
    step = jax.jit(jax.vmap(env.step))
    for t in range(2 * episode + 3):
        np.testing.assert_array_equal(
            jax.vmap(env.render)(ours), jax.vmap(ref_recall.shown)(theirs))
        actions = jax.random.randint(jax.random.PRNGKey(t), (6,), 0, ids)
        # half the envs answer what the verifier wants
        at = (t % episode - prompt) % prompt
        actions = actions.at[:3].set(ours.prompt[:3, at])
        step_keys = jax.random.split(jax.random.PRNGKey(1000 + t), 6)
        ours, o1, r1, d1 = step(ours, actions, step_keys)
        theirs, o2, r2, d2 = step_ref(theirs, actions, step_keys)
        for a, b in ((o1, o2), (r1, r2), (d1, d2), (ours.prompt, theirs["prompt"]),
                     (ours.t, theirs["t"]), (ours.last_action, theirs["last_action"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if t % episode >= prompt:
            assert np.asarray(r1[:3]).tolist() == [1.0, 1.0, 1.0]  # the verifier
        else:
            assert float(r1.sum()) == 0.0
        assert bool(d1.all()) == (t % episode == episode - 1)


def test_recall_by_name():
    assert jaxenv.get_env("recall").num_actions == 16384
    env = jaxenv.get_env("recall:256:4:16")
    assert (env.num_actions, env.prompt_len, env.episode_length) == (256, 4, 16)
    with pytest.raises(ValueError):
        jaxenv.get_env("recall:256")


# -- (e), (g) one fused update: its gradient is the reference's ----------------
def _fused(n_shards, n_envs=8, dtype=jnp.float32, grad_chunk_samples=32, seed=11):
    """The step's programs are traced at its first call: callers hold
    ``as_on_the_chip`` round that (the rollout's few tokens the one form of
    the expert layer, a learner chunk's many the other)."""
    env = RecallEnv(IDS, PROMPT, EPISODE)
    cfg = BA3CConfig(num_actions=IDS, batch_size=n_envs * EPISODE // n_shards)
    model = tiny(dtype)
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
    # the step donates its state: what a test keeps, it keeps on the host
    return env, cfg, model, step, state, jax.device_get(params)


@contextlib.contextmanager
def as_on_the_chip():
    """8 envs a decode step at or under, 32 samples a chunk over."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "DENSE_ROWS", 8)
        yield


@contextlib.contextmanager
def _kernel_in_the_step():
    """``through_the_kernel`` for a whole fused step. Pallas's interpreters
    bind a kernel's primitives on operands some of which vary over the mesh
    and some of which do not, which ``shard_map``'s typing of varying axes
    refuses (on the chip the kernel is one custom call, and
    tests/test_grouped_matmul.py compiles it for one): the step is built
    with that typing off, which changes no number on one device."""
    with through_the_kernel(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "shard_map", functools.partial(
            jax.shard_map, check_vma=False))
        yield


@pytest.fixture(scope="module", params=[(1, False), (2, False), (1, True)],
                ids=["one-device", "two-shards", "one-device-kernel"])
def one_update(request):
    """One fused update in float32 and what the reference makes of the same
    start and the same actions; once more with the learner's grouped
    products in the Pallas kernel (64 tokens a chunk: 128 sorted rows)."""
    import optax

    n_shards, kernel = request.param
    with _kernel_in_the_step() if kernel else contextlib.nullcontext():
        env, cfg, model, step, state, params = _fused(
            n_shards, grad_chunk_samples=64 if kernel else 32)
        per = 8 // n_shards
        env_state0 = jax.device_get(state.env_state)
        keys = [np.asarray(jax.random.key_data(k)) if jnp.issubdtype(
            k.dtype, jax.dtypes.prng_key) else np.asarray(k) for k in state.key]
        with as_on_the_chip():
            put = step.put(state)
            products = str(jax.make_jaxpr(step.audit_jit)(
                put, jnp.float32(0), jnp.float32(0)))
            assert ("pallas_call" in products) == kernel
            assert ("ragged_dot" in products) != kernel
            new, metrics = step(
                put, HYPER["entropy_beta"], HYPER["learning_rate"])
        # the step says what it drew: [T, B_global] -> [shards, T, envs a shard]
        actions = np.stack([np.asarray(metrics["actions"])[:, s * per:(s + 1) * per]
                            for s in range(n_shards)])
        mu = optax.tree_utils.tree_get(new.train.opt_state, "mu")
        grad = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - 0.9), mu)
        u = dict(n_shards=n_shards, params=params, actions=actions, keys=keys,
                 env_state0=env_state0, new=new, metrics=metrics, grad=grad)
        u["reference"] = _reference_update(u)  # at this case's widths
    return u


def _reference_update(u):
    """The reference from the same env batch (handed over: this test is
    about the gradient) and the same per-shard streams."""
    numbers = {k: float(v) for k, v in HYPER.items()}
    per = 8 // u["n_shards"]
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for s in range(u["n_shards"]):
            env_state = {k: v[s * per:(s + 1) * per]
                         for k, v in u["env_state0"]._asdict().items()}
            l, g, *_ = reference._shard_pass(
                u["params"], env_state, jax.vmap(ref_recall.shown)(env_state),
                jnp.asarray(u["keys"][s]), jnp.asarray(u["actions"][s]), None,
                numbers, reference._spec_key(SPEC), None, 4)
            loss = loss + l
            grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
        n = 8.0 * EPISODE
        clipped = reference.clip_by_global_norm(
            jax.tree_util.tree_map(lambda g: g / n, grads), HYPER["grad_clip_norm"])
    return float(loss) / n, clipped


def test_the_fused_steps_gradient_is_the_references(one_update):
    loss, want = one_update["reference"]
    assert abs(float(one_update["metrics"]["loss"]) - loss) < 2e-4
    for layer, leaves in want.items():
        for leaf, g in leaves.items():
            got = one_update["grad"][layer][leaf]
            scale = max(float(jnp.abs(g).max()), 1e-4)
            np.testing.assert_allclose(
                got, g, atol=2e-3 * scale, err_msg=f"{layer}/{leaf}")


def test_a_fused_update_moves_the_state_and_counts_its_tokens(one_update):
    new, metrics, n_shards = (one_update[k] for k in ("new", "metrics", "n_shards"))
    held = np.asarray(metrics["moe_tokens_per_expert"])
    assert held.shape == (2, 2)
    # about a quarter (2 of 8 held) of the 2 x tokens assignments a layer
    assert 0.05 < held.sum() / (2 * 2 * 8 * EPISODE) < 0.6
    # the blocks run beyond the first, summed over chunks and shards: a
    # chunk's 64 rows are under a tile, so one block holds them all
    ran = np.asarray(metrics["moe_overflow_blocks"])
    assert ran.dtype == np.int32 and ran.tolist() == [0, 0]
    assert int(metrics["episodes"]) == 8  # every env ended its episode
    # what it generated: every env's tokens and actions, [T, B_global]; from
    # the prompt's end on an env shows its own last action
    tokens, actions = (np.asarray(metrics[k]) for k in ("tokens", "actions"))
    assert tokens.shape == actions.shape == (EPISODE, 8)
    np.testing.assert_array_equal(tokens[PROMPT + 1:], actions[PROMPT:-1])
    assert np.asarray(new.policy_carry[1]).all()  # the next token opens one
    assert len(new.policy_carry[0].pos.sharding.device_set) == n_shards
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), new.train.params,
        one_update["params"])
    assert moved["layer_2"]["w1"] > 0 and moved["embed"]["table"] > 0
    assert moved["layer_2"]["expert_bias"] == 0.0  # the buffer stays
    assert np.isfinite(float(metrics["loss"]))


def test_learner_chunks_are_whole_envs():
    assert learner_chunks(128, 128 * 256, 4096) == 8      # 16 envs a chunk
    assert learner_chunks(8, 128, 32) == 4
    assert learner_chunks(6, 6 * 16, 40) == 3             # 2.4 -> 3 divides 6
    assert learner_chunks(81920, 81920, 4096) == 20       # the conv cells' rule


# -- the scopes of the sequence policy are in the compiled step -----------------
@pytest.fixture(scope="module")
def compiled_op_names():
    _, _, _, step, state, _ = _fused(1, dtype=jnp.bfloat16)
    with as_on_the_chip():
        hlo = step.audit_jit.lower(
            step.put(state), jnp.float32(0.01), jnp.float32(1e-3)
        ).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', hlo))


#: open only round the Pallas grouped products, which this small step's
#: chunks (64 rows on the CPU) do not reach
_BY_KERNEL = tuple(profiling.policy_scope(under, profiling.MOE_EXPERTS_GMM)
                   for under in (profiling.ROLLOUT_POLICY, profiling.LEARNER))


@pytest.mark.parametrize("scope", profiling.SEQUENCE_SCOPES)
def test_every_sequence_scope_is_in_the_compiled_step(compiled_op_names, scope):
    assert scope in profiling.ALL_SCOPES and scope not in profiling.SCOPES
    found = {profiling.scope_of(name) for name in compiled_op_names}
    there = any(s is not None and (s == scope or s.startswith(scope + "/"))
                for s in found)
    # this policy's layers, and of those all but the kernel's
    mine = scope == profiling.ROLLOUT_WEIGHTS_BF16 or any(
        scope == profiling.policy_scope(under, layer)
        for under in (profiling.ROLLOUT_POLICY, profiling.LEARNER)
        for layer in profiling.LFM2_LAYERS)
    assert there == (mine and scope not in _BY_KERNEL), scope


def test_the_kernels_scope_is_in_the_learner_where_the_kernel_is_lowered():
    """``learner/moe/experts/gmm`` holds the Pallas calls, forward and
    backward, of a learner whose products run in the kernel (time there
    says the mechanism ran); the rollout's few tokens never reach it."""
    with _kernel_in_the_step(), as_on_the_chip():
        _, _, _, step, state, _ = _fused(1, grad_chunk_samples=64)
        hlo = step.audit_jit.lower(
            step.put(state), jnp.float32(0.01), jnp.float32(1e-3)
        ).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    gmm = {n for n in names if profiling.scope_of(n) == profiling.policy_scope(
        profiling.LEARNER, profiling.MOE_EXPERTS_GMM)}
    assert any(profiling.is_backward(n) for n in gmm)
    assert any(not profiling.is_backward(n) for n in gmm)
    assert not any((profiling.scope_of(n) or "").startswith(_BY_KERNEL[0])
                   for n in names)


# -- (h) what is refused ---------------------------------------------------------
def test_a_segment_that_starts_mid_episode_is_refused():
    env = RecallEnv(IDS, PROMPT, EPISODE)
    cfg = BA3CConfig(num_actions=IDS, batch_size=64)
    mesh = make_mesh(num_data=1, num_model=1, devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="mid-episode"):
        make_fused_step(tiny(), make_optimizer(1e-3), cfg, mesh, env, EPISODE // 2)
    from distributed_ba3c_tpu.envs.jaxenv import pong
    with pytest.raises(ValueError, match="episode length"):
        make_fused_step(tiny(), make_optimizer(1e-3), cfg, mesh, pong, 20)


def _builders():
    from distributed_ba3c_tpu.fused.overlap import make_overlap_step
    from distributed_ba3c_tpu.parallel.train_step import (
        make_macro_train_step,
        make_train_step,
    )
    from distributed_ba3c_tpu.parallel.vtrace_step import (
        make_vtrace_macro_step,
        make_vtrace_train_step,
    )
    from distributed_ba3c_tpu.pod.learner import make_pod_learner_step
    from distributed_ba3c_tpu.predict.server import make_fwd_sample

    return [make_overlap_step, make_train_step, make_macro_train_step,
            make_vtrace_train_step, make_vtrace_macro_step,
            make_pod_learner_step, make_fwd_sample, make_greedy_eval]


@pytest.mark.parametrize("builder", _builders(), ids=lambda f: f.__name__)
def test_every_other_trainer_refuses_a_policy_that_carries_state(builder):
    needed = {
        name: None for name, p in inspect.signature(builder).parameters.items()
        if p.default is p.empty and name != "model"}
    with pytest.raises(ValueError, match="carries state"):
        builder(model=tiny(), **needed)
    # and the stateless policy is not refused by the same check
    policy.refuse_carry(policy.build_model("ba3cnet", BA3CConfig()), "anything")


@pytest.mark.parametrize("argv", [
    ["--trainer", "tpu_vtrace_ba3c"], ["--trainer", "tpu_sync_ba3c"],
    ["--trainer", "tpu_fused_ba3c", "--task", "eval", "--load", "x"],
])
def test_the_cli_refuses_a_carry_policy_off_the_fused_trainer(argv, capsys):
    from distributed_ba3c_tpu import cli

    with pytest.raises(SystemExit) as e:
        cli.main(["--model", "lfm2-moe", "--model_cut", "tiny", "--env", "fake",
                  "--tpu_lock", "off"] + argv)
    assert e.value.code == 2
    assert "carries state" in capsys.readouterr().err


def test_the_registry_builds_by_name():
    cfg = BA3CConfig(num_actions=6)
    assert type(policy.build_model("ba3cnet", cfg)).__name__ == "BA3CNet"
    model = policy.build_model("lfm2-moe", cfg.replace(num_actions=16384))
    assert policy.carries_state(model) and model.hidden_size == 2048
    assert model.experts_held == 8 and model.num_experts == 32
    with pytest.raises(ValueError):
        policy.build_model("no-such-model", cfg)
    with pytest.raises(ValueError):
        policy.build_model("lfm2-moe", cfg, "no-such-cut")
    with pytest.raises(ValueError):
        policy.build_model("ba3cnet", cfg, "tiny")
