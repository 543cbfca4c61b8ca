"""Nemotron-3-Nano-30B-A3B as a token-sequence policy, at a size the CPU runs
(hidden 64, 4 Mamba-2 heads of 8 in 2 groups on a state of 16, 2 of 32
experts of width 24 beside a shared expert of 48, 4 query heads over 2 K/V
heads, vocabulary 32, episodes of 24 = three SSD chunks): the model against
the benchmark's plain reference (logits, value, loss, every gradient leaf),
on both paths of the expert layer, decoding through the carry against the
unroll across a reset, the shares of experts and of the vocabulary adding up
to the uncut layer and head, the fused step through ``cli.py``'s parser, the
scopes, the refusals.
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

from benchmark import opcount_nemotronh  # noqa: E402
from benchmark.reference import nemotron_h as reference, recall as ref_recall  # noqa: E402
from benchmark.reference.ba3c import clip_by_global_norm  # noqa: E402
from distributed_ba3c_tpu import cli  # noqa: E402
from distributed_ba3c_tpu.config import BA3CConfig  # noqa: E402
from distributed_ba3c_tpu.envs import jaxenv  # noqa: E402
from distributed_ba3c_tpu.envs.jaxenv.recall import RecallEnv  # noqa: E402
from distributed_ba3c_tpu.fused.loop import (  # noqa: E402
    create_fused_state,
    make_fused_step,
)
from distributed_ba3c_tpu.models import policy  # noqa: E402
from distributed_ba3c_tpu.models.nemotron_h import (  # noqa: E402
    ATTENTION, CUTS, EXPERTS, MAMBA, PATTERN, NemotronH)
from distributed_ba3c_tpu.ops import moe, ssd  # noqa: E402
from distributed_ba3c_tpu.ops.gradproc import make_optimizer  # noqa: E402
from distributed_ba3c_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

IDS, PROMPT, EPISODE = 32, 4, 24
SHARES = 16  # chips that share a layer expert parallel
#: the configuration's keys at the small cut, as the reference reads them
TINY_CONFIG = {
    "hidden_size": 64, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
    "layer_norm_epsilon": 1e-5, "time_step_min": 1e-3, "time_step_max": 1e-1,
    "time_step_floor": 1e-4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "n_routed_experts": 2,
    "num_experts_per_tok": 3, "routed_scaling_factor": 2.5,
    "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 48,
    "mlp_hidden_act": "relu2", "mlp_bias": False, "use_conv_bias": True,
    "mamba_proj_bias": False, "attention_bias": False,
    "tie_word_embeddings": False, "n_group": 1, "topk_group": 1,
    "n_shared_experts": 1, "norm_topk_prob": True, "vocab_size": IDS,
    "hybrid_override_pattern": PATTERN,
    "published": {"n_routed_experts": 32},
    "held": {"layers": [0, 1, 4, 5, 6], "expert_offset": 0},
}
SPEC = reference.spec_of(TINY_CONFIG)
#: the same blocks with every expert: what the sixteen shares add up to
UNCUT_SPEC = dict(SPEC, experts=SHARES * SPEC["experts"])
HYPER = {"gamma": 0.99, "entropy_beta": 0.01, "value_loss_coef": 0.5,
         "grad_clip_norm": 0.5, "learning_rate": 1e-3, "adam_epsilon": 1e-3}
#: bfloat16 at a hidden size of 64 is coarse: a fifth of the largest logit
TOLERANCE = [(jnp.float32, 2e-4), (jnp.bfloat16, 0.2)]
#: envs whose whole episodes are few enough tokens for ``DENSE_ROWS`` (every
#: held expert computes every token) and enough for the sorted rows
ENVS = {"every-token": 3, "sorted-rows": 12}


def tiny(compute_dtype=jnp.float32, **kw) -> NemotronH:
    fields = dict(CUTS["tiny"], num_actions=IDS, max_positions=EPISODE,
                  compute_dtype=compute_dtype)
    return NemotronH(**dict(fields, **kw))


def params_of(seed, spec=SPEC):
    """The reference's seeded weights with every vector moved off its start
    (unit gains and ``D`` = 1 hide a wrong reading)."""
    params = reference.init_params(jax.random.PRNGKey(seed), spec)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1000), 256))
    return {layer: {leaf: x + 0.1 * jax.random.normal(next(keys), x.shape)
                    if x.ndim == 1 and leaf not in ("bias", "expert_bias") else x
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

    carry, (logits, value) = jax.lax.scan(
        one, model.init_carry(B), (jnp.swapaxes(tokens, 0, 1), fresh))
    return jnp.swapaxes(logits, 0, 1), jnp.swapaxes(value, 0, 1), carry


# -- the architecture as the configuration states it ----------------------------
def test_the_published_kinds_and_the_cuts():
    assert len(PATTERN) == 52
    assert (PATTERN.count(MAMBA), PATTERN.count(EXPERTS),
            PATTERN.count(ATTENTION)) == (23, 23, 6)
    whole = NemotronH()
    # one whole period: the kinds come from the pattern and the ids held
    assert "".join(whole.layer_kinds) == "MEMEM*EME" == PATTERN[:9]
    assert whole.layer_ids == tuple(range(9))
    # a sixteenth of the routed experts, the widths untouched
    assert whole.experts_held * SHARES == whole.n_routed_experts == 128
    assert (whole.hidden_size, whole.mamba_num_heads, whole.mamba_head_dim,
            whole.ssm_state_size, whole.n_groups, whole.conv_kernel,
            whole.chunk_size, whole.num_attention_heads,
            whole.num_key_value_heads, whole.head_dim, whole.num_experts_per_tok,
            whole.moe_intermediate_size, whole.moe_shared_expert_intermediate_size,
            whole.routed_scaling_factor, whole.layer_norm_epsilon) == (
        2688, 64, 64, 128, 8, 4, 128, 32, 2, 128, 6, 1856, 3712, 2.5, 1e-5)
    assert (whole.d_inner, whole.conv_width) == (4096, 6144)
    assert whole.moe_intermediate_size % 128 == 64  # 14.5 lanes
    small = tiny()
    assert "".join(small.layer_kinds) == "MEM*E"
    assert small.experts_held * SHARES == small.n_routed_experts
    # another layer of the pattern is another kind, with no table in the code
    assert dataclasses.replace(whole, layer_ids=(5, 12, 51)).layer_kinds == (
        ATTENTION, ATTENTION, EXPERTS)


def test_the_programs_parameters_are_the_references():
    ours = tiny().init_params(jax.random.PRNGKey(5))
    theirs = reference.init_params(jax.random.PRNGKey(5), SPEC)
    assert jax.tree_util.tree_structure(ours) == jax.tree_util.tree_structure(theirs)
    for a, b in zip(jax.tree_util.tree_leaves(ours),
                    jax.tree_util.tree_leaves(theirs), strict=True):
        np.testing.assert_array_equal(a, b)
    served = tiny(jnp.bfloat16).rollout_params(ours)
    kept = {leaf for leaves in served.values() for leaf, x in leaves.items()
            if x.dtype == jnp.float32}
    assert {"conv_w", "conv_b", "A_log", "D", "dt_bias", "gate_norm", "norm",
            "router", "expert_bias", "kernel", "bias"} == kept
    start = ours["layer_0"]
    assert float(jnp.abs(start["D"] - 1).max()) == 0
    assert 1.0 <= float(jnp.exp(start["A_log"]).min()) <= float(
        jnp.exp(start["A_log"]).max()) <= 16.0
    step = jax.nn.softplus(start["dt_bias"])
    assert 1e-3 * 0.999 <= float(step.min()) <= float(step.max()) <= 1e-1 * 1.001


def test_the_held_parameter_count_is_the_operation_counts():
    size = lambda tree: sum(  # noqa: E731
        int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(tree))
    shapes = jax.eval_shape(tiny().init_params, jax.random.PRNGKey(0))
    assert size(shapes) == opcount_nemotronh.params_held(TINY_CONFIG)
    # the cell's: every leaf of the chip's share at the published widths
    whole = jax.eval_shape(NemotronH().init_params, jax.random.PRNGKey(0))
    assert size(whole) == 666_966_145


@pytest.mark.parametrize("path", sorted(ENVS))
@pytest.mark.parametrize("dtype,tol", TOLERANCE)
def test_unroll_agrees_with_the_reference(path, dtype, tol):
    model, params = tiny(dtype), params_of(3)
    tokens = tokens_of(4, batch=ENVS[path])
    assert (tokens.size > moe.DENSE_ROWS) == (path == "sorted-rows")
    out, aux = jax.jit(lambda p, t: model.unroll(p, t, with_routes=True))(
        params, tokens)
    # the reference computes WITH the routes the program chose (in bfloat16
    # a near-tie flips, and a flipped route moves its token by a whole
    # expert) and says beside them what it would have chosen
    with jax.default_matmul_precision("highest"):
        logits, value, routes = reference.forward(
            params, tokens, SPEC, forced_routes=aux["routes"])
    scale = float(jnp.abs(logits).max())
    assert scale > 1.0
    np.testing.assert_allclose(out.logits, logits, atol=tol * scale)
    np.testing.assert_allclose(out.value, value, atol=tol * scale)
    assert aux["routes"].shape == routes.shape == (2, ENVS[path], EPISODE, 3)
    if dtype == jnp.float32:
        np.testing.assert_array_equal(
            jnp.sort(aux["routes"], -1), jnp.sort(routes, -1))
    # the counter: the tokens the router sent to each held expert
    held = np.asarray(aux["moe_tokens_per_expert"])
    want = np.stack([(np.asarray(aux["routes"])[layer] == e).sum()
                     for layer in range(2) for e in range(2)]).reshape(2, 2)
    np.testing.assert_array_equal(held, want)
    assert np.asarray(aux["moe_overflow_blocks"]).tolist() == [0, 0]


def _loss(forward):
    def value(params, tokens, actions, returns):
        logits, values = forward(params, tokens)
        logp = jax.nn.log_softmax(logits)
        logp_a = jnp.take_along_axis(logp, actions[..., None], -1)[..., 0]
        adv = returns - jax.lax.stop_gradient(values)
        return (-jnp.sum(logp_a * adv) + 0.5 * 0.5 * jnp.sum(jnp.square(values - returns))
                + 0.01 * jnp.sum(jnp.exp(logp) * logp))
    return value


@pytest.fixture(scope="module", params=sorted(ENVS))
def both_gradients(request):
    """The A2C loss and its gradient through the policy's unroll and through
    the reference's forward, float32, on either path of the expert layer."""
    model, params = tiny(), params_of(21)
    batch = ENVS[request.param]
    tokens = tokens_of(22, batch=batch)
    actions = tokens_of(23, batch=batch)
    returns = jax.random.normal(jax.random.PRNGKey(24), tokens.shape)
    ours = jax.jit(jax.value_and_grad(_loss(
        lambda p, t: tuple(model.unroll(p, t)[0]))))(params, tokens, actions, returns)
    with jax.default_matmul_precision("highest"):
        theirs = jax.jit(jax.value_and_grad(_loss(
            lambda p, t: reference.forward(p, t, SPEC)[:2])))(
            params, tokens, actions, returns)
    return ours, theirs


_LEAVES = [f"{layer}/{leaf}" for layer, leaves in sorted(
    jax.eval_shape(lambda: reference.init_params(jax.random.PRNGKey(0), SPEC)).items())
    for leaf in sorted(leaves)]


def test_the_loss_is_the_references(both_gradients):
    (ours, _), (theirs, _) = both_gradients
    assert abs(float(ours) - float(theirs)) < 2e-4 * max(abs(float(theirs)), 1.0)


@pytest.mark.parametrize("name", _LEAVES)
def test_a_leafs_gradient_of_the_loss_is_the_references(both_gradients, name):
    (_, got), (_, want) = both_gradients
    layer, leaf = name.split("/")
    g, w = got[layer][leaf], want[layer][leaf]
    scale = float(jnp.abs(w).max())
    if leaf == "expert_bias":  # it only chooses: no gradient reaches it
        assert scale == 0 and float(jnp.abs(g).max()) == 0
        return
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(g, w, atol=2e-3 * scale, err_msg=name)


# -- the learner's recurrence in its kernels ------------------------------------------
#: the small cut with the recurrence at the kernels' shapes: 4 heads of 64
#: channels in 2 groups, states of 128 numbers a row, chunks of 128 positions
LANES = dict(mamba_head_dim=64, ssm_state_size=128, chunk_size=128)


def test_the_unroll_through_the_kernels_is_the_plain_forms_and_the_decode_is_untouched(
        monkeypatch):
    """The loss and every leaf's gradient with ``ssd_chunked``'s kernels
    (interpreted) against its plain form, within float32 rounding, over
    episodes that are no whole chunks; ``step`` traces the same program
    either way, with no kernel in it."""
    length = 160
    model = tiny(max_positions=length, **LANES)
    params = params_of(31, reference.spec_of(dict(TINY_CONFIG, **LANES)))
    tokens, actions = tokens_of(32, 2, length), tokens_of(33, 2, length)
    returns = jax.random.normal(jax.random.PRNGKey(34), tokens.shape)

    def both_ways():  # a function of its own: which path a trace took is kept
        return jax.jit(jax.value_and_grad(_loss(
            lambda p, t: tuple(model.unroll(p, t)[0]))))

    def decode_step():
        return str(jax.make_jaxpr(lambda p, t, carry, fresh: model.step(
            p, t, carry, fresh))(
            params, tokens[:, 0], model.init_carry(2), jnp.ones((2,), bool)))

    plain, plain_step = both_ways()(params, tokens, actions, returns), decode_step()
    monkeypatch.setattr(ssd, "INTERPRET", True)
    assert "pallas_call" in str(jax.make_jaxpr(both_ways())(
        params, tokens, actions, returns))
    kernels = both_ways()(params, tokens, actions, returns)
    assert abs(float(kernels[0]) - float(plain[0])) < 1e-5 * abs(float(plain[0]))
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(kernels[1]),
                                 jax.tree_util.tree_leaves(plain[1])):
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(
            got, want, atol=1e-4 * scale, err_msg=jax.tree_util.keystr(path))
    assert decode_step() == plain_step and "pallas_call" not in plain_step


# -- the decode through the carry ---------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 0.1)])
def test_step_through_the_carry_is_the_unroll_with_a_fresh_in_the_middle(dtype, tol):
    """Position by position, two episodes back to back: the second opens in
    the middle of the rollout, on whatever the first left in the carry. In
    bfloat16 a router's near-tie flips now and then between the two forms
    (their operands round to either side), and a flipped route moves its
    token by a whole expert: nine tokens in ten are held to the tolerance."""
    model = tiny(dtype)
    params = params_of(7)
    tokens = tokens_of(7, length=2 * EPISODE)
    out, _ = jax.jit(model.unroll)(params, tokens.reshape(6, EPISODE))
    logits, value, carry = jax.jit(
        lambda p, t: decode(model, p, t, fresh_at=(EPISODE,)))(
        model.rollout_params(params) if dtype == jnp.bfloat16 else params, tokens)
    scale = float(jnp.abs(out.logits).max())
    gap = jnp.abs(logits.reshape(6, EPISODE, IDS) - out.logits).max(-1)
    value_gap = jnp.abs(value.reshape(6, EPISODE) - out.value)
    if dtype == jnp.float32:
        assert float(gap.max()) <= tol * scale and float(value_gap.max()) <= tol * scale
    else:
        assert float(jnp.mean(gap <= tol * scale)) >= 0.9
    assert carry.pos.tolist() == [EPISODE] * 3


@pytest.mark.parametrize("at", [1, 7, 13])
def test_a_fresh_token_forgets_the_episode_before(at):
    model, params = tiny(), params_of(8)
    tokens = tokens_of(8)
    logits, _, _ = decode(model, params, tokens, fresh_at=(at,))
    alone, _, _ = decode(model, params, tokens[:, at:])
    np.testing.assert_allclose(logits[:, at:], alone, atol=2e-5)
    whole, _, _ = decode(model, params, tokens)
    assert float(jnp.abs(logits[:, at:] - whole[:, at:]).max()) > 1e-3


def test_the_carrys_bytes_and_gauges():
    model = tiny()
    h, P, N, g = 4, 8, 16, 2
    states, tails, kv, small = model.carry_bytes()
    assert states == 2 * h * P * N * 4            # a float32 matrix a head, 2 layers
    assert tails == 2 * 3 * (h * P + 2 * g * N) * 4  # the conv's last three inputs
    assert kv == 2 * EPISODE * (2 * 16) * 4       # k and v of the one attention block
    assert small == 4 + 2 * h * 4                 # the position, the last step sizes
    # the cell's: 2.1 MB of state a layer an env, constant in the episode's
    # length; one row of 256 lanes a position for the one K/V
    whole = NemotronH().carry_bytes()
    assert whole[0] == 4 * 64 * 64 * 128 * 4 and whole[1] == 4 * 3 * 6144 * 4
    assert whole[2] == 2 * 2048 * 256 * 2
    _, _, carry = decode(model, params_of(2), tokens_of(2))
    gauges = model.carry_gauges(carry)
    assert np.asarray(gauges["carry_bytes_per_env"]).tolist() == list(
        model.carry_bytes())
    largest = max(float(jnp.abs(s).max()) for s, _, _ in carry.mamba)
    assert float(gauges["ssm_state_absmax"]) == pytest.approx(largest)
    steps = float(np.mean([np.asarray(dt) for _, _, dt in carry.mamba]))
    assert float(gauges["ssm_dt_mean"]) == pytest.approx(steps)
    assert largest > 0 and 0 < steps < 1
    stats = model.epoch_stats(dict(
        {k: np.asarray(v) for k, v in gauges.items()},
        moe_tokens_per_expert=np.array([[3, 1], [2, 2]]),
        moe_overflow_blocks=np.array([0, 1])))
    assert stats == {"ssm_state_absmax": pytest.approx(largest),
                     "ssm_dt_mean": pytest.approx(steps),
                     "carry_bytes_per_env": float(sum(model.carry_bytes())),
                     "moe_load_max_over_mean": 1.5, "moe_overflow_blocks": 1.0}


def test_a_state_kept_in_bfloat16_decodes_another_answer():
    """The benchmark's control is no no-op: the carry's state in bfloat16."""
    params, tokens = params_of(9), tokens_of(9)
    low = tiny(state_dtype=jnp.bfloat16)
    assert low.init_carry(1).mamba[0][0].dtype == jnp.bfloat16
    through = lambda model: jax.jit(  # noqa: E731
        lambda p, t: (decode(model, p, t), model.unroll(p, t)[0].logits))
    (sound, _, _), learner = through(tiny())(params, tokens)
    (rounded, _, carry), learner_low = through(low)(params, tokens)
    assert carry.mamba[0][0].dtype == jnp.bfloat16
    assert 1e-4 < float(jnp.abs(sound - rounded).max()) < 0.5
    assert float(jnp.abs(learner - learner_low).max()) > 1e-5


# -- the shares add up --------------------------------------------------------------
@pytest.fixture(scope="module")
def uncut():
    """An expert block with all 32 experts, and its input."""
    params = params_of(12, UNCUT_SPEC)["layer_1"]
    return params, jax.random.normal(jax.random.PRNGKey(13), (2, EPISODE, 64))


@pytest.mark.parametrize("path", sorted(ENVS))
def test_sixteen_expert_shares_with_the_shared_expert_once_are_the_uncut_layer(
        uncut, path):
    """The 16 chips' partial sums of the routed experts, plus the shared
    expert that every chip computes alike counted ONCE, are the uncut
    reference's expert block (an assignment to an absent expert adds nothing
    here and is another chip's)."""
    p, x = uncut
    x = jnp.concatenate([x] * (ENVS[path] // 2 + 1))[:ENVS[path]]
    rows = x.reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        u = reference._rms(x, p["norm"], SPEC["eps"])
        whole, routes = reference.experts_mixer(p, u, UNCUT_SPEC)
    total, counted = 0.0, 0
    for s in range(SHARES):
        model = tiny(expert_offset=2 * s)
        held = dict(p, w1=p["w1"][2 * s:2 * s + 2], w2=p["w2"][2 * s:2 * s + 2])
        mixed, (counts, chosen, _) = model.experts_mixer(held, rows)
        shared = model.shared_expert(
            held, reference._rms(rows, p["norm"], SPEC["eps"]))
        total = total + (mixed - shared)
        counted += int(counts.sum())
        np.testing.assert_array_equal(
            jnp.sort(chosen, -1), jnp.sort(routes.reshape(-1, 3), -1))
    assert counted == rows.shape[0] * 3  # every assignment is some chip's
    np.testing.assert_allclose(
        (total + shared).reshape(x.shape), whole,
        atol=2e-5 * float(jnp.abs(whole).max()))


def test_an_expert_block_has_room_for_twice_the_even_share():
    """A learner chunk of one env at the cell: 768 expected rows a block of
    8 held experts; the policy's own margin makes a block 1,536 rows (the
    shared margin's 1,024 ran a second pass on a third of the seeds)."""
    from distributed_ba3c_tpu.models import nemotron_h

    assert nemotron_h.EXPERT_ROWS_MARGIN == 1.0
    assert moe.block_rows(2048, 6, 8, 128) == 1024  # the shared margin
    assert moe.block_rows(2048, 6, 8, 128, nemotron_h.EXPERT_ROWS_MARGIN) == 1536
    # a margin handed over is the policy's alone: the other cells' blocks stay
    assert moe.block_rows(4096, 4, 8, 32) == 5120
    assert moe.block_rows(8192, 8, 16, 128) == 10240


def test_eight_vocabulary_slices_logits_are_the_uncut_heads():
    params = params_of(14)
    x = jax.random.normal(jax.random.PRNGKey(15), (5, 64))
    whole = tiny()._head(params, x)
    per = IDS // 8
    for s in range(8):
        table = params["head"]["table"][s * per:(s + 1) * per]
        part = tiny(num_actions=per)._head(
            dict(params, head={"table": table}), x)
        np.testing.assert_allclose(
            part.logits, whole.logits[:, s * per:(s + 1) * per], atol=1e-6)
        np.testing.assert_allclose(part.value, whole.value, atol=1e-6)


def test_sixteen_query_heads_a_kv_head_go_through_as_two_groups_of_eight():
    """At the published grouping the learner's attention lays each K/V head
    down twice (the kernels take 8 query heads a K/V head): the same
    function, and the K/V's gradient adds up."""
    model = tiny(num_attention_heads=32, num_key_value_heads=2)
    spec = dict(SPEC, q_heads=32)
    p = params_of(16, spec)["layer_5"]
    x = jax.random.normal(jax.random.PRNGKey(17), (2, EPISODE, 64))

    def ours(p):
        return model.attention_mixer(p, x)

    def theirs(p):
        return reference.attention_mixer(
            p, reference._rms(x, p["norm"], SPEC["eps"]), spec)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(ours(p), theirs(p), atol=2e-5)
        got = jax.grad(lambda p: jnp.sum(jnp.sin(ours(p))))(p)
        want = jax.grad(lambda p: jnp.sum(jnp.sin(theirs(p))))(p)
    for leaf in ("wk", "wv", "wq", "wo"):
        np.testing.assert_allclose(
            got[leaf], want[leaf], atol=2e-4 * float(jnp.abs(want[leaf]).max()))


# -- the fused step, built from ``cli.py``'s parser -----------------------------------
N_SHARDS, N_ENVS = 2, 24
ARGV = ["--trainer", "tpu_fused_ba3c", "--model", "nemotron-h", "--model_cut",
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
    model = dataclasses.replace(
        policy.build_model(args.model, cfg, args.model_cut).for_env(env),
        compute_dtype=jnp.float32)
    assert isinstance(model, NemotronH) and model == tiny()
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
            l, g, *_, flipped = reference._shard_pass(
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
        model.carry_bytes())
    held, fresh = two_updates["carry"]
    largest = max(float(np.abs(s).max()) for s, _, _ in held.mamba)
    assert float(metrics["ssm_state_absmax"]) == pytest.approx(largest)
    assert largest > 0
    # the mean step size of the last position, the larger of the two shards'
    per = N_ENVS // N_SHARDS
    by_shard = [np.mean([np.asarray(dt)[s * per:(s + 1) * per]
                         for _, _, dt in held.mamba]) for s in range(N_SHARDS)]
    assert float(metrics["ssm_dt_mean"]) == pytest.approx(max(by_shard))
    assert np.asarray(fresh).all() and held.pos.tolist() == [EPISODE] * N_ENVS
    # the experts' counters: every token of the update, summed over the shards
    routed = np.asarray(metrics["moe_tokens_per_expert"])
    assert routed.shape == (2, 2) and 0 < routed.sum() <= 2 * 3 * N_ENVS * EPISODE
    assert np.asarray(metrics["moe_overflow_blocks"]).shape == (2,)
    stats = model.epoch_stats(metrics)
    assert set(stats) == {"ssm_state_absmax", "ssm_dt_mean", "carry_bytes_per_env",
                          "moe_load_max_over_mean", "moe_overflow_blocks"}
    for name in ("loss", "ssm_state_absmax", "ssm_dt_mean"):
        assert np.isfinite(two_updates["metrics_2"][name])
    for before, after in (("params", "first_params"),
                          ("first_params", "second_params")):
        moved = jax.tree_util.tree_map(
            lambda a, b: float(np.abs(a - b).max()), two_updates[after],
            two_updates[before])
        for layer, leaf in (("layer_0", "A_log"), ("layer_0", "in_proj"),
                            ("layer_4", "conv_b"), ("layer_4", "D"),
                            ("layer_4", "dt_bias"), ("layer_1", "router"),
                            ("layer_1", "w1"), ("layer_6", "shared_w2"),
                            ("layer_5", "wk"), ("layer_5", "wo"),
                            ("embed", "table"), ("head", "table")):
            assert moved[layer][leaf] > 0, (before, layer, leaf)
        assert moved["layer_1"]["expert_bias"] == 0  # it only chooses


def test_the_fused_loop_names_no_model():
    import inspect

    from distributed_ba3c_tpu.fused import loop

    source = inspect.getsource(loop)
    for name in ("nemotron", "mamba", "ssd", "NemotronH"):
        assert name not in source, name


# -- the scopes ----------------------------------------------------------------------
#: open only round the Pallas kernels (the grouped products, the recurrence's
#: chunks), which this small step (experts of 24, chunks of 8, on the CPU)
#: does not reach
_BY_KERNEL = (profiling.MOE_EXPERTS_GMM, profiling.OP_MAMBA2_SSD_KERNEL)


def test_this_policys_layers_are_among_the_policies_layers():
    assert set(profiling.NEMOTRON_H_LAYERS) <= set(profiling.POLICY_LAYERS)
    assert len(set(profiling.POLICY_LAYERS)) == len(profiling.POLICY_LAYERS)
    assert {profiling.OP_MAMBA2, profiling.OP_MAMBA2_IN_PROJ,
            profiling.OP_MAMBA2_CONV, profiling.OP_MAMBA2_SSD,
            profiling.OP_MAMBA2_OUT, profiling.MOE_SHARED, profiling.OP_ATTN_FULL,
            *_BY_KERNEL} <= set(profiling.NEMOTRON_H_LAYERS)
    assert profiling.scope_of(
        "jit(multi_step)/learner/transpose(jvp(learner))/jvp()/checkpoint/"
        "rematted_computation/op_mamba2/ssd/dot_general"
    ) == "learner/op_mamba2/ssd"
    assert profiling.scope_of(
        "jit(multi_step)/learner/transpose(jvp(learner))/checkpoint/op_mamba2/"
        "ssd/transpose(jvp(ssd_chunks))/jit(_backward)/ssd_chunks_backward/"
        "pallas_call") == "learner/op_mamba2/ssd/ssd_chunks"
    assert profiling.scope_of(
        "jit(multi_step)/rollout/while/body/policy/moe/shared/dot_general"
    ) == "rollout/policy/moe/shared"
    # what was there keeps its place: the new layers come after (and a later
    # policy's after them)
    earlier = (profiling.LFM2_LAYERS + profiling.PHI4_FLASH_LAYERS
               + profiling.KEYE_VL2_LAYERS + profiling.OLMO_HYBRID_LAYERS)
    before = [l for l in profiling.POLICY_LAYERS if l in earlier]
    assert list(profiling.POLICY_LAYERS[:len(before)]) == before
    own = [l for l in profiling.NEMOTRON_H_LAYERS if l not in earlier]
    assert list(profiling.POLICY_LAYERS[len(before):len(before) + len(own)]) == own
    # the decode's kernel takes 8 query heads a K/V head and never runs here
    assert f"{profiling.OP_ATTN_FULL}/{profiling.DECODE_ATTEND}" not in (
        profiling.NEMOTRON_H_LAYERS)


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
        for layer in profiling.NEMOTRON_H_LAYERS)
    assert there == (mine and not scope.endswith(_BY_KERNEL)), scope


def test_the_learners_recurrence_is_marked_forward_and_backward(two_updates):
    recurrence = {n for n in two_updates["op_names"] if profiling.scope_of(n) ==
                  profiling.policy_scope(profiling.LEARNER, profiling.OP_MAMBA2_SSD)}
    assert any(profiling.is_backward(n) for n in recurrence)
    assert any(not profiling.is_backward(n) for n in recurrence)
    rollout = {n for n in two_updates["op_names"] if profiling.scope_of(n) ==
               profiling.policy_scope(profiling.ROLLOUT_POLICY,
                                      profiling.OP_MAMBA2_SSD)}
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
        cli.main(argv + ["--model", "nemotron-h", "--model_cut", "tiny"])
    assert e.value.code == 2
    assert "carries state" in capsys.readouterr().err


def test_every_other_path_refuses_it_through_refuse_carry():
    with pytest.raises(ValueError, match="carries state.*NemotronH"):
        policy.refuse_carry(tiny(), "the greedy on-device evaluator")
    assert policy.carries_state(tiny())


def test_the_registry_builds_by_name():
    cfg = BA3CConfig(num_actions=IDS)
    model = policy.build_model("nemotron-h", cfg, "tiny")
    assert isinstance(model, NemotronH) and policy.carries_state(model)
    assert model.hidden_size == 64 and model.num_actions == IDS
    whole = policy.build_model("nemotron-h", cfg)
    assert whole.hidden_size == 2688 and whole.layer_ids == tuple(range(9))
    assert policy.build_model("nemotron-h", cfg, "chip-share-16") == whole
    env = RecallEnv(IDS, PROMPT, EPISODE)
    assert whole.for_env(env) == dataclasses.replace(
        whole, num_actions=IDS, max_positions=EPISODE)
    with pytest.raises(ValueError, match="model_cut"):
        policy.build_model("nemotron-h", cfg, "head-share-3")
    help_text = cli.make_parser().format_help()
    assert "nemotron-h" in help_text and "chip-share-16" in help_text
