"""Olmo-Hybrid-7B as a token-sequence policy, at a size the CPU runs (hidden
64, 2 heads of an uncut 6 of both kinds, keys of 8 and values of 16,
vocabulary 32, episodes of 24 = three chunks of the delta rule): the model
against the benchmark's plain reference (logits, value, loss, every
gradient leaf), decoding through the carry against the unroll across a
reset, the shares of heads and of the vocabulary adding up to the uncut
layer and head, the fused step through ``cli.py``'s parser, the scopes, the
refusals.
"""

import dataclasses
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

from benchmark import opcount_olmohybrid  # noqa: E402
from benchmark.reference import olmo_hybrid as reference, recall as ref_recall  # noqa: E402
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
from distributed_ba3c_tpu.models.olmo_hybrid import (  # noqa: E402
    CUTS, FULL, LAYER_TYPES, LINEAR, OlmoHybrid)
from distributed_ba3c_tpu.ops import delta_rule  # noqa: E402
from distributed_ba3c_tpu.ops.gradproc import make_optimizer  # noqa: E402
from distributed_ba3c_tpu.parallel.mesh import make_mesh  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

IDS, PROMPT, EPISODE = 32, 4, 24
SHARES = 3  # chips that share a layer by heads
#: the configuration's keys at the small cut, as the reference reads them
TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 2,
    "num_key_value_heads": 2, "head_dim": 16, "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "linear_key_head_dim": 8,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "attention_bias": False,
    "rms_norm_eps": 1e-6, "vocab_size": IDS, "layer_types": list(LAYER_TYPES),
    "held": {"layers": [0, 1, 2, 3]},
}
SPEC = reference.spec_of(TINY_CONFIG)
#: the same layers with every head: what the three shares add up to
UNCUT_SPEC = dict(SPEC, heads=SHARES * SPEC["heads"],
                  lin_heads=SHARES * SPEC["lin_heads"])
HYPER = {"gamma": 0.99, "entropy_beta": 0.01, "value_loss_coef": 0.5,
         "grad_clip_norm": 0.5, "learning_rate": 1e-3, "adam_epsilon": 1e-3}
#: bfloat16 at a hidden size of 64 is coarse: a fifth of the largest logit
TOLERANCE = [(jnp.float32, 2e-4), (jnp.bfloat16, 0.2)]


def tiny(compute_dtype=jnp.float32, **kw) -> OlmoHybrid:
    fields = dict(CUTS["tiny"], num_actions=IDS, max_positions=EPISODE,
                  compute_dtype=compute_dtype)
    return OlmoHybrid(**dict(fields, **kw))


def params_of(seed, spec=SPEC):
    """The reference's seeded weights with every vector moved off its start
    (unit gains hide a wrong reading)."""
    params = reference.init_params(jax.random.PRNGKey(seed), spec)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1000), 256))
    return {layer: {leaf: x + 0.1 * jax.random.normal(next(keys), x.shape)
                    if x.ndim == 1 and leaf != "bias" else x
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
    assert len(LAYER_TYPES) == 32
    assert LAYER_TYPES.count(LINEAR) == 24 and LAYER_TYPES.count(FULL) == 8
    assert all(kind == (FULL if i % 4 == 3 else LINEAR)
               for i, kind in enumerate(LAYER_TYPES))
    whole = OlmoHybrid()
    assert whole.layer_kinds == (LINEAR, LINEAR, LINEAR, FULL)
    # a third of the published 30 heads of every mixer, the widths untouched
    assert (whole.num_attention_heads, whole.linear_num_heads) == (10, 10)
    assert (whole.hidden_size, whole.intermediate_size, whole.head_dim,
            whole.linear_key_head_dim, whole.linear_value_head_dim,
            whole.linear_conv_kernel_dim, whole.rms_norm_eps) == (
        3840, 11008, 128, 96, 192, 4, 1e-6)
    small = tiny()
    assert small.num_attention_heads * SHARES == 6 == small.linear_num_heads * SHARES


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
    assert {"conv_w", "A_log", "dt_bias", "o_norm", "q_norm", "k_norm",
            "mix_norm", "ffn_norm", "norm", "kernel", "bias"} == kept


def test_the_held_parameter_count_is_the_operation_counts():
    with open(os.path.join(
            ROOT, "benchmark/configs/olmo-hybrid-7b-recall-fused-a2c.json")) as f:
        config = json.load(f)
    shapes = jax.eval_shape(OlmoHybrid().init_params, jax.random.PRNGKey(0))
    held = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert held == opcount_olmohybrid.params_held(config) == 712_039_037
    assert held == config["deployment"]["parameters_held"]


# -- against the plain reference --------------------------------------------------
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("dtype,tol", TOLERANCE)
def test_unroll_agrees_with_the_reference(seed, dtype, tol):
    params, tokens = params_of(seed), tokens_of(seed)
    out, aux = jax.jit(tiny(dtype).unroll)(params, tokens)
    with jax.default_matmul_precision("highest"):
        logits, value = reference.forward(params, tokens, SPEC)
    assert aux == {}
    scale = float(jnp.abs(logits).max())
    assert float(jnp.abs(out.logits - logits).max()) < tol * scale
    assert float(jnp.abs(out.value - value).max()) < tol * max(
        float(jnp.abs(value).max()), 1e-2)


def _loss(forward):
    def fn(params, tokens, actions, returns):
        logits, value = forward(params, tokens)
        logp = jax.nn.log_softmax(logits)
        logp_a = jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]
        advantage = returns - jax.lax.stop_gradient(value)
        return (-jnp.sum(logp_a * advantage) + 0.25 * jnp.sum(jnp.square(value - returns))
                + 0.01 * jnp.sum(jnp.exp(logp) * logp))
    return fn


@pytest.fixture(scope="module")
def both_gradients():
    params, tokens = params_of(3), tokens_of(3)
    actions = tokens_of(4)
    returns = jax.random.normal(jax.random.PRNGKey(5), tokens.shape)
    model = tiny()

    def ours(p, t):
        out, _ = model.unroll(p, t)
        return out.logits, out.value

    got = jax.jit(jax.value_and_grad(_loss(ours)))(params, tokens, actions, returns)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(_loss(
            lambda p, t: reference.forward(p, t, SPEC))))(
            params, tokens, actions, returns)
    return got, want


_LEAVES = [f"{layer}/{leaf}" for layer, leaves in sorted(
    jax.eval_shape(lambda: reference.init_params(
        jax.random.PRNGKey(0), SPEC)).items()) for leaf in sorted(leaves)]


def test_the_loss_is_the_references(both_gradients):
    (loss, _), (want, _) = both_gradients
    assert abs(float(loss) - float(want)) < 1e-4 * abs(float(want))


@pytest.mark.parametrize("name", _LEAVES)
def test_a_leafs_gradient_of_the_loss_is_the_references(both_gradients, name):
    layer, leaf = name.split("/")
    (_, got), (_, want) = both_gradients
    g, w = got[layer][leaf], want[layer][leaf]
    scale = float(jnp.abs(w).max())
    assert scale > 0, "a leaf the loss does not reach"
    np.testing.assert_allclose(g, w, atol=2e-3 * scale, err_msg=name)


# -- the learner's delta rule in its kernels ------------------------------------------
#: the small cut with the rule at the kernels' shapes: 2 heads of 96 keys and
#: 192 values (the cell's), chunks of 64 positions
LANES = dict(linear_key_head_dim=96, linear_value_head_dim=192)
#: (the tiny cut's chunks are 8 positions; the reference has no chunks)
CHUNKS = dict(delta_chunk=delta_rule.CHUNK)


def _at_the_kernels_shapes(length):
    """(model, params, tokens, actions) of that cut over 2 episodes."""
    return (tiny(max_positions=length, **LANES, **CHUNKS),
            params_of(31, reference.spec_of(dict(TINY_CONFIG, **LANES))),
            tokens_of(32, 2, length), tokens_of(33, 2, length))


def test_the_unroll_through_the_kernels_is_the_plain_forms_and_the_decode_is_untouched(
        monkeypatch):
    """The loss and every leaf's gradient with ``delta_chunked``'s kernels
    (interpreted) against its plain form, within float32 rounding, over
    episodes that are no whole chunks; ``step`` traces the same program
    either way, with no kernel in it."""
    model, params, tokens, actions = _at_the_kernels_shapes(160)
    returns = jax.random.normal(jax.random.PRNGKey(34), tokens.shape)

    def both_ways():  # a function of its own: which path a trace took is kept
        return jax.jit(jax.value_and_grad(_loss(
            lambda p, t: tuple(model.unroll(p, t)[0]))))

    def decode_step():
        return str(jax.make_jaxpr(lambda p, t, carry, fresh: model.step(
            p, t, carry, fresh))(
            params, tokens[:, 0], model.init_carry(2), jnp.ones((2,), bool)))

    plain, plain_step = both_ways()(params, tokens, actions, returns), decode_step()
    monkeypatch.setattr(delta_rule, "INTERPRET", True)
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


@pytest.mark.parametrize("engaged", [True, False], ids=["kernels", "plain"])
def test_the_kernels_scope_in_the_lowered_unroll_says_whether_they_engaged(
        monkeypatch, engaged):
    """``learner``-side op names of the unroll's backward: under
    ``op_linattn/delta`` either way, under ``delta_chunks`` inside it only
    where the kernels took the shapes."""
    monkeypatch.setattr(delta_rule, "INTERPRET", engaged)
    model, params, tokens, actions = _at_the_kernels_shapes(128)
    text = jax.jit(jax.grad(_loss(lambda p, t: tuple(model.unroll(p, t)[0])))).lower(
        params, tokens, actions, jnp.zeros(tokens.shape)).as_text(debug_info=True)
    assert profiling.OP_LINATTN_DELTA in text
    assert (profiling.OP_LINATTN_DELTA_KERNEL in text) == engaged
    assert ("triangular_solve" in text) != engaged


# -- the decode through the carry ---------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 0.1)])
def test_step_through_the_carry_is_the_unroll_with_a_fresh_in_the_middle(dtype, tol):
    """Position by position, two episodes back to back: the second opens in
    the middle of the rollout, on whatever the first left in the carry. In
    bfloat16 the two forms of the delta rule, which agree to 1e-6, round a
    product's operand to either side now and then (one part in 256 of it),
    and a hidden size of 64 averages nothing away."""
    model = tiny(dtype)
    params = params_of(7)
    tokens = tokens_of(7, length=2 * EPISODE)
    out, _ = jax.jit(model.unroll)(params, tokens.reshape(6, EPISODE))
    logits, value, carry = jax.jit(
        lambda p, t: decode(model, p, t, fresh_at=(EPISODE,)))(
        model.rollout_params(params) if dtype == jnp.bfloat16 else params, tokens)
    scale = float(jnp.abs(out.logits).max())
    np.testing.assert_allclose(
        logits.reshape(6, EPISODE, IDS), out.logits, atol=tol * scale)
    np.testing.assert_allclose(
        value.reshape(6, EPISODE), out.value, atol=tol * scale)
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
    H, K, V = 2, 8, 16
    states, tails, kv, small = model.carry_bytes()
    assert states == 3 * H * K * V * 4          # a float32 matrix a head, 3 layers
    assert tails == 3 * 3 * H * (2 * K + V) * 4  # the conv's last three inputs
    assert kv == 2 * EPISODE * (2 * 16) * 4     # k and v of one full layer (float32 here)
    assert small == 4 + 3 * H * 4               # the position, the last gates
    # the cell's: 0.74 MB of state a layer, constant in the episode's length
    whole = OlmoHybrid().carry_bytes()
    assert whole[0] == 3 * 10 * 96 * 192 * 4 and whole[2] == 2 * 2048 * 1280 * 2
    _, _, carry = decode(model, params_of(2), tokens_of(2))
    gauges = model.carry_gauges(carry)
    assert np.asarray(gauges["carry_bytes_per_env"]).tolist() == list(
        model.carry_bytes())
    largest = max(float(jnp.abs(s).max()) for s, _, _ in carry.linear)
    assert float(gauges["linattn_state_absmax"]) == pytest.approx(largest)
    gates = float(np.mean([np.asarray(g) for _, _, g in carry.linear]))
    assert float(gauges["linattn_gate_mean"]) == pytest.approx(gates)
    assert largest > 0 and 0 < gates < 1
    stats = model.epoch_stats({k: np.asarray(v) for k, v in gauges.items()})
    assert stats == {"linattn_state_absmax": pytest.approx(largest),
                     "linattn_gate_mean": pytest.approx(gates),
                     "carry_bytes_per_env": float(sum(model.carry_bytes()))}


def test_a_state_kept_in_bfloat16_decodes_another_answer():
    """The benchmark's control is no no-op: the carry's state in bfloat16."""
    params, tokens = params_of(9), tokens_of(9)
    low = tiny(state_dtype=jnp.bfloat16)
    assert low.init_carry(1).linear[0][0].dtype == jnp.bfloat16
    through = lambda model: jax.jit(  # noqa: E731
        lambda p, t: (decode(model, p, t), model.unroll(p, t)[0].logits))
    (sound, _, _), learner = through(tiny())(params, tokens)
    (rounded, _, carry), learner_low = through(low)(params, tokens)
    assert carry.linear[0][0].dtype == jnp.bfloat16
    assert 1e-4 < float(jnp.abs(sound - rounded).max()) < 0.5
    assert float(jnp.abs(learner - learner_low).max()) > 1e-5


# -- the shares add up --------------------------------------------------------------
def _columns(x, heads, share):
    """The columns of ``share``'s heads: x [.., heads * w] -> [.., heads / 3 * w]."""
    w = x.shape[-1] // heads
    per = heads // SHARES
    return x[..., share * per * w:(share + 1) * per * w]


def _linear_share(p, share):
    H, K = UNCUT_SPEC["lin_heads"], SPEC["K"]
    sections = lambda x: jnp.split(x, (H * K, 2 * H * K), axis=-1)  # noqa: E731
    cut = lambda x, heads=H: _columns(x, heads, share)  # noqa: E731
    return dict(
        wqkv=jnp.concatenate([cut(s) for s in sections(p["wqkv"])], -1),
        conv_w=jnp.concatenate([cut(s) for s in sections(p["conv_w"])], -1),
        wz=cut(p["wz"]), wa=cut(p["wa"]), wb=cut(p["wb"]),
        A_log=cut(p["A_log"]), dt_bias=cut(p["dt_bias"]), o_norm=p["o_norm"],
        wo=_columns(p["wo"].T, H, share).T)


def _full_share(p, share):
    H = UNCUT_SPEC["heads"]
    cut = lambda x: _columns(x, H, share)  # noqa: E731
    return dict(wq=cut(p["wq"]), wk=cut(p["wk"]), wv=cut(p["wv"]),
                q_norm=cut(p["q_norm"]), k_norm=cut(p["k_norm"]),
                wo=cut(p["wo"].T).T)


@pytest.fixture(scope="module")
def uncut():
    return (params_of(12, UNCUT_SPEC),
            jax.random.normal(jax.random.PRNGKey(13), (2, EPISODE, 64)))


def test_three_head_shares_of_a_linear_mixer_add_up_to_the_uncut_layer(uncut):
    """A linear head's norms and gate are its own: its share is exact."""
    params, x = uncut
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: reference.linear_mixer(p, x, UNCUT_SPEC))(
            params["layer_1"], x)
    mixer = jax.jit(tiny().linear_mixer)
    parts = [mixer(_linear_share(params["layer_1"], s), x) for s in range(SHARES)]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(sum(parts), want, atol=2e-5 * scale)
    assert all(float(jnp.abs(p).max()) > 0.05 * scale for p in parts)


def test_three_head_shares_of_the_full_mixer_add_up_given_the_norms_sums(uncut):
    """The q / k norm spans the heads: each share is handed the sums of
    squares over all three, the one quantity a deployment would add up."""
    params, x = uncut
    with jax.default_matmul_precision("highest"):
        want = reference.full_mixer(params["layer_3"], x, UNCUT_SPEC)
    model = tiny()
    shares = [_full_share(params["layer_3"], s) for s in range(SHARES)]
    sums = [model.qk_sums(p, x) for p in shares]
    whole = (sum(s[0] for s in sums), sum(s[1] for s in sums),
             sum(s[2] for s in sums))
    assert whole[2] == UNCUT_SPEC["heads"] * SPEC["head_dim"]
    parts = [model.full_mixer(p, x, whole) for p in shares]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(sum(parts), want, atol=2e-5 * scale)
    # left to itself a share norms over its own heads: the cut's departure
    alone = sum(model.full_mixer(p, x) for p in shares)
    assert float(jnp.abs(alone - want).max()) > 1e-3 * scale


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


# -- the fused step, built from ``cli.py``'s parser -----------------------------------
ARGV = ["--trainer", "tpu_fused_ba3c", "--model", "olmo-hybrid", "--model_cut",
        "tiny", "--env", f"jax:recall:{IDS}:{PROMPT}:{EPISODE}", "--rollout_len",
        str(EPISODE), "--batch_size", str(4 * EPISODE), "--grad_chunk_samples",
        str(2 * EPISODE), "--learning_rate", "0.001", "--adam_epsilon", "0.001",
        "--grad_clip_norm", "0.5", "--entropy_beta", "0.01"]
N_SHARDS, N_ENVS = 2, 8


@pytest.fixture(scope="module")
def two_updates():
    """Two fused updates on two shards in float32 (chunks of 2 envs), built
    as ``cli.py`` builds them, and what the reference makes of the first
    from the same start and the same actions."""
    import optax

    args = cli.make_parser().parse_args(ARGV)
    cfg = cli.build_config(args)
    env = jaxenv.get_env(args.env.split(":", 1)[1])
    model = dataclasses.replace(
        policy.build_model(args.model, cfg, args.model_cut).for_env(env),
        compute_dtype=jnp.float32)
    assert isinstance(model, OlmoHybrid) and model == tiny()
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
    numbers = {k: float(v) for k, v in HYPER.items()}
    loss, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        for s in range(N_SHARDS):
            env_state = {k: v[s * per:(s + 1) * per]
                         for k, v in env_state0._asdict().items()}
            l, g, *_ = reference._shard_pass(
                params, env_state, jax.vmap(ref_recall.shown)(env_state),
                jnp.asarray(keys[s]), jnp.asarray(actions[s]), numbers,
                reference._spec_key(SPEC), None, 2)
            loss = loss + l
            grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
        n = float(N_ENVS * EPISODE)
        clipped = clip_by_global_norm(
            jax.tree_util.tree_map(lambda g: g / n, grads), HYPER["grad_clip_norm"])
    return dict(params=params, first_params=first_params, carry=first_carry,
                metrics=metrics, metrics_2=jax.device_get(metrics_2),
                second_params=jax.device_get(second.train.params), grad=grad,
                reference=(float(loss) / n, clipped), model=model,
                op_names=set(re.findall(r'op_name="([^"]*)"', hlo)))


def test_the_fused_steps_gradient_is_the_references(two_updates):
    loss, want = two_updates["reference"]
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
    largest = max(float(np.abs(s).max()) for s, _, _ in held.linear)
    assert float(metrics["linattn_state_absmax"]) == pytest.approx(largest)
    assert largest > 0
    # the mean gate of the last position, the larger of the two shards'
    per = N_ENVS // N_SHARDS
    by_shard = [np.mean([np.asarray(g)[s * per:(s + 1) * per]
                         for _, _, g in held.linear]) for s in range(N_SHARDS)]
    assert float(metrics["linattn_gate_mean"]) == pytest.approx(max(by_shard))
    assert np.asarray(fresh).all() and held.pos.tolist() == [EPISODE] * N_ENVS
    for name in ("loss", "linattn_state_absmax", "linattn_gate_mean"):
        assert np.isfinite(two_updates["metrics_2"][name])
    for before, after in (("params", "first_params"),
                          ("first_params", "second_params")):
        moved = jax.tree_util.tree_map(
            lambda a, b: float(np.abs(a - b).max()), two_updates[after],
            two_updates[before])
        for layer, leaf in (("layer_0", "A_log"), ("layer_1", "wqkv"),
                            ("layer_2", "conv_w"), ("layer_2", "wb"),
                            ("layer_3", "q_norm"), ("layer_3", "wo"),
                            ("embed", "table"), ("head", "table")):
            assert moved[layer][leaf] > 0, (before, layer, leaf)


def test_the_fused_loop_names_no_model():
    import inspect

    from distributed_ba3c_tpu.fused import loop

    source = inspect.getsource(loop)
    for name in ("olmo", "linattn", "delta_rule", "OlmoHybrid"):
        assert name not in source, name


# -- the scopes ----------------------------------------------------------------------
#: open only round the Pallas kernels of the decode's attention and of the
#: learner's delta rule, which this small step (heads of 16 lanes, chunks of
#: 8 positions, on the CPU) does not reach
_BY_KERNEL = (f"{profiling.OP_ATTN_FULL}/{profiling.DECODE_ATTEND}",
              profiling.OP_LINATTN_DELTA_KERNEL)


def test_this_policys_layers_are_among_the_policies_layers():
    assert set(profiling.OLMO_HYBRID_LAYERS) <= set(profiling.POLICY_LAYERS)
    assert len(set(profiling.POLICY_LAYERS)) == len(profiling.POLICY_LAYERS)
    assert {profiling.OP_LINATTN, profiling.OP_LINATTN_IN_PROJ,
            profiling.OP_LINATTN_CONV, profiling.OP_LINATTN_DELTA,
            profiling.OP_LINATTN_OUT, *_BY_KERNEL} <= set(profiling.OLMO_HYBRID_LAYERS)
    assert profiling.scope_of(
        "jit(multi_step)/learner/transpose(jvp(learner))/jvp()/checkpoint/"
        "rematted_computation/op_linattn/delta/triangular_solve"
    ) == "learner/op_linattn/delta"
    assert profiling.scope_of(
        "jit(multi_step)/learner/transpose(jvp(learner))/checkpoint/op_linattn/"
        "delta/transpose(jvp(delta_chunks))/jit(_backward)/delta_chunks_backward/"
        "pallas_call") == "learner/op_linattn/delta/delta_chunks"
    # what was there keeps its place: the rule's kernels' scope comes last
    assert profiling.OLMO_HYBRID_LAYERS[-1] == profiling.OP_LINATTN_DELTA_KERNEL


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
        for layer in profiling.OLMO_HYBRID_LAYERS)
    assert there == (mine and not scope.endswith(_BY_KERNEL)), scope


def test_the_learners_delta_rule_is_marked_forward_and_backward(two_updates):
    delta = {n for n in two_updates["op_names"] if profiling.scope_of(n) ==
             profiling.policy_scope(profiling.LEARNER, profiling.OP_LINATTN_DELTA)}
    assert any(profiling.is_backward(n) for n in delta)
    assert any(not profiling.is_backward(n) for n in delta)
    rollout = {n for n in two_updates["op_names"] if profiling.scope_of(n) ==
               profiling.policy_scope(profiling.ROLLOUT_POLICY,
                                      profiling.OP_LINATTN_DELTA)}
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
        cli.main(argv + ["--model", "olmo-hybrid", "--model_cut", "tiny"])
    assert e.value.code == 2
    assert "carries state" in capsys.readouterr().err


def test_every_other_path_refuses_it_through_refuse_carry():
    with pytest.raises(ValueError, match="carries state.*OlmoHybrid"):
        policy.refuse_carry(tiny(), "the greedy on-device evaluator")
    assert policy.carries_state(tiny())


def test_the_registry_builds_by_name():
    cfg = BA3CConfig(num_actions=IDS)
    model = policy.build_model("olmo-hybrid", cfg, "tiny")
    assert isinstance(model, OlmoHybrid) and policy.carries_state(model)
    assert model.hidden_size == 64 and model.num_actions == IDS
    whole = policy.build_model("olmo-hybrid", cfg)
    assert whole.hidden_size == 3840 and whole.layer_ids == (0, 1, 2, 3)
    assert policy.build_model("olmo-hybrid", cfg, "head-share-3") == whole
    env = RecallEnv(IDS, PROMPT, EPISODE)
    assert whole.for_env(env) == dataclasses.replace(
        whole, num_actions=IDS, max_positions=EPISODE)
    with pytest.raises(ValueError, match="model_cut"):
        policy.build_model("olmo-hybrid", cfg, "chip-share-8")
    assert "olmo-hybrid" in cli.make_parser().format_help()
