"""models/sequence.py: what the token-sequence policies share, held for
every carrying policy of the registry at its ``tiny`` cut, so that a policy
added to ``policy.MODELS`` is held to it by its registry line alone."""

import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ba3c_tpu import cli
from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.envs.jaxenv.recall import RecallEnv
from distributed_ba3c_tpu.models import layers, policy, sequence
from distributed_ba3c_tpu.ops import moe

CARRYING = [name for name in policy.MODELS if name != policy.DEFAULT_MODEL]
IDS, PROMPT, EPISODE = 32, 4, 16


def tiny(name):
    cfg = BA3CConfig(num_actions=IDS)
    return policy.build_model(name, cfg, "tiny").for_env(
        RecallEnv(IDS, PROMPT, EPISODE))


@pytest.mark.parametrize("name", CARRYING)
def test_a_carrying_policy_gives_the_protocol(name):
    cfg = BA3CConfig(num_actions=7)
    model = policy.build_model(name, cfg, "tiny")
    assert isinstance(model, sequence.SequencePolicy)
    assert policy.carries_state(model) and model.num_actions == 7
    for method in ("init_params", "init_carry", "step", "unroll",
                   "rollout_params", "for_env"):
        assert callable(getattr(model, method)), method
    for_env = model.for_env(RecallEnv(IDS, PROMPT, EPISODE))
    assert type(for_env) is type(model)
    assert (for_env.num_actions, for_env.max_positions) == (IDS, EPISODE)
    # the carry is an env a row, the position first
    carry = jax.eval_shape(lambda: for_env.init_carry(3))
    assert carry.pos.shape == (3,) and carry.pos.dtype == jnp.int32
    assert all(leaf.shape[0] == 3 for leaf in jax.tree_util.tree_leaves(carry))
    assert model.final_norm_eps > 0


@pytest.mark.parametrize("name", CARRYING)
def test_the_snapshot_keeps_float32_what_the_policy_names(name):
    model = tiny(name)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    served = jax.eval_shape(model.rollout_params, params)
    assert jax.tree_util.tree_structure(served) == jax.tree_util.tree_structure(params)
    cast = 0
    for layer, leaves in served.items():
        for leaf, x in leaves.items():
            assert x.shape == params[layer][leaf].shape
            keeps = (x.ndim < 2 or layer == "value"
                     or leaf in model.float32_leaves)
            want = jnp.float32 if keeps else model.compute_dtype
            assert x.dtype == want, (layer, leaf, x.dtype)
            cast += not keeps
    assert cast > 0
    # every name the policy keeps float32 is a leaf it has
    names = {leaf for leaves in params.values() for leaf in leaves}
    assert set(model.float32_leaves) <= names


@pytest.mark.parametrize("name", CARRYING)
def test_the_head_reads_the_table_its_class_names(name):
    model = tiny(name)
    params = model.init_params(jax.random.PRNGKey(1))
    assert (model.head_table == "embed") == ("head" not in params)
    x = jax.random.normal(jax.random.PRNGKey(2), (5, model.hidden_size))
    out = model._head(params, x)
    assert out.logits.shape == (5, IDS) and out.value.shape == (5,)

    def with_table(group, scale):
        return {**params, group: {"table": scale * params[group]["table"]}}

    doubled = model._head(with_table(model.head_table, 2.0), x)
    np.testing.assert_allclose(doubled.logits, 2.0 * out.logits, rtol=2e-2, atol=1e-6)
    if model.head_table != "embed":  # untied: the embedding is not the head's
        other = model._head(with_table("embed", 2.0), x)
        np.testing.assert_array_equal(other.logits, out.logits)
    np.testing.assert_array_equal(doubled.value, out.value)


@pytest.mark.parametrize("name", CARRYING)
def test_init_params_is_the_scaffolds_order_of_keys(name):
    """The embedding draws the first key and the value head the last, so a
    policy's layers see the keys between whatever the scaffold holds."""
    model = tiny(name)
    params = model.init_params(jax.random.PRNGKey(3))
    init = sequence.Seeded(jax.random.PRNGKey(3), len(model.layer_ids))
    d = model.hidden_size
    np.testing.assert_array_equal(
        params["embed"]["table"], init.normal((IDS, d), d))
    assert list(params)[0] == "embed" and list(params)[-1] == "value"
    assert [k for k in params if k.startswith("layer_")] == [
        model.layer_name(i) for i in range(len(model.layer_ids))]
    assert float(jnp.max(jnp.abs(params["value"]["kernel"]))) < 0.1
    assert all(leaf.dtype == jnp.float32
               for leaf in jax.tree_util.tree_leaves(params))


@pytest.mark.parametrize("name", CARRYING)
def test_carry_bytes_count_the_whole_carry(name):
    model = tiny(name)
    carry = jax.eval_shape(lambda: model.init_carry(1))
    whole = sum(x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(carry))
    assert model._carry_bytes(lambda c: (c.pos, c)) == (4, whole)
    if hasattr(model, "carry_bytes"):  # by kind: the kinds are the whole
        assert sum(model.carry_bytes()) == whole


@pytest.mark.parametrize("name", list(policy.MODELS))
def test_build_model_refuses_what_the_registry_does_not_hold(name):
    cfg = BA3CConfig(num_actions=6)
    with pytest.raises(ValueError, match="model_cut"):
        policy.build_model(name, cfg, "no-such-cut")
    with pytest.raises(ValueError, match="unknown model"):
        policy.build_model(name + "-2", cfg)
    model = policy.build_model(name, cfg)
    if name == policy.DEFAULT_MODEL:
        assert not policy.carries_state(model)
        return
    # no cut is the module's first, and every cut builds
    cuts = policy.cuts_by_model()[name]
    assert model == policy.build_model(name, cfg, cuts[0])
    assert "tiny" in cuts
    for cut in cuts:
        assert type(policy.build_model(name, cfg, cut)) is type(model)


def test_cut_fields_is_one_function_of_a_modules_cuts():
    cuts = {"whole": {}, "small": {"hidden_size": 8}}
    assert sequence.cut_fields(cuts, None) == {} == sequence.cut_fields(cuts, "whole")
    fields = sequence.cut_fields(cuts, "small")
    assert fields == {"hidden_size": 8} and fields is not cuts["small"]
    with pytest.raises(ValueError, match=r"unknown --model_cut 'tiny'.*small.*whole"):
        sequence.cut_fields(cuts, "tiny")


def test_the_help_names_every_policy_and_cut_from_the_registry():
    text = " ".join(cli.make_parser().format_help().split())
    for name in policy.MODELS:
        assert name in text
    for name, cuts in policy.cuts_by_model().items():
        assert f"{name}: {' | '.join(cuts)}" in text
    assert cli.make_parser().get_default("model") == policy.DEFAULT_MODEL


def test_parsing_flags_imports_no_policy_module():
    """Neither a parser nor a ``ba3cnet`` model imports a sequence policy
    (and so no Pallas); asking for help does, to list the cuts."""
    code = (
        "import sys\n"
        "from distributed_ba3c_tpu import cli\n"
        "from distributed_ba3c_tpu.models import policy\n"
        "args = cli.make_parser().parse_args([])\n"
        "policy.build_model(args.model, cli.build_config(args))\n"
        "loaded = [m for m in sys.modules if 'pallas' in m or m.endswith("
        "('.sequence', '.lfm2_moe', '.nemotron_h'))]\n"
        "assert not loaded, loaded\n"
        "cli.make_parser().format_help()\n"
        "assert 'distributed_ba3c_tpu.models.nemotron_h' in sys.modules\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


def test_decode_opening_at_fresh_rows():
    carry_pos = jnp.asarray([5, 0, 9, 2], jnp.int32)
    fresh = jnp.asarray([False, True, True, False])
    pos, keep = sequence.decode_opening(carry_pos, fresh)
    np.testing.assert_array_equal(pos, [5, 0, 0, 2])
    np.testing.assert_array_equal(keep, [True, False, False, True])
    assert pos.dtype == jnp.int32 and keep.dtype == jnp.bool_


@pytest.mark.parametrize("trailing", [(6,), (2, 3)])
def test_write_row_against_a_loop(trailing):
    B, P = 4, 5
    rng = np.random.default_rng(0)
    cache = rng.normal(size=(B, P, *trailing)).astype(np.float32)
    new = rng.normal(size=(B, 1, *trailing)).astype(np.float32)
    at = np.asarray([4, 0, 2, 2])
    want = cache.copy()
    for b in range(B):
        want[b, at[b]] = new[b, 0]
    got = jax.jit(sequence.write_row)(
        jnp.arange(B), jnp.asarray(cache), jnp.asarray(at),
        jnp.asarray(new).reshape(B, 1, -1))
    np.testing.assert_array_equal(got, want)


def test_the_conv_a_position_at_a_time_is_the_conv_over_an_episode():
    taps_n, B, T, c = 4, 2, 9, 3
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    taps = jax.random.normal(keys[0], (taps_n, c))
    u = jax.random.normal(keys[1], (B, T, c))
    bias = jax.random.normal(keys[2], (c,))
    whole = bias + layers.causal_conv(taps, u)
    tail = jnp.zeros((B, taps_n - 1, c))
    for t in range(T):
        conv, tail = layers.conv_step(bias + taps[0] * u[:, t], taps, u[:, t], tail)
        np.testing.assert_allclose(conv, whole[:, t], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(tail, u[:, :-taps_n:-1])


def test_routed_layers_gather_the_expert_layers_counts():
    B, T, k, held = 2, 3, 2, 4
    routed = moe.RoutedLayers(B, T)
    assert routed.aux() == {}
    layer = lambda i: (jnp.full((held,), i), jnp.full((B * T, k), i),  # noqa: E731
                       jnp.asarray(i))
    routed.take(layer(1))
    routed.take(None)  # a layer without experts
    routed.take(layer(2))
    aux = routed.aux(with_routes=True)
    assert aux["moe_tokens_per_expert"].shape == (2, held)
    assert aux["moe_overflow_blocks"].tolist() == [1, 2]
    assert aux["routes"].shape == (2, B, T, k)
    assert set(routed.aux()) == {"moe_tokens_per_expert", "moe_overflow_blocks"}


def test_the_unroll_skeleton_hands_each_layer_back_in_order():
    """Embed, every held layer once and in order with its own parameters,
    the head, then ``aux``; the side channels go from layer to layer."""
    model = tiny("olmo-hybrid")
    params = model.init_params(jax.random.PRNGKey(5))
    tokens = jnp.zeros((2, EPISODE), jnp.int32)
    seen, took = [], []

    def layer(i, p, x):
        seen.append((i, set(p) == set(params[model.layer_name(i)])))
        return x + 1.0, i

    out, aux = model._unroll(params, tokens, layer, took.append,
                             lambda: {"layers": len(took)})
    n = len(model.layer_ids)
    assert seen == [(i, True) for i in range(n)] and took == list(range(n))
    assert aux == {"layers": n}
    assert out.logits.shape == (2, EPISODE, IDS) and out.value.shape == (2, EPISODE)
    want = model._head(params, (model._embed(params, tokens) + n).reshape(
        2 * EPISODE, -1))
    np.testing.assert_array_equal(out.logits.reshape(-1, IDS), want.logits)

    calls = []

    def threaded(i, p, x, total, last):
        calls.append((i, last is None))
        return x, (total + 1.0, x)

    _, aux = model._unroll(params, tokens, threaded, side=(jnp.zeros(()), None))
    assert aux == {} and calls == [(i, i == 0) for i in range(n)]
