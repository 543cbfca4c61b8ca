"""Plain float32 reference of Olmo-Hybrid-7B's layers, cut to one chip's
share, and of one fused A2C update of it on the recall game.

Written from the published architecture (allenai/Olmo-Hybrid-7B
``config.json``, ``model_type olmo_hybrid``; the gated delta rule of
arXiv:2412.06464; the configuration's file lists what is assumed beyond the
config). Every layer is ``h = x + RMSNorm(Mixer(x))``, ``x' = h +
RMSNorm(W_down(silu(W_gate h) * W_up h))``, the norms on the sub-blocks'
outputs; the mixer by the config's ``layer_types``:

- linear attention: ``[q; k; v] = silu(sum_lag w_lag (x W_qkv)_{t-lag})``
  over 4 taps, zero before the episode; a head's ``q / |q| / sqrt(K)``, ``k
  / |k|``; ``beta = 2 sigmoid(x W_b)``, ``alpha = exp(-exp(A_log) softplus(x
  W_a + dt_bias))``; THE RECURRENCE ONE POSITION AT A TIME on a state ``S``
  ``[V, K]`` a head from zero,

      S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T,    o_t = S_t q_t;

  out ``(RMSNorm_V(o) * silu(x W_z)) W_o``.
- full attention: ``q``, ``k`` each through an RMSNorm with a gain over the
  whole projection (the heads held), scores over ``sqrt(D)`` with the ``T x
  T`` causal mask written out, one key/value head a query head, ``W_o``.

No chunk, no cache, no kernel: whole episodes go through at once, an env at
a time so that it fits (each layer recomputed in the backward, which
changes no value). The share of heads is the configuration's: the weights
handed over are the share's, and a mixer's output is the share's part of
the sum. Everything is float32 under ``jax.default_matmul_precision(
"highest")`` and imports nothing of the program. ``lower`` (``fp8``) puts
the matrix operands in float8. Returns, clip, Adam and the lowered operands
are ``reference/ba3c.py``'s; the game is ``reference/recall.py``'s; the
update's frame is ``reference/phi4_flash.py``'s.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.ba3c import HYPER, LOWER
from benchmark.reference.lfm2_moe import (
    _block_rows,
    _blocks,
    _play,
    _rms,
    _silu,
    _spec_key,
    initial_env,
)
from benchmark.reference.phi4_flash import _finish, _returns, _softplus

__all__ = ["spec_of", "init_params", "forward", "follow_updates",
           "logits_of", "recurrence", "linear_mixer", "full_mixer"]

VALUE_INIT_SCALE = 0.01
A_MIN, A_MAX = 1.0, 16.0
DT_MIN, DT_MAX = 1e-3, 1e-1
L2_EPS = 1e-6
LINEAR, FULL = "linear_attention", "full_attention"


def spec_of(config: dict) -> dict:
    """What the reference computes with, from the configuration's file."""
    assert config["linear_num_key_heads"] == config["linear_num_value_heads"]
    assert config["num_key_value_heads"] == config["num_attention_heads"]
    assert config["linear_allow_neg_eigval"] and not config["attention_bias"]
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "heads": config["num_attention_heads"], "head_dim": config["head_dim"],
        "lin_heads": config["linear_num_key_heads"],
        "K": config["linear_key_head_dim"], "V": config["linear_value_head_dim"],
        "taps": config["linear_conv_kernel_dim"], "eps": config["rms_norm_eps"],
        "ids": config["vocab_size"],
        "layers": tuple((i, config["layer_types"][i])
                        for i in config["held"]["layers"]),
    }


def init_params(key, spec: dict):
    """Seeded float32 weights, ``{layer: {leaf: array}}``: normal kernels
    scaled by 1/sqrt(fan_in), unit gains; ``exp(A_log)`` uniform in [1, 16],
    ``dt_bias`` the inverse softplus of step sizes log-uniform in [1e-3,
    1e-1]. The benchmark hands the same tree to the program."""
    d, f = spec["d"], spec["f"]
    H, K, V = spec["lin_heads"], spec["K"], spec["V"]
    hq, width = spec["heads"] * spec["head_dim"], H * (2 * K + V)
    keys = iter(jax.random.split(key, 16 * len(spec["layers"]) + 4))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)

    def uniform(shape, low, high):
        return low + (high - low) * jax.random.uniform(next(keys), shape, jnp.float32)

    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    params = {"embed": {"table": normal((spec["ids"], d), d)}}
    for i, kind in spec["layers"]:
        p = {"mix_norm": ones((d,)), "ffn_norm": ones((d,)),
             "w_gate": normal((d, f), d), "w_up": normal((d, f), d),
             "w_down": normal((f, d), f)}
        if kind == LINEAR:
            step = jnp.exp(uniform((H,), math.log(DT_MIN), math.log(DT_MAX)))
            p["wqkv"] = normal((d, width), d)
            p["wz"] = normal((d, H * V), d)
            p["wa"], p["wb"] = normal((d, H), d), normal((d, H), d)
            p["conv_w"] = normal((spec["taps"], width), spec["taps"])
            p["A_log"] = jnp.log(uniform((H,), A_MIN, A_MAX))
            p["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
            p["o_norm"] = ones((V,))
            p["wo"] = normal((H * V, d), H * V)
        else:
            p["wq"], p["wk"] = normal((d, hq), d), normal((d, hq), d)
            p["wv"], p["wo"] = normal((d, hq), d), normal((hq, d), hq)
            p["q_norm"], p["k_norm"] = ones((hq,)), ones((hq,))
        params[f"layer_{i}"] = p
    params["final"] = {"norm": ones((d,))}
    params["head"] = {"table": normal((spec["ids"], d), d)}
    params["value"] = {"kernel": VALUE_INIT_SCALE * normal((d, 1), d),
                       "bias": jnp.zeros((1,), jnp.float32)}
    return params


def recurrence(queries, keys, values, alpha, beta):
    """The gated delta rule ONE POSITION AT A TIME with the state written
    out: queries, keys [B, T, H, K], values [B, T, H, V], alpha, beta [B, T,
    H] -> o [B, T, H, V]."""
    B, _, H, K = queries.shape

    def position(S, at):  # S [B, H, V, K]
        q_t, k_t, v_t, a_t, b_t = at
        Sk = jnp.einsum("bhvk,bhk->bhv", S, k_t)
        S = a_t[..., None, None] * (
            S - b_t[..., None, None] * Sk[..., :, None] * k_t[..., None, :])
        S = S + b_t[..., None, None] * v_t[..., :, None] * k_t[..., None, :]
        return S, jnp.einsum("bhvk,bhk->bhv", S, q_t)

    by_time = lambda y: jnp.swapaxes(y, 0, 1)  # noqa: E731
    _, o = jax.lax.scan(
        position, jnp.zeros((B, H, values.shape[-1], K), jnp.float32),
        tuple(by_time(y) for y in (queries, keys, values, alpha, beta)))
    return by_time(o)


def linear_mixer(p, x, spec, q=LOWER[None]):
    """x [B, T, d] -> the held heads' part of the mixer's output [B, T, d]."""
    B, T, _ = x.shape
    H, K, V = spec["lin_heads"], spec["K"], spec["V"]
    u = q(x) @ q(p["wqkv"])
    conv = p["conv_w"][0] * u
    for lag in range(1, spec["taps"]):
        conv = conv + p["conv_w"][lag] * jnp.pad(u, ((0, 0), (lag, 0), (0, 0)))[:, :T]
    u = _silu(conv)
    heads = lambda y: y.reshape(B, T, H, -1)  # noqa: E731
    unit = lambda y: y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + L2_EPS)  # noqa: E731
    queries = unit(heads(u[..., :H * K])) / math.sqrt(K)
    keys = unit(heads(u[..., H * K:2 * H * K]))
    values = heads(u[..., 2 * H * K:])
    beta = 2.0 / (1.0 + jnp.exp(-(q(x) @ q(p["wb"]))))
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * _softplus(q(x) @ q(p["wa"]) + p["dt_bias"]))

    o = recurrence(queries, keys, values, alpha, beta)
    o = _rms(o, p["o_norm"], spec["eps"]) * _silu(heads(q(x) @ q(p["wz"])))
    return q(o.reshape(B, T, H * V)) @ q(p["wo"])


def full_mixer(p, x, spec, q=LOWER[None]):
    """x [B, T, d] -> the held heads' part of the mixer's output [B, T, d]."""
    B, T, _ = x.shape
    D = spec["head_dim"]
    heads = lambda y: y.reshape(B, T, -1, D)  # noqa: E731
    queries = heads(_rms(q(x) @ q(p["wq"]), p["q_norm"], spec["eps"]))
    keys = heads(_rms(q(x) @ q(p["wk"]), p["k_norm"], spec["eps"]))
    values = heads(q(x) @ q(p["wv"]))
    at = jnp.arange(T)
    allowed = at[None, :] <= at[:, None]  # the T x T mask
    scores = jnp.einsum("bqhd,bshd->bhqs", q(queries), q(keys)) / math.sqrt(D)
    scores = jnp.where(allowed[None, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bhqs,bshd->bqhd", q(probs), q(values))
    return q(o.reshape(B, T, -1)) @ q(p["wo"])


def _layer(kind, spec, lower, p, x):
    q = LOWER[lower]
    mixer = linear_mixer if kind == LINEAR else full_mixer
    h = x + _rms(mixer(p, x, spec, q), p["mix_norm"], spec["eps"])
    y = q(_silu(q(h) @ q(p["w_gate"])) * (q(h) @ q(p["w_up"]))) @ q(p["w_down"])
    return h + _rms(y, p["ffn_norm"], spec["eps"])


def forward(params, tokens, spec, lower=None):
    """tokens int32 [B, T], whole episodes from their first step ->
    (logits [B, T, ids], value [B, T])."""
    q = LOWER[lower]
    x = params["embed"]["table"][tokens]
    for i, kind in spec["layers"]:
        layer = jax.checkpoint(functools.partial(_layer, kind, spec, lower))
        x = layer(params[f"layer_{i}"], x)
    h = _rms(x, params["final"]["norm"], spec["eps"])
    logits = q(h) @ q(params["head"]["table"]).T
    value = (h @ params["value"]["kernel"])[..., 0] + params["value"]["bias"][0]
    return logits, value


def a2c_loss_sum(params, tokens, actions, returns, beta, value_coef, spec, lower):
    """-> (the A2C loss SUMMED over every transition of the episodes given,
    the logits)."""
    logits, value = forward(params, tokens, spec, lower)
    logp = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]
    advantage = returns - jax.lax.stop_gradient(value)
    policy = -jnp.sum(logp_a * advantage)
    value_l = 0.5 * jnp.sum(jnp.square(value - returns))
    entropy = -jnp.sum(jnp.exp(logp) * logp)
    return policy + value_coef * value_l - beta * entropy, logits


@functools.partial(jax.jit, static_argnames=("spec_key", "lower", "block_envs"))
def _shard_pass(params, env_state, shown, key, forced, hyper, spec_key, lower,
                block_envs):
    """One shard's rollout under the forced actions and the SUM of the loss
    and of its gradient over the shard's transitions. -> (loss, grads,
    env_state, shown, key, margins [T, B], the tokens the envs showed [T, B])."""
    spec = dict(spec_key)
    T, B = forced.shape
    (env_state, shown, key), (tokens, rewards, dones, act_keys) = _play(
        env_state, shown, key, forced, spec["ids"], T)
    returns = _returns(rewards, dones, hyper["gamma"])
    rows = _block_rows(B, block_envs)
    by_env = lambda x: _blocks(jnp.swapaxes(x, 0, 1), rows)  # noqa: E731

    def add_block(acc, block):
        first, tokens_b, actions_b, returns_b = block
        (loss, logits), grads = jax.value_and_grad(a2c_loss_sum, has_aux=True)(
            params, tokens_b, actions_b, returns_b,
            hyper["entropy_beta"], hyper["value_loss_coef"], spec, lower)

        def margin(_, step):
            t, k_act = step
            step_logits = jax.lax.dynamic_index_in_dim(logits, t, 1, keepdims=False)
            # a categorical draw is the argmax of the logits plus Gumbel
            # noise: one key a step for the whole shard's [B, ids]
            noise = jax.lax.dynamic_slice_in_dim(
                jax.random.gumbel(k_act, (B, spec["ids"]), step_logits.dtype),
                first, rows)
            noisy = step_logits + noise
            played = jax.lax.dynamic_index_in_dim(actions_b, t, 1, keepdims=False)
            return None, jnp.max(noisy, -1) - jnp.take_along_axis(
                noisy, played[:, None], axis=1)[:, 0]

        _, margins = jax.lax.scan(margin, None, (jnp.arange(T), act_keys))
        return (acc[0] + loss,
                jax.tree_util.tree_map(jnp.add, acc[1], grads)), margins

    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), margins = jax.lax.scan(
        add_block, zero,
        (jnp.arange(0, B, rows), by_env(tokens), by_env(forced), by_env(returns)))
    margins = jnp.swapaxes(margins, 0, 1).reshape(T, B)  # [blocks, T, rows]
    return loss, grads, env_state, shown, key, margins, tokens


def follow_updates(params, env_key, shard_keys, n_envs, spec, hyper, n_updates,
                   actions, prompt, lower=None, block_envs=1):
    """Follow a fused A2C run on the recall game through its first updates,
    playing ``actions[update]`` ([shards, T, envs a shard] int32, a whole
    episode each) in place of draws of its own. Env ``i`` belongs to shard
    ``i // (n_envs / shards)``. -> what ``reference/ba3c.py``'s gives:
    ``losses``, ``first_grad``, ``delta``, ``states``, ``action_margin``,
    ``action_flips``; the two trees come back as host arrays (712 M
    parameters, and Adam's moments beside them, leave the device no room
    for two more copies). ``params`` is consumed."""
    numbers = {k: float(hyper[k]) for k in HYPER}
    n_shards = len(shard_keys)
    per = n_envs // n_shards
    key = _spec_key(spec)
    with jax.default_matmul_precision("highest"):
        env_state, shown = initial_env(env_key, n_envs, spec["ids"], prompt)
        keys = [jnp.asarray(k) for k in shard_keys]
        start = jax.device_get(params)
        mu = nu = None
        losses, first_grad, margins, states = [], None, [], []
        for count in range(1, n_updates + 1):
            loss, grads, parts = 0.0, None, []
            for s in range(n_shards):
                cut = lambda x: x[s * per:(s + 1) * per]  # noqa: E731
                l, g, env_s, shown_s, keys[s], margin, tokens = _shard_pass(
                    params, jax.tree_util.tree_map(cut, env_state), cut(shown),
                    keys[s], jnp.asarray(actions[count - 1][s]), numbers, key,
                    lower, block_envs)
                loss = loss + l
                grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
                # what each env showed all through the update is part of
                # the state it is compared by: the final state alone is a
                # fresh episode's, whatever was played
                parts.append((dict(env_s, shown=jnp.swapaxes(tokens, 0, 1)),
                              shown_s))
                margins.append(jax.device_get(margin))
            n = float(n_envs * actions[count - 1][0].shape[0])
            if mu is None:  # not before the gradient's pass: 5.7 GB
                mu = jax.tree_util.tree_map(jnp.zeros_like, params)
                nu = jax.tree_util.tree_map(jnp.zeros_like, params)
            params, mu, nu, clipped = _finish(params, grads, mu, nu, count, n, numbers)
            env_state = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs), *[p[0] for p in parts])
            shown = jnp.concatenate([p[1] for p in parts])
            states.append(jax.device_get((env_state, shown)))
            del env_state["shown"]
            if first_grad is None:
                first_grad = jax.device_get(clipped)
            del clipped, grads
            losses.append(float(loss) / n)
        delta = jax.tree_util.tree_map(
            lambda a, b: a - b, jax.device_get(params), start)
    return {
        "losses": losses, "first_grad": first_grad, "delta": delta,
        "states": states,
        "action_margin": float(max(m.max() for m in margins)),
        "action_flips": float(sum((m > 0).sum() for m in margins)
                              / sum(m.size for m in margins)),
    }


@functools.partial(jax.jit, static_argnames=("spec_key", "lower", "block_envs"))
def _logits_of(params, tokens, spec_key, lower, block_envs):
    rows = _block_rows(tokens.shape[0], block_envs)
    logits = jax.lax.map(
        lambda block: forward(params, block, dict(spec_key), lower)[0],
        _blocks(tokens, rows))
    return logits.reshape(tokens.shape[0], tokens.shape[1], -1)


def logits_of(params, tokens, spec, lower=None, block_envs=1):
    """The forward alone over ``tokens`` [B, T]: logits [B, T, ids]."""
    with jax.default_matmul_precision("highest"):
        return _logits_of(params, tokens, _spec_key(spec), lower, block_envs)
