"""Plain float32 reference of LFM2-8B-A1B's layers, cut to one chip's share,
and of one fused A2C update of it on the recall game.

Written from the published architecture (LiquidAI/LFM2-8B-A1B
``config.json``, ``model_type lfm2_moe``; the configuration's file lists
what is assumed beyond it). Every layer is ``h = x + Op(RMSNorm(x))``,
``y = h + FFN(RMSNorm(h))``:

- ``conv``: ``[b, c, u] = split3(z W_in)``, ``v = b * u``, ``s_t = k_0 v_t
  + k_1 v_{t-1} + k_2 v_{t-2}`` (zero before the episode), ``(c * s) W_out``;
- ``full_attention``: per-head RMSNorm on q and k, rotate-half RoPE, a full
  ``T x T`` causal mask, each key/value head repeated for its query heads;
- dense FFN ``(silu(z W_1) * z W_3) W_2``; expert FFN: sigmoid scores, the
  top k of scores + bias chosen, weights the chosen scores over their sum
  + 1e-6, then A LOOP OVER THE EXPERTS HELD HERE, each computed for every
  token and weighted by what the router gave it there (0 where not chosen);
  the absent experts add nothing, as on the chip that this share stands for;
- final RMSNorm, logits against the held embedding rows (tied), a value head.

No cache, no grouping, no sort: the whole episode goes through at once.
Everything is float32 under ``jax.default_matmul_precision("highest")``
and imports nothing of the program. ``lower`` (``fp8``) puts the matrix
operands in float8, the control's precision. The algorithm's pieces
(returns, clip, Adam, the lowered operands) are ``reference/ba3c.py``'s.

An update: the env batch plays the actions it is handed (``recall.py``),
which fixes every token the policy saw; one forward over the episodes
gives the logits the rollout would have sampled from, so the reference says
which of the handed actions it would not have drawn; the A2C loss and its
gradient over all transitions, in blocks of envs; clip; Adam.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import recall
from benchmark.reference.ba3c import (
    ADAM_B1,
    HYPER,
    LOWER,
    adam_update,
    clip_by_global_norm,
    n_step_returns,
)

__all__ = ["ADAM_B1", "spec_of", "init_params", "forward", "follow_updates"]

VALUE_INIT_SCALE = 0.01
EXPERT_BIAS_SCALE = 0.01


def spec_of(config: dict) -> dict:
    """What the reference computes with, from the configuration's file: the
    published keys, and ``held`` (which layers, experts and ids live here)."""
    held = config["held"]
    layers = []
    for i in held["layers"]:
        ffn = "dense" if i < config["num_dense_layers"] else "experts"
        layers.append((i, config["layer_types"][i], ffn))
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "fe": config["moe_intermediate_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "taps": config["conv_L_cache"], "eps": config["norm_eps"],
        "theta": float(config["rope_theta"]),
        "experts_all": config["published"]["num_experts"],
        "experts": config["num_experts"],
        "expert_offset": held["expert_offset"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk": config["norm_topk_prob"],
        "scale": float(config["routed_scaling_factor"]),
        "ids": config["vocab_size"], "layers": tuple(layers),
    }


def init_params(key, spec: dict):
    """Seeded float32 weights, ``{layer: {leaf: array}}``: normal kernels
    scaled by 1/sqrt(fan_in), unit gains, the expert bias a small seeded
    buffer. The benchmark hands the same tree to the program."""
    d, f, fe = spec["d"], spec["f"], spec["fe"]
    hq = spec["heads"] * spec["head_dim"]
    hkv = spec["kv_heads"] * spec["head_dim"]
    keys = iter(jax.random.split(key, 16 * len(spec["layers"]) + 4))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)

    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    params = {"embed": {"table": normal((spec["ids"], d), d)}}
    for i, op, ffn in spec["layers"]:
        p = {"op_norm": ones((d,)), "ffn_norm": ones((d,))}
        if op == "conv":
            p["conv_in"] = normal((d, 3 * d), d)
            p["conv_taps"] = normal((spec["taps"], d), spec["taps"])
            p["conv_out"] = normal((d, d), d)
        else:
            p["wq"], p["wk"] = normal((d, hq), d), normal((d, hkv), d)
            p["wv"], p["wo"] = normal((d, hkv), d), normal((hq, d), hq)
            p["q_norm"], p["k_norm"] = ones((spec["head_dim"],)), ones((spec["head_dim"],))
        if ffn == "dense":
            p["w1"], p["w3"] = normal((d, f), d), normal((d, f), d)
            p["w2"] = normal((f, d), f)
        else:
            e = spec["experts"]
            p["router"] = normal((d, spec["experts_all"]), d)
            p["expert_bias"] = EXPERT_BIAS_SCALE * jax.random.normal(
                next(keys), (spec["experts_all"],), jnp.float32)
            p["w1"], p["w3"] = normal((e, d, fe), d), normal((e, d, fe), d)
            p["w2"] = normal((e, fe, d), fe)
        params[f"layer_{i}"] = p
    params["final"] = {"norm": ones((d,))}
    # a value head that starts near zero, as actor-critic code starts it:
    # at unit scale V ~ N(0, 1) against returns of 0 swamps the advantage
    params["value"] = {"kernel": VALUE_INIT_SCALE * normal((d, 1), d), "bias": jnp.zeros((1,), jnp.float32)}
    return params


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _rope(x, theta):
    """x [B, T, H, D]: position t rotates pair (i, i + D/2) by t * theta^(-2i/D)."""
    T, D = x.shape[1], x.shape[3]
    freq = theta ** (-jnp.arange(D // 2, dtype=jnp.float32) * 2.0 / D)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _conv_op(p, z, spec, q):
    b, c, u = jnp.split(q(z) @ q(p["conv_in"]), 3, axis=-1)
    v = b * u
    T = v.shape[1]
    s = p["conv_taps"][0] * v
    for lag in (1, 2):
        back = jnp.pad(v, ((0, 0), (lag, 0), (0, 0)))[:, :T]
        s = s + p["conv_taps"][lag] * back
    return q(c * s) @ q(p["conv_out"])


def _attention_op(p, z, spec, q):
    B, T, _ = z.shape
    H, KV, D = spec["heads"], spec["kv_heads"], spec["head_dim"]
    qh = (q(z) @ q(p["wq"])).reshape(B, T, H, D)
    kh = (q(z) @ q(p["wk"])).reshape(B, T, KV, D)
    vh = (q(z) @ q(p["wv"])).reshape(B, T, KV, D)
    qh = _rope(_rms(qh, p["q_norm"], spec["eps"]), spec["theta"])
    kh = _rope(_rms(kh, p["k_norm"], spec["eps"]), spec["theta"])
    kh = jnp.repeat(kh, H // KV, axis=2)  # head h reads kv head h // (H / KV)
    vh = jnp.repeat(vh, H // KV, axis=2)
    scores = jnp.einsum("bqhd,bshd->bhqs", q(qh), q(kh)) / math.sqrt(D)
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]  # [q, s]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum("bhqs,bshd->bqhd", q(probs), q(vh))
    return q(out.reshape(B, T, H * D)) @ q(p["wo"])


def _experts_ffn(p, z, spec, q, forced=None):
    """-> (this share's part of the layer, the expert ids [B, T, k] this
    side chooses). ``forced`` are the ids another side chose: computed with
    in place of this side's own (the weights are still this side's scores)."""
    scores = 1.0 / (1.0 + jnp.exp(-(z @ p["router"])))
    _, own = jax.lax.top_k(scores + p["expert_bias"], spec["top_k"])
    chosen = own if forced is None else forced
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if spec["norm_topk"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
    weights = weights * spec["scale"]

    def one_expert(out, expert):  # the experts held here, one at a time
        e, w1, w3, w2 = expert
        mine = jnp.sum(
            jnp.where(chosen == spec["expert_offset"] + e, weights, 0.0), -1)
        hidden = _silu(q(z) @ q(w1)) * (q(z) @ q(w3))
        return out + mine[..., None] * (q(hidden) @ q(w2)), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(z),
        (jnp.arange(spec["experts"]), p["w1"], p["w3"], p["w2"]))
    return out, own


def forward(params, tokens, spec, lower=None, forced_routes=None):
    """tokens int32 [B, T], whole episodes from their first step ->
    (logits [B, T, ids], value [B, T], routes [expert layers, B, T, k]).

    ``forced_routes`` (the same shape as the routes) are the experts
    another side chose for every token and layer: this side then computes
    with those, as it plays the actions it is handed, and its ``routes``
    say what it would have chosen itself at each of those points. Two
    router scores within a rounding error of each other near the k-th place
    flip between precisions, and a flipped choice moves that token, and
    through the operators the tokens after it, by a whole expert: forced,
    the two sides compute one function and their logits compare tightly."""
    q = LOWER[lower]
    x = params["embed"]["table"][tokens]
    routes = []
    for i, op, ffn in spec["layers"]:
        p = params[f"layer_{i}"]
        z = _rms(x, p["op_norm"], spec["eps"])
        h = x + (_conv_op if op == "conv" else _attention_op)(p, z, spec, q)
        z = _rms(h, p["ffn_norm"], spec["eps"])
        if ffn == "dense":
            y = q(_silu(q(z) @ q(p["w1"])) * (q(z) @ q(p["w3"]))) @ q(p["w2"])
        else:
            forced = None if forced_routes is None else forced_routes[len(routes)]
            y, chosen = _experts_ffn(p, z, spec, q, forced)
            routes.append(chosen)
        x = h + y
    h = _rms(x, params["final"]["norm"], spec["eps"])
    logits = q(h) @ q(params["embed"]["table"]).T
    value = (h @ params["value"]["kernel"])[..., 0] + params["value"]["bias"][0]
    return logits, value, jnp.stack(routes)


def _blocks(x, rows):
    return x.reshape(x.shape[0] // rows, rows, *x.shape[1:])


def _block_rows(n, most):
    return max(d for d in range(1, min(n, most) + 1) if n % d == 0)


@functools.partial(jax.jit, static_argnames=("n_envs", "ids", "prompt"))
def initial_env(key, n_envs, ids, prompt):
    state = jax.vmap(lambda k: recall.reset(k, ids, prompt))(
        jax.random.split(key, n_envs))
    return state, jax.vmap(recall.shown)(state)


def _play(env_state, shown, key, forced, ids, episode):
    """One shard's env batch through ``forced`` ([T, B] actions). ->
    ((env_state, shown, key), (tokens shown, rewards, dones, the key each
    step's action was drawn with)), the latter [T, B] / [T]."""

    def env_step(carry, actions):
        env_state, shown, key = carry
        key, k_act, k_env = jax.random.split(key, 3)
        env_keys = jax.random.split(k_env, shown.shape[0])
        env_state, new_shown, reward, done = jax.vmap(
            lambda s, a, k: recall.step(s, a, k, ids, episode)
        )(env_state, actions, env_keys)
        return (env_state, new_shown, key), (
            shown, reward, done.astype(jnp.float32), k_act)

    return jax.lax.scan(env_step, (env_state, shown, key), forced)


def a2c_loss_sum(params, tokens, actions, returns, routes, beta, value_coef,
                 spec, lower):
    """The A2C loss SUMMED over every transition of the episodes given,
    computed with ``routes`` where another side's are handed over."""
    logits, value, _ = forward(params, tokens, spec, lower, routes)
    logp = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]
    advantage = returns - jax.lax.stop_gradient(value)
    policy = -jnp.sum(logp_a * advantage)
    value_l = 0.5 * jnp.sum(jnp.square(value - returns))
    entropy = -jnp.sum(jnp.exp(logp) * logp)
    return policy + value_coef * value_l - beta * entropy


@functools.partial(jax.jit, static_argnames=("spec_key", "lower", "block_envs"))
def _shard_pass(params, env_state, shown, key, forced, routes, hyper, spec_key,
                lower, block_envs):
    """One shard's rollout under the forced actions and the SUM of the loss
    and of its gradient over the shard's transitions, the latter computed
    with ``routes`` ([expert layers, B, T, k]: the experts the other side's
    learner chose; None: this side's own). -> (loss, grads, env_state,
    shown, key, margins [T, B], the tokens the envs showed [T, B])."""
    spec = dict(spec_key)
    T, B = forced.shape
    (env_state, shown, key), (tokens, rewards, dones, act_keys) = _play(
        env_state, shown, key, forced, spec["ids"], T)
    returns = n_step_returns(rewards, dones, jnp.zeros((B,)), hyper["gamma"])
    rows = _block_rows(B, block_envs)
    by_env = lambda x: _blocks(jnp.swapaxes(x, 0, 1), rows)  # noqa: E731
    logits = jax.lax.map(
        lambda block: forward(params, block, spec, lower)[0], by_env(tokens))
    logits = logits.reshape(B, T, -1)

    def margin(_, step):
        t, k_act, played = step
        step_logits = jax.lax.dynamic_index_in_dim(logits, t, 1, keepdims=False)
        # a categorical draw is the argmax of the logits plus Gumbel noise
        noisy = step_logits + jax.random.gumbel(
            k_act, step_logits.shape, step_logits.dtype)
        return None, jnp.max(noisy, -1) - jnp.take_along_axis(
            noisy, played[:, None], axis=1)[:, 0]

    _, margins = jax.lax.scan(margin, None, (jnp.arange(T), act_keys, forced))

    def add_block(acc, block):
        tokens_b, actions_b, returns_b, *routes_b = block
        loss, grads = jax.value_and_grad(a2c_loss_sum)(
            params, tokens_b, actions_b, returns_b,
            jnp.swapaxes(routes_b[0], 0, 1) if routes_b else None,
            hyper["entropy_beta"], hyper["value_loss_coef"], spec, lower)
        return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], grads)), None

    blocks = (by_env(tokens), by_env(forced), by_env(returns))
    if routes is not None:  # [layers, B, ...] -> blocks of envs
        blocks += (_blocks(jnp.swapaxes(routes, 0, 1), rows),)
    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(add_block, zero, blocks)
    return loss, grads, env_state, shown, key, margins, tokens


@jax.jit
def _finish(params, grads, mu, nu, count, n, hyper):
    grads = clip_by_global_norm(
        jax.tree_util.tree_map(lambda g: g / n, grads), hyper["grad_clip_norm"])
    params, mu, nu = adam_update(
        params, grads, mu, nu, count, hyper["learning_rate"], hyper["adam_epsilon"])
    return params, mu, nu, grads


def _spec_key(spec):
    return tuple(sorted(spec.items()))


def follow_updates(params, env_key, shard_keys, n_envs, spec, hyper, n_updates,
                   actions, prompt, lower=None, block_envs=4, routes=None):
    """Follow a fused A2C run on the recall game through its first updates,
    playing ``actions[update]`` ([shards, T, envs a shard] int32, a whole
    episode each) in place of draws of its own and, where given, learning
    with ``routes[update]`` ([shards, expert layers, envs a shard, T, k]:
    the experts the other side's learner chose for every token; a flipped
    near-tie is a different function, see ``forward``). Env ``i`` belongs
    to shard ``i // (n_envs / shards)``. -> what ``reference/ba3c.py``'s gives:
    ``losses``, ``first_grad``, ``delta``, ``states``, ``action_margin``,
    ``action_flips``."""
    numbers = {k: float(hyper[k]) for k in HYPER}
    n_shards = len(shard_keys)
    per = n_envs // n_shards
    key = _spec_key(spec)
    with jax.default_matmul_precision("highest"):
        env_state, shown = initial_env(env_key, n_envs, spec["ids"], prompt)
        keys = [jnp.asarray(k) for k in shard_keys]
        start = params
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, first_grad, margins, states = [], None, [], []
        for count in range(1, n_updates + 1):
            loss, grads, parts = 0.0, None, []
            for s in range(n_shards):
                cut = lambda x: x[s * per:(s + 1) * per]  # noqa: E731
                l, g, env_s, shown_s, keys[s], margin, tokens = _shard_pass(
                    params, jax.tree_util.tree_map(cut, env_state), cut(shown),
                    keys[s], jnp.asarray(actions[count - 1][s]),
                    None if routes is None else jnp.asarray(routes[count - 1][s]),
                    numbers, key, lower, block_envs)
                loss = loss + l
                grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
                # what each env showed all through the update is part of
                # the state it is compared by: the final state alone is a
                # fresh episode's, whatever was played
                parts.append((dict(env_s, shown=jnp.swapaxes(tokens, 0, 1)),
                              shown_s))
                margins.append(jax.device_get(margin))
            n = float(n_envs * actions[count - 1][0].shape[0])
            params, mu, nu, clipped = _finish(params, grads, mu, nu, count, n, numbers)
            env_state = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs), *[p[0] for p in parts])
            shown = jnp.concatenate([p[1] for p in parts])
            states.append(jax.device_get((env_state, shown)))
            del env_state["shown"]
            if first_grad is None:
                first_grad = clipped
            losses.append(float(loss) / n)
        delta = jax.tree_util.tree_map(jnp.subtract, params, start)
    return {
        "losses": losses, "first_grad": first_grad, "delta": delta,
        "states": states,
        "action_margin": float(max(m.max() for m in margins)),
        "action_flips": float(sum((m > 0).sum() for m in margins)
                              / sum(m.size for m in margins)),
    }


@functools.partial(jax.jit, static_argnames=("spec_key", "lower"))
def _logits_and_routes(params, tokens, forced_routes, spec_key, lower):
    logits, _, routes = forward(
        params, tokens, dict(spec_key), lower, forced_routes)
    return logits, routes


def logits_and_routes(params, tokens, spec, forced_routes, lower=None):
    """The forward alone over ``tokens`` [B, T], computed with the routes
    another side chose: (logits, the routes this side would choose)."""
    with jax.default_matmul_precision("highest"):
        return _logits_and_routes(
            params, tokens, forced_routes, _spec_key(spec), lower)
