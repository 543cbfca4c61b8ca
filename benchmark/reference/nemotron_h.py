"""Plain float32 reference of NVIDIA-Nemotron-3-Nano-30B-A3B's blocks, cut
to one chip's share, and of one fused A2C update of it on the recall game.

Written from the published architecture (nvidia/NVIDIA-Nemotron-3-Nano-30B-
A3B-BF16 ``config.json``, ``model_type nemotron_h``; Mamba-2 as
arXiv:2405.21060 has it, the family as arXiv:2504.03624; the configuration's
file lists what is assumed beyond the config). A block is ``x <- x +
Mixer(RMSNorm(x))`` with ONE mixer, by ``hybrid_override_pattern``'s letter
at the block's published index:

- ``M``, Mamba-2: ``[z; xBC; dt] = u W_in``; ``xBC <- silu(sum_lag w_lag
  xBC_{t-lag} + b)`` over 4 taps, zero before the episode; ``[x; B; C] =
  xBC``; ``dt <- softplus(dt + dt_bias)``; ``A = -exp(A_log)``; THE
  RECURRENCE ONE POSITION AT A TIME on a state ``H`` ``[P, N]`` a head from
  zero, a group's ``B`` / ``C`` serving its consecutive heads,

      H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T,    y_t = H_t C_t + D x_t;

  out ``RMSNorm_group(y * silu(z)) W_out``, the gate first and the statistic
  over each group's channels.
- ``E``, experts: ``s = sigmoid(u W_r)`` over all the published experts,
  the top k of ``s + bias`` chosen, weights ``scale * s[chosen] / (sum
  s[chosen] + 1e-20)``, then A LOOP OVER THE EXPERTS HELD HERE, each ``W2
  relu(W1 u)^2`` computed for every token and weighted by what the router
  gave it there; the shared expert, of the same form, for every token.
- ``*``, attention: no bias, no rotary embedding, scores over ``sqrt(D)``
  with the ``T x T`` causal mask written out, each key/value head serving
  ``H / KV`` query heads, ``W_o``.

No chunk, no cache, no grouping, no sort of assignments: whole episodes go
through at once, an env at a time so that it fits (each block recomputed in
the backward, which changes no value). The share of experts and of the
vocabulary is the configuration's: the weights handed over are the share's,
and an assignment to an absent expert adds nothing. Everything is float32
under ``jax.default_matmul_precision("highest")`` and imports nothing of
the program. ``lower`` (``fp8``) puts the matrix operands in float8.
Returns, clip, Adam and the lowered operands are ``reference/ba3c.py``'s;
the game is ``reference/recall.py``'s; the update's frame is
``reference/keye_vl2.py``'s without its selections: the loss and its
gradient are computed WITH the routes another side's learner used, where
they are handed over, and the reference says beside them what it would have
chosen itself.
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
           "logits_of", "recurrence", "mamba_mixer", "attention_mixer",
           "experts_mixer"]

VALUE_INIT_SCALE = 0.01
A_MIN, A_MAX = 1.0, 16.0
EXPERT_BIAS_SCALE = 0.01
#: under the sum of the chosen scores, as published
NORM_EPS = 1e-20
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def spec_of(config: dict) -> dict:
    """What the reference computes with, from the configuration's file."""
    assert config["mlp_hidden_act"] == "relu2" and not config["mlp_bias"]
    assert config["use_conv_bias"] and not config["mamba_proj_bias"]
    assert not config["attention_bias"] and not config["tie_word_embeddings"]
    assert config["n_group"] == 1 and config["topk_group"] == 1
    assert config["n_shared_experts"] == 1 and config["norm_topk_prob"]
    held = config["held"]
    return {
        "d": config["hidden_size"],
        "heads": config["mamba_num_heads"], "P": config["mamba_head_dim"],
        "N": config["ssm_state_size"], "groups": config["n_groups"],
        "taps": config["conv_kernel"], "eps": config["layer_norm_epsilon"],
        "dt_min": config["time_step_min"], "dt_max": config["time_step_max"],
        "dt_floor": config["time_step_floor"],
        "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"], "head_dim": config["head_dim"],
        "experts": config["n_routed_experts"],
        "all_experts": config["published"]["n_routed_experts"],
        "expert_offset": held["expert_offset"],
        "top_k": config["num_experts_per_tok"],
        "scale": config["routed_scaling_factor"],
        "fe": config["moe_intermediate_size"],
        "fs": config["moe_shared_expert_intermediate_size"],
        "ids": config["vocab_size"],
        "layers": tuple((i, config["hybrid_override_pattern"][i])
                        for i in held["layers"]),
    }


def init_params(key, spec: dict):
    """Seeded float32 weights, ``{layer: {leaf: array}}``: normal kernels
    scaled by 1/sqrt(fan_in), unit gains, ``D`` 1; ``exp(A_log)`` uniform in
    [1, 16], ``dt_bias`` the inverse softplus of step sizes log-uniform in
    [dt_min, dt_max] floored at dt_floor; a small seeded choosing bias. The
    benchmark hands the same tree to the program."""
    d, h = spec["d"], spec["heads"]
    inner = h * spec["P"]
    width = inner + 2 * spec["groups"] * spec["N"]
    hq, hkv = (spec["q_heads"] * spec["head_dim"],
               spec["kv_heads"] * spec["head_dim"])
    fe, fs, taps = spec["fe"], spec["fs"], spec["taps"]
    keys = iter(jax.random.split(key, 16 * len(spec["layers"]) + 4))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)

    def uniform(shape, low, high):
        return low + (high - low) * jax.random.uniform(next(keys), shape, jnp.float32)

    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    params = {"embed": {"table": normal((spec["ids"], d), d)}}
    for i, kind in spec["layers"]:
        p = {"norm": ones((d,))}
        if kind == MAMBA:
            step = jnp.maximum(jnp.exp(uniform(
                (h,), math.log(spec["dt_min"]), math.log(spec["dt_max"]))),
                spec["dt_floor"])
            p["in_proj"] = normal((d, inner + width + h), d)
            p["conv_w"] = normal((taps, width), taps)
            p["conv_b"] = normal((width,), taps)
            p["A_log"] = jnp.log(uniform((h,), A_MIN, A_MAX))
            p["D"] = ones((h,))
            p["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
            p["gate_norm"] = ones((inner,))
            p["out_proj"] = normal((inner, d), inner)
        elif kind == ATTENTION:
            p["wq"], p["wk"] = normal((d, hq), d), normal((d, hkv), d)
            p["wv"], p["wo"] = normal((d, hkv), d), normal((hq, d), hq)
        else:
            e = spec["experts"]
            p["router"] = normal((d, spec["all_experts"]), d)
            p["expert_bias"] = EXPERT_BIAS_SCALE * jax.random.normal(
                next(keys), (spec["all_experts"],), jnp.float32)
            p["w1"], p["w2"] = normal((e, d, fe), d), normal((e, fe, d), fe)
            p["shared_w1"] = normal((d, fs), d)
            p["shared_w2"] = normal((fs, d), fs)
        params[f"layer_{i}"] = p
    params["final"] = {"norm": ones((d,))}
    params["head"] = {"table": normal((spec["ids"], d), d)}
    params["value"] = {"kernel": VALUE_INIT_SCALE * normal((d, 1), d),
                       "bias": jnp.zeros((1,), jnp.float32)}
    return params


def recurrence(x, dt, A, B, C, D):
    """The Mamba-2 recurrence ONE POSITION AT A TIME with the state written
    out: x [B, T, h, P], dt [B, T, h], A, D [h], B, C [B, T, g, N] -> y [B,
    T, h, P]. Head ``i`` reads group ``i // (h / g)``."""
    batch, _, h, P = x.shape
    per = h // B.shape[2]
    B, C = jnp.repeat(B, per, axis=2), jnp.repeat(C, per, axis=2)  # a head's own

    def position(H, at):  # H [B, h, P, N]
        x_t, dt_t, B_t, C_t = at
        H = (jnp.exp(dt_t * A)[..., None, None] * H
             + (dt_t[..., None] * x_t)[..., :, None] * B_t[..., None, :])
        return H, jnp.einsum("bhpn,bhn->bhp", H, C_t) + D[:, None] * x_t

    # the scan in stretches that the backward runs again, which changes no
    # value: kept whole, 2,048 states of 64 heads are 4.3 GB a layer an env
    T = x.shape[1]
    stretch = _block_rows(T, 128)
    by_time = lambda v: jnp.swapaxes(v, 0, 1).reshape(  # noqa: E731
        T // stretch, stretch, *v.shape[:1], *v.shape[2:])
    _, y = jax.lax.scan(
        jax.checkpoint(lambda H, at: jax.lax.scan(position, H, at)),
        jnp.zeros((batch, h, P, B.shape[-1]), jnp.float32),
        tuple(by_time(v) for v in (x, dt, B, C)))
    return jnp.swapaxes(y.reshape(T, batch, h, P), 0, 1)


def mamba_mixer(p, u, spec, q=LOWER[None]):
    """u [B, T, d], normed -> the mixer's output [B, T, d]."""
    batch, T, _ = u.shape
    h, P, g, N = spec["heads"], spec["P"], spec["groups"], spec["N"]
    inner = h * P
    zxbcdt = q(u) @ q(p["in_proj"])
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * g * N]
    dt = _softplus(zxbcdt[..., 2 * inner + 2 * g * N:] + p["dt_bias"])
    conv = p["conv_b"] + p["conv_w"][0] * xbc
    for lag in range(1, spec["taps"]):
        conv = conv + p["conv_w"][lag] * jnp.pad(
            xbc, ((0, 0), (lag, 0), (0, 0)))[:, :T]
    xbc = _silu(conv)
    y = recurrence(
        xbc[..., :inner].reshape(batch, T, h, P), dt, -jnp.exp(p["A_log"]),
        xbc[..., inner:inner + g * N].reshape(batch, T, g, N),
        xbc[..., inner + g * N:].reshape(batch, T, g, N), p["D"])
    gated = (y.reshape(batch, T, inner) * _silu(z)).reshape(batch, T, g, -1)
    normed = gated / jnp.sqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + spec["eps"])
    return q(normed.reshape(batch, T, inner) * p["gate_norm"]) @ q(p["out_proj"])


def attention_mixer(p, u, spec, q=LOWER[None]):
    """u [B, T, d], normed -> the mixer's output [B, T, d]."""
    batch, T, _ = u.shape
    D, KV = spec["head_dim"], spec["kv_heads"]
    per = spec["q_heads"] // KV
    queries = (q(u) @ q(p["wq"])).reshape(batch, T, KV, per, D)
    keys = (q(u) @ q(p["wk"])).reshape(batch, T, KV, D)
    values = (q(u) @ q(p["wv"])).reshape(batch, T, KV, D)
    at = jnp.arange(T)
    allowed = at[None, :] <= at[:, None]  # the T x T mask
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q(queries), q(keys)) / math.sqrt(D)
    scores = jnp.where(allowed[None, None, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bkgqs,bskd->bqkgd", q(probs), q(values))
    return q(o.reshape(batch, T, -1)) @ q(p["wo"])


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def experts_mixer(p, u, spec, q=LOWER[None], forced=None):
    """u [B, T, d], normed -> (this share's part of the routed experts' sum
    plus the shared expert, the expert ids [B, T, k] this side chooses).
    ``forced`` are the ids another side chose."""
    scores = 1.0 / (1.0 + jnp.exp(-(u @ p["router"])))
    _, own = jax.lax.top_k(scores + p["expert_bias"], spec["top_k"])
    chosen = own if forced is None else forced
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = spec["scale"] * weights / (
        jnp.sum(weights, -1, keepdims=True) + NORM_EPS)

    def one_expert(out, expert):  # the experts held here, one at a time
        e, w1, w2 = expert
        mine = jnp.sum(
            jnp.where(chosen == spec["expert_offset"] + e, weights, 0.0), -1)
        return out + mine[..., None] * (q(_relu2(q(u) @ q(w1))) @ q(w2)), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(u),
        (jnp.arange(spec["experts"]), p["w1"], p["w2"]))
    shared = q(_relu2(q(u) @ q(p["shared_w1"]))) @ q(p["shared_w2"])
    return out + shared, own


def _layer(kind, spec, lower, p, x, forced_route):
    q = LOWER[lower]
    u = _rms(x, p["norm"], spec["eps"])
    if kind == EXPERTS:
        mixed, routes = experts_mixer(p, u, spec, q, forced_route)
        return x + mixed, routes
    mixer = mamba_mixer if kind == MAMBA else attention_mixer
    return x + mixer(p, u, spec, q), None


def forward(params, tokens, spec, lower=None, forced_routes=None):
    """tokens int32 [B, T], whole episodes from their first step ->
    (logits [B, T, ids], value [B, T], routes [expert blocks, B, T, k]: what
    this side would choose). ``forced_routes`` (the shape of the last): the
    experts another side chose, which the expert blocks then compute with."""
    q = LOWER[lower]
    x = params["embed"]["table"][tokens]
    routes, at = [], 0
    for i, kind in spec["layers"]:
        forced = None
        if kind == EXPERTS and forced_routes is not None:
            forced = forced_routes[at]
        layer = jax.checkpoint(functools.partial(_layer, kind, spec, lower))
        x, own = layer(params[f"layer_{i}"], x, forced)
        if kind == EXPERTS:
            routes.append(own)
            at += 1
    h = _rms(x, params["final"]["norm"], spec["eps"])
    logits = q(h) @ q(params["head"]["table"]).T
    value = (h @ params["value"]["kernel"])[..., 0] + params["value"]["bias"][0]
    return logits, value, jnp.stack(routes)


def a2c_loss_sum(params, tokens, actions, returns, routes, beta, value_coef,
                 spec, lower):
    """-> (the A2C loss SUMMED over every transition of the episodes given,
    (the logits, this side's own routes))."""
    logits, value, own = forward(params, tokens, spec, lower, routes)
    logp = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]
    advantage = returns - jax.lax.stop_gradient(value)
    policy = -jnp.sum(logp_a * advantage)
    value_l = 0.5 * jnp.sum(jnp.square(value - returns))
    entropy = -jnp.sum(jnp.exp(logp) * logp)
    return policy + value_coef * value_l - beta * entropy, (logits, own)


def _expert_layers(spec) -> int:
    return sum(kind == EXPERTS for _, kind in spec["layers"])


@functools.partial(jax.jit, static_argnames=("spec_key", "lower", "block_envs"))
def _shard_pass(params, env_state, shown, key, forced, routes, hyper, spec_key,
                lower, block_envs):
    """One shard's rollout under the forced actions and the SUM of the loss
    and of its gradient over the shard's transitions, computed with
    ``routes`` ([expert blocks, B, T, k]) where given. -> (loss, grads,
    env_state, shown, key, margins [T, B], tokens [T, B], route flips
    [expert blocks])."""
    spec = dict(spec_key)
    T, B = forced.shape
    (env_state, shown, key), (tokens, rewards, dones, act_keys) = _play(
        env_state, shown, key, forced, spec["ids"], T)
    returns = _returns(rewards, dones, hyper["gamma"])
    rows = _block_rows(B, block_envs)
    by_env = lambda x: _blocks(jnp.swapaxes(x, 0, 1), rows)  # noqa: E731
    n_layers = _expert_layers(spec)

    def add_block(acc, block):
        first, tokens_b, actions_b, returns_b, *forced_b = block
        routes_b = jnp.swapaxes(forced_b[0], 0, 1) if forced_b else None
        (loss, (logits, own)), grads = jax.value_and_grad(
            a2c_loss_sum, has_aux=True)(
            params, tokens_b, actions_b, returns_b, routes_b,
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
        if forced_b:
            flips = jnp.sum(jnp.any(
                jnp.sort(own, -1) != jnp.sort(routes_b, -1), axis=-1), axis=(1, 2))
        else:
            flips = jnp.zeros(n_layers, jnp.int32)
        return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], grads),
                acc[2] + flips), margins

    blocks = (jnp.arange(0, B, rows), by_env(tokens), by_env(forced), by_env(returns))
    if routes is not None:  # [expert blocks, B, T, k] -> blocks of envs
        blocks += (_blocks(jnp.swapaxes(routes, 0, 1), rows),)
    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, params),
            jnp.zeros(n_layers, jnp.int32))
    (loss, grads, flips), margins = jax.lax.scan(add_block, zero, blocks)
    margins = jnp.swapaxes(margins, 0, 1).reshape(T, B)  # [blocks, T, rows]
    return loss, grads, env_state, shown, key, margins, tokens, flips


def follow_updates(params, env_key, shard_keys, n_envs, spec, hyper, n_updates,
                   actions, prompt, lower=None, block_envs=1, routes=None):
    """Follow a fused A2C run on the recall game through its first updates,
    playing ``actions[update]`` ([shards, T, envs a shard] int32, a whole
    episode each) in place of draws of its own and, where given, learning
    with ``routes[update]`` ([shards, expert blocks, envs a shard, T, k]):
    the experts the other side's learner chose. Env ``i`` belongs to shard
    ``i // (n_envs / shards)``. -> what ``reference/ba3c.py``'s gives
    (``losses``, ``first_grad``, ``delta``, ``states``, ``action_margin``,
    ``action_flips``; the two trees as host arrays) and, of the handed
    routes, ``route_flip_share`` with its share a block. ``params`` is
    consumed."""
    numbers = {k: float(hyper[k]) for k in HYPER}
    n_shards = len(shard_keys)
    per = n_envs // n_shards
    key = _spec_key(spec)
    n_layers = _expert_layers(spec)
    with jax.default_matmul_precision("highest"):
        env_state, shown = initial_env(env_key, n_envs, spec["ids"], prompt)
        keys = [jnp.asarray(k) for k in shard_keys]
        start = jax.device_get(params)
        mu = nu = None
        losses, first_grad, margins, states = [], None, [], []
        route_flips = jnp.zeros(n_layers, jnp.int32)
        tokens_seen = 0.0
        for count in range(1, n_updates + 1):
            loss, grads, parts = 0.0, None, []
            for s in range(n_shards):
                cut = lambda x: x[s * per:(s + 1) * per]  # noqa: E731
                l, g, env_s, shown_s, keys[s], margin, tokens, flips = _shard_pass(
                    params, jax.tree_util.tree_map(cut, env_state), cut(shown),
                    keys[s], jnp.asarray(actions[count - 1][s]),
                    None if routes is None else jnp.asarray(routes[count - 1][s]),
                    numbers, key, lower, block_envs)
                loss = loss + l
                grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
                route_flips = route_flips + flips
                # what each env showed all through the update is part of
                # the state it is compared by: the final state alone is a
                # fresh episode's, whatever was played
                parts.append((dict(env_s, shown=jnp.swapaxes(tokens, 0, 1)),
                              shown_s))
                margins.append(jax.device_get(margin))
            n = float(n_envs * actions[count - 1][0].shape[0])
            tokens_seen += n
            if mu is None:  # not before the gradient's pass: 5.3 GB
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
    by_layer = [float(x) / tokens_seen for x in route_flips]
    return {
        "losses": losses, "first_grad": first_grad, "delta": delta,
        "states": states,
        "action_margin": float(max(m.max() for m in margins)),
        "action_flips": float(sum((m > 0).sum() for m in margins)
                              / sum(m.size for m in margins)),
        "route_flip_share": float(sum(by_layer) / max(n_layers, 1)),
        "route_flips_by_layer": by_layer,
    }


@functools.partial(jax.jit, static_argnames=("spec_key", "lower", "block_envs"))
def _logits_of(params, tokens, spec_key, lower, block_envs):
    rows = _block_rows(tokens.shape[0], block_envs)
    logits = jax.lax.map(
        lambda block: forward(params, block, dict(spec_key), lower)[0],
        _blocks(tokens, rows))
    return logits.reshape(tokens.shape[0], tokens.shape[1], -1)


def logits_of(params, tokens, spec, lower=None, block_envs=1):
    """The forward alone over ``tokens`` [B, T], with this side's OWN
    routes: logits [B, T, ids]."""
    with jax.default_matmul_precision("highest"):
        return _logits_of(params, tokens, _spec_key(spec), lower, block_envs)
