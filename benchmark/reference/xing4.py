"""Plain float32 reference of Xing4.0-29B-A4B's layers, cut to one chip's
share, and of one fused A2C update of it on the recall game.

Written from the published architecture (XingChen-AGI/Xing4.0-29B-A4B
``config.json``, ``model_type xing4_0``: a DeepSeek-V3-shaped decoder,
arXiv:2412.19437, with latent attention as arXiv:2405.04434 section 2.1 has
it, whose residual path is manifold-constrained hyper-connections,
arXiv:2512.24880 section 4.2 over arXiv:2409.19606; the configuration's file
lists what is assumed beyond the config). A token's residual is ``n`` = 4
streams ``X`` [n, d]: the embedding's row replicated into each; a layer is
two sub-blocks ``f`` (attention, then feed-forward), each

    v  = vec(X);  v' = v / sqrt(mean(v^2) + hc_eps)             (no gain)
    [p | o | r] = v' Phi                                         n + n + n^2
    H_pre = sigmoid(a_pre p + b_pre);  H_post = 2 sigmoid(a_post o + b_post)
    R = clip(a_res mat(r) + b_res, -30, 30);  M = exp(R)
    20 times:  M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    u = sum_j H_pre[j] X[j];   y = f(RMSNorm(u))
    X[i] <- sum_j M[i, j] X[j] + H_post[i] y

THE 20 ITERATIONS A PYTHON LOOP, the streams an axis of their own ``[B, T,
n, d]``; after the last layer the streams' sum, the final RMSNorm, the
untied head and the value head.

- attention, THE EXPANDED FORM ONLY (no cache, no absorbed product): ``c_q
  = RMSNorm(z W_qa)``; ``[q_nope | q_rope]_h = (c_q W_qb)_h``; ``[c | k_r] =
  z W_kva``, ``c <- RMSNorm(c)``; ``[k_nope | v]_h = (c W_kvb)_h`` for every
  held head; RoPE (rotate-half at YaRN's frequencies) on ``q_rope`` and
  ``k_r``; scores ``s_att (q_nope . k_nope + q_rope . k_r)`` with the ``T x
  T`` causal mask written out, ``s_att = (nope + rope)^-0.5 m^2``, ``m =
  0.1 mscale_all_dim ln(factor) + 1``; softmax; ``W_o``.
- feed-forward: the dense SwiGLU (a published layer under
  ``first_k_dense_replace``), or ``s = sigmoid(z W_r)`` over all the
  published experts, the top k of ``s + bias`` chosen, weights ``scale *
  s[chosen] / (sum s[chosen] + 1e-20)``, then A LOOP OVER THE EXPERTS HELD
  HERE, each ``W2 (silu(W1 z) * W3 z)`` computed for every token and
  weighted by what the router gave it there; the shared expert, a SwiGLU
  every token takes.

Departures from the published description, each the configuration's:
multi-token prediction is left out; RoPE is rotate-half where the checkpoint
interleaves pairs (a fixed permutation of ``W_qb``'s and ``W_kva``'s columns
that seeded weights cannot tell apart); ``Phi`` is stored ``[n + n + n^2, n
d]`` (the program's layout: the same numbers transposed); a chip's share of
heads, experts and vocabulary is the weights handed over, and an assignment
to an absent expert adds nothing.

No chunk, no cache, no grouping, no sort of assignments: whole episodes go
through at once, an env at a time so that it fits (each layer recomputed in
the backward, which changes no value). Everything is float32 under
``jax.default_matmul_precision("highest")`` and imports nothing of the
program. ``lower`` (``fp8``) puts the matrix operands in float8. Returns,
clip, Adam and the lowered operands are ``reference/ba3c.py``'s; the game is
``reference/recall.py``'s; the update's frame is ``reference/nemotron_h.py``'s:
the loss and its gradient are computed WITH the routes another side's
learner used, where they are handed over, and the reference says beside
them what it would have chosen itself.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

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
from benchmark.reference.phi4_flash import _finish, _returns

__all__ = ["spec_of", "init_params", "forward", "follow_updates",
           "logits_of", "mappings", "sub_block", "attention", "feed_forward",
           "yarn_frequencies"]

VALUE_INIT_SCALE = 0.01
EXPERT_BIAS_SCALE = 0.01
#: under the sum of the chosen scores, as published
NORM_EPS = 1e-20
DENSE, EXPERTS = "dense", "experts"
ATTN, FFN = "attn", "ffn"
#: the mappings' seeded start. THE PAPER STARTS THE GATES ``a`` AT 0.01 and
#: trains them; here they start at 1, 1 and 2 (``a_pre``, ``a_post``,
#: ``a_res``), because seeded weights are never trained and at 0.01 the
#: mappings are one constant for every token: the comparison could then not
#: tell 20 Sinkhorn iterations from 5, nor float32 mappings from bfloat16
#: ones. ``b_res`` twice the identity, ``b_pre`` = -ln(n - 1) (``H_pre``
#: round 1/n), ``b_post`` 0 (``H_post`` round 1)
HC_GATES = (1.0, 1.0, 2.0)
HC_RES_DIAGONAL = 2.0


def spec_of(config: dict) -> dict:
    """What the reference computes with, from the configuration's file."""
    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assert config["scoring_func"] == "sigmoid" and config["topk_method"] == "noaux_tc"
    assert config["n_group"] == 1 and config["topk_group"] == 1
    assert config["norm_topk_prob"] and not config["tie_word_embeddings"]
    assert config["moe_layer_freq"] == 1
    yarn = config["rope_scaling"]
    assert yarn["type"] == "yarn"
    held = config["held"]
    return {
        "d": config["hidden_size"], "n": config["hc_mult"],
        "iters": config["hc_sinkhorn_iters"], "hc_eps": config["hc_eps"],
        "clamp_min": config["mhc_h_res_clamp_min"],
        "clamp_max": config["mhc_h_res_clamp_max"],
        "eps": config["rms_norm_eps"],
        "heads": config["num_attention_heads"],
        "rq": config["q_lora_rank"], "rkv": config["kv_lora_rank"],
        "nope": config["qk_nope_head_dim"], "rope": config["qk_rope_head_dim"],
        "v": config["v_head_dim"], "theta": config["rope_theta"],
        "factor": yarn["factor"], "beta_fast": yarn["beta_fast"],
        "beta_slow": yarn["beta_slow"], "mscale": yarn["mscale"],
        "mscale_all_dim": yarn["mscale_all_dim"],
        "original": yarn["original_max_position_embeddings"],
        "f": config["intermediate_size"], "fe": config["moe_intermediate_size"],
        "fs": config["moe_intermediate_size"] * config["n_shared_experts"],
        "experts": config["n_routed_experts"],
        "all_experts": config["published"]["n_routed_experts"],
        "expert_offset": held["expert_offset"],
        "top_k": config["num_experts_per_tok"],
        "scale": config["routed_scaling_factor"],
        "ids": config["vocab_size"],
        "layers": tuple(
            (i, DENSE if i < config["first_k_dense_replace"] else EXPERTS)
            for i in held["layers"]),
    }


def init_params(key, spec: dict):
    """Seeded float32 weights, ``{layer: {leaf: array}}``: normal kernels
    scaled by 1/sqrt(fan_in), unit gains, the mappings' start above, a small
    seeded choosing bias. The benchmark hands the same tree to the program."""
    d, n, H = spec["d"], spec["n"], spec["heads"]
    nope, rot, v, rq, rkv = (spec[k] for k in ("nope", "rope", "v", "rq", "rkv"))
    keys = iter(jax.random.split(key, 16 * len(spec["layers"]) + 4))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)

    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    params = {"embed": {"table": normal((spec["ids"], d), d)}}
    for i, kind in spec["layers"]:
        p = {}
        for sub in (ATTN, FFN):
            p[f"{sub}_norm"] = ones((d,))
            p[f"{sub}_hc_phi"] = normal((2 * n + n * n, n * d), n * d)
            p[f"{sub}_hc_alpha"] = jnp.asarray(HC_GATES, jnp.float32)
            p[f"{sub}_hc_b_pre"] = jnp.full((n,), -math.log(n - 1.0), jnp.float32)
            p[f"{sub}_hc_b_post"] = jnp.zeros((n,), jnp.float32)
            p[f"{sub}_hc_b_res"] = HC_RES_DIAGONAL * jnp.eye(n, dtype=jnp.float32)
        p["wq_a"], p["q_norm"] = normal((d, rq), d), ones((rq,))
        p["wq_b"] = normal((rq, H * (nope + rot)), rq)
        p["wkv_a"], p["kv_norm"] = normal((d, rkv + rot), d), ones((rkv,))
        p["wkv_b"] = normal((rkv, H * (nope + v)), rkv)
        p["wo"] = normal((H * v, d), H * v)
        if kind == DENSE:
            f = spec["f"]
            p["w1"], p["w3"] = normal((d, f), d), normal((d, f), d)
            p["w2"] = normal((f, d), f)
        else:
            e, fe, fs = spec["experts"], spec["fe"], spec["fs"]
            p["router"] = normal((d, spec["all_experts"]), d)
            p["expert_bias"] = EXPERT_BIAS_SCALE * jax.random.normal(
                next(keys), (spec["all_experts"],), jnp.float32)
            p["w1"], p["w3"] = normal((e, d, fe), d), normal((e, d, fe), d)
            p["w2"] = normal((e, fe, d), fe)
            p["shared_w1"], p["shared_w3"] = normal((d, fs), d), normal((d, fs), d)
            p["shared_w2"] = normal((fs, d), fs)
        params[f"layer_{i}"] = p
    params["final"] = {"norm": ones((d,))}
    params["head"] = {"table": normal((spec["ids"], d), d)}
    params["value"] = {"kernel": VALUE_INIT_SCALE * normal((d, 1), d),
                       "bias": jnp.zeros((1,), jnp.float32)}
    return params


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def mappings(p, sub, X, spec):
    """X [B, T, n, d] -> (H_pre [B, T, n], H_post [B, T, n], H_res [B, T, n,
    n]: ``H_res[i, j]`` weighs stream j into stream i)."""
    n, eps = spec["n"], spec["hc_eps"]
    v = X.reshape(*X.shape[:2], -1)
    v = v / jnp.sqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
    proj = v @ p[f"{sub}_hc_phi"].T  # Phi is stored transposed
    a_pre, a_post, a_res = p[f"{sub}_hc_alpha"]
    H_pre = _sigmoid(a_pre * proj[..., :n] + p[f"{sub}_hc_b_pre"])
    H_post = 2.0 * _sigmoid(a_post * proj[..., n:2 * n] + p[f"{sub}_hc_b_post"])
    R = jnp.clip(
        a_res * proj[..., 2 * n:].reshape(*proj.shape[:2], n, n)
        + p[f"{sub}_hc_b_res"], spec["clamp_min"], spec["clamp_max"])
    M = jnp.exp(R)
    for _ in range(spec["iters"]):  # columns first, then rows
        M = M / (jnp.sum(M, axis=-2, keepdims=True) + eps)
        M = M / (jnp.sum(M, axis=-1, keepdims=True) + eps)
    return H_pre, H_post, M


def sub_block(p, sub, X, f, spec):
    """One sub-block ``f(z [B, T, d]) -> (y, extra)`` under its mappings: X
    [B, T, n, d] -> (X, extra, how far its mixing matrices are from doubly
    stochastic, summed over the tokens: the largest |row or column sum - 1|
    of each)."""
    H_pre, H_post, H_res = mappings(p, sub, X, spec)
    u = jnp.einsum("btj,btjd->btd", H_pre, X)
    y, extra = f(_rms(u, p[f"{sub}_norm"], spec["eps"]))
    mixed = jnp.einsum("btij,btjd->btid", H_res, X)
    off = jnp.maximum(jnp.max(jnp.abs(jnp.sum(H_res, -1) - 1.0), -1),
                      jnp.max(jnp.abs(jnp.sum(H_res, -2) - 1.0), -1))
    return (mixed + H_post[..., None] * y[:, :, None, :], extra,
            jnp.sum(jax.lax.stop_gradient(off)))


def yarn_frequencies(spec) -> np.ndarray:
    """The ``rope / 2`` rotary frequencies under ``rope_scaling`` (type
    yarn), as the DeepSeek-V3 family's public module computes them."""
    dim, base = spec["rope"], spec["theta"]
    extrapolated = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    interpolated = extrapolated / spec["factor"]

    def correction(turns):
        return dim * math.log(spec["original"] / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction(spec["beta_fast"])), 0)
    high = min(math.ceil(correction(spec["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # 1 where a frequency stays as it is
    return (interpolated * (1.0 - keep) + extrapolated * keep).astype(np.float32)


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope(x, spec):
    """x [B, T, H, D]: position t rotates pair (i, i + D/2) by t times the
    i-th YaRN frequency; cos and sin times ``mscale / mscale_all_dim``'s
    ratio (1 as published)."""
    T, D = x.shape[1], x.shape[3]
    angle = (jnp.arange(T, dtype=jnp.float32)[:, None]
             * jnp.asarray(yarn_frequencies(spec))[None, :])
    ratio = (_mscale(spec["factor"], spec["mscale"])
             / _mscale(spec["factor"], spec["mscale_all_dim"]))
    cos = ratio * jnp.cos(angle)[None, :, None, :]
    sin = ratio * jnp.sin(angle)[None, :, None, :]
    a, b = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, z, spec, q=LOWER[None]):
    """z [B, T, d], normed -> the held heads' part of the attention's
    output [B, T, d]: keys and values of every head expanded from the latent."""
    B, T, _ = z.shape
    H, nope, v, rkv = spec["heads"], spec["nope"], spec["v"], spec["rkv"]
    c_q = _rms(q(z) @ q(p["wq_a"]), p["q_norm"], spec["eps"])
    queries = (q(c_q) @ q(p["wq_b"])).reshape(B, T, H, -1)
    q_nope, q_rope = queries[..., :nope], _rope(queries[..., nope:], spec)
    ckv = q(z) @ q(p["wkv_a"])
    c = _rms(ckv[..., :rkv], p["kv_norm"], spec["eps"])
    k_rope = _rope(ckv[..., None, rkv:], spec)[:, :, 0]  # one key, every head's
    kv = (q(c) @ q(p["wkv_b"])).reshape(B, T, H, nope + v)
    k_nope, values = kv[..., :nope], kv[..., nope:]
    m = _mscale(spec["factor"], spec["mscale_all_dim"])
    scale = m * m / math.sqrt(nope + spec["rope"])
    scores = scale * (
        jnp.einsum("bqhn,bshn->bhqs", q(q_nope), q(k_nope))
        + jnp.einsum("bqhr,bsr->bhqs", q(q_rope), q(k_rope)))
    at = jnp.arange(T)
    allowed = at[None, :] <= at[:, None]  # the T x T mask
    scores = jnp.where(allowed[None, None], scores, -jnp.inf)
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    o = jnp.einsum("bhqs,bshv->bqhv", q(probs), q(values))
    return q(o.reshape(B, T, -1)) @ q(p["wo"])


def _swiglu(z, w1, w3, w2, q):
    return q(_silu(q(z) @ q(w1)) * (q(z) @ q(w3))) @ q(w2)


def feed_forward(kind, p, z, spec, q=LOWER[None], forced=None):
    """z [B, T, d], normed -> (the dense SwiGLU, None) or (this share's part
    of the routed experts' sum plus the shared expert, the expert ids [B, T,
    k] this side chooses). ``forced`` are the ids another side chose."""
    if kind == DENSE:
        return _swiglu(z, p["w1"], p["w3"], p["w2"], q), None
    scores = _sigmoid(z @ p["router"])
    _, own = jax.lax.top_k(scores + p["expert_bias"], spec["top_k"])
    chosen = own if forced is None else forced
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = spec["scale"] * weights / (
        jnp.sum(weights, -1, keepdims=True) + NORM_EPS)

    def one_expert(out, expert):  # the experts held here, one at a time
        e, w1, w3, w2 = expert
        mine = jnp.sum(
            jnp.where(chosen == spec["expert_offset"] + e, weights, 0.0), -1)
        return out + mine[..., None] * _swiglu(z, w1, w3, w2, q), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(z),
        (jnp.arange(spec["experts"]), p["w1"], p["w3"], p["w2"]))
    shared = _swiglu(z, p["shared_w1"], p["shared_w3"], p["shared_w2"], q)
    return out + shared, own


def _layer(kind, spec, lower, p, X, forced_route):
    q = LOWER[lower]
    X, _, off_a = sub_block(
        p, ATTN, X, lambda z: (attention(p, z, spec, q), None), spec)
    X, own, off_f = sub_block(
        p, FFN, X, lambda z: feed_forward(kind, p, z, spec, q, forced_route),
        spec)
    return X, own, off_a + off_f


def forward(params, tokens, spec, lower=None, forced_routes=None):
    """tokens int32 [B, T], whole episodes from their first step ->
    (logits [B, T, ids], value [B, T], routes [expert layers, B, T, k]: what
    this side would choose, the mixing matrices' distance from doubly
    stochastic summed over tokens and sub-blocks). ``forced_routes`` (the
    shape of the routes): the experts another side chose, which the expert
    layers then compute with."""
    q = LOWER[lower]
    x = params["embed"]["table"][tokens]
    X = jnp.repeat(x[:, :, None, :], spec["n"], axis=2)  # every stream the row
    routes, at, off = [], 0, 0.0
    for i, kind in spec["layers"]:
        forced = None
        if kind == EXPERTS and forced_routes is not None:
            forced = forced_routes[at]
        layer = jax.checkpoint(functools.partial(_layer, kind, spec, lower))
        X, own, off_i = layer(params[f"layer_{i}"], X, forced)
        off = off + off_i
        if kind == EXPERTS:
            routes.append(own)
            at += 1
    h = _rms(jnp.sum(X, axis=2), params["final"]["norm"], spec["eps"])
    logits = q(h) @ q(params["head"]["table"]).T
    value = (h @ params["value"]["kernel"])[..., 0] + params["value"]["bias"][0]
    return logits, value, jnp.stack(routes), off


def a2c_loss_sum(params, tokens, actions, returns, routes, beta, value_coef,
                 spec, lower):
    """-> (the A2C loss SUMMED over every transition of the episodes given,
    (the logits, this side's own routes, the mixing matrices' summed
    distance from doubly stochastic))."""
    logits, value, own, off = forward(params, tokens, spec, lower, routes)
    logp = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]
    advantage = returns - jax.lax.stop_gradient(value)
    policy = -jnp.sum(logp_a * advantage)
    value_l = 0.5 * jnp.sum(jnp.square(value - returns))
    entropy = -jnp.sum(jnp.exp(logp) * logp)
    return policy + value_coef * value_l - beta * entropy, (logits, own, off)


def _expert_layers(spec) -> int:
    return sum(kind == EXPERTS for _, kind in spec["layers"])


@functools.partial(jax.jit, static_argnames=("spec_key", "lower", "block_envs"))
def _shard_pass(params, env_state, shown, key, forced, routes, hyper, spec_key,
                lower, block_envs):
    """One shard's rollout under the forced actions and the SUM of the loss
    and of its gradient over the shard's transitions, computed with
    ``routes`` ([expert layers, B, T, k]) where given. -> (loss, grads,
    env_state, shown, key, margins [T, B], tokens [T, B], route flips
    [expert layers], the mixing matrices' summed distance from doubly
    stochastic)."""
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
        (loss, (logits, own, off)), grads = jax.value_and_grad(
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
                acc[2] + flips, acc[3] + off), margins

    blocks = (jnp.arange(0, B, rows), by_env(tokens), by_env(forced), by_env(returns))
    if routes is not None:  # [expert layers, B, T, k] -> blocks of envs
        blocks += (_blocks(jnp.swapaxes(routes, 0, 1), rows),)
    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, params),
            jnp.zeros(n_layers, jnp.int32), jnp.float32(0.0))
    (loss, grads, flips, off), margins = jax.lax.scan(add_block, zero, blocks)
    margins = jnp.swapaxes(margins, 0, 1).reshape(T, B)  # [blocks, T, rows]
    return loss, grads, env_state, shown, key, margins, tokens, flips, off


def follow_updates(params, env_key, shard_keys, n_envs, spec, hyper, n_updates,
                   actions, prompt, lower=None, block_envs=1, routes=None):
    """Follow a fused A2C run on the recall game through its first updates,
    playing ``actions[update]`` ([shards, T, envs a shard] int32, a whole
    episode each) in place of draws of its own and, where given, learning
    with ``routes[update]`` ([shards, expert layers, envs a shard, T, k]):
    the experts the other side's learner chose. Env ``i`` belongs to shard
    ``i // (n_envs / shards)``. -> what ``reference/ba3c.py``'s gives
    (``losses``, ``first_grad``, ``delta``, ``states``, ``action_margin``,
    ``action_flips``; the two trees as host arrays) and, of the handed
    routes, ``route_flip_share`` with its share a layer, and
    ``mhc_doubly_stochastic_gap``: the mean over tokens and sub-blocks of
    this side's mixing matrices' distance from doubly stochastic. ``params``
    is consumed."""
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
        tokens_seen, off_total = 0.0, 0.0
        for count in range(1, n_updates + 1):
            loss, grads, parts = 0.0, None, []
            for s in range(n_shards):
                cut = lambda x: x[s * per:(s + 1) * per]  # noqa: E731
                l, g, env_s, shown_s, keys[s], margin, tokens, flips, off = _shard_pass(
                    params, jax.tree_util.tree_map(cut, env_state), cut(shown),
                    keys[s], jnp.asarray(actions[count - 1][s]),
                    None if routes is None else jnp.asarray(routes[count - 1][s]),
                    numbers, key, lower, block_envs)
                loss = loss + l
                grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
                route_flips = route_flips + flips
                off_total += float(off)
                # what each env showed all through the update is part of
                # the state it is compared by: the final state alone is a
                # fresh episode's, whatever was played
                parts.append((dict(env_s, shown=jnp.swapaxes(tokens, 0, 1)),
                              shown_s))
                margins.append(jax.device_get(margin))
            n = float(n_envs * actions[count - 1][0].shape[0])
            tokens_seen += n
            if mu is None:  # not before the gradient's pass: 5.4 GB
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
        "mhc_doubly_stochastic_gap": off_total / (
            tokens_seen * 2 * len(spec["layers"])),
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
