"""Plain float32 reference of Keye-VL-2.0-30B-A3B's language-model layers,
cut to one chip's share, and of one fused A2C update of it on the recall
game.

Written from the published architecture (Kwai-Keye/Keye-VL-2.0-30B-A3B
``config.json``, ``model_type KeyeVL2``: a Qwen3-MoE-shaped decoder with
``sa_config``'s indexer, whose equations are DeepSeek-V3.2-Exp's; the
configuration's file lists what is assumed beyond the config). Every layer,
``z = RMSNorm(x)``:

- main attention: per-head RMSNorm on q and k, rotate-half RoPE, each of
  the 4 key/value heads serving 8 query heads, scale ``128^-0.5``;
- indexer, on ``stop_gradient(z)``: ``q^I = RoPE(z W_q^I)`` [16, 64], ``k^I
  = RoPE(LayerNorm(z W_k^I))`` [64], ``w = z W_w * 16^-0.5 * 64^-0.5``;
  ``I[t, s] = sum_j w[t, j] relu(q^I[t, j] . k^I[s])``, the WHOLE ``T x T``
  matrix;
- selection BY A FULL SORT of each query's row: the live keys (``s <= t``)
  in descending order of ``I``, stable (a tie to the lower position), the
  first ``min(t + 1, topk)`` kept;
- ``softmax`` over the kept keys only (the rest at ``-inf``), the full
  ``T x T`` mask written out; ``W_o``; ``x += o``;
- experts: ``p = softmax(z' W_r)`` over all 128, the top 8 chosen, weights
  ``p[chosen] / sum p[chosen]``, then A LOOP OVER THE EXPERTS HELD HERE, each
  computed for every token and weighted by what the router gave it there;
- final RMSNorm, an untied head over the held ids, a value head;
- ``L_I = mean_t KL(p_t || softmax_{s in S_t} I[t, s])`` with ``p_t`` the
  main attention's probabilities summed over the heads and normalised, under
  ``stop_gradient``: the only path to the indexer's leaves.

No cache, no grouping, no sort of assignments, no blocks of rows: whole
episodes go through at once, an env at a time so that it fits, a key/value
head's 8 query heads at a time. Everything is float32 under
``jax.default_matmul_precision("highest")`` and imports nothing of the
program. ``lower`` (``fp8``) puts the matrix operands in float8, the
control's precision. Returns, clip, Adam and the lowered operands are
``reference/ba3c.py``'s; the game is ``reference/recall.py``'s.

An update: the env batch plays the actions it is handed; the loss (A2C +
``indexer_loss_coef * sum_layers L_I``) and its gradient over all
transitions are computed WITH the routes and WITH the selections another
side's learner used, where they are handed over, and the reference says
beside them what it would have chosen itself (``forward``'s ``routes`` and
``selected``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.ba3c import (
    ADAM_B1,
    HYPER,
    LOWER,
    adam_update,
    clip_by_global_norm,
    n_step_returns,
)
from benchmark.reference.lfm2_moe import (
    _block_rows,
    _blocks,
    _play,
    _rms,
    _rope,
    _silu,
    _spec_key,
    initial_env,
)

__all__ = ["ADAM_B1", "spec_of", "init_params", "forward", "follow_updates",
           "logits_of"]

VALUE_INIT_SCALE = 0.01
INDEXER_LEAVES = ("idx_wq", "idx_wk", "idx_k_norm", "idx_k_norm_b", "idx_ww")


def spec_of(config: dict) -> dict:
    """What the reference computes with, from the configuration's file: the
    published keys, and ``held`` (which layers, experts and ids live here)."""
    held, sa = config["held"], config["sa_config"]
    return {
        "d": config["hidden_size"], "fe": config["moe_intermediate_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"], "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "experts_all": config["published"]["num_experts"],
        "experts": config["num_experts"],
        "expert_offset": held["expert_offset"],
        "top_k": config["num_experts_per_tok"],
        "norm_topk": config["norm_topk_prob"],
        "index_heads": sa["indexer_num_heads"],
        "index_dim": sa["indexer_head_dim"], "index_topk": sa["topk"],
        "index_coef": float(config["algorithm"]["indexer_loss_coef"]),
        "ids": config["vocab_size"], "layers": tuple(held["layers"]),
    }


def init_params(key, spec: dict):
    """Seeded float32 weights, ``{layer: {leaf: array}}``: normal kernels
    scaled by 1/sqrt(fan_in), unit gains, a zero bias. The benchmark hands
    the same tree to the program."""
    d, fe, D = spec["d"], spec["fe"], spec["head_dim"]
    hq, hkv = spec["heads"] * D, spec["kv_heads"] * D
    hi, di, e = spec["index_heads"], spec["index_dim"], spec["experts"]
    keys = iter(jax.random.split(key, 16 * len(spec["layers"]) + 4))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)

    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    params = {"embed": {"table": normal((spec["ids"], d), d)}}
    for i in spec["layers"]:
        params[f"layer_{i}"] = dict(
            attn_norm=ones((d,)), ffn_norm=ones((d,)),
            wq=normal((d, hq), d), wk=normal((d, hkv), d),
            wv=normal((d, hkv), d), wo=normal((hq, d), hq),
            q_norm=ones((D,)), k_norm=ones((D,)),
            idx_wq=normal((d, hi * di), d), idx_wk=normal((d, di), d),
            idx_k_norm=ones((di,)), idx_k_norm_b=jnp.zeros((di,), jnp.float32),
            idx_ww=normal((d, hi), d),
            router=normal((d, spec["experts_all"]), d),
            w1=normal((e, d, fe), d), w3=normal((e, d, fe), d),
            w2=normal((e, fe, d), fe))
    params["final"] = {"norm": ones((d,))}
    params["head"] = {"table": normal((spec["ids"], d), d)}
    params["value"] = {"kernel": VALUE_INIT_SCALE * normal((d, 1), d),
                       "bias": jnp.zeros((1,), jnp.float32)}
    return params


def _ln(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def select_by_sort(index, topk: int):
    """index [B, T, T] float32 -> bool [B, T, T]: for query ``t`` the
    ``min(t + 1, topk)`` keys ``s <= t`` of largest ``index[t, s]``, each
    row sorted whole, descending and stable (a tie to the lower position)."""
    T = index.shape[-1]
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    masked = jnp.where(causal, index, -jnp.inf)
    order = jnp.argsort(-masked, axis=-1, stable=True)  # rank -> position
    rank = jnp.argsort(order, axis=-1)                   # position -> rank
    return (rank < topk) & causal


def _sparse_attention(p, z, spec, q, forced):
    """-> (the operator's output [B, T, d], this layer's ``sum_t KL_t``, the
    selection this side makes [B, T, T] bool). ``forced``: another side's
    selection, computed with in place of this side's own."""
    B, T, _ = z.shape
    H, KV, D = spec["heads"], spec["kv_heads"], spec["head_dim"]
    Hi, Di = spec["index_heads"], spec["index_dim"]
    qh = (q(z) @ q(p["wq"])).reshape(B, T, H, D)
    kh = (q(z) @ q(p["wk"])).reshape(B, T, KV, D)
    vh = (q(z) @ q(p["wv"])).reshape(B, T, KV, D)
    qh = _rope(_rms(qh, p["q_norm"], spec["eps"]), spec["theta"])
    kh = _rope(_rms(kh, p["k_norm"], spec["eps"]), spec["theta"])

    zi = jax.lax.stop_gradient(z)
    qi = _rope((q(zi) @ q(p["idx_wq"])).reshape(B, T, Hi, Di), spec["theta"])
    ki = _ln(q(zi) @ q(p["idx_wk"]), p["idx_k_norm"], p["idx_k_norm_b"], spec["eps"])
    ki = _rope(ki[:, :, None, :], spec["theta"])[:, :, 0, :]
    w = (q(zi) @ q(p["idx_ww"])) * (Hi ** -0.5 * Di ** -0.5)
    dots = jnp.einsum("bqjd,bsd->bqjs", q(qi), q(ki))
    index = jnp.sum(jnp.maximum(dots, 0.0) * w[..., None], axis=2)  # [B, T, T]
    index = jnp.where(index == 0, 0.0, index)  # one zero: -0.0 ties with 0.0

    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    own = select_by_sort(jax.lax.stop_gradient(index), spec["index_topk"])
    chosen = own if forced is None else forced & causal

    def one_kv_head(heads):  # its 8 query heads against every key
        qg, kg, vg = heads  # [B, T, G, D], [B, T, D], [B, T, D]
        scores = jnp.einsum("bqgd,bsd->bgqs", q(qg), q(kg)) / math.sqrt(D)
        scores = jnp.where(chosen[:, None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        probs = jnp.exp(scores)
        probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
        return (jnp.einsum("bgqs,bsd->bqgd", q(probs), q(vg)),
                jnp.sum(probs, axis=1))

    G = H // KV
    out, mass = jax.lax.map(
        jax.checkpoint(one_kv_head),
        (jnp.moveaxis(qh.reshape(B, T, KV, G, D), 2, 0),
         jnp.moveaxis(kh, 2, 0), jnp.moveaxis(vh, 2, 0)))
    out = jnp.moveaxis(out, 0, 2).reshape(B, T, H * D)  # [KV, B, T, G, D] ->
    target = jax.lax.stop_gradient(jnp.sum(mass, axis=0)) / H  # [B, T, T]

    logits = jnp.where(chosen, index, -jnp.inf)
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    log_q = logits - jnp.log(jnp.sum(jnp.exp(logits), axis=-1, keepdims=True))
    there = chosen & (target > 0)
    kl = jnp.sum(jnp.where(
        there, target * (jnp.log(jnp.where(there, target, 1.0))
                         - jnp.where(there, log_q, 0.0)), 0.0))
    return q(out) @ q(p["wo"]), kl, own


def _experts_ffn(p, z, spec, q, forced=None):
    """-> (this share's part of the layer, the expert ids [B, T, k] this
    side chooses). ``forced`` are the ids another side chose."""
    logits = z @ p["router"]
    logits = logits - jnp.max(logits, axis=-1, keepdims=True)
    scores = jnp.exp(logits) / jnp.sum(jnp.exp(logits), axis=-1, keepdims=True)
    _, own = jax.lax.top_k(scores, spec["top_k"])
    chosen = own if forced is None else forced
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if spec["norm_topk"]:
        weights = weights / jnp.sum(weights, -1, keepdims=True)

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


def _layer(spec, lower, p, x, forced_route, forced_select):
    q = LOWER[lower]
    z = _rms(x, p["attn_norm"], spec["eps"])
    mixed, kl, selected = _sparse_attention(p, z, spec, q, forced_select)
    h = x + mixed
    y, routes = _experts_ffn(
        p, _rms(h, p["ffn_norm"], spec["eps"]), spec, q, forced_route)
    return h + y, kl, routes, selected


def forward(params, tokens, spec, lower=None, forced_routes=None,
            forced_selected=None):
    """tokens int32 [B, T], whole episodes from their first step ->
    (logits [B, T, ids], value [B, T], ``sum_t KL_t`` a layer [layers],
    routes [layers, B, T, k], selected [layers, B, T, T] bool).

    ``forced_routes`` / ``forced_selected`` (the shapes of the last two)
    are the experts and the keys another side chose for every token and
    layer: this side then computes with those, as it plays the actions it
    is handed, and its own ``routes`` and ``selected`` say what it would
    have chosen at each of those points. Two scores within a rounding error
    of each other near the k-th place flip between precisions, and a
    flipped choice is a different function: forced, the two sides compute
    one function and their numbers compare tightly."""
    q = LOWER[lower]
    x = params["embed"]["table"][tokens]
    # the layers are of one kind: one loop over their stacked weights (and
    # over the handed-over choices, a layer each), its body compiled once
    stacked = jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves),
        *[params[f"layer_{i}"] for i in spec["layers"]])

    def one_layer(x, layer):
        p, forced_route, forced_select = layer
        x, kl, chosen, kept = jax.checkpoint(
            functools.partial(_layer, spec, lower))(p, x, forced_route, forced_select)
        return x, (kl, chosen, kept)

    x, (kls, routes, selected) = jax.lax.scan(
        one_layer, x, (stacked, forced_routes, forced_selected))
    h = _rms(x, params["final"]["norm"], spec["eps"])
    logits = q(h) @ q(params["head"]["table"]).T
    value = (h @ params["value"]["kernel"])[..., 0] + params["value"]["bias"][0]
    return logits, value, kls, routes, selected


def loss_sum(params, tokens, actions, returns, routes, selected, beta,
             value_coef, spec, lower):
    """-> (the differentiated total SUMMED over every transition of the
    episodes given: A2C + ``index_coef * sum_layers sum_t KL_t``; (its A2C
    part, ``sum_t KL_t`` a layer, this side's own routes and selections, the
    logits))."""
    logits, value, kls, own_routes, own_selected = forward(
        params, tokens, spec, lower, routes, selected)
    logp = jax.nn.log_softmax(logits)
    logp_a = jnp.take_along_axis(logp, actions[..., None], axis=-1)[..., 0]
    advantage = returns - jax.lax.stop_gradient(value)
    policy = -jnp.sum(logp_a * advantage)
    value_l = 0.5 * jnp.sum(jnp.square(value - returns))
    entropy = -jnp.sum(jnp.exp(logp) * logp)
    a2c = policy + value_coef * value_l - beta * entropy
    return a2c + spec["index_coef"] * jnp.sum(kls), (
        a2c, kls, own_routes, own_selected, logits)


def flips(own, forced):
    """(pairs one side alone chose, pairs either side chose counted on each:
    ``|A xor B|``, ``|A| + |B|``) of two selections [.., T, T] bool, over
    the queries past the top-k alone: before it both sides keep every key."""
    return (jnp.sum(own ^ forced, dtype=jnp.int32),
            jnp.sum(own, dtype=jnp.int32) + jnp.sum(forced, dtype=jnp.int32))


@functools.partial(jax.jit, static_argnames=("spec_key", "lower", "block_envs"))
def _shard_pass(params, env_state, shown, key, forced, routes, selected, hyper,
                spec_key, lower, block_envs):
    """One shard's rollout under the forced actions and the SUM of the loss
    and of its gradient over the shard's transitions, computed with
    ``routes`` ([layers, B, T, k]) and ``selected`` ([layers, B, T, T / 8]
    uint8, the mask's bits) where given. -> (the total, its A2C part, ``sum
    KL`` a layer, grads, env_state, shown, key, margins [T, B], tokens [T,
    B], route flips [layers], (selection flips, pairs) [layers] each)."""
    spec = dict(spec_key)
    T, B = forced.shape
    (env_state, shown, key), (tokens, rewards, dones, act_keys) = _play(
        env_state, shown, key, forced, spec["ids"], T)
    returns = n_step_returns(rewards, dones, jnp.zeros((B,)), hyper["gamma"])
    rows = _block_rows(B, block_envs)
    by_env = lambda x: _blocks(jnp.swapaxes(x, 0, 1), rows)  # noqa: E731
    past = (jnp.arange(T) >= spec["index_topk"])[:, None]

    def add_block(acc, block):
        first, tokens_b, actions_b, returns_b, *forced_b = block
        routes_b = selected_b = None
        if forced_b:
            routes_b = jnp.swapaxes(forced_b[0], 0, 1)
            selected_b = jnp.unpackbits(
                jnp.swapaxes(forced_b[1], 0, 1), axis=-1, count=T).astype(bool)
        (loss, (a2c, kls, own_r, own_s, logits)), grads = jax.value_and_grad(
            loss_sum, has_aux=True)(
            params, tokens_b, actions_b, returns_b, routes_b, selected_b,
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
            route_flips = jnp.sum(jnp.any(
                jnp.sort(own_r, -1) != jnp.sort(routes_b, -1), axis=-1), axis=(1, 2))
            causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
            one_side, either = jax.vmap(flips)(
                own_s & past, selected_b & causal & past)
        else:
            route_flips = one_side = either = jnp.zeros(len(spec["layers"]), jnp.int32)
        acc = (acc[0] + loss, acc[1] + a2c, acc[2] + kls,
               jax.tree_util.tree_map(jnp.add, acc[3], grads),
               acc[4] + route_flips, acc[5] + one_side, acc[6] + either)
        return acc, margins

    n_layers = len(spec["layers"])
    blocks = (jnp.arange(0, B, rows), by_env(tokens), by_env(forced), by_env(returns))
    if routes is not None:  # [layers, B, ...] -> blocks of envs
        blocks += (_blocks(jnp.swapaxes(routes, 0, 1), rows),
                   _blocks(jnp.swapaxes(selected, 0, 1), rows))
    zero = (jnp.float32(0.0), jnp.float32(0.0), jnp.zeros(n_layers),
            jax.tree_util.tree_map(jnp.zeros_like, params),
            *(jnp.zeros(n_layers, jnp.int32) for _ in range(3)))
    (loss, a2c, kls, grads, route_flips, one_side, either), margins = jax.lax.scan(
        add_block, zero, blocks)
    margins = jnp.swapaxes(margins, 0, 1).reshape(T, B)  # [blocks, T, rows]
    return (loss, a2c, kls, grads, env_state, shown, key, margins, tokens,
            route_flips, one_side, either)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _finish(params, grads, mu, nu, count, n, hyper):
    grads = clip_by_global_norm(
        jax.tree_util.tree_map(lambda g: g / n, grads), hyper["grad_clip_norm"])
    params, mu, nu = adam_update(
        params, grads, mu, nu, count, hyper["learning_rate"], hyper["adam_epsilon"])
    return params, mu, nu, grads


def follow_updates(params, env_key, shard_keys, n_envs, spec, hyper, n_updates,
                   actions, prompt, lower=None, block_envs=1, routes=None,
                   selected=None):
    """Follow a fused A2C run on the recall game through its first updates,
    playing ``actions[update]`` ([shards, T, envs a shard] int32) in place of
    draws of its own and, where given, learning with ``routes[update]``
    ([shards, layers, envs a shard, T, k]) and ``selected[update]`` ([shards,
    layers, envs a shard, T, T / 8] uint8: the bits of the mask of the keys
    each query read): the experts and keys the other side's learner chose.
    Env ``i`` belongs to shard ``i // (n_envs / shards)``. -> what
    ``reference/ba3c.py``'s gives (``losses``: the differentiated total, a
    transition; ``first_grad`` and ``delta`` as host arrays) and, of the
    handed choices, ``route_flip_share`` and ``select_flip_share`` with
    their shares a layer, ``a2c_losses`` and ``indexer_kl`` (a layer, an
    update). ``params`` is consumed."""
    numbers = {k: float(hyper[k]) for k in HYPER}
    n_shards = len(shard_keys)
    per = n_envs // n_shards
    key = _spec_key(spec)
    n_layers = len(spec["layers"])
    with jax.default_matmul_precision("highest"):
        env_state, shown = initial_env(env_key, n_envs, spec["ids"], prompt)
        keys = [jnp.asarray(k) for k in shard_keys]
        start = jax.device_get(params)
        mu = nu = None
        losses, a2c_losses, kl_means, first_grad, margins, states = [], [], [], None, [], []
        route_flips = jnp.zeros(n_layers, jnp.int32)
        one_side = jnp.zeros(n_layers, jnp.int32)
        either = jnp.zeros(n_layers, jnp.int32)
        tokens_seen = 0
        for count in range(1, n_updates + 1):
            loss, a2c, kls, grads, parts = 0.0, 0.0, 0.0, None, []
            for s in range(n_shards):
                cut = lambda x: x[s * per:(s + 1) * per]  # noqa: E731
                (l, a, k, g, env_s, shown_s, keys[s], margin, tokens,
                 r_flips, s_one, s_either) = _shard_pass(
                    params, jax.tree_util.tree_map(cut, env_state), cut(shown),
                    keys[s], jnp.asarray(actions[count - 1][s]),
                    None if routes is None else jnp.asarray(routes[count - 1][s]),
                    None if selected is None else jnp.asarray(selected[count - 1][s]),
                    numbers, key, lower, block_envs)
                loss, a2c, kls = loss + l, a2c + a, kls + k
                grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
                route_flips, one_side, either = (
                    route_flips + r_flips, one_side + s_one, either + s_either)
                parts.append((dict(env_s, shown=jnp.swapaxes(tokens, 0, 1)),
                              shown_s))
                margins.append(jax.device_get(margin))
            n = float(n_envs * actions[count - 1][0].shape[0])
            tokens_seen += n
            if mu is None:  # not before the gradient's pass
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
            a2c_losses.append(float(a2c) / n)
            kl_means.append([float(x) / n for x in kls])
        delta = jax.tree_util.tree_map(
            lambda a, b: a - b, jax.device_get(params), start)
    route_by_layer = [float(x) / tokens_seen for x in route_flips]
    select_by_layer = [float(a) / max(float(b), 1.0)
                       for a, b in zip(one_side, either, strict=True)]
    return {
        "losses": losses, "first_grad": first_grad, "delta": delta,
        "states": states, "a2c_losses": a2c_losses, "indexer_kl": kl_means,
        "action_margin": float(max(m.max() for m in margins)),
        "action_flips": float(sum((m > 0).sum() for m in margins)
                              / sum(m.size for m in margins)),
        "route_flip_share": float(sum(route_by_layer) / n_layers),
        "route_flips_by_layer": route_by_layer,
        "select_flip_share": float(
            sum(float(x) for x in one_side) / max(sum(float(x) for x in either), 1.0)),
        "select_flips_by_layer": select_by_layer,
    }


@functools.partial(jax.jit, static_argnames=("spec_key", "lower", "block_envs"))
def _logits_of(params, tokens, spec_key, lower, block_envs):
    rows = _block_rows(tokens.shape[0], block_envs)
    logits = jax.lax.map(
        lambda block: forward(params, block, dict(spec_key), lower)[0],
        _blocks(tokens, rows))
    return logits.reshape(tokens.shape[0], tokens.shape[1], -1)


def logits_of(params, tokens, spec, lower=None, block_envs=1):
    """The forward alone over ``tokens`` [B, T], with this side's OWN routes
    and selections: logits [B, T, ids]."""
    with jax.default_matmul_precision("highest"):
        return _logits_of(params, tokens, _spec_key(spec), lower, block_envs)
