"""Plain float32 reference of Phi-4-mini-flash-reasoning's layers, cut to the
published layers one chip holds, and of one fused A2C update of it on the
recall game.

Written from the published architecture (microsoft/Phi-4-mini-flash-reasoning
``config.json``, ``model_type phi4flash``; arXiv:2507.06607; the
configuration's file lists what is assumed beyond the config). Every layer
is ``h = x + Mixer(LN(x))``, ``x' = h + W_down(silu(W_gate LN(h)) * W_up
LN(h))``, LayerNorm with gain and bias; the mixer by published index:

- Mamba: ``[u, z] = a W_in``; ``u = silu(b_c + sum_k w_k u_{t-k})`` over 4
  taps, zero before the episode; ``[dt, B, C] = u W_x``; ``dt = softplus(dt
  W_dt + b_dt)``; ``A = -exp(A_log)``; THE RECURRENCE ONE POSITION AT A TIME,
  ``s_t = exp(dt_t A) * s_{t-1} + (dt_t u_t) B_t^T`` on a state ``[c, n]``
  from zero, ``y_t = s_t C_t + D u_t``; out ``(y * silu(z)) W_out``. The
  layer the configuration names also hands ``y`` on as ``m``.
- window / full attention: ``[q, k, v] = a W_qkv + b``; the 40 query heads
  as 20 pairs ``(q_1, q_2)``, the 20 K/V heads as 10 pairs, each K/V pair
  repeated for its two query pairs; ``A_j = softmax(q_j k_j^T / 8 + mask)``
  with the ``T x T`` mask written out (causal; the window also ``t - s <
  512``); ``o = (A_1 - lam A_2) [v_1 ; v_2]``, ``RMSNorm_128(o) (1 -
  lam_init)``, ``W_o`` with its bias. The full layer hands its ``k, v`` on.
- memory unit: ``(silu(a W_1) * m) W_2``; cross attention: queries only,
  the full layer's keys and values, causal.

No cache, no ring, no chunking over positions: whole episodes go through at
once, in blocks of envs so that it fits (each layer recomputed in the
backward, which changes no value). Everything is float32 under
``jax.default_matmul_precision("highest")`` and imports nothing of the
program. ``lower`` (``fp8``) puts the matrix operands in float8, the
control's precision. Returns, clip, Adam and the lowered operands are
``reference/ba3c.py``'s; the game is ``reference/recall.py``'s.
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
)
from benchmark.reference.lfm2_moe import (
    _block_rows,
    _blocks,
    _play,
    _spec_key,
    initial_env,
)

__all__ = ["ADAM_B1", "spec_of", "init_params", "forward", "follow_updates",
           "logits_of"]

VALUE_INIT_SCALE = 0.01
LAMBDA_INIT_SCALE = 0.1
DT_MIN, DT_MAX = 1e-3, 1e-1
MAMBA, WINDOW, FULL, GMU, CROSS = (
    "mamba", "window_attention", "full_attention", "memory_unit",
    "cross_attention")


def kind_of(i: int, n: int) -> str:
    """The mixer of published layer ``i`` of ``n``: a state-space layer every
    second layer up to ``n / 2``, window attention between them; then one
    full-attention layer; then memory units and cross layers by turns."""
    half = n // 2
    if i <= half:
        return MAMBA if i % 2 == 0 else WINDOW
    if i == half + 1:
        return FULL
    return GMU if i % 2 == 0 else CROSS


def spec_of(config: dict) -> dict:
    """What the reference computes with, from the configuration's file."""
    n = config["published"]["num_hidden_layers"]
    held = tuple((i, kind_of(i, n)) for i in config["held"]["layers"])
    ssm = config["state_space"]
    return {
        "d": config["hidden_size"], "f": config["intermediate_size"],
        "heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "window": config["sliding_window"], "eps": config["layer_norm_eps"],
        "c": ssm["d_inner"], "n": ssm["d_state"], "taps": ssm["d_conv"],
        "dt_rank": ssm["dt_rank"], "ids": config["vocab_size"],
        "layers": held, "memory_layer": n // 2,
    }


def init_params(key, spec: dict):
    """Seeded float32 weights, ``{layer: {leaf: array}}``: normal kernels
    scaled by 1/sqrt(fan_in), unit gains, zero biases; ``A_log`` the
    family's ``log(1 .. n)``, ``D`` ones, ``dt_bias`` the inverse softplus
    of step sizes log-uniform in [1e-3, 1e-1]; the four vectors of ``lam``
    normal at 0.1. The benchmark hands the same tree to the program."""
    d, f, c, n = spec["d"], spec["f"], spec["c"], spec["n"]
    D = spec["head_dim"]
    hq, hkv = spec["heads"] * D, spec["kv_heads"] * D
    keys = iter(jax.random.split(key, 16 * len(spec["layers"]) + 4))

    def normal(shape, fan_in):
        return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)

    ones = functools.partial(jnp.ones, dtype=jnp.float32)
    zeros = functools.partial(jnp.zeros, dtype=jnp.float32)
    params = {"embed": {"table": normal((spec["ids"], d), d)}}
    for i, kind in spec["layers"]:
        p = {"mix_norm": ones((d,)), "mix_norm_b": zeros((d,)),
             "ffn_norm": ones((d,)), "ffn_norm_b": zeros((d,)),
             "w_gate": normal((d, f), d), "w_up": normal((d, f), d),
             "w_down": normal((f, d), f)}
        if kind == MAMBA:
            step = jnp.exp(
                jax.random.uniform(next(keys), (c,), jnp.float32)
                * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
            p["in_proj"] = normal((d, 2 * c), d)
            p["conv_w"] = normal((spec["taps"], c), spec["taps"])
            p["conv_b"] = zeros((c,))
            p["x_proj"] = normal((c, spec["dt_rank"] + 2 * n), c)
            p["dt_proj"] = normal((spec["dt_rank"], c), spec["dt_rank"])
            p["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
            p["A_log"] = jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (c, n))
            p["D"] = ones((c,))
            p["out_proj"] = normal((c, d), c)
        elif kind == GMU:
            p["gmu_in"], p["gmu_out"] = normal((d, c), d), normal((c, d), c)
        else:
            if kind == CROSS:
                p["wq"], p["bq"] = normal((d, hq), d), zeros((hq,))
            else:
                p["wqkv"] = normal((d, hq + 2 * hkv), d)
                p["bqkv"] = zeros((hq + 2 * hkv,))
            p["wo"], p["bo"] = normal((hq, d), hq), zeros((d,))
            p["sub_norm"] = ones((2 * D,))
            for name in ("lam_q1", "lam_k1", "lam_q2", "lam_k2"):
                p[name] = LAMBDA_INIT_SCALE * jax.random.normal(
                    next(keys), (D,), jnp.float32)
        params[f"layer_{i}"] = p
    params["final"] = {"norm": ones((d,)), "norm_b": zeros((d,))}
    params["value"] = {"kernel": VALUE_INIT_SCALE * normal((d, 1), d),
                       "bias": zeros((1,))}
    return params


def _ln(x, gain, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gain + bias


def _silu(x):
    return x / (1.0 + jnp.exp(-x))


def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _mamba(p, a, spec, q):
    """-> (the mixer's output [B, T, d], y [B, T, c] before the gate)."""
    c, n, R = spec["c"], spec["n"], spec["dt_rank"]
    T = a.shape[1]
    u, z = jnp.split(q(a) @ q(p["in_proj"]), 2, axis=-1)
    conv = p["conv_b"] + p["conv_w"][0] * u
    for lag in range(1, spec["taps"]):
        back = jnp.pad(u, ((0, 0), (lag, 0), (0, 0)))[:, :T]
        conv = conv + p["conv_w"][lag] * back
    u = _silu(conv)
    selected = q(u) @ q(p["x_proj"])
    low, B, C = selected[..., :R], selected[..., R:R + n], selected[..., R + n:]
    dt = _softplus(q(low) @ q(p["dt_proj"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])  # [c, n]

    def position(s, at):  # the recurrence, one position
        u_t, dt_t, B_t, C_t = at
        s = (jnp.exp(dt_t[:, :, None] * A) * s
             + (dt_t * u_t)[:, :, None] * B_t[:, None, :])
        return s, jnp.sum(s * C_t[:, None, :], axis=-1) + p["D"] * u_t

    by_time = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
    _, y = jax.lax.scan(
        position, jnp.zeros((a.shape[0], c, n), jnp.float32),
        (by_time(u), by_time(dt), by_time(B), by_time(C)))
    y = by_time(y)
    return q(y * _silu(z)) @ q(p["out_proj"]), y


def _lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def _keys_values(p, a, spec, q):
    """a [B, T, d] -> (queries [B, T, H, D], (k_1, k_2 [B, T, KV/2, D], the
    value pairs [v_1 ; v_2] [B, T, KV/2, 2D]))."""
    B, T, _ = a.shape
    H, KV, D = spec["heads"], spec["kv_heads"], spec["head_dim"]
    qkv = q(a) @ q(p["wqkv"]) + p["bqkv"]
    queries = qkv[..., :H * D].reshape(B, T, H, D)
    k = qkv[..., H * D:(H + KV) * D].reshape(B, T, KV // 2, 2, D)
    v = qkv[..., (H + KV) * D:].reshape(B, T, KV // 2, 2 * D)
    return queries, (k[:, :, :, 0], k[:, :, :, 1], v)


def _diff_attention(p, i, queries, kv, spec, q, window=None):
    """Differential attention of published layer ``i``: queries [B, T, H, D]
    over ``kv`` (another layer's, for a cross layer), causal, and within
    ``window`` positions where given."""
    B, T, H, D = queries.shape
    k1, k2, v = kv
    pairs = queries.reshape(B, T, H // 2, 2, D)
    repeat = (H // 2) // k1.shape[2]  # query pairs a K/V pair serves
    at_q, at_k = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    allowed = at_k <= at_q
    if window is not None:
        allowed = allowed & (at_q - at_k < window)

    def softmax_of(q_j, k_j):
        k_j = jnp.repeat(k_j, repeat, axis=2)  # pair h reads K/V pair h // repeat
        scores = jnp.einsum("bqhd,bshd->bhqs", q(q_j), q(k_j)) / math.sqrt(D)
        scores = jnp.where(allowed[None, None], scores, -jnp.inf)
        scores = scores - jnp.max(scores, axis=-1, keepdims=True)
        e = jnp.exp(scores)
        return e / jnp.sum(e, axis=-1, keepdims=True)

    start = _lambda_init(i)
    lam = (jnp.exp(jnp.sum(p["lam_q1"] * p["lam_k1"]))
           - jnp.exp(jnp.sum(p["lam_q2"] * p["lam_k2"])) + start)
    a1 = softmax_of(pairs[:, :, :, 0], k1)
    a2 = softmax_of(pairs[:, :, :, 1], k2)
    o = jnp.einsum("bhqs,bshe->bqhe", q(a1) - lam * q(a2),
                   q(jnp.repeat(v, repeat, axis=2)))
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + spec["eps"])
    o = o * p["sub_norm"] * (1.0 - start)
    return q(o.reshape(B, T, H * D)) @ q(p["wo"]) + p["bo"]


def _layer(i, kind, spec, lower, p, x, memory, shared):
    q = LOWER[lower]
    a = _ln(x, p["mix_norm"], p["mix_norm_b"], spec["eps"])
    if kind == MAMBA:
        mixed, y = _mamba(p, a, spec, q)
        if i == spec["memory_layer"]:
            memory = y
    elif kind == GMU:
        mixed = q(_silu(q(a) @ q(p["gmu_in"])) * memory) @ q(p["gmu_out"])
    elif kind == CROSS:
        B, T, _ = a.shape
        queries = (q(a) @ q(p["wq"]) + p["bq"]).reshape(
            B, T, spec["heads"], spec["head_dim"])
        mixed = _diff_attention(p, i, queries, shared, spec, q)
    else:
        queries, kv = _keys_values(p, a, spec, q)
        mixed = _diff_attention(
            p, i, queries, kv, spec, q,
            window=spec["window"] if kind == WINDOW else None)
        if kind == FULL:
            shared = kv
    h = x + mixed
    z = _ln(h, p["ffn_norm"], p["ffn_norm_b"], spec["eps"])
    y = q(_silu(q(z) @ q(p["w_gate"])) * (q(z) @ q(p["w_up"]))) @ q(p["w_down"])
    return h + y, memory, shared


def forward(params, tokens, spec, lower=None):
    """tokens int32 [B, T], whole episodes from their first step ->
    (logits [B, T, ids], value [B, T])."""
    q = LOWER[lower]
    x = params["embed"]["table"][tokens]
    memory, shared = None, None
    for i, kind in spec["layers"]:
        layer = jax.checkpoint(functools.partial(_layer, i, kind, spec, lower))
        x, memory, shared = layer(params[f"layer_{i}"], x, memory, shared)
    final = params["final"]
    h = _ln(x, final["norm"], final["norm_b"], spec["eps"])
    logits = q(h) @ q(params["embed"]["table"]).T
    value = (h @ params["value"]["kernel"])[..., 0] + params["value"]["bias"][0]
    return logits, value


def _returns(rewards, dones, gamma):
    """[T, B] rewards and done flags -> [T, B] returns, 0 beyond the end."""

    def back(acc, step):
        reward, done = step
        acc = reward + gamma * (1.0 - done) * acc
        return acc, acc

    _, out = jax.lax.scan(
        back, jnp.zeros_like(rewards[0]), (rewards, dones), reverse=True)
    return out


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


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _finish(params, grads, mu, nu, count, n, hyper):
    grads = clip_by_global_norm(
        jax.tree_util.tree_map(lambda g: g / n, grads), hyper["grad_clip_norm"])
    params, mu, nu = adam_update(
        params, grads, mu, nu, count, hyper["learning_rate"], hyper["adam_epsilon"])
    return params, mu, nu, grads


def follow_updates(params, env_key, shard_keys, n_envs, spec, hyper, n_updates,
                   actions, prompt, lower=None, block_envs=2):
    """Follow a fused A2C run on the recall game through its first updates,
    playing ``actions[update]`` ([shards, T, envs a shard] int32, a whole
    episode each) in place of draws of its own. Env ``i`` belongs to shard
    ``i // (n_envs / shards)``. -> what ``reference/ba3c.py``'s gives:
    ``losses``, ``first_grad``, ``delta``, ``states``, ``action_margin``,
    ``action_flips``; the two trees come back as host arrays (697 M
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
            if mu is None:  # not before the gradient's pass: 5.6 GB
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


def logits_of(params, tokens, spec, lower=None, block_envs=2):
    """The forward alone over ``tokens`` [B, T]: logits [B, T, ids]."""
    with jax.default_matmul_precision("highest"):
        return _logits_of(params, tokens, _spec_key(spec), lower, block_envs)
