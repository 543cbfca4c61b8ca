"""Plain float32 reference of BA3CNet and of one fused A2C update.

Written from the architecture (arXiv:1801.02852; Tensorpack train-atari):

    uint8 [B, 84, 84, 4] / 255
    conv 32@5x5 relu pool2 | conv 32@5x5 relu pool2 | conv 64@4x4 relu pool2
    conv 64@3x3 relu | fc 512 + PReLU (one slope) | policy head, value head

and from the algorithm: a T-step rollout of every env under the sampled
policy, n-step returns bootstrapped from the value of the last stack and cut
at episode ends, the loss  -log pi(a|s) (R - V)  +  c/2 (V - R)^2  -  beta H,
all three batch means; gradients clipped to a global norm, then Adam.

Everything is float32 under ``jax.default_matmul_precision("highest")`` and
imports nothing of the program; one whole update (every shard's rollout and
gradient in blocks of rows, the clip, Adam) is one compiled program, so a
new checkout compiles little. ``lower`` puts the matrix operands of the
forward and of the backward in a lower precision: with ``fp8`` that is the
control which the comparison has to fail.
Parameters are a dict ``{layer: {leaf: array}}`` with flax's default names
for that stack (``Conv_0..3``, ``Dense_0..2``, ``PReLU_0``), the one thing
the reference and the program have to agree on to be handed the same
weights.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference import pong

#: (features, kernel, pooled) of the conv stack; fc width; frames per stack
CONVS = ((32, 5, True), (32, 5, True), (64, 4, True), (64, 3, False))
FC_UNITS = 512
FRAMES = 4
IMAGE = 84
PRELU_INIT = 0.001

ADAM_B1, ADAM_B2 = 0.9, 0.999


def init_params(key, num_actions: int = pong.NUM_ACTIONS):
    """Seeded weights: normal kernels scaled by 1/sqrt(fan_in), zero biases.

    The benchmark hands the same tree to the program and to the reference,
    so neither takes weights the other made."""
    params = {}
    c_in, side = FRAMES, IMAGE
    keys = iter(jax.random.split(key, len(CONVS) + 3))
    for i, (feats, k, pooled) in enumerate(CONVS):
        fan_in = k * k * c_in
        params[f"Conv_{i}"] = {
            "kernel": jax.random.normal(next(keys), (k, k, c_in, feats))
            / math.sqrt(fan_in),
            "bias": jnp.zeros((feats,), jnp.float32),
        }
        c_in, side = feats, side // 2 if pooled else side
    flat = side * side * c_in
    for name, n_in, n_out in (
        ("Dense_0", flat, FC_UNITS),
        ("Dense_1", FC_UNITS, num_actions),
        ("Dense_2", FC_UNITS, 1),
    ):
        params[name] = {
            "kernel": jax.random.normal(next(keys), (n_in, n_out))
            / math.sqrt(n_in),
            "bias": jnp.zeros((n_out,), jnp.float32),
        }
    params["PReLU_0"] = {"alpha": jnp.float32(PRELU_INIT)}
    return params


def _round_to(dtype, top):
    """Round through ``dtype`` under a per-tensor scale that puts the
    largest value on ``top``: what a tensor held in that type would carry."""

    def rounded(x):
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return (x / scale).astype(dtype).astype(jnp.float32) * scale

    return rounded


def _lowered(forward_round, backward_round):
    """An operand as a lower precision computes with it: its value rounded
    on the way in, its gradient rounded on the way back."""

    @jax.custom_vjp
    def operand(x):
        return forward_round(x)

    operand.defvjp(
        lambda x: (forward_round(x), None),
        lambda _, g: (backward_round(g),),
    )
    return operand


#: the precisions the forward's and backward's matrix operands may be put in.
#: ``fp8`` (e4m3 values, e5m2 gradients, each under a per-tensor scale) is the
#: step below the configuration's bfloat16: the control.
LOWER = {
    None: lambda x: x,
    "fp8": _lowered(_round_to(jnp.float8_e4m3fn, 448.0),
                    _round_to(jnp.float8_e5m2, 57344.0)),
}


def _patches(x, k):
    """[B, H, W, C] -> [B, H, W, k*k*C]: each pixel's k x k neighbourhood
    under SAME padding, in the order a kernel's [k, k, C] axes flatten."""
    lo = (k - 1) // 2
    h, w = x.shape[1:3]
    x = jnp.pad(x, ((0, 0), (lo, k - 1 - lo), (lo, k - 1 - lo), (0, 0)))
    return jnp.concatenate(
        [x[:, i:i + h, j:j + w, :] for i in range(k) for j in range(k)], -1
    )


def _conv(x, kernel):
    """SAME convolution. Over a few input channels (the frames' 4) it is
    written as patches times a matrix: the same sums, and the TPU's
    compiler takes two minutes less over them in float32."""
    k, _, c_in, c_out = kernel.shape
    if c_in < 8:
        return _patches(x, k) @ kernel.reshape(k * k * c_in, c_out)
    return jax.lax.conv_general_dilated(
        x, kernel, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def forward(params, frames, lower=None):
    """frames uint8 [B, 84, 84, 4] -> (logits [B, A], value [B]), float32."""
    q = LOWER[lower]
    x = frames.astype(jnp.float32) / 255.0
    for i, (_, _, pooled) in enumerate(CONVS):
        layer = params[f"Conv_{i}"]
        x = _conv(q(x), q(layer["kernel"])) + layer["bias"]
        x = jnp.where(x > 0, x, 0.0)  # relu, with no gradient at exactly 0
        if pooled:
            x = jax.lax.reduce_window(
                x, -jnp.inf, jax.lax.max, (1, 2, 2, 1), (1, 2, 2, 1), "VALID"
            )
    x = x.reshape(x.shape[0], -1)
    x = q(x) @ q(params["Dense_0"]["kernel"]) + params["Dense_0"]["bias"]
    x = jnp.where(x >= 0, x, params["PReLU_0"]["alpha"] * x)
    logits = x @ params["Dense_1"]["kernel"] + params["Dense_1"]["bias"]
    value = (x @ params["Dense_2"]["kernel"] + params["Dense_2"]["bias"])[:, 0]
    return logits, value


def _block_size(n, block_rows):
    """The largest divisor of ``n`` that is at most ``block_rows``."""
    return max(d for d in range(1, min(n, block_rows) + 1) if n % d == 0)


def forward_in_blocks(params, frames, lower=None, block_rows=256):
    """``forward`` over blocks of rows: bounds the activations held at once."""
    n = frames.shape[0]
    rows = _block_size(n, block_rows)
    logits, value = jax.lax.map(
        lambda block: forward(params, block, lower),
        frames.reshape(n // rows, rows, *frames.shape[1:]),
    )
    return logits.reshape(n, -1), value.reshape(n)


def n_step_returns(rewards, dones, bootstrap, gamma):
    """[T, B] rewards and done flags -> [T, B] returns, R_T = bootstrap."""
    out, acc = [], bootstrap
    for t in range(rewards.shape[0] - 1, -1, -1):
        acc = rewards[t] + gamma * (1.0 - dones[t]) * acc
        out.append(acc)
    return jnp.stack(out[::-1])


def a2c_loss_sum(params, frames, actions, returns, beta, value_coef, lower):
    """The A2C loss SUMMED over the rows (callers divide by the batch)."""
    logits, value = forward(params, frames, lower)
    logp = jax.nn.log_softmax(logits)
    p = jnp.exp(logp)
    logp_a = jnp.take_along_axis(logp, actions[:, None], axis=1)[:, 0]
    advantage = returns - jax.lax.stop_gradient(value)
    policy = -jnp.sum(logp_a * advantage)
    value_l = 0.5 * jnp.sum(jnp.square(value - returns))
    entropy = -jnp.sum(p * logp)
    return policy + value_coef * value_l - beta * entropy


def _rollout(params, env_state, stack, key, forced, use_forced, lower):
    """One shard's rollout: ``forced.shape[0]`` env-steps of every env.

    ``forced`` ([T, B] int32) are the actions another side took here; where
    ``use_forced`` they are played in place of this side's own draw. ->
    ((env_state, stack, key), (frames, actions played, rewards, dones,
    margins)), each of the latter [T, B, ...]; ``margins`` is how far the
    played action's perturbed logit lies below this side's best (0 where
    this side would have drawn the same action)."""

    def env_step(carry, forced_t):
        env_state, stack, key = carry
        logits, _ = forward_in_blocks(params, stack, lower)
        key, k_act, k_env = jax.random.split(key, 3)
        # a categorical draw is the argmax of the logits plus Gumbel noise
        perturbed = logits + jax.random.gumbel(k_act, logits.shape, logits.dtype)
        own = jnp.argmax(perturbed, axis=-1).astype(jnp.int32)
        actions = jnp.where(use_forced, forced_t, own)
        margin = jnp.max(perturbed, axis=-1) - jnp.take_along_axis(
            perturbed, actions[:, None], axis=1)[:, 0]
        env_keys = jax.random.split(k_env, stack.shape[0])
        env_state, frame, reward, done = jax.vmap(pong.step)(
            env_state, actions, env_keys
        )
        # a finished episode's frames do not reach into the next one
        keep = (~done).astype(stack.dtype)[:, None, None, None]
        new_stack = jnp.concatenate([stack[..., 1:] * keep, frame[..., None]], -1)
        return (env_state, new_stack, key), (
            stack, actions, reward, done.astype(jnp.float32), margin)

    return jax.lax.scan(env_step, (env_state, stack, key), forced)


@functools.partial(jax.jit, static_argnames=("n_envs",))
def initial_env(key, n_envs):
    """Env batch and frame stacks as a run starts them from ``key``."""
    env_state = jax.vmap(pong.reset)(jax.random.split(key, n_envs))
    frame = jax.vmap(pong.render)(env_state)
    stack = jnp.zeros((n_envs, IMAGE, IMAGE, FRAMES), jnp.uint8)
    return env_state, stack.at[..., -1].set(frame)


def shard_gradient(params, env_state, stack, key, forced, use_forced, hyper,
                   lower=None, block_rows=256):
    """One shard's rollout and the mean loss and gradient over its batch.

    -> (loss, grads, env_state, stack, key, actions [T, B], margins [T, B]).
    ``block_rows`` only bounds the activations held at once; the sums are
    over the whole batch."""
    (env_state, stack, key), (frames, actions, rewards, dones, margins) = _rollout(
        params, env_state, stack, key, forced, use_forced, lower
    )
    returns = n_step_returns(
        rewards, dones, forward_in_blocks(params, stack, lower)[1], hyper["gamma"]
    )
    n = actions.size
    rows = _block_size(n, block_rows)

    def blocks(x):
        return x.reshape(n // rows, rows, *x.shape[2:])

    def add_block(acc, block):
        loss, grads = jax.value_and_grad(a2c_loss_sum)(
            params, *block, hyper["entropy_beta"], hyper["value_loss_coef"], lower
        )
        return (acc[0] + loss, jax.tree_util.tree_map(jnp.add, acc[1], grads)), None

    zero = (jnp.float32(0.0), jax.tree_util.tree_map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(
        add_block, zero, (blocks(frames), blocks(actions), blocks(returns))
    )
    grads = jax.tree_util.tree_map(lambda g: g / n, grads)
    return loss / n, grads, env_state, stack, key, actions, margins


def clip_by_global_norm(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)))
    factor = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree_util.tree_map(lambda g: g * factor, grads)


def adam_update(params, grads, mu, nu, count, lr, eps):
    """-> (params, mu, nu) after update number ``count`` (from 1)."""
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
    nu = tm(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
    c1, c2 = 1 - ADAM_B1 ** count, 1 - ADAM_B2 ** count
    params = tm(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu,
    )
    return params, mu, nu


@functools.partial(jax.jit, static_argnames=("lower",))
def _update(params, mu, nu, count, env_state, stack, shard_keys, forced,
            use_forced, hyper, lower):
    """One whole update, one compiled program: every shard's rollout and
    gradient in turn, their mean clipped, Adam. Env ``i`` of the flat batch
    belongs to shard ``i // (n_envs / n_shards)``; ``forced`` is [shards, T,
    envs a shard]."""
    n_shards = shard_keys.shape[0]

    def split(x):
        return x.reshape(n_shards, x.shape[0] // n_shards, *x.shape[1:])

    def join(x):
        return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])

    def one_shard(args):
        return shard_gradient(params, *args, use_forced, hyper, lower)

    loss, grads, env_state, stack, shard_keys, played, margins = jax.lax.map(
        one_shard,
        (jax.tree_util.tree_map(split, env_state), split(stack), shard_keys, forced),
    )
    grads = clip_by_global_norm(
        jax.tree_util.tree_map(lambda g: jnp.mean(g, 0), grads),
        hyper["grad_clip_norm"],
    )
    params, mu, nu = adam_update(
        params, grads, mu, nu, count, hyper["learning_rate"], hyper["adam_epsilon"]
    )
    return (params, mu, nu, jnp.mean(loss), grads,
            jax.tree_util.tree_map(join, env_state), join(stack), shard_keys,
            played, margins)


#: the numbers of ``hyper`` the reference computes with
HYPER = ("gamma", "entropy_beta", "value_loss_coef", "grad_clip_norm",
         "learning_rate", "adam_epsilon")


def follow_updates(params, env_key, shard_keys, n_envs, hyper, n_updates,
                   lower=None, actions=None):
    """Follow a fused A2C run through its first updates.

    ``shard_keys`` ([shards, 2]) are the per-shard random streams; env ``i``
    belongs to shard ``i // (n_envs / n_shards)``. ``actions[update]``
    ([shards, T, envs a shard] int32) are the actions another side took,
    played in place of this side's own draws (as a served model's reference
    is run over the served tokens); None lets this side draw its own. ->
    dict with ``losses`` (one per update), ``first_grad`` (the clipped
    gradient Adam was given in update one), ``delta`` (parameters after the
    last update minus before), ``actions`` (those played), ``states`` (after
    each update, the env batch and its frame stacks, on the host),
    ``action_margin`` (the widest gap by which a played action's perturbed
    logit lay below this side's best) and ``action_flips`` (the share of
    played actions this side would not have drawn)."""
    shard_keys = jnp.asarray(shard_keys)
    numbers = {k: float(hyper[k]) for k in HYPER}
    drawn = jnp.zeros(
        (shard_keys.shape[0], hyper["rollout_len"], n_envs // shard_keys.shape[0]),
        jnp.int32,
    )
    with jax.default_matmul_precision("highest"):
        env_state, stack = initial_env(env_key, n_envs)
        start = params
        mu = jax.tree_util.tree_map(jnp.zeros_like, params)
        nu = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, first_grad, played, margins, states = [], None, [], [], []
        for count in range(1, n_updates + 1):
            forced = drawn if actions is None else jnp.asarray(actions[count - 1])
            (params, mu, nu, loss, grads, env_state, stack, shard_keys, acts,
             margin) = _update(
                params, mu, nu, count, env_state, stack, shard_keys, forced,
                actions is not None, numbers, lower,
            )
            if first_grad is None:
                first_grad = grads
            losses.append(float(loss))
            played.append(jax.device_get(acts))
            margins.append(jax.device_get(margin))
            states.append(jax.device_get((env_state, stack)))
        delta = jax.tree_util.tree_map(jnp.subtract, params, start)
    return {
        "losses": losses, "first_grad": first_grad, "delta": delta,
        "actions": played, "states": states,
        "action_margin": float(max(m.max() for m in margins)),
        "action_flips": float(sum((m > 0).sum() for m in margins)
                              / sum(m.size for m in margins)),
    }
