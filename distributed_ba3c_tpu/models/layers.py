"""Reusable layers mirroring the reference's model zoo where flax lacks them.

Reference equivalent: ``tensorpack/models/nonlin.py`` (PReLU) and friends
(SURVEY.md §2.6 #17). Conv/Dense/Pooling come from flax.linen directly — we do
not re-wrap what the library already expresses idiomatically.

Below ``PReLU``: what the token-sequence policies share as plain functions
of arrays (what they share as policies, from the cut lookup to the unroll's
skeleton, is models/sequence.py's). Their parameters are float32 trees
``{layer: {leaf: array}}``; matrices multiply in the compute type
(bfloat16) with float32 accumulation.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope


class PReLU(nn.Module):
    """Parametric ReLU with a single learnable slope (tensorpack default).

    tensorpack's ``PReLU`` initialises alpha to 0.001 and shares it across the
    whole activation map; we keep that so the flagship model matches the
    reference architecture knob-for-knob.
    """

    init_alpha: float = 0.001

    @nn.compact
    def __call__(self, x):
        alpha = self.param(
            "alpha", lambda _key, shape: jnp.full(shape, self.init_alpha, jnp.float32), ()
        )
        alpha = alpha.astype(x.dtype)
        return jnp.where(x >= 0, x, alpha * x)


# -- shared by the token-sequence policies ------------------------------------
def mm(x, w, compute_dtype, out_dtype=None):
    """``x @ w`` with both operands in the compute type, accumulated in
    float32, given out in ``out_dtype`` (the compute type if None)."""
    return jnp.dot(x.astype(compute_dtype), w.astype(compute_dtype),
                   preferred_element_type=out_dtype or compute_dtype)


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def layer_norm(x, gain, bias, eps):
    x = x.astype(jnp.float32)
    centred = x - jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(centred * centred, -1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * gain + bias


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float):
    """YaRN's ``dim / 2`` rotary frequencies (arXiv:2309.00071, as the
    DeepSeek-V3 family computes them): ``theta^(-2i/dim)`` where a frequency
    turns more than ``beta_fast`` times within the ``original`` positions,
    that over ``factor`` where it turns fewer than ``beta_slow`` times, and
    a linear ramp between the two dimensions where those counts fall. A
    constant of the shapes, float32."""
    extrapolated = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def dimension_of(turns):  # the dimension that turns so often in `original`
        return dim * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dimension_of(beta_fast)), 0)
    high = min(math.ceil(dimension_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # the family's own guard against a ramp of no width
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return jnp.asarray(
        extrapolated / factor * ramp + extrapolated * (1.0 - ramp), jnp.float32)


def yarn_attention_scale(factor: float, mscale_all_dim: float) -> float:
    """What YaRN multiplies the softmax's scale by (the DeepSeek-V3 family's
    rule under ``rope_scaling``): ``m^2``, ``m = 0.1 mscale_all_dim
    ln(factor) + 1``."""
    m = 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return m * m


def rope(x, positions, theta, inv_freq=None):
    """Rotate-half RoPE over the whole head. ``x`` [..., T, H, D] float32,
    ``positions`` broadcastable to [..., T]. ``inv_freq`` [D / 2]: the
    frequencies where they are not ``theta``'s own (:func:`yarn_inv_freq`)."""
    half = x.shape[-1] // 2
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[..., None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def swiglu(z, w_gate, w_up, w_down, compute_dtype):
    """``W_down (silu(W_gate z) * W_up z)``: z [N, d] float32 -> [N, d]
    float32. ``gate`` and ``up`` leave their products in the compute type."""
    gate = mm(z, w_gate, compute_dtype).astype(jnp.float32)
    up = mm(z, w_up, compute_dtype).astype(jnp.float32)
    return mm(jax.nn.silu(gate) * up, w_down, compute_dtype, jnp.float32)


def attend(q, k, v, mask, compute_dtype, scale=None):
    """Masked grouped-query attention. q [B, Tq, H, D], k [B, Tk, KV, D],
    v [B, Tk, KV, Dv], mask [B or 1, Tq, Tk] -> [B, Tq, H * Dv] float32; one
    KV head serves H / KV query heads; ``scale`` is 1/sqrt(D) if None."""
    B, Tq, H, D = q.shape
    KV = k.shape[2]
    q = q.reshape(B, Tq, KV, H // KV, D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(D) if scale is None else scores * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(compute_dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Tq, -1)


def causal_conv(taps, u):
    """A depthwise causal convolution over whole episodes, zero before the
    episode: ``taps`` [n, c] (``taps[k]`` weighs the input ``k`` back), ``u``
    [B, T, c] -> ``sum_k taps[k] u_{t-k}`` [B, T, c]. A bias is the caller's
    to add, on the side of the sum its policy adds it."""
    n, T = taps.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(taps[k] * padded[:, n - 1 - k:n - 1 - k + T] for k in range(n))


def conv_step(first, taps, u, tail):
    """The same convolution a position at a time: ``first`` is the caller's
    ``taps[0] * u`` (with its bias, where it has one, on the side its policy
    adds it), ``tail`` [B, n - 1, c] the last inputs, the newest first ->
    (``first + sum_{k >= 1} taps[k] u_{t-k}`` [B, c], the next tail)."""
    conv = first + sum(
        taps[k] * tail[:, k - 1] for k in range(1, taps.shape[0]))
    return conv, jnp.concatenate([u[:, None], tail[:, :-1]], 1)


def embed_rows(table, tokens, compute_dtype):
    """The held embedding rows of ``tokens``, rounded to the compute type
    whichever table they are read from (float32 in the learner, the
    rollout's snapshot): one value."""
    with device_scope(profiling.EMBED):
        return table[tokens].astype(compute_dtype).astype(jnp.float32)


def tied_head(h, table, value_params, compute_dtype):
    """h [N, d] float32, already through the final norm -> (logits over the
    vocabulary ids held here: ``table``'s rows times ``h``; the trainer's
    float32 value head on the same ``h``). ``table`` [ids, d] is the
    embedding where the head is tied, a head's own rows where it is not."""
    logits = jnp.dot(
        h.astype(compute_dtype), table.astype(compute_dtype).T,
        preferred_element_type=jnp.float32,
    )
    value = jnp.dot(
        h, value_params["kernel"], precision=jax.lax.Precision.HIGHEST,
    )[:, 0] + value_params["bias"][0]
    return logits, value


def matrices_in(params, compute_dtype, keep=()):
    """The rollout's snapshot: every leaf of two or more dimensions in the
    compute type, so that a decode step reads 2 bytes a weight and not 4.
    Vectors, the leaves named in ``keep`` and the value head stay float32."""

    def cast(layer, leaves):
        if layer == "value":
            return leaves
        return {k: (v.astype(compute_dtype)
                    if v.ndim >= 2 and k not in keep else v)
                for k, v in leaves.items()}

    return {layer: cast(layer, leaves) for layer, leaves in params.items()}
