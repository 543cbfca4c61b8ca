"""The gated delta rule of a linear-attention layer (arXiv:2412.06464), on a
float32 matrix state a head.

For a head with keys of ``K`` and values of ``V`` numbers, over positions
``t``, with a gate ``alpha_t`` in (0, 1], a step size ``beta_t`` in (0, 2)
and ``|k_t| = 1``:

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``S`` lies ``[K, V]``, keys on the rows (the transpose of the paper's
``[V, K]``, so that ``q S`` and ``k^T u`` are plain products). The rule
erases what the state held along ``k_t`` (all of it at ``beta = 1``, its
mirror image past that: the negative eigenvalue) and writes ``v_t`` there.
Written as ``S_t = alpha_t S_{t-1} + k_t u_t^T`` with the pseudo-value
``u_t = beta_t (v_t - alpha_t S_{t-1}^T k_t)``. Two forms, one recurrence:

- :func:`delta_step`: one position from a state, the rollout's decode step:
  elementwise products and sums in float32 (no matrix unit: it would round
  its operands);
- :func:`delta_chunked`: whole sequences from the zero state in chunks of
  ``C`` positions, the learner's (the WY / UT-transform form of
  arXiv:2406.06484 with the gate's cumulative products). Inside a chunk
  that opens on the state ``S_0``, with ``g_i = prod_{j<=i} alpha_j``, the
  pseudo-values solve a unit lower-triangular system,

      (I + A) U = diag(beta) V - diag(beta g) K S_0,
      A[i, j] = beta_i (g_i / g_j) (k_i . k_j)   for j < i,

  so ``U = U_0 - W S_0`` with ``[U_0 | W] = (I + A)^-1 [beta V | beta g K]``,
  one triangular solve a chunk that needs no state and runs for every chunk
  at once. What is left is a scan over the chunks with three small products
  a step:

      U   = U_0 - W S_0
      O   = diag(g) Q S_0 + (Q K^T * (g_i / g_j) * [j <= i]) U
      S_C = g_C S_0 + (diag(g_C / g) K)^T U

  Each step of that scan is a ``jax.checkpoint``: the backward keeps the
  state at the chunk boundaries (``T / C`` states a head, as
  ``ops/ssm.py:selective_scan`` does) and never ``[T, K, V]`` a head (151 MB
  a layer an env in float32 at 2,048 positions of 10 heads of 96 x 192).
  Every ratio ``g_i / g_j`` is the exponential of a sum of logarithms under
  its mask, so a gate near 0 underflows to 0 and nothing overflows.

Everything is float32 and every product is at the highest precision: the
state is the layer's memory of the whole episode, and the rule's products
are a hundredth of the layer's (a head's ``[64, 96]`` against the
projections' ``[3840, 3840]``). Plain ``jax.numpy``: no kernel yet;
``benchmark/layer_metrics/delta_rule_roofline.py`` is its yardstick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: positions a chunk of the sequence form takes
CHUNK = 64
#: the least gate the sequence form tells from 0: its logarithm has to be
#: finite for the sums of logarithms to be
LEAST_GATE = 1e-37

_HIGHEST = jax.lax.Precision.HIGHEST


def delta_step(S, q, k, v, alpha, beta):
    """One position. ``S`` [b, h, K, V]; ``q``, ``k`` [b, h, K]; ``v`` [b, h,
    V]; ``alpha``, ``beta`` [b, h] -> (S, o [b, h, V] float32). ``S`` keeps
    its type (a control keeps it in bfloat16); the arithmetic is float32."""
    kept = alpha[..., None, None] * S.astype(jnp.float32)
    u = beta[..., None] * (v - jnp.sum(kept * k[..., None], axis=-2))
    new = kept + k[..., None] * u[..., None, :]
    o = jnp.sum(new * q[..., None], axis=-2)
    return new.astype(S.dtype), o


def _chunk_step(state_dtype, S, xs):
    """A chunk from the state it opens on. ``S`` [b, h, K, V]; per chunk
    ``u0`` [b, h, C, V], ``w``, ``q_in``, ``k_out`` [b, h, C, K], ``qk`` [b,
    h, C, C], ``g_last`` [b, h] -> (S after the chunk, kept in
    ``state_dtype`` between chunks; o [b, h, C, V])."""
    u0, w, q_in, k_out, qk, g_last = xs
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, precision=_HIGHEST)  # noqa: E731
    u = u0 - dot("bhck,bhkv->bhcv", w, S)
    o = dot("bhck,bhkv->bhcv", q_in, S) + dot("bhcj,bhjv->bhcv", qk, u)
    S = g_last[..., None, None] * S + dot("bhck,bhcv->bhkv", k_out, u)
    return S.astype(state_dtype).astype(S.dtype), o


def delta_chunked(q, k, v, alpha, beta, chunk: int = CHUNK,
                  state_dtype=jnp.float32):
    """Whole sequences from the zero state. ``q``, ``k`` [b, T, h, K]; ``v``
    [b, T, h, V]; ``alpha``, ``beta`` [b, T, h], float32 -> (o [b, T, h, V],
    the state after the last position [b, h, K, V]). ``state_dtype``: what
    the state is kept in between chunks (float32; a control's bfloat16)."""
    b, T, h, K = q.shape
    C = min(chunk, T)
    pad = -T % C
    if pad:  # positions that leave the state as it is: alpha 1, beta 0
        rows = lambda x, fill=0.0: jnp.pad(  # noqa: E731
            x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
            constant_values=fill)
        q, k, v, alpha, beta = rows(q), rows(k), rows(v), rows(alpha, 1.0), rows(beta)
    N = (T + pad) // C

    def by_chunk(x):  # [b, T, h, ...] -> [b, h, N, C, ...]
        x = x.reshape(b, N, C, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, alpha, beta = (by_chunk(x) for x in (q, k, v, alpha, beta))
    log_alpha = jnp.log(jnp.maximum(alpha, LEAST_GATE))
    at = jnp.arange(C)
    below = at[:, None] > at[None, :]
    # g_i / g_j for j <= i, 0 elsewhere: the exponential of sum_{j < l <= i}
    # log alpha_l, each sum accumulated from its own j (the difference of two
    # cumulative sums from the chunk's start would lose a gate near 1 that
    # follows one near 0)
    ratio = jnp.exp(jnp.where(
        at[:, None] >= at[None, :],
        jnp.cumsum(jnp.where(below, log_alpha[..., :, None], 0.0), axis=-2),
        -jnp.inf))
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, precision=_HIGHEST)  # noqa: E731
    A = jnp.where(
        below, beta[..., None] * ratio * dot("...ik,...jk->...ij", k, k), 0.0)
    g = jnp.exp(jnp.cumsum(log_alpha, axis=-1))[..., None]
    # (I + A) [U_0 | W] = [beta V | beta g K]: the solve takes the diagonal as 1
    solved = jax.lax.linalg.triangular_solve(
        A, jnp.concatenate([beta[..., None] * v, (beta[..., None] * g) * k], -1),
        left_side=True, lower=True, unit_diagonal=True)
    u0, w = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    qk = ratio * dot("...ik,...jk->...ij", q, k)
    g_last = g[..., -1, 0]
    k_out = ratio[..., -1, :, None] * k  # g_C / g_i

    by_time = lambda x: jnp.moveaxis(x, 2, 0)  # chunks lead the scan  # noqa: E731
    # zeros that vary as the inputs do (under shard_map a constant would be
    # typed as the same on every shard, and the scan's carry is not)
    state = (jnp.zeros_like(k[:, :, 0, 0])[..., :, None]
             * jnp.zeros_like(v[:, :, 0, 0])[..., None, :])
    state, o = jax.lax.scan(
        jax.checkpoint(functools.partial(_chunk_step, state_dtype)), state,
        tuple(by_time(x) for x in (u0, w, g * q, k_out, qk, g_last)))
    o = jnp.moveaxis(o, 0, 2)                      # [b, h, N, C, V]
    o = jnp.moveaxis(o, 1, 3).reshape(b, N * C, h, -1)
    return o[:, :T], state
