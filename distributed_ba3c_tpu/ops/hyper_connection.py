"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880 over
hyper-connections, arXiv:2409.19606): the residual path of a policy that
keeps ``n`` residual streams where the others keep one.

A sub-block ``f`` (an attention, a feed-forward) does not see ``x + f(
norm(x))``. A token's ``n`` streams ``X`` in ``R^{n x d}`` are read through
a learned mixture, written back into all ``n``, and mixed among themselves
by a matrix that is projected onto the doubly stochastic ones, all three
computed from the token's own streams:

    v' = vec(X) / sqrt(mean(vec(X)^2) + eps)                   no gain
    [p | o | r] = v' Phi                                        n + n + n^2
    H_pre  = sigmoid(a_pre p + b_pre)              [n]   read weights
    H_post = 2 sigmoid(a_post o + b_post)          [n]   write weights
    R      = clip(a_res mat(r) + b_res, lo, hi)    [n, n]
    M = exp(R);  iters times:  M <- M / (colsum(M) + eps);  M <- M / (rowsum(M) + eps)
    H_res = M                                      rows then sum to 1, columns nearly
    u = sum_j H_pre[j] X[j];   y = f(norm(u))
    X[i] <- sum_j H_res[i, j] X[j] + H_post[i] y

:func:`mappings` gives the three, :func:`read` the sub-block's input,
:func:`write` the streams after it. Everything is float32; ``v' Phi`` runs
at the highest precision (24 columns: the cost is reading ``X``).

**How the numbers lie.** The streams are a tuple of ``n`` arrays ``[N,
d]``, never one array: as ``[N, n, d]`` a float32 tile's 8 sublanes would
hold 4 streams and as many rows of padding, in HBM and in every pass over
it; side by side as ``[N, n d]`` reading a stream is a slice and writing the
four a concatenation, whose backward pads every slice back to the full
width (the step's learner then held 2.9 GB of activations an env and did
not fit the chip). ``vec(X) Phi`` is the sum of the streams' own products
with their columns of ``Phi``. The mappings lie with the tokens LAST (``[n,
N]``, ``[n, n, N]``): 4 x 4 numbers a token as the last two dimensions would
be one padded tile a token, 64 times their bytes, through 40 normalisations
and their backward. ``phi`` is ``[n + n + n^2, n d]`` for the same reason
(24 columns of 128 lanes otherwise).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Mappings(NamedTuple):
    """A sub-block's three mappings of ``N`` tokens, the tokens last."""

    pre: jax.Array   # [n, N] float32, non-negative: the read weights
    post: jax.Array  # [n, N] float32, non-negative: the write weights
    res: jax.Array   # [n, n, N] float32: res[i, j] weighs stream j into i


def sinkhorn(logits, iters: int, eps: float):
    """``exp(logits)`` [n, n, N] through ``iters`` rounds of (columns to sum
    1, rows to sum 1): the paper's ``T_r(T_c(.))``, ``eps`` in each
    denominator."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def mappings(streams, p, iters: int, eps: float, clamp,
             dtype=jnp.float32) -> Mappings:
    """``streams``: n arrays [N, d]; a sub-block's leaves ``p`` (``phi`` [2 n
    + n^2, n d]; ``alpha`` [3]: ``a_pre``, ``a_post``, ``a_res``; ``b_pre``,
    ``b_post`` [n]; ``b_res`` [n, n]) -> :class:`Mappings`. ``clamp`` (lo,
    hi) bounds the mixing logits before the exponential. ``dtype``: what
    all of it is computed in (float32; a control of the benchmark's
    comparison asks for less)."""
    n, d = len(streams), streams[0].shape[-1]
    streams = [s.astype(dtype) for s in streams]
    p = {k: v.astype(dtype) for k, v in p.items()}
    phi = p["phi"].reshape(-1, n, d)
    # v' Phi as (v Phi) / rms(v): the norm is one number a token, and the
    # normed streams are never written (nor kept for the backward)
    proj = sum(jnp.einsum("kd,nd->kn", phi[:, j], s, precision=HIGHEST)
               for j, s in enumerate(streams))
    mean_square = sum(jnp.sum(s * s, -1) for s in streams) / (n * d)
    proj = proj * jax.lax.rsqrt(mean_square + eps)[None, :]
    a_pre, a_post, a_res = p["alpha"]
    pre = jax.nn.sigmoid(a_pre * proj[:n] + p["b_pre"][:, None])
    post = 2.0 * jax.nn.sigmoid(a_post * proj[n:2 * n] + p["b_post"][:, None])
    logits = jnp.clip(
        a_res * proj[2 * n:].reshape(n, n, -1) + p["b_res"][:, :, None], *clamp)
    return Mappings(pre, post, sinkhorn(logits, iters, eps))


def read(streams, h: Mappings):
    """The sub-block's input ``sum_j H_pre[j] X[j]``: n x [N, d] -> [N, d]."""
    return sum(h.pre[j][:, None] * s for j, s in enumerate(streams))


def write(streams, h: Mappings, y):
    """The streams after the sub-block: ``X[i] <- sum_j H_res[i, j] X[j] +
    H_post[i] y``; ``y`` [N, d] -> n x [N, d]."""
    return tuple(
        sum(h.res[i, j][:, None] * s for j, s in enumerate(streams))
        + h.post[i][:, None] * y
        for i in range(len(streams)))


def doubly_stochastic_gap(res):
    """How far ``res`` [n, n, N] is from doubly stochastic, a token: the
    largest ``|row sum - 1|`` or ``|column sum - 1|`` -> [N]."""
    rows = jnp.abs(jnp.sum(res, axis=1) - 1.0)
    cols = jnp.abs(jnp.sum(res, axis=0) - 1.0)
    return jnp.maximum(jnp.max(rows, axis=0), jnp.max(cols, axis=0))
