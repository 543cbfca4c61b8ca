"""A routed-expert feed-forward layer that is told which experts it holds.

The layer of a sparse-expert language model, as one of the chips that share
it runs it (expert parallelism's own arithmetic, ROADMAP A3):

    s = sigmoid(z W_r)                       float32, all ``num_experts``
    chosen = top-k of (s + bias)             the bias only chooses
    w = s[chosen] / (sum s[chosen] + 1e-6) * scale
    out = sum_i w_i W2_i (silu(W1_i z) * W3_i z)     over the chosen i HELD here

``expert_offset`` and the leading axis of ``w1``/``w3``/``w2`` say which
experts live here: ids ``[offset, offset + held)``. Routing is over all of
them; an assignment to an absent expert adds nothing here (on its own chip
it would; the chips' parts sum to the whole layer, tests/test_lfm2_moe.py).
No token is dropped and there is no capacity: the ``N * k`` assignments are
sorted by expert, the held ones first, and the grouped products
(``jax.lax.ragged_dot``, which the TPU compiler lowers to one grouped-matmul
kernel that visits only the tiles the groups cover) run over exactly the
rows routed here, however uneven.

Backward: ``ragged_dot`` has its own transpose; the two permutations are
gathers both ways (``_permute``), so no scatter-add is traced.

A decode step has few tokens (128 an update's rollout step): there every
product is bound by reading the experts' matrices, whichever rows it
computes, and the grouped kernel takes three times that read (PERF.md, PR
26: 218 us a product of 59 MB). So at or under ``DENSE_ROWS`` tokens every
held expert computes every token (a batched product that streams each
matrix once) and the router's weights, zero where an expert was not
chosen, pick the result: the same sum, no sort and no gather.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

NORM_EPS = 1e-6  # the ``+ 1e-6`` of norm_topk_prob
#: tokens at or under which the held experts compute every token: under the
#: v5e's 240 FLOP a byte a bfloat16 product of so few rows is bound by its
#: matrix's bytes, so the rows nobody routed here cost no time
DENSE_ROWS = 256


class Routing(NamedTuple):
    experts: jax.Array  # [N, k] int32 ids over ALL experts
    weights: jax.Array  # [N, k] float32 combine weights


def route(z, router_w, expert_bias, top_k: int, norm_topk_prob: bool = True,
          scale: float = 1.0) -> Routing:
    """Sigmoid scores in float32 (``z`` [N, d] float32, ``router_w``
    [d, E]); the bias moves the choice and never the weight."""
    with device_scope(profiling.MOE_ROUTER):
        scores = jax.nn.sigmoid(jnp.dot(
            z.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ))
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(expert_bias)[None, :], top_k
        )
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk_prob:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + NORM_EPS)
        return Routing(experts.astype(jnp.int32), weights * scale)


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation of the rows; the cotangent goes back by
    the inverse permutation, a gather too."""
    del inverse
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(res, g):
    _, inverse = res
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def held_counts(experts, expert_offset: int, held: int, num_experts: int):
    """(local ids [N*k] with the held experts at 0..held-1 and the absent
    ones above, tokens routed to each held expert [held] int32)."""
    local = (experts.reshape(-1) - expert_offset) % num_experts
    counts = jnp.sum(
        (local[:, None] == jnp.arange(held, dtype=local.dtype)[None, :]),
        axis=0, dtype=jnp.int32,
    )
    return local, counts


def expert_ffn(z, routing: Routing, w1, w3, w2, expert_offset: int,
               num_experts: int):
    """This chip's part of the routed feed-forward.

    ``z`` [N, d] in the compute type; ``w1``/``w3`` [held, d, f] and ``w2``
    [held, f, d] in the compute type. -> (out [N, d] float32, tokens routed
    to each held expert [held] int32)."""
    n, d = z.shape
    k = routing.experts.shape[1]
    held = w1.shape[0]
    if n <= DENSE_ROWS:
        return _every_token(z, routing, w1, w3, w2, expert_offset, num_experts)
    with device_scope(profiling.MOE_DISPATCH):
        local, counts = held_counts(
            routing.experts, expert_offset, held, num_experts
        )
        order = jnp.argsort(local, stable=True)  # held experts' rows first
        inverse = jnp.argsort(order)
        here = jnp.arange(n * k) < jnp.sum(counts)  # rows of a held expert
        rows = _permute(jnp.repeat(z, k, axis=0), order, inverse)
        # the grouped products say nothing about rows outside every group:
        # hold them at zero on the way in (so nothing comes back through
        # them) and on the way out
        rows = jnp.where(here[:, None], rows, 0)
    with device_scope(profiling.MOE_EXPERTS):
        gate = jax.lax.ragged_dot(rows, w1, counts)
        up = jax.lax.ragged_dot(rows, w3, counts)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(z.dtype)
        y = jax.lax.ragged_dot(act, w2, counts)
    with device_scope(profiling.MOE_COMBINE):
        # the rows go back in the compute type (half the bytes of the
        # gather); the weighted sum over a token's k experts is float32
        y = jnp.where(here[:, None], y, 0)
        y = _permute(y, inverse, order).reshape(n, k, d)
        out = jnp.sum(
            y.astype(jnp.float32) * routing.weights[:, :, None], axis=1)
    return out, counts


def _every_token(z, routing: Routing, w1, w3, w2, expert_offset: int,
                 num_experts: int):
    """``expert_ffn`` for few tokens: each held expert computes them all."""
    held = w1.shape[0]
    with device_scope(profiling.MOE_DISPATCH):
        _, counts = held_counts(
            routing.experts, expert_offset, held, num_experts)
        ids = expert_offset + jnp.arange(held, dtype=routing.experts.dtype)
        # [n, held]: what the router gave expert e for token n, 0 if unchosen
        share = jnp.sum(
            jnp.where(routing.experts[:, :, None] == ids[None, None, :],
                      routing.weights[:, :, None], 0.0), axis=1)
    with device_scope(profiling.MOE_EXPERTS):
        gate = jnp.einsum("nd,edf->enf", z, w1)
        up = jnp.einsum("nd,edf->enf", z, w3)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(z.dtype)
        y = jnp.einsum("enf,efd->end", act, w2)
    with device_scope(profiling.MOE_COMBINE):
        out = jnp.einsum("end,ne->nd", y.astype(jnp.float32), share)
    return out, counts
