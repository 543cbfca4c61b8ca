"""A routed-expert feed-forward layer that is told which experts it holds.

The layer of a sparse-expert language model, as one of the chips that share
it runs it (expert parallelism's own arithmetic, ROADMAP A3):

    s = sigmoid(z W_r)                       float32, all ``num_experts``
    chosen = top-k of (s + bias)             the bias only chooses
    w = s[chosen] / (sum s[chosen] + 1e-6) * scale
    out = sum_i w_i W2_i (silu(W1_i z) * W3_i z)     over the chosen i HELD here

That is the ``sigmoid`` scoring (LFM2's: a bias that evens the load out).
**Which expert form runs is read off the operands**: where ``w3`` is None
an expert is ``W2_i relu(W1_i z)^2`` (Nemotron-H's: no gate matrix, two
grouped products forward and not three); everything round the products is
the same. A shared expert that every token takes is the policy's own dense
product (``models/nemotron_h.py``): this layer does not learn of it.
The ``softmax`` scoring (:func:`route` with ``scoring="softmax"``; Qwen3-MoE's
and Keye-VL-2.0's) has no bias: ``s = softmax(z W_r)`` over all
``num_experts``, ``chosen`` the top k of ``s`` itself, ``w = s[chosen] / sum
s[chosen]`` (no ``1e-6``).

``expert_offset`` and the leading axis of ``w1``/``w3``/``w2`` say which
experts live here: ids ``[offset, offset + held)``. Routing is over all of
them; an assignment to an absent expert adds nothing here (on its own chip
it would; the chips' parts sum to the whole layer, tests/test_lfm2_moe.py).
No token is dropped and there is no capacity: the ``N * k`` assignments are
sorted by expert, the held ones first, and the grouped products
(``ops/grouped_matmul.py:grouped_dot``: a Pallas kernel on the TPU,
``jax.lax.ragged_dot`` elsewhere and at widths off whole lanes; either
visits only the tiles the groups cover) run over exactly the rows routed
here, however uneven.

**Blocks.** The kernel skips the rows of absent experts, but everything
round it (the gather in, ``silu * up``, the products' outputs, the masks,
the gather back) would still touch all ``N * k`` sorted rows where a chip
that holds 8 of 32 experts is routed a quarter of them. So the sorted rows
are worked on ``R`` at a time, :func:`block_rows` from the shapes alone: the
rows an even router sends here and a margin, in whole tiles. Block ``b`` is
sorted rows ``[b R, (b + 1) R)``: its token rows gathered from ``z`` by
``order[b R : (b + 1) R] // k`` (no ``N * k`` copy of ``z`` exists), the
experts' groups clipped to that window, and each token adds the rows of its
that lie in the block, weighted. Block 0 always runs, block ``b > 0`` while
``b R`` is under the rows held here: a router that sends more than ``R``
rows costs another block and nothing else, and the layer reports how many
it ran beyond the first (``moe_overflow_blocks``; 0 whenever the bound
held). **The overflow is a loop and not a branch**: one ``while`` whose body
is traced once, forward and backward. A bounded copy beside a whole-batch
copy under ``lax.cond`` computes the same and was refused for its set-up
(PERF.md, PR 27 and PR 28: each copy of the layer is traced, lowered, and
read back from the compile cache as part of the step, 8 layer-places a
step). Where the chip holds every expert ``R`` is ``N * k``: one block, the
layer as it was before there were blocks.

Backward: the blocks are one ``custom_vjp``. Its forward keeps nothing of a
block; its backward is the same loop, each needed block run again and pulled
back (``grouped_dot`` has its own transposes), its ``[R, f]`` intermediates
alive only while it runs: what a rematerialised layer would recompute
anyway, without the residuals. Rows go in and out by gathers both ways
(``_take_rows``, ``_combine``), so no scatter-add is traced.

A decode step has few tokens (128 an update's rollout step): there every
product is bound by reading the experts' matrices, whichever rows it
computes, and the grouped kernel takes three times that read (PERF.md, PR
26: 218 us a product of 59 MB). So at or under ``DENSE_ROWS`` tokens every
held expert computes every token (a batched product that streams each
matrix once) and the router's weights, zero where an expert was not
chosen, pick the result: the same sum, no sort and no gather.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ba3c_tpu.ops.grouped_matmul import grouped_dot
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

NORM_EPS = 1e-6  # the ``+ 1e-6`` of norm_topk_prob
#: tokens at or under which the held experts compute every token: under the
#: v5e's 240 FLOP a byte a bfloat16 product of so few rows is bound by its
#: matrix's bytes, so the rows nobody routed here cost no time
DENSE_ROWS = 256
#: a block is whole multiples of this many rows (and so whole row tiles of
#: the grouped kernel, ``ops/grouped_matmul.py:ROW_TILE``)
ROW_TILE = 512
#: room in a block over the rows an even router sends to the experts held
#: here. The share of a chunk's assignments that lands on 8 of 32 experts
#: read 0.243-0.251 across seeds (PERF.md, PR 26) where even is 0.250, so
#: 4,096 expected rows, exactly 8 tiles, were 8 or 9 by the seed; a quarter
#: more (10 tiles: 5,120 of the chunk's 16,384 rows) holds every one of those
#: and a router that drifts a fifth off even while it trains. Past it the
#: layer is still exact: it runs another block.
HELD_ROWS_MARGIN = 0.25


class Routing(NamedTuple):
    experts: jax.Array  # [N, k] int32 ids over ALL experts
    weights: jax.Array  # [N, k] float32 combine weights


def route(z, router_w, expert_bias, top_k: int, norm_topk_prob: bool = True,
          scale: float = 1.0, scoring: str = "sigmoid") -> Routing:
    """Scores in float32 (``z`` [N, d] float32, ``router_w`` [d, E]).
    ``scoring`` ``sigmoid``: the bias moves the choice and never the weight;
    ``softmax``: over all E, no bias (``expert_bias`` None)."""
    with device_scope(profiling.MOE_ROUTER):
        logits = jnp.dot(
            z.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if scoring == "softmax":
            assert expert_bias is None, "the softmax scoring has no bias"
            scores = jax.nn.softmax(logits, axis=-1)
            _, experts = jax.lax.top_k(scores, top_k)
        else:
            scores = jax.nn.sigmoid(logits)
            _, experts = jax.lax.top_k(
                scores + jax.lax.stop_gradient(expert_bias)[None, :], top_k
            )
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if norm_topk_prob and scoring == "softmax":  # the published rule: no eps
            weights = weights / jnp.sum(weights, -1, keepdims=True)
        elif norm_topk_prob:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + NORM_EPS)
        return Routing(experts.astype(jnp.int32), weights * scale)


def held_counts(experts, expert_offset: int, held: int, num_experts: int):
    """(local ids [N*k] with the held experts at 0..held-1 and the absent
    ones above, tokens routed to each held expert [held] int32)."""
    local = (experts.reshape(-1) - expert_offset) % num_experts
    counts = jnp.sum(
        (local[:, None] == jnp.arange(held, dtype=local.dtype)[None, :]),
        axis=0, dtype=jnp.int32,
    )
    return local, counts


def load_stats(metrics: dict) -> dict:
    """An epoch's scalars from a step's two expert counters (a policy's
    ``epoch_stats``): ``moe_tokens_per_expert`` [expert layers, held] and
    ``moe_overflow_blocks`` [expert layers]."""
    held = np.asarray(metrics["moe_tokens_per_expert"])
    return {
        # how evenly the router loads the experts held here: the fullest
        # one's tokens over the mean, in the worst layer
        "moe_load_max_over_mean": float(np.max(
            held.max(axis=-1) / np.maximum(held.mean(axis=-1), 1e-9))),
        # blocks of sorted rows the expert layers ran beyond their first:
        # 0 while the rows routed here fit the bound
        "moe_overflow_blocks": float(np.sum(metrics["moe_overflow_blocks"])),
    }


class RoutedLayers:
    """What the expert layers of one unroll over ``[B, T]`` tokens counted,
    taken a layer at a time as the unroll hands it over (:func:`expert_ffn`'s
    counts, the chosen expert ids ``[B * T, k]`` and the blocks run beyond
    the first; ``None`` from a layer without experts), and made the
    ``aux`` the two counters above are read from."""

    def __init__(self, batch: int, length: int):
        self.shape = (batch, length)
        self.counts, self.routes, self.overflow = [], [], []

    def take(self, routed) -> None:
        if routed is not None:
            counts, experts, overflow = routed
            self.counts.append(counts)
            self.routes.append(experts.reshape(*self.shape, -1))
            self.overflow.append(overflow)

    def aux(self, with_routes: bool = False) -> dict:
        """``moe_tokens_per_expert`` [expert layers, held] and
        ``moe_overflow_blocks`` [expert layers] (nothing of a policy's cut
        without an expert layer) and, asked, every token's chosen experts:
        ``routes`` [expert layers, B, T, k]."""
        aux = {"moe_tokens_per_expert": jnp.stack(self.counts),
               "moe_overflow_blocks": jnp.stack(self.overflow)} if self.counts else {}
        if with_routes:
            aux["routes"] = jnp.stack(self.routes)
        return aux


def block_rows(n: int, k: int, held: int, num_experts: int,
               margin: float | None = None) -> int:
    """``R``: the sorted rows one block of the grouped form works on, from
    the shapes alone: the ``n * k * held / num_experts`` rows an even router
    sends here, plus the margin (:data:`HELD_ROWS_MARGIN` where None), in
    whole tiles of the grouped kernel, and never more than all ``n * k`` (a
    chip that holds every expert: one block of everything, the layer as it
    was before there were blocks)."""
    expected = n * k * held / num_experts
    margin = HELD_ROWS_MARGIN if margin is None else margin
    tiles = math.ceil(expected * (1 + margin) / ROW_TILE)
    return min(n * k, ROW_TILE * tiles)


class _Sorted(NamedTuple):
    """A chunk's ``N * k`` assignments sorted by local expert id, the held
    experts' rows first (a stable sort: a token's order within an expert)."""

    order: jax.Array    # [blocks * R] sorted row -> assignment n * k + j
    inverse: jax.Array  # [k, N] assignment (n, j) -> sorted row, at [j, n]
    counts: jax.Array   # [held] rows of each held expert


@jax.custom_vjp
def _take_rows(z, tokens, where):
    """``z[tokens]``: a block's R rows from the tokens' ``[N, d]``. ``where``
    [k, N] says at which row of the block each assignment of a token lies
    (R: at none), so the cotangent goes back as a gather too: no scatter-add
    is traced."""
    del where
    return z[tokens]


def _take_rows_fwd(z, tokens, where):
    return z[tokens], where


def _take_rows_bwd(where, g):
    return _gather_sum(g, where, None).astype(g.dtype), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _gather_sum(rows, where, weights):
    """out[n] = sum_j weights[j, n] * rows[where[j, n]] in float32, a row of
    zeros standing at ``where == len(rows)``. ``j`` leads: the k picked
    ``[N, d]`` lie one behind the other and add up where they lie (as ``[N,
    k, d]`` all of it was relaid out before the sum: a ``reshape`` of 0.24
    ms a layer, my chip run, PR 28)."""
    padded = jnp.concatenate([rows, jnp.zeros_like(rows[:1])])
    picked = padded[where].astype(jnp.float32)
    if weights is not None:
        picked = picked * weights[:, :, None]
    return jnp.sum(picked, axis=0)


@jax.custom_vjp
def _combine(y, weights, assignments, tokens, where):
    """The weighted sum over each token's rows in a block ``y`` [R, d]:
    ``weights`` [k, N] -> [N, d] float32. Backward: the block's rows of the
    cotangent, gathered by token (``assignments``, ``tokens`` [R]: which
    (n, j) a row is)."""
    del assignments, tokens
    return _gather_sum(y, where, weights)


def _combine_fwd(y, weights, assignments, tokens, where):
    return _gather_sum(y, where, weights), (
        y, weights, assignments, tokens, where)


def _combine_bwd(res, g):
    y, weights, assignments, tokens, where = res
    g_rows = g[tokens]  # [R, d] float32
    of_row = weights[assignments % weights.shape[0], tokens]
    d_y = (g_rows * of_row[:, None]).astype(y.dtype)
    d_w = jnp.sum(g_rows * y.astype(jnp.float32), axis=-1)  # by row, [R]
    d_w = jnp.concatenate([d_w, jnp.zeros_like(d_w[:1])])[where]
    return d_y, d_w, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _activation(gate, up, dtype):
    """An expert's hidden rows from its first products, in float32: ``silu(
    gate) * up``, or ``relu(gate)^2`` where the expert has no ``W3``."""
    gate = gate.astype(jnp.float32)
    if up is None:
        return jnp.square(jax.nn.relu(gate)).astype(dtype)
    return (jax.nn.silu(gate) * up.astype(jnp.float32)).astype(dtype)


def _block(b, z, weights, w1, w3, w2, s: _Sorted, block: int):
    """Sorted rows ``[b * block, (b + 1) * block)`` through the experts and
    back to their tokens (``weights`` [k, N]): this block's part of the
    layer, [N, d] float32. ``w3`` None: the two-matrix expert."""
    k = weights.shape[0]
    lo = b * block
    with device_scope(profiling.MOE_DISPATCH):
        assignments = jax.lax.dynamic_slice(s.order, (lo,), (block,))
        tokens = assignments // k
        ends = jnp.cumsum(s.counts)
        # the held experts' groups, clipped to this block's window of rows
        sizes = (jnp.clip(ends, lo, lo + block)
                 - jnp.clip(ends - s.counts, lo, lo + block))
        here = lo + jnp.arange(block) < ends[-1]  # rows of a held expert
        at = s.inverse - lo
        where = jnp.where((at >= 0) & (at < block), at, block)
        rows = _take_rows(z, tokens, where)
        # the grouped products say nothing about rows outside every group
        # (the kernel leaves them unwritten, NaN as likely as not): hold
        # them at zero on the way in (so nothing comes back through them)
        # and on the way out
        rows = jnp.where(here[:, None], rows, 0)
    with device_scope(profiling.MOE_EXPERTS):
        gate = grouped_dot(rows, w1, sizes)
        up = None if w3 is None else grouped_dot(rows, w3, sizes)
        y = grouped_dot(_activation(gate, up, z.dtype), w2, sizes)
    with device_scope(profiling.MOE_COMBINE):
        # the rows go back in the compute type (half the bytes of the
        # gather); the weighted sum over a token's k experts is float32
        y = jnp.where(here[:, None], y, 0)
        return _combine(y, weights, assignments, tokens, where)


def _zeros(like, dtype=None):
    """Zeros of ``like``'s shape that vary over the mesh axes ``like`` varies
    over: under ``shard_map`` a loop's carry keeps one type."""
    out = jnp.zeros(like.shape, dtype or like.dtype)
    varying = tuple(jax.typeof(like).vma)
    return jax.lax.pcast(out, varying, to="varying") if varying else out


def _blocks_needed(s: _Sorted, block: int):
    """Block 0 always, block ``b > 0`` while ``b * block`` is under the rows
    held here."""
    return jnp.maximum(1, -(-jnp.sum(s.counts) // block))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _blocks(z, weights, w1, w3, w2, s: _Sorted, block: int):
    """The sum of the needed blocks: one loop, its body traced once."""
    def body(b, out):
        return out + _block(b, z, weights, w1, w3, w2, s, block)

    return jax.lax.fori_loop(
        0, _blocks_needed(s, block), body, _zeros(z, jnp.float32))


def _blocks_fwd(z, weights, w1, w3, w2, s, block):
    # nothing of a block is kept: the backward runs the blocks again (as a
    # rematerialised layer would have), so a block's [R, f] intermediates
    # live only while it runs
    return _blocks(z, weights, w1, w3, w2, s, block), (
        z, weights, w1, w3, w2, s)


def _blocks_bwd(block, res, g):
    z, weights, w1, w3, w2, s = res
    operands = (z, weights, w1, w3, w2)

    def body(b, acc):
        _, pull = jax.vjp(
            lambda *ops: _block(b, *ops, s, block), *operands)
        return jax.tree_util.tree_map(
            lambda a, d: a + d.astype(a.dtype), acc, pull(g))

    # a token's k rows may lie in different blocks: its cotangent adds up in
    # float32; the experts' matrices add up as their products leave them
    # (an absent ``w3`` has no cotangent: None, an empty tree, all through)
    acc = (_zeros(z, jnp.float32),) + tuple(
        None if x is None else _zeros(x) for x in operands[1:])
    d_z, *rest = jax.lax.fori_loop(0, _blocks_needed(s, block), body, acc)
    return (d_z.astype(z.dtype), *rest, None)


_blocks.defvjp(_blocks_fwd, _blocks_bwd)


def expert_ffn(z, routing: Routing, w1, w3, w2, expert_offset: int,
               num_experts: int, rows_margin: float | None = None):
    """This chip's part of the routed feed-forward. ``rows_margin``: a
    policy's own room in a block of sorted rows over an even router's share
    (:func:`block_rows`), where its router's load on the held experts is
    known to stray further than :data:`HELD_ROWS_MARGIN`.

    ``z`` [N, d] in the compute type; ``w1``/``w3`` [held, d, f] and ``w2``
    [held, f, d] in the compute type; ``w3`` None for an expert of two
    matrices, ``W2 relu(W1 z)^2``. -> (out [N, d] float32, tokens routed
    to each held expert [held] int32, blocks run beyond the first int32)."""
    n, k = routing.experts.shape
    if n <= DENSE_ROWS:
        return _every_token(z, routing, w1, w3, w2, expert_offset, num_experts)
    return _sorted_rows(z, routing, w1, w3, w2, expert_offset, num_experts,
                        block_rows(n, k, w1.shape[0], num_experts, rows_margin))


def held_experts(z, routing: Routing, p, compute_dtype, expert_offset: int,
                 num_experts: int, rows_margin: float | None = None):
    """:func:`expert_ffn` as a policy's expert layer calls it: ``z`` [N, d]
    float32 and the layer's leaves ``p`` (``w1``, ``w2`` and, of an expert
    of three matrices, ``w3``), cast to the compute type here -> (out [N, d]
    float32, what the layer counts for :class:`RoutedLayers`: the tokens
    routed to each held expert, the chosen expert ids [N, k], the blocks of
    sorted rows run beyond the first)."""
    cd = compute_dtype
    out, counts, overflow = expert_ffn(
        z.astype(cd), routing, p["w1"].astype(cd),
        p["w3"].astype(cd) if "w3" in p else None, p["w2"].astype(cd),
        expert_offset, num_experts, rows_margin)
    return out, (counts, routing.experts, overflow)


# a jit of its own, so that the places a step holds this layer (4 expert
# layers x the 2 places the fused step differentiates its learner, each
# traced forward, rematerialised and backward) share one trace and one
# lowering by their shapes: the step's first call is set-up (PERF.md, PR 28)
@functools.partial(
    jax.jit, static_argnames=("expert_offset", "num_experts", "block"))
def _sorted_rows(z, routing: Routing, w1, w3, w2, expert_offset: int,
                 num_experts: int, block: int):
    """``expert_ffn`` for many tokens: the assignments sorted by expert and
    worked on ``block`` sorted rows at a time."""
    n, k = routing.experts.shape
    with device_scope(profiling.MOE_DISPATCH):
        local, counts = held_counts(
            routing.experts, expert_offset, w1.shape[0], num_experts
        )
        order = jnp.argsort(local, stable=True)  # held experts' rows first
        inverse = jnp.argsort(order).reshape(n, k).T
        # whole blocks: the rows past n * k belong to no expert
        order = jnp.pad(order, (0, -(n * k) % block))
        s = _Sorted(order, inverse, counts)
    out = _blocks(z, routing.weights.T, w1, w3, w2, s, block)
    return out, counts, _blocks_needed(s, block) - 1


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
        up = None if w3 is None else jnp.einsum("nd,edf->enf", z, w3)
        y = jnp.einsum("enf,efd->end", _activation(gate, up, z.dtype), w2)
    with device_scope(profiling.MOE_COMBINE):
        out = jnp.einsum("end,ne->nd", y.astype(jnp.float32), share)
    return out, counts, jnp.zeros((), jnp.int32)
