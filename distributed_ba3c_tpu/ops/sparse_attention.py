"""A learner's causal attention over whole episodes under a selection's
mask, in Pallas TPU kernels that keep the scores in fast memory.

    attend_selected(q [B, T, H, D], k [B, T, KV, D], v [B, T, KV, D],
                    selection [B, T, T] bool or None, scale)
        -> (out [B, T, H * D] float32, shared [B, T, T] float32)

Query ``t`` of env ``b`` attends over the keys ``s <= t`` that
``selection[b, t]`` marks (every ``s <= t`` without a selection), of which
there is at least one; one K/V head serves ``H / KV`` query heads. ``out`` is
what ``layers.attend`` computes under the mask ``s <= t & selection``, in its
precisions: operands in their own type, float32 scores, maximum, sum and
output, the probabilities rounded to the operands' type before the product
with ``v``. ``shared`` is the probabilities summed over the ``H`` heads (a
row sums to ``H``), which an indexer's loss reads as its target; no gradient
passes it. ``out`` is differentiable in q, k and v.

**The masked-dense form writes every head's ``[T, T]`` scores to HBM**, in
float32, masks them, writes the probabilities again and reads them for the
product; its backward moves the same matrices once more. At ``keye-vl2``'s
cell (2 envs x 32 heads x 4,096 positions, in blocks of 512 queries) that is
1.2 GB a pass a layer an env, about twenty passes an update: 1.9 s of an
11.2 s update at a tenth of the matrix unit's rate (PERF.md, PR 39).

**Four kernels, none of which writes a score.** All walk (query tile, key
tile) pairs of ``tile`` x ``tile`` positions; the tiles after the diagonal
hold no live pair and are not visited: their grid steps map to the diagonal's
blocks, which are in fast memory already (an unchanged block index fetches
nothing), and their bodies are skipped. A tile's mask is one more blocked
operand, ``[tile, tile]`` of the selection beside the K tile, shared by the
heads of a K/V group, which the body works through in a loop (Mosaic unrolls
what a body says).

- *forward*, grid (env, K/V head, query tile, key tile): a streaming softmax,
  the running maximum and sum in fast memory and the output accumulated in
  its own block; it writes the output and each row's log-sum-exp. A row may
  have no selected key in a tile, the first one too: the running maximum is
  then still ``-inf`` and the body adds nothing (guarded: ``exp(-inf - -inf)``
  is NaN). So the probabilities are rounded before the division by their sum
  and not after it: the one place where its arithmetic is not ``attend``'s.
- *shared*, grid (env, key tile, query tile): q.k a tile at a time against
  the stored log-sum-exp, summed over the heads: 1/H of the scores' size,
  written keys by queries and turned round outside.
- *backward*, the usual two: dK and dV over the query tiles of a key tile,
  dQ over the key tiles of a query tile. Both rebuild a tile's probabilities as
  ``exp(score - log-sum-exp)``. The residuals are q, k, v, the selection,
  the output and the log-sum-exp; the cotangent is rounded to the operands'
  type for its products, as the TPU's default precision rounds it.

*shared* and dK/dV work on tiles of keys by queries (the selection turned
round outside, one byte a pair): every product is then a plain or a
transposed-right one, and a row's log-sum-exp lies as a row ``[.., T]`` of
whole lanes. Where a tile is queries by keys (*forward*, dQ) it lies as a
column ``[.., T, 1]``, which fast memory pads to 128 lanes (2 MB a K/V head
a tile; all 32 heads' columns of *shared* would be 16 MB twice over). No
kernel transposes anything.

**Which path runs is read off the input**, as ``ops/decode_attention.py``
reads it: the kernels on a TPU where ``D`` is whole lanes and ``T`` whole
tiles; the masked-dense form anywhere else (the ``tiny`` cut, the CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_ba3c_tpu.ops.pallas_tpu import LANE, NT, runs_mosaic, vary_alike

#: positions a side of a (query tile, key tile) pair, at most
TILE = 512
#: fast memory a kernel may take: the blocks of a step twice over (the next
#: step's are fetched while this one computes) and the body's float32 tiles,
#: 12-22 MB at tiles of 512. Not more than they need: the compiler keeps
#: operands of its own in fast memory round a call, and a kernel that claimed
#: 64 MB was refused on the chip inside a larger program (PERF.md, PR 39)
VMEM_BYTES = 32 * 2**20
#: the kernels under Pallas's interpreter, whatever the backend: the tests'
#: way to run them on the CPU (tier-1 cannot run Mosaic)
INTERPRET = False



def tile_of(q, k):
    """The kernels' tile for queries ``q`` [B, T, H, D] over keys ``k`` [B,
    T, KV, D]: the most whole lanes' worth of positions that divide ``T``
    and fit :data:`TILE`; None where the masked-dense form runs."""
    _, T, H, D = q.shape
    if not (INTERPRET or runs_mosaic()):
        return None
    if k.shape[1] != T or D % LANE or H % k.shape[2]:
        return None
    return max((t for t in range(LANE, min(T, TILE) + 1, LANE) if T % t == 0),
               default=None)


def tiles_visited_share(q, k) -> float:
    """Of the ``T x T`` square's (query tile, key tile) pairs, the share a
    call of these shapes visits: the pairs up to the diagonal where the
    kernels run, every pair where the masked-dense form does."""
    tile = tile_of(q, k)
    if tile is None:
        return 1.0
    n = q.shape[1] // tile
    return (n + 1) / (2 * n)


def _dense(q, k, v, selection, scale):
    """The masked-dense form: every head's ``[T, T]`` scores written out."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    at = jnp.arange(T)
    mask = (at[None, :] <= at[:, None])[None]
    if selection is not None:
        mask = mask & selection
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", q.reshape(B, T, KV, H // KV, D), k,
        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    shared = jax.lax.stop_gradient(jnp.sum(probs, axis=(1, 2)))
    return out.reshape(B, T, H * D), shared


def _set_bias(bias, selection_ref, q_tile, k_tile, keys_first: bool):
    """``bias`` [tile, tile] float32 <- 0 where the key is at or before the
    query and selected, ``-inf`` elsewhere; rows are queries, or keys where
    ``keys_first``."""
    tile = bias.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, bias.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, bias.shape, 1)
    if keys_first:
        alive = k_tile * tile + rows <= q_tile * tile + cols
    else:
        alive = k_tile * tile + cols <= q_tile * tile + rows
    for ref in selection_ref:
        alive = alive & (ref[...].astype(jnp.int32) != 0)
    bias[...] = jnp.where(alive, 0.0, -jnp.inf)


def _heads_loop(heads: int, body):
    """``body(g)`` for every head ``g`` of a block, in a loop: Mosaic unrolls
    what a body says, and the step holds 48 of these kernels."""
    def one(g, _):
        body(g)
    jax.lax.fori_loop(0, heads, one, None)


def _params(*semantics):
    # no ``cost_estimate``: told whole buffers' bytes, the compiler staged
    # one through fast memory round a kernel (PERF.md, PR 33)
    return pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=VMEM_BYTES)


def _split(q, k):
    """(B, T, KV heads, query heads a K/V head, D) of q [B, T, H, D] over k
    [B, T, KV, D]."""
    B, T, H, D = q.shape
    KV = k.shape[2]
    return B, T, KV, H // KV, D


def _lanes(x):
    """[B, T, heads, D] -> [B, T, heads * D]: a position's heads side by
    side in one row."""
    return x.reshape(*x.shape[:2], -1)


# each kernel a ``jax.jit`` of its own: the layers of a policy share one
# trace and one lowering to Mosaic, which is set-up
@functools.partial(jax.jit, static_argnames=("scale", "tile", "interpret"))
def _forward(q, k, v, selection, scale, tile, interpret=False):
    """-> (out [B, T, H * D] float32, each row's log-sum-exp [B, KV, G, T,
    1] float32)."""
    B, T, KV, G, D = _split(q, k)
    marks = () if selection is None else (selection,)
    vma, (q, k, v, *marks) = vary_alike(_lanes(q), _lanes(k), _lanes(v), *marks)
    n = T // tile

    def kernel(q_ref, k_ref, v_ref, *refs):
        *mark_ref, out_ref, lse_ref, top, total, bias = refs
        i, j = pl.program_id(2), pl.program_id(3)

        @pl.when(j == 0)
        def _():
            top[...] = jnp.full(top.shape, -jnp.inf, jnp.float32)
            total[...] = jnp.zeros(total.shape, jnp.float32)
            out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

        @pl.when(j <= i)
        def _():
            _set_bias(bias, mark_ref, i, j, keys_first=False)

            def head(g):
                lanes = pl.ds(pl.multiple_of(g * D, D), D)
                scores = jax.lax.dot_general(
                    q_ref[:, lanes], k_ref[...], NT,
                    preferred_element_type=jnp.float32) * scale + bias[...]
                before = top[g]
                now = jnp.maximum(before, scores.max(axis=-1, keepdims=True))
                # no key selected in any tile so far: nothing to shrink and
                # nothing to add, not ``exp(-inf - -inf)``
                base = jnp.where(now == -jnp.inf, 0.0, now)
                shrink = jnp.exp(before - base)
                probs = jnp.exp(scores - base)
                top[g] = now
                total[g] = shrink * total[g] + probs.sum(axis=-1, keepdims=True)
                out_ref[:, lanes] = shrink * out_ref[:, lanes] + jnp.dot(
                    probs.astype(v_ref.dtype), v_ref[...],
                    preferred_element_type=jnp.float32)

            _heads_loop(G, head)

        @pl.when(j == i)
        def _():
            def head(g):
                lanes = pl.ds(pl.multiple_of(g * D, D), D)
                out_ref[:, lanes] = out_ref[:, lanes] / total[g]
                lse_ref[g] = top[g] + jnp.log(total[g])

            _heads_loop(G, head)

    # past the diagonal: the diagonal's blocks again, not fetched again
    queries = pl.BlockSpec((None, tile, G * D), lambda b, h, i, j: (b, i, h))
    keys = pl.BlockSpec(
        (None, tile, D), lambda b, h, i, j: (b, jnp.minimum(j, i), h))
    mark = [pl.BlockSpec(
        (None, tile, tile), lambda b, h, i, j: (b, i, jnp.minimum(j, i)))
        for _ in marks]
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, T, KV * G * D), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((B, KV, G, T, 1), jnp.float32, vma=vma)),
        grid=(B, KV, n, n),
        in_specs=[queries, keys, keys, *mark],
        out_specs=(
            queries,
            pl.BlockSpec((None, None, G, tile, 1),
                         lambda b, h, i, j: (b, h, 0, i, 0))),
        scratch_shapes=[
            pltpu.VMEM((G, tile, 1), jnp.float32),
            pltpu.VMEM((G, tile, 1), jnp.float32),
            pltpu.VMEM((tile, tile), jnp.float32),
        ],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_attend_forward",
    )(q, k, v, *marks)


@functools.partial(jax.jit, static_argnames=("scale", "tile", "interpret"))
def _shared(q, k, lse, selection_t, scale, tile, interpret=False):
    """The probabilities summed over the heads, keys by queries: [B, T, T]
    float32, zeros before the diagonal's tiles. ``lse`` [B, KV, G, T] lies as
    rows; ``selection_t`` [B, keys, queries]."""
    B, T, KV, G, D = _split(q, k)
    marks = () if selection_t is None else (selection_t,)
    vma, (q, k, lse, *marks) = vary_alike(_lanes(q), _lanes(k), lse, *marks)
    n = T // tile

    def kernel(q_ref, k_ref, lse_ref, *refs):
        *mark_ref, out_ref, bias = refs
        j, i = pl.program_id(1), pl.program_id(2)  # key tile, query tile
        out_ref[...] = jnp.zeros(out_ref.shape, jnp.float32)

        @pl.when(i >= j)
        def _():
            _set_bias(bias, mark_ref, i, j, keys_first=True)

            def head(h):
                lanes = pl.ds(pl.multiple_of(h * D, D), D)
                group = pl.ds(pl.multiple_of((h // G) * D, D), D)
                scores = jax.lax.dot_general(
                    k_ref[:, group], q_ref[:, lanes], NT,
                    preferred_element_type=jnp.float32) * scale + bias[...]
                out_ref[...] += jnp.exp(scores - lse_ref[pl.ds(h, 1), :])

            _heads_loop(KV * G, head)

    # before the diagonal: the diagonal's blocks, fetched once
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, T, T), jnp.float32, vma=vma),
        grid=(B, n, n),
        in_specs=[
            pl.BlockSpec((None, tile, KV * G * D),
                         lambda b, j, i: (b, jnp.maximum(i, j), 0)),
            pl.BlockSpec((None, tile, KV * D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, KV * G, tile),
                         lambda b, j, i: (b, 0, jnp.maximum(i, j))),
            *[pl.BlockSpec((None, tile, tile),
                           lambda b, j, i: (b, j, jnp.maximum(i, j)))
              for _ in marks]],
        out_specs=pl.BlockSpec((None, tile, tile), lambda b, j, i: (b, j, i)),
        scratch_shapes=[pltpu.VMEM((tile, tile), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_attend_shared",
    )(q, k, lse.reshape(B, KV * G, T), *marks)


@functools.partial(jax.jit, static_argnames=("scale", "tile", "interpret"))
def _backward_kv(q, k, v, d_out, lse, delta, selection_t, scale, tile,
                 interpret=False):
    """dK, dV [B, T, KV * D] float32. ``lse``, ``delta`` [B, KV, G, T] lie as
    rows; ``selection_t`` [B, keys, queries]; ``d_out`` [B, T, H * D] in the
    operands' type."""
    B, T, KV, G, D = _split(q, k)
    marks = () if selection_t is None else (selection_t,)
    vma, (q, k, v, d_out, lse, delta, *marks) = vary_alike(
        _lanes(q), _lanes(k), _lanes(v), d_out, lse, delta, *marks)
    n = T // tile

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs):
        *mark_ref, dk_ref, dv_ref, bias = refs
        j, i = pl.program_id(2), pl.program_id(3)  # key tile, query tile

        @pl.when(i == 0)
        def _():
            dk_ref[...] = jnp.zeros(dk_ref.shape, jnp.float32)
            dv_ref[...] = jnp.zeros(dv_ref.shape, jnp.float32)

        @pl.when(i >= j)
        def _():
            _set_bias(bias, mark_ref, i, j, keys_first=True)

            def head(g):
                lanes = pl.ds(pl.multiple_of(g * D, D), D)
                row = pl.ds(g, 1)
                q_g, do_g = q_ref[:, lanes], do_ref[:, lanes]
                scores = jax.lax.dot_general(
                    k_ref[...], q_g, NT,
                    preferred_element_type=jnp.float32) * scale + bias[...]
                probs = jnp.exp(scores - lse_ref[row, :])  # [keys, queries]
                dv_ref[...] += jnp.dot(
                    probs.astype(do_g.dtype), do_g,
                    preferred_element_type=jnp.float32)
                d_probs = jax.lax.dot_general(
                    v_ref[...], do_g, NT, preferred_element_type=jnp.float32)
                d_scores = probs * (d_probs - delta_ref[row, :]) * scale
                dk_ref[...] += jnp.dot(
                    d_scores.astype(q_g.dtype), q_g,
                    preferred_element_type=jnp.float32)

            _heads_loop(G, head)

    # before the diagonal: the diagonal's blocks, fetched once
    keys = pl.BlockSpec((None, tile, D), lambda b, h, j, i: (b, j, h))
    queries = pl.BlockSpec(
        (None, tile, G * D), lambda b, h, j, i: (b, jnp.maximum(i, j), h))
    rows = pl.BlockSpec(
        (None, None, G, tile), lambda b, h, j, i: (b, h, 0, jnp.maximum(i, j)))
    mark = [pl.BlockSpec(
        (None, tile, tile), lambda b, h, j, i: (b, j, jnp.maximum(i, j)))
        for _ in marks]
    grad = jax.ShapeDtypeStruct((B, T, KV * D), jnp.float32, vma=vma)
    return pl.pallas_call(
        kernel,
        out_shape=(grad, grad),
        grid=(B, KV, n, n),
        in_specs=[queries, keys, keys, queries, rows, rows, *mark],
        out_specs=(keys, keys),
        scratch_shapes=[pltpu.VMEM((tile, tile), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_attend_backward_kv",
    )(q, k, v, d_out, lse, delta, *marks)


@functools.partial(jax.jit, static_argnames=("scale", "tile", "interpret"))
def _backward_q(q, k, v, d_out, lse, delta, selection, scale, tile,
                interpret=False):
    """dQ [B, T, H * D] float32. ``lse``, ``delta`` [B, KV, G, T, 1] lie as
    columns."""
    B, T, KV, G, D = _split(q, k)
    marks = () if selection is None else (selection,)
    vma, (q, k, v, d_out, lse, delta, *marks) = vary_alike(
        _lanes(q), _lanes(k), _lanes(v), d_out, lse, delta, *marks)
    n = T // tile

    def kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs):
        *mark_ref, dq_ref, bias = refs
        i, j = pl.program_id(2), pl.program_id(3)

        @pl.when(j == 0)
        def _():
            dq_ref[...] = jnp.zeros(dq_ref.shape, jnp.float32)

        @pl.when(j <= i)
        def _():
            _set_bias(bias, mark_ref, i, j, keys_first=False)

            def head(g):
                lanes = pl.ds(pl.multiple_of(g * D, D), D)
                scores = jax.lax.dot_general(
                    q_ref[:, lanes], k_ref[...], NT,
                    preferred_element_type=jnp.float32) * scale + bias[...]
                probs = jnp.exp(scores - lse_ref[g])
                d_probs = jax.lax.dot_general(
                    do_ref[:, lanes], v_ref[...], NT,
                    preferred_element_type=jnp.float32)
                d_scores = probs * (d_probs - delta_ref[g]) * scale
                dq_ref[:, lanes] += jnp.dot(
                    d_scores.astype(k_ref.dtype), k_ref[...],
                    preferred_element_type=jnp.float32)

            _heads_loop(G, head)

    queries = pl.BlockSpec((None, tile, G * D), lambda b, h, i, j: (b, i, h))
    keys = pl.BlockSpec(
        (None, tile, D), lambda b, h, i, j: (b, jnp.minimum(j, i), h))
    columns = pl.BlockSpec(
        (None, None, G, tile, 1), lambda b, h, i, j: (b, h, 0, i, 0))
    mark = [pl.BlockSpec(
        (None, tile, tile), lambda b, h, i, j: (b, i, jnp.minimum(j, i)))
        for _ in marks]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, T, KV * G * D), jnp.float32, vma=vma),
        grid=(B, KV, n, n),
        in_specs=[queries, keys, keys, queries, columns, columns, *mark],
        out_specs=queries,
        scratch_shapes=[pltpu.VMEM((tile, tile), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="sparse_attend_backward_q",
    )(q, k, v, d_out, lse, delta, *marks)


def _keys_first(selection):
    """[B, queries, keys] -> [B, keys, queries], for the kernels whose tiles
    are keys by queries."""
    return None if selection is None else jnp.swapaxes(selection, 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _kernel_attend(q, k, v, selection, scale, tile, interpret):
    """-> (out, each row's log-sum-exp as a column)."""
    return _forward(q, k, v, selection, scale, tile, interpret)


def _kernel_attend_fwd(q, k, v, selection, scale, tile, interpret):
    out, lse = _forward(q, k, v, selection, scale, tile, interpret)
    return (out, lse), (q, k, v, selection, out, lse)


def _kernel_attend_bwd(scale, tile, interpret, res, cotangents):
    q, k, v, selection, out, lse = res
    d_out, d_lse = cotangents
    B, T, KV, G, D = _split(q, k)
    # a score's cotangent is prob * (d_prob - delta): delta a row's sum of
    # out * d_out, less what the log-sum-exp's own cotangent adds (d lse / d
    # score is the probability)
    delta = jnp.sum((out * d_out).reshape(B, T, KV, G, D), axis=-1)
    delta = jnp.transpose(delta, (0, 2, 3, 1))[..., None] - d_lse
    d_out = d_out.astype(q.dtype)
    dk, dv = _backward_kv(
        q, k, v, d_out, lse[..., 0], delta[..., 0],
        _keys_first(selection), scale, tile, interpret)
    dq = _backward_q(q, k, v, d_out, lse, delta, selection, scale, tile, interpret)
    return (dq.astype(q.dtype).reshape(q.shape), dk.astype(k.dtype).reshape(k.shape),
            dv.astype(v.dtype).reshape(v.shape), None)


_kernel_attend.defvjp(_kernel_attend_fwd, _kernel_attend_bwd)


def attend_selected(q, k, v, selection, scale):
    """Causal grouped-query attention over whole episodes under
    ``selection`` (None: every key at or before the query): q [B, T, H, D],
    k, v [B, T, KV, D], selection [B, T, T] bool -> (out [B, T, H * D]
    float32, the probabilities summed over the heads [B, T, T] float32,
    which no gradient passes)."""
    tile = tile_of(q, k)
    if tile is None:
        return _dense(q, k, v, selection, scale)
    if selection is not None:
        selection = selection.astype(jnp.int8)
    out, lse = _kernel_attend(
        q, k, v, selection, float(scale), tile, INTERPRET)
    stop = jax.lax.stop_gradient
    shared = _shared(
        stop(q), stop(k), stop(lse)[..., 0], _keys_first(selection),
        float(scale), tile, INTERPRET)
    return out, jnp.swapaxes(shared, 1, 2)
