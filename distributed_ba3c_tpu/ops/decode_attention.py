"""A decode step's attention over the rows of a K/V buffer that hold a
position, in a Pallas TPU kernel.

    decode_attend(q [B, H, W], k [B, rows, G * W], v [B, rows, G * W],
                  length [B] int32, scale) -> [B, H, W] float32

One query row a head, ``H / G`` heads a K/V group (head ``h`` reads group
``h // (H / G)``, a group's ``W`` lanes of a row lying beside the next
group's), env ``b`` attending over rows ``[0, length[b])`` of its buffers
and over nothing else; ``length`` is at least 1. What ``layers.attend``
computes under the mask ``row < length``, in its precisions: operands in the
buffers' type, float32 scores, float32 maximum, sum and output, the
probabilities rounded to the buffers' type before the product with ``v``.

The buffers are a rollout's carry (``models/phi4_flash.py``: a ring of the
last ``window`` positions, the K/V one layer writes and later layers read):
rows at or past ``length`` hold zeros or an older episode, and a product
against the whole buffer under a mask reads them all, whatever the
position. The kernel walks a grid of (env, block of rows) with ``length`` as
a scalar-prefetch argument: a block wholly at or past ``length[b]`` maps to
the env's last live block, which is in fast memory already (an unchanged
block index fetches nothing), and its body is skipped; inside the block
that holds the boundary the rows at or past it are masked. Over the live
blocks it keeps a running maximum, sum and output (a streaming softmax), so
the probabilities are rounded before the division by their sum and not
after it: the one place where its arithmetic is not ``attend``'s.

**One product a block, not one a group.** The queries are laid out
block-diagonally, each row on its own group's lanes beside zeros (the way
``models/phi4_flash.py`` lays a pair's two queries on the halves of a pair),
a group's rows filled up to a float32 tile: ``[G * 8, G * W]`` against the
block's ``[rows, G * W]``. The matrix unit's time is the loading of the K
and V tiles, which is the same either way; ten small products one after the
other each waited out their own latency, 4 us a block against 1.6 us of
reading (PERF.md, PR 33). Of the second product's ``[G * 8, G * W]`` the
diagonal tiles are the answer.

**Blocks** are a function of the shapes (:func:`block_rows`): whole rows
(every group of an env) and as many as :data:`BLOCK_BYTES` holds: a grid
step costs about 0.35 us, as much as 0.3 MB of reading, whether its block is
live or not, and a finer block reads fewer rows past the length (256 rows
at the hybrid's cell, 0.66 MB; 128 and 512 measured 2 % slower there).

**Which path runs is read off the input**, as ``ops/grouped_matmul.py``
reads it: the kernel on a TPU where ``W`` is whole lanes, ``rows`` whole
blocks and a group's queries one tile; ``layers.attend`` under the mask
anywhere else (the ``tiny`` cut, the CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_ba3c_tpu.models import layers
# ba3clint: disable=A5 — how a pallas_call says over which mesh axes it varies under shard_map: one copy, for both modules of kernels
from distributed_ba3c_tpu.ops.grouped_matmul import LANE, _vary_alike
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

#: bytes of K (and as many of V) a grid step reads: a block's rows, each
#: every group of one env
BLOCK_BYTES = 768 * 2**10
#: query rows a group is given in the kernel: a float32 tile's sublanes, so
#: that a group's rows of the output are whole tiles
GROUP_ROWS = 8
#: the kernel under Pallas's interpreter, whatever the backend: the tests'
#: way to run it on the CPU (tier-1 cannot run Mosaic)
INTERPRET = False


def block_rows(rows: int, row_bytes: int):
    """Rows of a block for a buffer of ``rows`` rows of ``row_bytes`` (all
    groups of an env): the most whole lanes' worth that divide ``rows`` and
    fit :data:`BLOCK_BYTES`, or None where none does."""
    fit = [r for r in range(LANE, rows + 1, LANE)
           if rows % r == 0 and r * row_bytes <= BLOCK_BYTES]
    return max(fit, default=None)


def _backend_runs_mosaic() -> bool:
    return jax.default_backend() == "tpu"


def _block_of(q, k):
    """The kernel's block for queries ``q`` over buffers shaped like ``k``,
    or None where ``layers.attend`` runs."""
    _, H, W = q.shape
    _, rows, width = k.shape
    if not (INTERPRET or _backend_runs_mosaic()):
        return None
    groups = width // W
    if W % LANE or H % groups or H // groups > GROUP_ROWS:
        return None
    return block_rows(rows, width * k.dtype.itemsize)


# a ``jax.jit`` of its own: the sites of one shape (the full layer and every
# cross layer) share one trace and one lowering to Mosaic, which is set-up
@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def _kernel_attend(q, k, v, length, scale, block, interpret=False):
    """q [B, G, Hg, W]; k, v [B, rows, G * W]; length [B] -> q's shape,
    float32."""
    vma, (q, k, v, length) = _vary_alike(q, k, v, length)
    B, G, Hg, W = q.shape
    blocks = k.shape[1] // block
    M = G * GROUP_ROWS
    own = jnp.eye(G, dtype=q.dtype)[:, None, :, None]  # [G, 1, G, 1]
    q = jnp.pad(q, ((0, 0), (0, 0), (0, GROUP_ROWS - Hg), (0, 0)))
    q = (q[:, :, :, None, :] * own).reshape(B, M, G * W)

    def last_block(length, b):
        return (jnp.maximum(length[b], 1) - 1) // block

    def kernel(length, q_ref, k_ref, v_ref, out_ref, top, total, acc):
        b, j = pl.program_id(0), pl.program_id(1)

        @pl.when(j == 0)
        def _():
            top[...] = jnp.full(top.shape, -jnp.inf, jnp.float32)
            total[...] = jnp.zeros(total.shape, jnp.float32)
            acc[...] = jnp.zeros(acc.shape, jnp.float32)

        @pl.when(j <= last_block(length, b))
        def _():
            scores = jax.lax.dot_general(
                q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            row = j * block + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1)
            scores = jnp.where(row < length[b], scores, -jnp.inf)
            before = top[...]
            now = jnp.maximum(before, scores.max(axis=-1, keepdims=True))
            shrink = jnp.exp(before - now)
            probs = jnp.exp(scores - now)
            top[...] = now
            total[...] = shrink * total[...] + probs.sum(axis=-1, keepdims=True)
            acc[...] = shrink * acc[...] + jnp.dot(
                probs.astype(v_ref.dtype), v_ref[...],
                preferred_element_type=jnp.float32)

        @pl.when(j == blocks - 1)
        def _():
            for g in range(G):  # a group's rows against its own lanes
                rows = slice(g * GROUP_ROWS, (g + 1) * GROUP_ROWS)
                out_ref[g] = acc[rows, g * W:(g + 1) * W] / total[rows]

    def rows_index(b, j, length):
        # past the env's last live block: that block again, not fetched again
        return b, jnp.minimum(j, last_block(length, b)), 0

    rows = pl.BlockSpec((None, block, G * W), rows_index)
    # no ``cost_estimate``: told the whole buffers' bytes, the compiler
    # staged an 84 MB buffer through fast memory for the call, in and out,
    # every step (read off the program compiled for a v5e, PR 33)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(
            (B, G, GROUP_ROWS, W), jnp.float32, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((None, M, G * W), lambda b, j, length: (b, 0, 0)),
                rows, rows],
            out_specs=pl.BlockSpec(
                (None, G, GROUP_ROWS, W), lambda b, j, length: (b, 0, 0, 0)),
            grid=(B, blocks),
            scratch_shapes=[
                pltpu.VMEM((M, 1), jnp.float32),
                pltpu.VMEM((M, 1), jnp.float32),
                pltpu.VMEM((M, G * W), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=profiling.DECODE_ATTEND,
    )(length, q, k, v)
    return out[:, :, :Hg]


def decode_attend(q, k, v, length, scale):
    """One query row a head over rows ``[0, length[b])`` of env ``b``'s
    buffers: q [B, H, W], k, v [B, rows, G * W], length [B] int32 (at least
    1) -> [B, H, W] float32."""
    B, H, W = q.shape
    rows, G = k.shape[1], k.shape[2] // W
    block = _block_of(q, k)
    if block is None:
        mask = jnp.arange(rows)[None, None, :] < length[:, None, None]
        return layers.attend(
            q[:, None], k.reshape(B, rows, G, W), v.reshape(B, rows, G, W),
            mask, v.dtype, scale=scale).reshape(B, H, W)
    with device_scope(profiling.DECODE_ATTEND):
        out = _kernel_attend(
            q.reshape(B, G, H // G, W), k, v, length.astype(jnp.int32),
            float(scale), block, INTERPRET)
    return out.reshape(B, H, W)
