"""The exact top-k of each row as a mask, without a sort.

    select_mask(scores [..., n] float32, live [..., n] bool, k) -> bool [..., n]

The ``min(k, live entries)`` live entries of largest score, a tie at the
k-th place going to the lower position first (``jax.lax.top_k``'s order, and
a stable descending sort's). What a learner needs of a selection is the set,
not its order, and a row of 4,096 scores sorted 2,048 deep costs a sort
where two searches by bits do:

1. the k-th largest value. A float32's bits, the sign bit flipped (all bits
   where the sign is set), order as unsigned integers the way the floats
   order; an entry that is not live gets key 0, below every float's. The
   largest ``tau`` with ``count(key >= tau) >= k`` is built bit by bit from
   the top, 32 counts over the row: radix select.
2. where more entries tie at ``tau`` than places are left, which of them:
   those at the ``k - count(key > tau)`` lowest positions, the position of
   the last of them found the same way over the position's bits.

That is 32 + ``bit_length(n - 1)`` counts over the row, each waiting on the
one before. **How many of them run is read off the input**, in three regimes,
and the mask is the same mask in each (where a row has no more than ``k``
live entries its top ``min(k, live)`` IS ``live``):

1. ``n <= k``, which the shapes say: ``live`` is returned and no op is
   emitted (the learner's blocks of queries whose keys end at or before the
   top-k);
2. no row holds more than ``k`` live entries, which one count over ``live``
   says at run time: ``live`` again, the searches not executed (a
   ``lax.cond``; a decode step in an episode's first ``k`` positions). A
   batch where one row fits and another does not runs the searches for all
   of it;
3. otherwise the two searches. On a TPU where ``n`` is whole lanes and the
   rows whole tiles a Pallas kernel takes them a block of rows at a time
   (:func:`block_rows`: a decode step's ``[16, 4096]`` is one block), the
   block's keys resident in fast memory and both loops inside the body;
   anywhere else (a CPU, a small cut, an odd width) the same two loops in
   ``jax.numpy``. Only this regime opens the device scope
   ``op_indexer/select/radix`` (``utils/profiling.py``): time there says
   the searches ran, none in an episode's first ``k`` positions is the skip.

In the kernel the keys order as ``int32`` (the unsigned key with its top bit
flipped once more: Mosaic compares signed), and the tie search runs over one
array: ``-1`` where the key is above ``tau``, the position where it ties,
the largest integer elsewhere, so a pass is one compare and a count, and
the mask is ``<= last``.

Exact for every input (no ``approx_max_k``); nothing here is differentiated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_ba3c_tpu.ops.pallas_tpu import LANE, runs_mosaic, vary_alike
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

_SIGN = jnp.uint32(0x80000000)
_INT_MIN, _INT_MAX = -2**31, 2**31 - 1
#: rows of an ``int32`` tile: a block of the kernel is whole tiles
ROW_TILE = 8
#: bytes of ``int32`` keys a block of the kernel holds in fast memory, and as
#: many again of scores, of ``live`` and of the mask (each of those twice: the
#: pipeline's two buffers). A decode step's ``[16, 4096]`` is one block of 256
#: KB; the learner's ``[1024, hi]`` goes in blocks of 64 rows: a pass's count
#: waits on a reduction across lanes whatever the block, so more rows a block
#: hide more of it (``[1024, 4096]`` alone on a v5e: 0.71 / 0.43 / 0.32 /
#: 0.24 / 0.20 ms at 8 / 16 / 32 / 64 / 128 rows, the plain loops 0.25;
#: PERF.md, PR 43), and 7 MB is what a kernel may take of fast memory inside
#: a step without asking for more
BLOCK_BYTES = 2**20
#: the kernel under Pallas's interpreter, whatever the backend: the tests'
#: way to run it on the CPU (tier-1 cannot run Mosaic)
INTERPRET = False
#: the kernel's name in a compiled program and in a capture
SELECT_RADIX = "select_radix"


def ordered_bits(x):
    """float32 -> uint32 that orders as the floats do (-0.0 as 0.0: equal,
    as a sort's comparison has them)."""
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(bits & _SIGN != 0, ~bits, bits | _SIGN)


def _count(mask):
    return jnp.sum(mask, axis=-1, keepdims=True, dtype=jnp.int32)


def runs_searches(n: int, most_live: int, k: int) -> bool:
    """Whether a call over rows of ``n`` entries, the fullest holding
    ``most_live`` live ones, runs its searches (regime 3) or returns ``live``."""
    return n > k and most_live > k


def block_rows(rows: int, n: int):
    """Rows of the kernel's block for ``rows`` rows of ``n`` entries: the
    most whole tiles that divide ``rows`` and fit :data:`BLOCK_BYTES`, or
    None where the ``jax.numpy`` form runs."""
    if not (INTERPRET or runs_mosaic()) or n % LANE:
        return None
    fit = [r for r in range(ROW_TILE, rows + 1, ROW_TILE)
           if rows % r == 0 and r * n * 4 <= BLOCK_BYTES]
    return max(fit, default=None)


def _searches(scores, live, k: int):
    """Regime 3 in ``jax.numpy``: the two searches, a ``fori_loop`` each."""
    n = scores.shape[-1]
    key = jnp.where(live, ordered_bits(scores), jnp.uint32(0))

    def value_bit(i, tau):
        cand = tau | (_SIGN >> i.astype(jnp.uint32))
        return jnp.where(_count(key >= cand) >= k, cand, tau)

    # zeros that vary over the mesh axes the scores vary over: under
    # ``shard_map`` a loop's carry keeps one type
    zero = key[..., :1] & jnp.uint32(0)
    tau = jax.lax.fori_loop(0, 32, value_bit, zero)
    above = key > tau
    tie = live & (key == tau)
    left = k - _count(above)  # places the ties share: at least 1, or tau is 0
    pos = jnp.arange(n, dtype=jnp.int32)
    bits = max(1, (n - 1).bit_length())

    def position_bit(i, last):
        cand = last | (jnp.int32(1) << (bits - 1 - i))
        return jnp.where(_count(tie & (pos < cand)) < left, cand, last)

    # the largest position with fewer than ``left`` ties below it: the
    # ``left``-th tie itself
    last = jax.lax.fori_loop(0, bits, position_bit, zero.astype(jnp.int32))
    return live & (above | (tie & (pos <= last)))


# a ``jax.jit`` of its own: the sites of one shape (four layers of the decode
# step and of the bootstrap's, a learner's block in every layer and in both
# copies ``chunk_grad`` traces) share one trace and one lowering to Mosaic
@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def _kernel_searches(scores, live, k: int, block: int, interpret=False):
    """Regime 3 in a Pallas kernel: scores [rows, n] float32, live [rows, n]
    int32 (nonzero: live) -> [rows, n] int32 (nonzero: selected)."""
    vma, (scores, live) = vary_alike(scores, live)
    rows, n = scores.shape
    bits = max(1, (n - 1).bit_length())

    def kernel(scores_ref, live_ref, out_ref, key_ref):
        alive = live_ref[...] != 0
        x = scores_ref[...]
        raw = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.int32)
        # ``ordered_bits`` with its top bit flipped: the same order, signed
        key_ref[...] = jnp.where(
            alive, jnp.where(raw < 0, raw ^ _INT_MAX, raw), _INT_MIN)
        zero = jnp.zeros((block, 1), jnp.int32)

        def value_bit(i, tau):  # ``tau``: the unsigned threshold's bits
            cand = tau | (jnp.int32(1) << (31 - i))
            enough = _count(key_ref[...] >= (cand ^ _INT_MIN)) >= k
            return jnp.where(enough, cand, tau)

        tau = jax.lax.fori_loop(0, 32, value_bit, zero) ^ _INT_MIN
        key = key_ref[...]
        pos = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
        # above ``tau``: before every position; a tie: its position; the
        # rest: after every position. ``count(above)`` + the ties below a
        # position < k, as the other form's ``< left``
        out_ref[...] = jnp.where(
            key > tau, -1, jnp.where(alive & (key == tau), pos, _INT_MAX))

        def position_bit(i, last):
            cand = last | (jnp.int32(1) << (bits - 1 - i))
            return jnp.where(_count(out_ref[...] < cand) < k, cand, last)

        last = jax.lax.fori_loop(0, bits, position_bit, zero)
        out_ref[...] = (out_ref[...] <= last).astype(jnp.int32)

    rows_of = pl.BlockSpec((block, n), lambda i: (i, 0))
    # no ``cost_estimate`` (PERF.md, PR 33: the compiler stages what a call
    # says it reads)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.int32, vma=vma),
        grid=(rows // block,),
        in_specs=[rows_of, rows_of],
        out_specs=rows_of,
        scratch_shapes=[pltpu.VMEM((block, n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name=SELECT_RADIX,
    )(scores, live)


def _run_searches(scores, live, k: int):
    """Regime 3, by the form the input's shape and the backend allow."""
    n = scores.shape[-1]
    rows = scores.size // n
    block = block_rows(rows, n)
    with device_scope(profiling.OP_INDEXER_SELECT_RADIX):
        if block is None:
            return _searches(scores, live, k)
        kept = _kernel_searches(
            scores.astype(jnp.float32).reshape(rows, n),
            live.astype(jnp.int32).reshape(rows, n), k, block, INTERPRET)
        return (kept != 0).reshape(live.shape)


def select_mask(scores, live, k: int):
    """The top ``k`` of each row's live entries, ties to the lower position."""
    # the mask varies over the mesh axes either input varies over, whichever
    # regime makes it
    _, (scores, live) = vary_alike(scores, live)
    if scores.shape[-1] <= k:
        return live
    # the scores are made before the choice and whole: left free, the TPU
    # compiler sinks their producer into the searches' branch, and a decode
    # step's indexer then writes its 16 heads' dots to HBM for the branch to
    # weigh and sum where they were one fusion's output (PERF.md, PR 43)
    scores = jax.lax.optimization_barrier(scores)
    return jax.lax.cond(
        jnp.max(_count(live)) > k,
        functools.partial(_run_searches, k=k), lambda scores, live: live,
        scores, live)
