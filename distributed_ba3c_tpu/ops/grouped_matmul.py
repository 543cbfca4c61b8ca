"""The grouped product of a routed-expert layer, in a Pallas TPU kernel.

    grouped_dot(lhs [m, k], rhs [G, k, n], group_sizes [G]) -> [m, n]

Rows ``[sum(sizes[:g]), sum(sizes[:g + 1]))`` of ``lhs`` times ``rhs[g]``:
what ``jax.lax.ragged_dot`` computes, bf16 out of bf16 operands with float32
sums. **Rows outside every group** (past ``sum(group_sizes)``) come back
unspecified from the kernel and as zeros from ``ragged_dot``: a caller holds
them at zero itself (``ops/moe.py:_block``'s ``here``), on the way in too,
since the cotangent of ``lhs`` is unspecified in those rows as well.

Three forms, one schedule. The forward and dx (the same product with each
expert's matrix read transposed, ``transpose_rhs``: no relaid-out copy of
the matrices exists) walk the row tiles the groups cover, a tile that two
groups share once for each, and store only the group's rows of it. dW
(``lhs^T x g`` over each group's rows -> ``[G, k, n]``) walks the same
tiles with each group's sum held in fast memory until the group ends. The
schedule (which tile, which group, how many) is megablox's
``make_group_metadata``, computed on the device from ``group_sizes``; the
kernels are trimmed from megablox's ``gmm`` / ``tgmm`` (JAX's
``jax.experimental.pallas.ops.tpu.megablox``, Apache-2.0), which cannot run
as they are inside the fused step: a ``pallas_call`` under ``shard_map``
has to say over which mesh axes its output varies. Gone with the trim:
sharded groups, ``existing_out``, ragged ``k`` tiles.

**Tiles** are a function of the shapes (:func:`tiling`), the rule chosen on
the v5e at the language-model cell's shapes (PERF.md, PR 30): short row
tiles, and all of the contracted dimension and as many columns as fast
memory holds. With the contracted dimension whole, a step's block of the
expert's matrix depends on the group and the column tile alone, so
consecutive row tiles of one group do not fetch it again: each matrix is
read once a column tile instead of once a row tile (halving ``k`` cost a
fifth, 128-column tiles three times the time). **A tile that wide is worked
on in a loop inside the kernel**, 512 or 256 columns at a time (dW: rows of
the sum): Mosaic unrolls whatever a kernel's body says, a whole tile's
products came to 110-230 KB of code a kernel, and the step holds 96 of them
(4 expert layers x 2 places x 12 products): 10.7 MB more of executable to
read back at every start, +2.2 s of set-up (PERF.md, PR 30).

**A width off whole lanes is taken whole.** A block that spans a whole
dimension is legal in Mosaic whatever its size, so where ``k`` or ``n`` is
no multiple of 128 but is whole half-lanes and wider than a lane (an expert
width of 1,856 = 14.5 lanes, ``models/nemotron_h.py``'s), that dimension is
one tile and the loop inside the kernel ends on a shorter last chunk (1,856
= 3 x 512 + 320). No padded copy of a matrix is made.

**Which path runs is read off the input.** Mosaic needs a TPU and tiles
that fit the shapes: on another backend, or where ``m`` is off a multiple
of 128 or ``k`` or ``n`` is neither whole lanes nor such a width (the
``tiny`` cut), ``grouped_dot`` *is* ``jax.lax.ragged_dot``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

from distributed_ba3c_tpu.ops.pallas_tpu import LANE, runs_mosaic, vary_alike
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

#: rows of a tile. Eight groups of about 512 rows start on no tile's edge, so
#: a grid visits each group's last tile twice: 15 tiles of 512 for 8 tiles of
#: rows, 23 of 256 for 16. On the v5e 256 and 128 tie and 512 costs a fifth
#: more in every form (PERF.md, PR 30)
ROW_TILE = 256
#: fast memory the tiles of one kernel may take, double-buffered operands
#: and the float32 sum together (a v5e core has 128 MiB), and what a kernel
#: asks for beyond them: the body's float32 product and masked copies
VMEM_BUDGET = 40 * 2**20
VMEM_ROOM = 16 * 2**20
#: the kernel under Pallas's interpreter, whatever the backend: the tests'
#: way to run it on the CPU (tier-1 cannot run Mosaic)
INTERPRET = False

FORWARD, DW = "forward", "dw"  # dx is the forward with the matrix read transposed


def _divisors(x: int, cap: int):
    """Multiples of LANE that divide ``x``, the largest first, none over
    ``cap``."""
    return [t for t in range(min(x, cap) // LANE * LANE, 0, -LANE)
            if x % t == 0]


def _widths(x: int):
    """Tiles a contracted dimension or the columns may take: the multiples
    of LANE that divide ``x``, the largest first; of an ``x`` off whole
    lanes, ``x`` itself where it is whole half-lanes and wider than a lane
    (a block may span a whole dimension whatever its size), else none."""
    if x % LANE:
        return [x] if x > LANE and x % (LANE // 2) == 0 else []
    return _divisors(x, x)


def _vmem_bytes(form: str, tm: int, tk: int, tn: int, itemsize: int) -> int:
    """Double-buffered tiles of both operands and the output, and the
    float32 sum."""
    if form == DW:
        tiles = tm * tk + tm * tn + tk * tn
        return 2 * itemsize * tiles + 4 * tk * tn
    tiles = tm * tk + tk * tn + tm * tn
    return 2 * itemsize * tiles + 4 * tm * tn


def tiling(form: str, m: int, k: int, n: int, itemsize: int = 2):
    """(tm, tk, tn) of a form from its shapes, or None where no whole tiles
    fit: rows, contracted (dW: the output's rows), columns. The widest
    tiles the budget holds, the contracted dimension first: at the cell's
    shapes each form takes a whole expert matrix at once."""
    tms = _divisors(m, ROW_TILE)
    if not tms:
        return None
    for tk in _widths(k):
        for tn in _widths(n):
            if _vmem_bytes(form, tms[0], tk, tn, itemsize) <= VMEM_BUDGET:
                return tms[0], tk, tn
    return None


def _compiler_params(form: str, tiles, itemsize: int):
    """The grid's first axis is free to split over cores; the scope of fast
    memory is the tiles' and room for the body's float32 temporaries."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_vmem_bytes(form, *tiles, itemsize) + VMEM_ROOM)


def _chunk(width: int) -> int:
    """Columns (or rows) of a tile a kernel's inner loop works on at once:
    a chunk that divides the width, or, of a width off whole lanes, 512 with
    a shorter last chunk (:func:`_last_chunk`)."""
    return next((c for c in (512, 256, LANE) if width % c == 0), 512)


def _last_chunk(width: int) -> int:
    """What is left of ``width`` after its whole chunks: 0 where they cover
    it (every width of whole lanes)."""
    return width % _chunk(width)


def _rows_of_group(offsets, group, first_row, shape):
    """Which rows of a ``shape`` tile that starts at ``first_row`` belong to
    ``group``."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + first_row
    return (row >= offsets[group]) & (row < offsets[group + 1])


# each kernel a ``jax.jit`` of its own: the places a layer calls one shape of
# it (gate and up; the forward and its re-run in the backward) share one
# trace and one lowering to Mosaic, which is set-up (PERF.md, PR 30)
@functools.partial(
    jax.jit, static_argnames=("tiles", "transpose_rhs", "interpret"))
def gmm(lhs, rhs, group_sizes, tiles, transpose_rhs=False, interpret=False):
    """``lhs`` [m, k] x ``rhs`` [G, k, n] (``transpose_rhs``: [G, n, k]) by
    group -> [m, n] in ``lhs``'s type; rows outside every group unwritten."""
    vma, (lhs, rhs, group_sizes) = vary_alike(lhs, rhs, group_sizes)
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiles
    tiles_k = k // tk
    metadata, num_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=rhs.shape[0], visit_empty_groups=False)
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    chunk, tail = _chunk(tn), _last_chunk(tn)

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref,
               *acc_ref):
        i, k_i = pl.program_id(1), pl.program_id(2)
        # a tile two groups share is visited once for each, one after the
        # other: the rows of the other stay as they are
        mine = _rows_of_group(
            offsets, group_ids[i], m_tile_ids[i] * tm, (tm, chunk))

        def columns(j, _):
            # a tile's columns ``chunk`` at a time in a loop, not unrolled:
            # the kernel's code is a seventh of what the whole tile's is,
            # and the step holds 64 of these (set-up, PERF.md PR 30)
            work(pl.ds(pl.multiple_of(j * chunk, chunk), chunk), mine)

        def work(cols, mine):
            product = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[cols, :] if transpose_rhs
                else rhs_ref[:, cols], contract,
                preferred_element_type=jnp.float32)
            if tiles_k > 1:
                acc, = acc_ref

                @pl.when(k_i == 0)
                def _():
                    acc[:, cols] = product

                @pl.when(k_i > 0)
                def _():
                    acc[:, cols] += product

            @pl.when(k_i == tiles_k - 1)
            def _():
                total = product if tiles_k == 1 else acc_ref[0][:, cols]
                out_ref[:, cols] = jnp.where(
                    mine, total, out_ref[:, cols].astype(jnp.float32)
                ).astype(out_ref.dtype)

        jax.lax.fori_loop(0, tn // chunk, columns, None)
        if tail:  # a width off whole lanes: its last, shorter chunk
            work(pl.ds(tn - tail, tail), _rows_of_group(
                offsets, group_ids[i], m_tile_ids[i] * tm, (tm, tail)))

    def rhs_index(n_i, i, k_i, offsets, group_ids, m_tile_ids):
        return (group_ids[i],) + ((n_i, k_i) if transpose_rhs else (k_i, n_i))

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, i, k_i, o, g, t: (t[i], k_i)),
                pl.BlockSpec(
                    (None, tn, tk) if transpose_rhs else (None, tk, tn),
                    rhs_index),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda n_i, i, k_i, o, g, t: (t[i], n_i)),
            grid=(n // tn, num_tiles, tiles_k),
            scratch_shapes=(
                [] if tiles_k == 1 else [pltpu.VMEM((tm, tn), jnp.float32)]),
        ),
        compiler_params=_compiler_params(FORWARD, tiles, lhs.dtype.itemsize),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * (
                m * k * (n // tn) + rhs.size + m * n)),
        interpret=interpret,
        name="gmm_dx" if transpose_rhs else "gmm",
    )(*metadata, lhs, rhs)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def tgmm(lhs, g, group_sizes, tiles, interpret=False):
    """``lhs`` [m, k], ``g`` [m, n] -> [G, k, n] in ``lhs``'s type: each
    group's rows of ``lhs``, transposed, times its rows of ``g``; zeros for
    an empty group."""
    vma, (lhs, g, group_sizes) = vary_alike(lhs, g, group_sizes)
    m, k = lhs.shape
    n = g.shape[1]
    groups = group_sizes.shape[0]
    tm, tk, tn = tiles
    metadata, num_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=groups, visit_empty_groups=True)

    chunk, tail = _chunk(tk), _last_chunk(tk)

    def kernel(offsets, group_ids, m_tile_ids, lhs_ref, g_ref, out_ref, acc):
        i = pl.program_id(2)
        group = group_ids[i]
        last = pl.num_programs(2) - 1
        first_row = m_tile_ids[i] * tm
        whole = (offsets[group] <= first_row) & (
            first_row + tm <= offsets[group + 1])

        def over_rows(fn):
            # the sum's rows ``chunk`` at a time in a loop, not unrolled:
            # a fraction of the whole tile's code (see ``gmm``)
            jax.lax.fori_loop(0, tk // chunk, lambda j, _: fn(
                pl.ds(pl.multiple_of(j * chunk, chunk), chunk)), None)
            if tail:  # a width off whole lanes: its last, shorter chunk
                fn(pl.ds(tk - tail, tail))

        def add(rows, lhs_rows, g_rows):
            acc[rows, :] += jax.lax.dot_general(
                lhs_rows, g_rows, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        def mine(tile):
            keep = _rows_of_group(offsets, group, first_row, tile.shape)
            return jnp.where(keep, tile.astype(jnp.float32), 0).astype(tile.dtype)

        @pl.when((i == 0) | (group_ids[jnp.maximum(i - 1, 0)] != group))
        def _():
            over_rows(lambda rows: acc.__setitem__(
                (rows, slice(None)), jnp.zeros((rows.size, tn), jnp.float32)))

        @pl.when(whole)
        def _():
            over_rows(lambda rows: add(rows, lhs_ref[:, rows], g_ref[...]))

        @pl.when(~whole & (offsets[group + 1] > offsets[group]))
        def _():
            # a tile this group shares: the rows that are another group's,
            # or nobody's (unspecified, NaN as likely as not), count for
            # nothing, so they are zeroed on both sides of the product
            g_mine = mine(g_ref[...])
            over_rows(lambda rows: add(rows, mine(lhs_ref[:, rows]), g_mine))

        @pl.when((i == last) | (group_ids[jnp.minimum(i + 1, last)] != group))
        def _():
            over_rows(lambda rows: out_ref.__setitem__(
                (rows, slice(None)), acc[rows, :].astype(out_ref.dtype)))

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, k_i, i, o, g, t: (t[i], k_i)),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, i, o, g, t: (t[i], n_i)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda n_i, k_i, i, o, g, t: (g[i], k_i, n_i)),
            grid=(n // tn, k // tk, num_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=_compiler_params(DW, tiles, lhs.dtype.itemsize),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=lhs.dtype.itemsize * (
                m * k * (n // tn) + m * n * (k // tk) + groups * k * n)),
        interpret=interpret,
        name="gmm_dw",
    )(*metadata, lhs, g)


def _tiles_of(lhs, rhs, transpose_rhs: bool):
    """The tiles of the product, of its dx and of its dW (as
    :func:`_kernel_dot_bwd` calls them) where the kernel runs, else None."""
    if not (INTERPRET or runs_mosaic()):
        return None
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    size = lhs.dtype.itemsize
    tiles = (tiling(FORWARD, m, k, n, size), tiling(FORWARD, m, n, k, size),
             tiling(DW, m, *((n, k) if transpose_rhs else (k, n)), size))
    return None if None in tiles else tiles


def grouped_dot(lhs, rhs, group_sizes, *, transpose_rhs: bool = False):
    """``lhs`` [m, k] x ``rhs`` [G, k, n] by group -> [m, n] in the
    operands' type, differentiable in both. ``transpose_rhs``: ``rhs`` is
    [G, n, k] and read transposed."""
    tiles = _tiles_of(lhs, rhs, transpose_rhs)
    if tiles is None:
        if transpose_rhs:
            rhs = rhs.swapaxes(1, 2)
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return _kernel_dot(lhs, rhs, group_sizes, tiles, transpose_rhs, INTERPRET)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _kernel_dot(lhs, rhs, group_sizes, tiles, transpose_rhs, interpret):
    with device_scope(profiling.MOE_EXPERTS_GMM):
        return gmm(lhs, rhs, group_sizes, tiles[0], transpose_rhs, interpret)


def _kernel_dot_fwd(lhs, rhs, group_sizes, tiles, transpose_rhs, interpret):
    out = _kernel_dot(lhs, rhs, group_sizes, tiles, transpose_rhs, interpret)
    return out, (lhs, rhs, group_sizes)


def _kernel_dot_bwd(tiles, transpose_rhs, interpret, res, g):
    lhs, rhs, group_sizes = res
    with device_scope(profiling.MOE_EXPERTS_GMM):
        d_lhs = gmm(g, rhs, group_sizes, tiles[1], not transpose_rhs, interpret)
        # rhs [G, n, k] read transposed: g's columns are its rows
        pair = (g, lhs) if transpose_rhs else (lhs, g)
        d_rhs = tgmm(*pair, group_sizes, tiles[2], interpret)
    return d_lhs, d_rhs, None


_kernel_dot.defvjp(_kernel_dot_fwd, _kernel_dot_bwd)
