"""The Mamba-2 recurrence (state-space duality, arXiv:2405.21060), on a
float32 matrix state a head.

For head ``h`` of ``P`` channels whose group ``g`` shares ``B`` and ``C`` of
``N`` numbers (``heads / groups`` consecutive heads a group), over positions
``t``, with a step size ``dt_t > 0`` and a scalar ``A_h < 0``:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T          H [P, N], zero at a reset
    y_t = H_t C_t + D_h x_t

Where ``ops/ssm.py`` (Mamba-1) holds a diagonal state a channel with one
``B``, ``C`` for every channel and runs ``T`` dependent elementwise steps,
the decay here is a scalar a head, and that is what lets whole chunks go
through the matrix unit. Two forms, one recurrence:

- :func:`ssd_step`: one position from a state, the rollout's decode step:
  elementwise products and sums in float32 (no matrix unit: it would round
  its operands);
- :func:`ssd_chunked`: whole sequences from the zero state in chunks of
  ``Q`` positions, the learner's. With ``a_t = dt_t A`` and ``L_t`` its
  running sum inside a chunk that opens on ``H_prev``:

      y_t  = sum_{s <= t} exp(L_t - L_s) (C_t . B_s) dt_s x_s     the chunk's own part:
                 a [Q, Q] product a group, masked and decayed a head, then a
                 [Q, Q] x [Q, P] product a head
           + exp(L_t) H_prev C_t                                   what the chunk opened on
      S    = sum_s exp(L_Q - L_s) dt_s x_s B_s^T                   the chunk's state
      H    = exp(L_Q) H_prev + S                                   over the T / Q boundaries

  The mask is applied BEFORE the ``exp`` (``L_t - L_s`` is positive above
  the diagonal and would overflow where ``dt A`` is strongly negative);
  under it every exponent is at most 0. Each ``L_t - L_s`` is summed from
  its own ``s`` (``sum_{s < l <= t} a_l``, a running sum down each column),
  not taken as the difference of two running sums. The chunks run one after
  another, and the backward's residuals are the inputs and the state at the
  chunk boundaries (``T / Q`` states a head, 33.5 MB a sequence a layer at
  2,048 positions of 64 heads of 64 x 128), never a ``[T, heads, P, N]``
  array and never every chunk's ``[Q, Q]`` matrices at once.

Everything is float32 and every product is at the highest precision, as
``ops/delta_rule.py``'s are and for its reason: the state is the layer's
memory of the whole episode, and the recurrence's products are a fiftieth
of the layer's.

**Two forms of the chunked form, one arithmetic.** :func:`ssd_chunked_plain`
is plain ``jax.numpy``: a ``lax.scan`` over the chunks, each under
``jax.checkpoint``. It writes a chunk's ``[b, g, r, Q, Q]`` float32 arrays
(the masked sums of ``dt A``, their ``exp``, ``C B^T`` times it) to HBM and
reads them back between some twenty small fusions, and its backward does the
same with their cotangents: 576 ms of an 8.4 s update at
``fused-nemotron3nano-recall-32x2048``, against 45 ms by the bytes that have
to move (PERF.md, PR 44). **Two Pallas TPU kernels** keep them in fast
memory. Both walk a grid of (env, chunk, group), a chunk after the one before
it, with every head's state ``[h P, N]`` in a scratch of fast memory; per
chunk and group only ``x``, ``dt``, ``B``, ``C`` come in and ``y`` and the
boundary state go out, which is what ``benchmark/opcount_nemotronh.py:
ssd_bytes`` counts. ``C B^T`` is made once a group; the group's heads are
worked through in a loop (Mosaic unrolls what a body says), a tile of whole
lanes at a time: two heads of 64 channels side by side, each head's products
taken against the tile with the other head's lanes zeroed, which costs the
matrix unit nothing (it is 128 wide either way) and keeps every slice on a
tile's edge. A head's step sizes come as a column ``[Q, 1]`` picked from the
chunk's ``[Q, h]`` block; ``L_t - L_s`` is a running sum down the rows of
``a_l`` kept where ``l > s``, by doubling steps (each entry the sum of its
own terms); ``exp(L_t)`` is its column 0 and ``a_0``; ``exp(L_Q - L_s)`` its
last row, turned to a column through the diagonal.

- *forward* (``ssd_chunks_forward``) writes ``y`` and the state every chunk
  closed on: the last one is the sequence's, the others are what the
  backward starts each chunk from.
- *backward* (``ssd_chunks_backward``) walks the chunks from the last to the
  first with the state's cotangent in the scratch, makes a chunk's matrices
  again from the state it opened on, and writes ``dx``, ``d dt``, ``dB`` and
  ``dC`` (summed over a group's heads) as the operands lie, and two small
  ``[b, T, h]`` arrays, the cotangent of ``dt A`` and ``sum_p dy x``, of which
  ``dA`` and ``dD`` are sums over envs and positions (taken outside). The
  cotangent of ``a_l`` is what every ``(t, s)`` with ``t >= l > s`` holds of
  ``d ratio x ratio``: a running sum UP the rows, then a row's sum under the
  mask. The boundary's ``state_dtype`` rounding is the plain form's, forward
  and backward.

``ssd_chunked`` is a ``jax.custom_vjp`` over the two. **Which form runs is
read off the input** (:func:`kernels_take`), as ``ops/decode_attention.py``
and ``ops/grouped_matmul.py`` read it: the kernels on a TPU where a chunk is
128 positions, ``N`` whole lanes, ``P`` whole sublanes and a group's heads
whole tiles; the plain form anywhere else (the ``tiny`` cut, the CPU).
``benchmark/layer_metrics/ssd_roofline.py`` is the yardstick of either.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_ba3c_tpu.ops.pallas_tpu import (
    HIGHEST, LANE, NT, TN, dot_f32, running_sum, runs_mosaic, vary_alike)
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

#: positions a chunk of the sequence form takes (the config's ``chunk_size``)
CHUNK = 128
#: the kernels under Pallas's interpreter, whatever the backend: the tests'
#: way to run them on the CPU (tier-1 cannot run Mosaic)
INTERPRET = False
#: the kernels' names in a compiled program and in a capture
FORWARD_KERNEL, BACKWARD_KERNEL = "ssd_chunks_forward", "ssd_chunks_backward"



def ssd_step(H, x, dt, A, B, C, D):
    """One position. ``H`` [b, h, P, N]; ``x`` [b, h, P]; ``dt`` [b, h];
    ``A``, ``D`` [h]; ``B``, ``C`` [b, g, N] -> (H, y [b, h, P] float32).
    ``H`` keeps its type (a control keeps it in bfloat16); the arithmetic
    is float32."""
    b, h, P, N = H.shape
    g = B.shape[1]
    decay = jnp.exp(dt * A)                               # [b, h]
    held = H.astype(jnp.float32).reshape(b, g, h // g, P, N)
    write = (dt[..., None] * x).reshape(b, g, h // g, P)  # dt_t x_t
    new = (decay.reshape(b, g, h // g)[..., None, None] * held
           + write[..., None] * B[:, :, None, None, :])
    y = jnp.sum(new * C[:, :, None, None, :], axis=-1).reshape(b, h, P)
    return new.reshape(b, h, P, N).astype(H.dtype), y + D[:, None] * x


def ssd_chunked_plain(x, dt, A, B, C, D, chunk: int = CHUNK,
                       state_dtype=jnp.float32):
    """:func:`ssd_chunked` in plain ``jax.numpy``: what runs wherever the
    kernels do not, and what they are held against."""
    b, T, h, P = x.shape
    g, N = B.shape[2:]
    r = h // g
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        x, dt, B, C = _whole_chunks(pad, x, dt, B, C)
    n = (T + pad) // Q
    dot = lambda spec, *ops: jnp.einsum(spec, *ops, precision=HIGHEST)  # noqa: E731

    # the chunks leading, then the heads (by group, where B and C meet them)
    # so that a head's [Q, Q] and [Q, P] matrices lie together
    xs = x.reshape(b, n, Q, g, r, P).transpose(1, 0, 3, 4, 2, 5)  # [n, b, g, r, Q, P]
    dts = dt.reshape(b, n, Q, g, r).transpose(1, 0, 3, 4, 2)      # [n, b, g, r, Q]
    Bs = B.reshape(b, n, Q, g, N).transpose(1, 0, 3, 2, 4)        # [n, b, g, Q, N]
    Cs = C.reshape(b, n, Q, g, N).transpose(1, 0, 3, 2, 4)
    at = jnp.arange(Q)
    live = at[:, None] >= at[None, :]
    heads_A = A.reshape(g, r, 1)

    @jax.checkpoint
    def one_chunk(H, chunk_of):
        """A chunk from the state it opens on: one chunk's [Q, Q] matrices
        are live at a time, and the backward recomputes them from the
        boundary state the scan kept."""
        xs, dts, Bs, Cs = chunk_of
        a = dts * heads_A                                  # a_t = dt_t A
        # L_t - L_s = sum_{s < l <= t} a_l, each sum from its own s (a
        # running sum down the column of a_l kept where l > s; the
        # difference of two running sums loses a small step that follows
        # large ones, in the gradient most of all), and exp of it for
        # s <= t, 0 elsewhere: masked BEFORE the exp
        after = jnp.where(at[:, None] > at[None, :], a[..., :, None], 0.0)  # [.., l, s]
        ratio = jnp.exp(jnp.where(live, jnp.cumsum(after, axis=-2), -jnp.inf))
        written = dts[..., None] * xs                      # dt_s x_s
        from_open = jnp.exp(jnp.cumsum(a, axis=-1))        # exp(L_t) [b, g, r, Q]
        # the chunk's own part, and what it opened on
        cb = dot("bgtN,bgsN->bgts", Cs, Bs)
        y = dot("bgrts,bgrsp->bgrtp", cb[:, :, None] * ratio, written)
        y = y + from_open[..., None] * dot("bgrpN,bgtN->bgrtp", H, Cs)
        # the chunk's state (exp(L_Q - L_s) is the last row) and the boundary
        S = dot("bgrsp,bgsN->bgrpN", ratio[..., -1, :, None] * written, Bs)
        H = from_open[..., -1, None, None] * H + S
        return H.astype(state_dtype).astype(jnp.float32), y

    # zeros that vary as the inputs do (under shard_map a constant would be
    # typed as the same on every shard, and the scan's carry is not)
    zero = xs[0, :, :, :, 0, :, None] * Bs[0, :, :, None, 0, None, :] * 0.0
    H_last, y = jax.lax.scan(one_chunk, zero, (xs, dts, Bs, Cs))
    y = y.transpose(1, 0, 4, 2, 3, 5).reshape(b, n * Q, h, P)[:, :T]
    return y + D[:, None] * x[:, :T], H_last.reshape(b, h, P, N)


def _whole_chunks(pad: int, *arrays):
    """``arrays`` [b, T, ...] with ``pad`` more positions, which leave the
    state as it is: a ``dt`` of 0 decays nothing and writes nothing."""
    return tuple(
        jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)) for v in arrays)


def _heads_a_tile(P: int) -> int:
    """Heads whose ``P`` channels lie side by side in one tile of whole
    lanes, the kernels' unit of work: two of 64; 0 where no whole number of
    heads fills whole lanes."""
    if P % LANE == 0:
        return 1
    return LANE // P if LANE % P == 0 else 0


def kernels_take(x, B, chunk: int = CHUNK) -> bool:
    """Whether :func:`ssd_chunked` runs its kernels on ``x`` [b, T, h, P]
    and ``B`` [b, T, g, N] in chunks of ``chunk``: on a TPU (or under the
    interpreter), where a chunk is a tile's lanes of positions, ``N`` whole
    lanes, ``P`` whole sublanes and a group's heads whole tiles."""
    _, T, h, P = x.shape
    g, N = B.shape[2:]
    if not (INTERPRET or runs_mosaic()):
        return False
    side = _heads_a_tile(P)
    return (chunk == LANE and T >= chunk and N % LANE == 0 and P % 8 == 0
            and h % g == 0 and side > 0 and (h // g) % side == 0)


class _Chunk:
    """What both kernels make of a chunk's operands in fast memory, a tile
    of ``side`` heads of ``P`` channels at a time, of ``h`` heads in all. A
    ``[Q, Q]`` matrix is positions by positions: ``t`` or ``l`` down the
    rows (``row``), ``s`` along the lanes (``col``)."""

    def __init__(self, Q: int, h: int, P: int, side: int):
        self.Q, self.side = Q, side
        self.row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
        self.col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        self.at = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0)
        # which head a lane of a [Q, h] block is; which of the tile's heads a
        # lane (of x, y) or a row (of H) is
        self.head_lane = jax.lax.broadcasted_iota(jnp.int32, (Q, h), 1)
        self.lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, P * side), 1) // P
        self.row_head = jax.lax.broadcasted_iota(jnp.int32, (P * side, 1), 0) // P

    def column(self, per_head, head):
        """[Q, h] -> head ``head``'s [Q, 1]."""
        return jnp.sum(jnp.where(self.head_lane == head, per_head, 0.0), axis=1,
                       keepdims=True)

    def as_column(self, row):
        """[1, Q] along the lanes -> [Q, 1] down the rows."""
        spread = jnp.broadcast_to(row, (self.Q, self.Q))
        return jnp.sum(jnp.where(self.row == self.col, spread, 0.0), axis=1,
                       keepdims=True)

    def as_row(self, column):
        """[Q, 1] down the rows -> [1, Q] along the lanes."""
        spread = jnp.broadcast_to(column, (self.Q, self.Q))
        return jnp.sum(jnp.where(self.row == self.col, spread, 0.0), axis=0,
                       keepdims=True)

    def heads(self, steps, first, A_ref, D_ref, x_t):
        """The tile's ``side`` heads, ``first`` the first of them, from the
        chunk's step sizes ``steps`` [Q, h] and the tile of ``x`` [Q, W]:
        each a :class:`_Head`. ``ssd_chunked_plain``'s arithmetic: the mask
        before the exp, each L_t - L_s summed from its own s."""
        out = []
        for i in range(self.side):
            head = first + i
            dt = self.column(steps, head)
            a = dt * A_ref[head]
            seg = running_sum(jnp.where(self.row > self.col, a, 0.0))
            ratio = jnp.exp(jnp.where(self.row >= self.col, seg, -jnp.inf))
            out.append(_Head(
                head=head, A=A_ref[head], dt=dt, ratio=ratio,
                # L_t is column 0's sum (a_1 .. a_t) and a_0
                from_open=jnp.exp(seg[:, 0:1] + a[0:1, :]),
                to_end=self.as_column(ratio[self.Q - 1:self.Q, :]),
                written=jnp.where(self.lane_head == i, dt * x_t, 0.0),
                skip=jnp.full((1, 1), D_ref[head], jnp.float32)))
        return out

    def of_heads(self, values, rows: bool = False):
        """One value a head (each [Q, 1] or [1, 1]) -> the tile's: every lane
        (``rows``: every row, [W, 1]) its head's."""
        which = self.row_head if rows else self.lane_head
        out = values[0]
        for i in range(1, self.side):
            out = jnp.where(which == i, values[i], out)
        return out

    def tile(self, heads):
        """-> (exp(L_t) [Q, W], exp(L_Q - L_s) [Q, W], exp(L_Q) [W, 1] down
        the state's rows, D [1, W], dt_s x_s [Q, W]) of the tile's heads."""
        return (self.of_heads([hd.from_open for hd in heads]),
                self.of_heads([hd.to_end for hd in heads]),
                self.of_heads([hd.from_open[self.Q - 1:self.Q, :] for hd in heads],
                              rows=True),
                self.of_heads([hd.skip for hd in heads]),
                sum(hd.written for hd in heads))


class _Head(NamedTuple):
    """A head of a tile in a chunk: its number, ``A`` and ``D`` (``skip`` [1,
    1]), its step sizes ``dt`` [Q, 1], ``ratio`` [Q, Q] (exp(L_t - L_s) for s
    <= t, 0 elsewhere), ``from_open`` [Q, 1] (exp(L_t)), ``to_end`` [Q, 1]
    (exp(L_Q - L_s)) and ``written`` [Q, W] (dt_s x_s on its own lanes,
    zeros on the tile's other heads')."""
    head: jax.Array
    A: jax.Array
    dt: jax.Array
    ratio: jax.Array
    from_open: jax.Array
    to_end: jax.Array
    written: jax.Array
    skip: jax.Array


def _params():
    # no ``cost_estimate`` (PERF.md, PR 33). The envs are free to split over
    # cores; a chunk follows the one before it, and a chunk's groups share the
    # blocks that hold every head's step sizes
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _sizes(x, B):
    b, T, h, P = x.shape
    g, N = B.shape[2:]
    return b, T, h, P, g, N, h // g, T // CHUNK, _heads_a_tile(P)


# each kernel a ``jax.jit`` of its own: the Mamba-2 blocks of a policy share
# one trace and one lowering to Mosaic, which is set-up
@functools.partial(jax.jit, static_argnames=("state_dtype", "interpret"))
def _forward(x, dt, A, B, C, D, state_dtype=jnp.float32, interpret=False):
    """Whole chunks from the zero state: x [b, T, h, P]; dt [b, T, h]; A, D
    [h]; B, C [b, T, g, N] -> (y [b, T, h, P], the state each chunk closed
    on [b, T / Q, h, P, N]: the last one is the sequence's)."""
    b, T, h, P, g, N, r, n, side = _sizes(x, B)
    Q, W = CHUNK, P * side
    vma, (A, D, x, dt, B, C) = vary_alike(
        A, D, x.reshape(b, T, h * P), dt, B.reshape(b, T, g * N),
        C.reshape(b, T, g * N))

    def kernel(A_ref, D_ref, x_ref, dt_ref, B_ref, C_ref, y_ref, closed_ref, H):
        c, grp = pl.program_id(1), pl.program_id(2)
        of = _Chunk(Q, h, P, side)
        group = pl.multiple_of(grp * (r * P), r * P)

        @pl.when(c == 0)
        def _():
            H[pl.ds(group, r * P), :] = jnp.zeros((r * P, N), jnp.float32)

        Bm, Cm = B_ref[...], C_ref[...]
        cb = dot_f32(Cm, Bm, NT)                           # [t, s]
        steps = dt_ref[...]                              # [Q, h]

        def tile(k, _):
            lanes = pl.ds(pl.multiple_of(k * W, W), W)
            held = pl.ds(pl.multiple_of(group + k * W, W), W)
            x_t, H_t = x_ref[:, lanes], H[held, :]
            heads = of.heads(steps, grp * r + k * side, A_ref, D_ref, x_t)
            from_open, to_end, last, skip, written = of.tile(heads)
            # what the chunk opened on, the chunk's own part, the skip
            y_ref[:, lanes] = (
                from_open * dot_f32(Cm, H_t, NT)
                + sum(dot_f32(cb * hd.ratio, hd.written) for hd in heads)
                + skip * x_t)
            # the chunk's state and the boundary
            H_t = last * H_t + dot_f32(to_end * written, Bm, TN)         # [W, N]
            H_t = H_t.astype(state_dtype).astype(jnp.float32)
            H[held, :] = H_t
            closed_ref[lanes, :] = H_t

        jax.lax.fori_loop(0, r // side, tile, None)

    chunk_of_group = lambda w: pl.BlockSpec(  # noqa: E731
        (None, Q, w), lambda e, c, grp, *_: (e, c, grp))
    y, closed = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, T, h * P), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((b, n, h * P, N), jnp.float32, vma=vma)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n, g),
            in_specs=[
                chunk_of_group(r * P),
                pl.BlockSpec((None, Q, h), lambda e, c, grp, *_: (e, c, 0)),
                chunk_of_group(N), chunk_of_group(N)],
            out_specs=(
                chunk_of_group(r * P),
                pl.BlockSpec((None, None, r * P, N),
                             lambda e, c, grp, *_: (e, c, grp, 0))),
            scratch_shapes=[pltpu.VMEM((h * P, N), jnp.float32)],
        ),
        compiler_params=_params(),
        interpret=interpret,
        name=FORWARD_KERNEL,
    )(A, D, x, dt, B, C)
    return y.reshape(b, T, h, P), closed.reshape(b, n, h, P, N)


@functools.partial(jax.jit, static_argnames=("state_dtype", "interpret"))
def _backward(x, dt, A, B, C, D, closed, d_y, d_last, state_dtype=jnp.float32,
              interpret=False):
    """The cotangents of :func:`_forward`'s operands from those of ``y`` and
    of the last state, the chunks walked from the last to the first with the
    state's cotangent in fast memory and each chunk's matrices made again
    from the state it opened on: (dx, d dt, dB, dC as the operands lie; da,
    the cotangent of ``dt A``, and ``sum_p dy x``, [b, T, h] each: ``dA`` and
    ``dD`` are their sums over envs and positions)."""
    b, T, h, P, g, N, r, n, side = _sizes(x, B)
    Q, W = CHUNK, P * side
    vma, (A, D, x, dt, B, C, closed, d_y, d_last) = vary_alike(
        A, D, x.reshape(b, T, h * P), dt, B.reshape(b, T, g * N),
        C.reshape(b, T, g * N), closed.reshape(b, n, h * P, N),
        d_y.reshape(b, T, h * P), d_last.reshape(b, h * P, N))

    def kernel(A_ref, D_ref, x_ref, dt_ref, B_ref, C_ref, open_ref, dy_ref,
               dlast_ref, dx_ref, ddt_ref, da_ref, dskip_ref, dB_ref, dC_ref,
               dH, dcb):
        c, grp = pl.program_id(1), pl.program_id(2)  # c = 0: the LAST chunk
        of = _Chunk(Q, h, P, side)
        group = pl.multiple_of(grp * (r * P), r * P)

        @pl.when(c == 0)
        def _():
            dH[pl.ds(group, r * P), :] = dlast_ref[...]

        @pl.when(grp == 0)
        def _():
            for ref in (ddt_ref, da_ref, dskip_ref):
                ref[...] = jnp.zeros((Q, h), jnp.float32)

        Bm, Cm = B_ref[...], C_ref[...]
        cb = dot_f32(Cm, Bm, NT)
        steps = dt_ref[...]
        dcb[...] = jnp.zeros((Q, Q), jnp.float32)
        dB_ref[...] = jnp.zeros((Q, N), jnp.float32)
        dC_ref[...] = jnp.zeros((Q, N), jnp.float32)

        def tile(k, _):
            lanes = pl.ds(pl.multiple_of(k * W, W), W)
            held = pl.ds(pl.multiple_of(group + k * W, W), W)
            x_t, dy_t = x_ref[:, lanes], dy_ref[:, lanes]
            # the sequence's first chunk opened on zeros, every other on
            # what the one before it closed on
            H_t = jnp.where(c == n - 1, 0.0, open_ref[lanes, :])
            # through the boundary's rounding (float32: nothing)
            dH_t = dH[held, :].astype(state_dtype).astype(jnp.float32)
            from_H = dot_f32(Cm, H_t, NT)                    # [t, W]: H C_t
            to_S = dot_f32(Bm, dH_t, NT)                     # [s, W]: dS B_s
            heads = of.heads(steps, grp * r + k * side, A_ref, D_ref, x_t)
            from_open, to_end, last, skip, written = of.tile(heads)
            d_written = to_end * to_S
            d_a = []
            for i, hd in enumerate(heads):
                dy_mine = jnp.where(of.lane_head == i, dy_t, 0.0)
                # y = (cb ratio) written + ...
                d_mix = dot_f32(dy_t, hd.written, NT)        # [t, s]
                mix = cb * hd.ratio
                d_written = d_written + dot_f32(mix, dy_mine, TN)
                dcb[...] += d_mix * hd.ratio
                # ... + exp(L_t) H C_t; H' = exp(L_Q) H + S
                d_open = jnp.sum(dy_mine * from_H, axis=1, keepdims=True)
                d_open_last = jnp.sum(
                    jnp.where(of.row_head == i, dH_t * H_t, 0.0), keepdims=True)
                d_L = (d_open + jnp.where(of.at == Q - 1, d_open_last, 0.0)
                       ) * hd.from_open
                # S = sum_s exp(L_Q - L_s) written_s B_s^T
                d_end = jnp.sum(to_S * hd.written, axis=1, keepdims=True) * hd.to_end
                # the cotangent of each L_t - L_s: the mix's, L_t's in
                # column 0 (beside a_0), L_Q - L_s's in the last row; then of
                # a_l, which every (t, s) with t >= l > s holds
                d_seg = (d_mix * mix + jnp.where(of.col == 0, d_L, 0.0)
                         + jnp.where(of.row == Q - 1, of.as_row(d_end), 0.0))
                d_a.append(
                    jnp.sum(jnp.where(of.row > of.col, running_sum(d_seg, up=True), 0.0),
                            axis=1, keepdims=True)
                    + jnp.where(of.at == 0, jnp.sum(d_L, keepdims=True), 0.0))
            d_from_H = from_open * dy_t
            dC_ref[...] += dot_f32(d_from_H, H_t)
            dB_ref[...] += dot_f32(to_end * written, dH_t)
            dH[held, :] = last * dH_t + dot_f32(d_from_H, Cm, TN)
            dx_ref[:, lanes] = (
                of.of_heads([hd.dt for hd in heads]) * d_written + skip * dy_t)
            for i, hd in enumerate(heads):
                mine = of.lane_head == i
                d_dt = jnp.sum(jnp.where(mine, d_written * x_t, 0.0), axis=1,
                               keepdims=True) + d_a[i] * hd.A
                d_skip = jnp.sum(jnp.where(mine, dy_t * x_t, 0.0), axis=1,
                                 keepdims=True)
                for ref, value in ((ddt_ref, d_dt), (da_ref, d_a[i]),
                                   (dskip_ref, d_skip)):
                    ref[...] = jnp.where(of.head_lane == hd.head, value, ref[...])

        jax.lax.fori_loop(0, r // side, tile, None)
        dC_ref[...] += dot_f32(dcb[...], Bm)
        dB_ref[...] += dot_f32(dcb[...], Cm, TN)

    back = lambda c: n - 1 - c  # noqa: E731
    chunk_of_group = lambda w: pl.BlockSpec(  # noqa: E731
        (None, Q, w), lambda e, c, grp, *_: (e, back(c), grp))
    every_head = pl.BlockSpec((None, Q, h), lambda e, c, grp, *_: (e, back(c), 0))
    a_head = jax.ShapeDtypeStruct((b, T, h), jnp.float32, vma=vma)
    a_group = jax.ShapeDtypeStruct((b, T, g * N), jnp.float32, vma=vma)
    dx, ddt, da, dskip, dB, dC = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, T, h * P), jnp.float32, vma=vma),
            a_head, a_head, a_head, a_group, a_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n, g),
            in_specs=[
                chunk_of_group(r * P), every_head,
                chunk_of_group(N), chunk_of_group(N),
                # the state the chunk opened on: what the one before closed on
                pl.BlockSpec(
                    (None, None, r * P, N),
                    lambda e, c, grp, *_: (e, jnp.maximum(back(c) - 1, 0), grp, 0)),
                chunk_of_group(r * P),
                # the last state's cotangent, read at the last chunk alone
                # (an unchanged block index fetches nothing)
                pl.BlockSpec(
                    (None, r * P, N),
                    lambda e, c, grp, *_: (e, jnp.where(c == 0, grp, 0), 0))],
            out_specs=(
                chunk_of_group(r * P), every_head, every_head, every_head,
                chunk_of_group(N), chunk_of_group(N)),
            scratch_shapes=[pltpu.VMEM((h * P, N), jnp.float32),
                            pltpu.VMEM((Q, Q), jnp.float32)],
        ),
        compiler_params=_params(),
        interpret=interpret,
        name=BACKWARD_KERNEL,
    )(A, D, x, dt, B, C, closed, d_y, d_last)
    return (dx.reshape(b, T, h, P), ddt, dB.reshape(b, T, g, N),
            dC.reshape(b, T, g, N), da, dskip)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernel_chunked(x, dt, A, B, C, D, state_dtype, interpret):
    y, closed = _forward(x, dt, A, B, C, D, state_dtype, interpret)
    return y, closed[:, -1]


def _kernel_chunked_fwd(x, dt, A, B, C, D, state_dtype, interpret):
    y, closed = _forward(x, dt, A, B, C, D, state_dtype, interpret)
    return (y, closed[:, -1]), (x, dt, A, B, C, D, closed)


def _kernel_chunked_bwd(state_dtype, interpret, res, cotangents):
    x, dt, A, B, C, D, closed = res
    dx, ddt, dB, dC, da, dskip = _backward(
        x, dt, A, B, C, D, closed, *cotangents, state_dtype, interpret)
    return (dx, ddt, jnp.sum(da * dt, axis=(0, 1)), dB, dC,
            jnp.sum(dskip, axis=(0, 1)))


_kernel_chunked.defvjp(_kernel_chunked_fwd, _kernel_chunked_bwd)


def ssd_chunked(x, dt, A, B, C, D, chunk: int = CHUNK,
                state_dtype=jnp.float32):
    """Whole sequences from the zero state. ``x`` [b, T, h, P]; ``dt`` [b,
    T, h]; ``A``, ``D`` [h]; ``B``, ``C`` [b, T, g, N], float32 -> (y [b,
    T, h, P], the state after the last position [b, h, P, N]).
    ``state_dtype``: what the state is kept in between chunks (float32; a
    control's bfloat16). The kernels where :func:`kernels_take` the shapes,
    :func:`ssd_chunked_plain` anywhere else."""
    if not kernels_take(x, B, chunk):
        return ssd_chunked_plain(x, dt, A, B, C, D, chunk, state_dtype)
    T = x.shape[1]
    pad = -T % chunk
    if pad:
        x, dt, B, C = _whole_chunks(pad, x, dt, B, C)
    with device_scope(profiling.OP_MAMBA2_SSD_KERNEL):
        y, last = _kernel_chunked(x, dt, A, B, C, D, state_dtype, INTERPRET)
    return y[:, :T], last
