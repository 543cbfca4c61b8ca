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
projections' ``[3840, 3840]``).

**Two forms of the chunked form, one arithmetic.** :func:`delta_chunked_plain`
is the plain ``jax.numpy`` above: it writes every chunk's ``[64, 64]`` and
``[64, 288]`` float32 intermediates to HBM and reads them back, runs one
batched ``triangular_solve`` of 64 dependent rows and a checkpointed scan of
32 dependent boundary steps, three times an update: 653 ms of an 8.4 s update
at ``fused-olmohybrid-recall-32x2048``, against 20 ms by the bytes that have
to move (PERF.md, PR 41). **Two Pallas TPU kernels** keep a head's state and
a chunk's matrices in fast memory. Both walk a grid of (env, chunk), a chunk
after the one before it, with every head's state ``[h, 128, 256]`` in a
scratch of fast memory; per chunk only ``q``, ``k``, ``v``, ``alpha``,
``beta`` come in, as the layer lays them (a position's heads side by side in
one row), and ``o`` and the boundary states go out, which is what
``benchmark/opcount_olmohybrid.py:delta_rule_bytes`` counts. The heads are
worked through in a loop, two at a time (Mosaic unrolls what a body says):

- a head's 96 keys or 192 values are the two whole tiles of lanes they lie
  in, turned to lane 0 (``pltpu.roll`` by a dynamic amount) with the lanes
  beyond them zeroed, which costs the matrix unit nothing and keeps every
  product on whole tiles; results go back the same way, merged into the block
  under a mask. The keys' block reaches past the array's 960 lanes to the
  tile's edge: what lies there is masked before use and dropped on the way
  out;
- the two heads' ``[64, 64]`` matrices lie side by side in one ``[64, 128]``
  tile, so the running sums of ``log alpha`` (by doubling steps, each entry the
  sum of its own terms), the masks, the ``exp`` and the inverse serve both;
- **the solve is the inverse by halves**: a block's inverse from its two
  diagonal halves' ``D`` and its lower left quarter ``E`` is ``D - D E D``,
  from blocks of one position to the whole chunk in six doublings, two
  ``[64, 128] x [128, 128]`` products each for both heads at once. The other
  exact route, ``(I - N)^-1 = (I + N)(I + N^2) .. (I + N^32)``, costs the same
  eleven products and forms the powers of ``A``, whose entries grow like
  ``binom(63, 32) |a|^32`` where the keys of a chunk are alike (a conv and a
  SiLU before the normalisation give them a common part) and cancel in the
  product; by halves every entry is one the rule itself bounds;
- with the state at hand the system is solved for ``U`` itself, ``(I + A) U
  = beta (V - g K S_0)``, where the plain form, which solves before any state
  is known, solves for ``[U_0 | W]`` and takes ``U = U_0 - W S_0`` in the
  scan: the same sums in another order, a third less to solve;
- two products against the same matrix are one with their rows stacked (``K``
  over ``Q`` against the keys and against ``S_0``): the matrix unit takes a
  matrix in once for 128 rows as for 64.

- *forward* (``delta_chunks_forward``) writes ``o`` and the state every chunk
  closed on: the last one is the sequence's, the others are what the
  backward starts each chunk from (32 x 72 KB a head a sequence, what the
  checkpointed scan kept).
- *backward* (``delta_chunks_backward``) walks the chunks from the last to
  the first with the state's cotangent in the scratch, makes a chunk's
  matrices again from the state it opened on, and writes ``dq``, ``dk``,
  ``dv``, ``d log alpha`` and ``d beta`` as the operands lie (``d alpha`` from
  ``d log alpha`` outside, by the plain form's own clamped logarithm). With ``R = beta (V - g K S_0)`` and ``U =
  (I + A)^-1 R``: ``dR = (I + A)^-T dU`` and ``dA = -dR U^T`` below the
  diagonal. The cotangent of ``log alpha_l`` is what every ``(i, j)`` with ``i
  >= l > j`` holds of ``d ratio x ratio``: a running sum UP the rows, then a
  row's sum under the mask. The boundary's ``state_dtype`` rounding is the
  plain form's, forward and backward.

``delta_chunked`` is a ``jax.custom_vjp`` over the two. **Which form runs is
read off the input** (:func:`kernels_take`), as ``ops/ssd.py`` reads it: the
kernels on a TPU where a chunk is 64 positions, the heads go in pairs, ``K``
is whole sublanes within a tile's lanes, ``V`` wider than a tile, and every
head's lanes end within the two tiles they start in (the window a head is
read through), and the heads' states at once within fast memory; the plain
form anywhere else (the ``tiny`` cut, the CPU).
``benchmark/layer_metrics/delta_rule_roofline.py`` is the yardstick of either.
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

#: positions a chunk of the sequence form takes
CHUNK = 64
#: the kernels under Pallas's interpreter, whatever the backend: the tests'
#: way to run them on the CPU (tier-1 cannot run Mosaic)
INTERPRET = False
#: the kernels' names in a compiled program and in a capture
FORWARD_KERNEL, BACKWARD_KERNEL = "delta_chunks_forward", "delta_chunks_backward"
#: the least gate the sequence form tells from 0: its logarithm has to be
#: finite for the sums of logarithms to be
LEAST_GATE = 1e-37



def delta_step(S, q, k, v, alpha, beta):
    """One position. ``S`` [b, h, K, V]; ``q``, ``k`` [b, h, K]; ``v`` [b, h,
    V]; ``alpha``, ``beta`` [b, h] -> (S, o [b, h, V] float32). ``S`` keeps
    its type (a control keeps it in bfloat16); the arithmetic is float32."""
    kept = alpha[..., None, None] * S.astype(jnp.float32)
    u = beta[..., None] * (v - jnp.sum(kept * k[..., None], axis=-2))
    new = kept + k[..., None] * u[..., None, :]
    o = jnp.sum(new * q[..., None], axis=-2)
    return new.astype(S.dtype), o


def _log_gate(alpha):
    """``log alpha``, a gate under the least one taken as the least one (a
    finite logarithm, and no gradient to that gate)."""
    return jnp.log(jnp.maximum(alpha, LEAST_GATE))


def _chunk_step(state_dtype, S, xs):
    """A chunk from the state it opens on. ``S`` [b, h, K, V]; per chunk
    ``u0`` [b, h, C, V], ``w``, ``q_in``, ``k_out`` [b, h, C, K], ``qk`` [b,
    h, C, C], ``g_last`` [b, h] -> (S after the chunk, kept in
    ``state_dtype`` between chunks; o [b, h, C, V])."""
    u0, w, q_in, k_out, qk, g_last = xs
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)  # noqa: E731
    u = u0 - dot("bhck,bhkv->bhcv", w, S)
    o = dot("bhck,bhkv->bhcv", q_in, S) + dot("bhcj,bhjv->bhcv", qk, u)
    S = g_last[..., None, None] * S + dot("bhck,bhcv->bhkv", k_out, u)
    return S.astype(state_dtype).astype(S.dtype), o


def _whole_chunks(pad: int, q, k, v, alpha, beta):
    """The operands [b, T, ...] with ``pad`` more positions, which leave the
    state as it is: alpha 1, beta 0."""
    rows = lambda x, fill=0.0: jnp.pad(  # noqa: E731
        x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2), constant_values=fill)
    return rows(q), rows(k), rows(v), rows(alpha, 1.0), rows(beta)


def delta_chunked_plain(q, k, v, alpha, beta, chunk: int = CHUNK,
                        state_dtype=jnp.float32):
    """:func:`delta_chunked` in plain ``jax.numpy``: what runs wherever the
    kernels do not, and what they are held against."""
    b, T, h, K = q.shape
    C = min(chunk, T)
    pad = -T % C
    if pad:
        q, k, v, alpha, beta = _whole_chunks(pad, q, k, v, alpha, beta)
    N = (T + pad) // C

    def by_chunk(x):  # [b, T, h, ...] -> [b, h, N, C, ...]
        x = x.reshape(b, N, C, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, alpha, beta = (by_chunk(x) for x in (q, k, v, alpha, beta))
    log_alpha = _log_gate(alpha)
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
    dot = lambda spec, a, b: jnp.einsum(spec, a, b, precision=HIGHEST)  # noqa: E731
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


# -- the kernels -------------------------------------------------------------------
#: a head's keys and values in fast memory, on whole lanes: 96 in 128, 192 in 256
_WINDOW = 2 * LANE


#: what a kernel's scratch and blocks may take of fast memory: the compiler's
#: limit on a kernel's scope (a v5e: 16 MiB; what the body itself holds at a
#: time is small beside them)
_FAST_MEMORY = 16 * 2 ** 20


def _heads_in_window(h: int, width: int) -> bool:
    """Whether each of ``h`` heads' ``width`` lanes, side by side in a row,
    end within the two whole tiles the head starts in: all that
    ``_Chunk.take`` and ``put`` reach of a head (and with the last head's
    window the block of ``_blocks`` ends)."""
    return all(i * width % LANE + width <= _WINDOW for i in range(h))


def _tiles(h: int, width: int) -> int:
    """The lanes of a block of ``h`` heads of ``width`` side by side: the two
    tiles the last head's lanes lie in end it."""
    return ((h - 1) * width // LANE + 2) * LANE


def _fast_memory_held(h: int, K: int, V: int) -> int:
    """The bytes of fast memory the backward kernel's scratch and blocks
    take (the forward's are fewer): the state's cotangent of every head, and
    two buffers each of the state the chunk opened on and the last state's
    cotangent, of four blocks of keys, three of values and four of gates."""
    states = h * LANE * _WINDOW + 2 * 2 * h * K * _WINDOW
    rows = 2 * (4 * _tiles(h, K) + 3 * _tiles(h, V) + 4 * LANE)
    return 4 * (states + CHUNK * rows)


def kernels_take(q, v, chunk: int = CHUNK) -> bool:
    """Whether :func:`delta_chunked` runs its kernels on ``q`` [b, T, h, K]
    and ``v`` [b, T, h, V] in chunks of ``chunk``: on a TPU (or under the
    interpreter), where a chunk is half a tile's lanes of positions (two
    heads' ``[C, C]`` matrices fill one tile: the heads go in pairs), ``K``
    whole sublanes within a tile's lanes, ``V`` whole sublanes wider than a
    tile, every head's keys and values within the two tiles they start in
    (every ``h`` at ``V`` 136, 144, 160, 192 or 256; at 200 not the second
    head), and every head's state at once within fast memory (16 heads of 96
    x 192, 12 of 128 x 256)."""
    _, T, h, K = q.shape
    V = v.shape[-1]
    if not (INTERPRET or runs_mosaic()):
        return False
    return (chunk == CHUNK and T >= chunk and h % 2 == 0
            and K % 8 == 0 and K <= LANE and _heads_in_window(h, K)
            and V % 8 == 0 and LANE < V <= _WINDOW and _heads_in_window(h, V)
            and _fast_memory_held(h, K, V) <= _FAST_MEMORY)


class _Chunk:
    """What both kernels make of a chunk in fast memory, two heads at a time.
    The pair's ``[C, C]`` matrices lie side by side in one tile ``[C, 2 C]``
    (positions ``i`` down the rows, ``j`` along each half's lanes), so the
    elementwise work and the inverse's products serve both; a head's keys
    ``[C, 128]`` and values ``[C, 256]`` lie from lane 0, zeros beyond ``K``
    and ``V``."""

    def __init__(self, h: int):
        C = CHUNK
        lane = jax.lax.broadcasted_iota(jnp.int32, (C, LANE), 1)
        self.row = jax.lax.broadcasted_iota(jnp.int32, (C, LANE), 0)
        self.col, self.second = lane % C, lane >= C
        self.at = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        self.state_row = jax.lax.broadcasted_iota(jnp.int32, (LANE, 1), 0)
        self.below, self.live = self.row > self.col, self.row >= self.col
        self.diagonal = self.row == self.col
        self.head_lane = jax.lax.broadcasted_iota(jnp.int32, (C, h), 1)
        self.lane = jax.lax.broadcasted_iota(jnp.int32, (C, _WINDOW), 1)

    # a head's lanes of a block that holds every head's side by side, as the
    # layer lays them: the two whole tiles its lanes lie in, turned to lane 0
    def _lanes_of(self, head, width):
        first = head * width
        start = pl.multiple_of((first // LANE) * LANE, LANE)
        return pl.ds(start, _WINDOW), first - start

    def take(self, ref, head, width):
        """Head ``head``'s ``width`` lanes of ``ref`` [C, >= h width] -> [C,
        128] (``width`` within a tile) or [C, 256], zeros beyond them."""
        lanes, off = self._lanes_of(head, width)
        local = pltpu.roll(ref[:, lanes], (_WINDOW - off) % _WINDOW, 1)
        local = jnp.where(self.lane < width, local, 0.0)
        return local if width > LANE else local[:, :LANE]

    def put(self, ref, head, width, value):
        """``value`` [C, 128 or 256] from lane 0 -> head ``head``'s lanes of
        ``ref``; the other heads' lanes stay as they are."""
        lanes, off = self._lanes_of(head, width)
        if value.shape[1] < _WINDOW:
            value = jnp.concatenate([value, jnp.zeros_like(value)], axis=1)
        mine = (self.lane >= off) & (self.lane < off + width)
        ref[:, lanes] = jnp.where(mine, pltpu.roll(value, off, 1), ref[:, lanes])

    def column(self, per_head, head):
        """[C, h] -> head ``head``'s [C, 1]."""
        return jnp.sum(jnp.where(self.head_lane == head, per_head, 0.0), axis=1,
                       keepdims=True)

    def set_column(self, ref, head, value):
        ref[...] = jnp.where(self.head_lane == head, value, ref[...])

    # the pair's tile: the first head's half, the second's
    def pair(self, first, second):
        """Two heads' [C, 1] (or [C, 2 C]) -> [C, 2 C], each on its half."""
        return jnp.where(self.second, second, first)

    def half(self, m, i):
        """[C, 2 C] -> head ``i``'s half, zeros on the other's."""
        return jnp.where(self.second == bool(i), m, 0.0)

    def total(self, m, i):
        """The sums along head ``i``'s half of [C, 2 C] -> [C, 1]."""
        return jnp.sum(self.half(m, i), axis=1, keepdims=True)

    def as_column(self, row, i):
        """Head ``i``'s half of [1, 2 C] along the lanes -> [C, 1] down the
        rows."""
        spread = jnp.broadcast_to(row, self.row.shape)
        return self.total(jnp.where(self.diagonal, spread, 0.0), i)

    def as_row(self, first, second):
        """Two heads' [C, 1] down the rows -> [1, 2 C] along the lanes."""
        return jnp.sum(jnp.where(self.diagonal, self.pair(first, second), 0.0),
                       axis=0, keepdims=True)

    @staticmethod
    def rows(x, i):
        """[C, n] -> [2 C, n], head ``i``'s half of the rows and zeros on the
        other: what head ``i``'s half of a ``[C, 2 C]`` matrix multiplies."""
        return jnp.concatenate([jnp.zeros_like(x), x] if i
                               else [x, jnp.zeros_like(x)], axis=0)

    def each(self, m):
        """[C, 2 C] -> [2 C, 2 C], each head's half on its own rows: what the
        pair's matrices multiply, each head its own."""
        return jnp.concatenate([self.half(m, 0), self.half(m, 1)], axis=0)

    def inverse(self, A):
        """``A`` strictly lower triangular (a head a half) -> ``(I + A)^-1``,
        by halves: the inverse of a block's two diagonal halves ``D`` and the
        block's lower left quarter ``E`` give the block's, ``D - D E D``;
        from blocks of one position (the identity) to the whole chunk, two
        products a doubling for both heads. Every entry is a sum the rule
        itself bounds (a product of the chunk's reflections), where the
        powers of ``A`` are not."""
        D = jnp.where(self.diagonal, 1.0, 0.0)
        half = 1
        while half < CHUNK:
            both = 2 * half
            quarter = ((self.row // both == self.col // both)
                       & (self.row % both >= half) & (self.col % both < half))
            E = jnp.where(quarter, A, 0.0)
            D = D - (E if half == 1
                     else dot_f32(dot_f32(D, self.each(E)), self.each(D)))
            half = both
        return D

    def heads(self, q, k, v, alpha, beta, S):
        """A pair of heads' chunk from the states they open on (every operand
        a pair), ``delta_chunked_plain``'s arithmetic (the mask before the
        exp, each ``g_i / g_j`` summed from its own ``j``) with the state at
        hand: ``(I + A) U = beta (V - g K S)`` is solved for ``U`` itself.
        Two products against the same matrix are one, their rows stacked."""
        C, pair = CHUNK, (0, 1)
        log_alpha = [_log_gate(a) for a in alpha]
        seg = running_sum(jnp.where(self.below, self.pair(*log_alpha), 0.0))
        ratio = jnp.exp(jnp.where(self.live, seg, -jnp.inf))       # g_i / g_j
        # g_i: column 0's sum (log alpha_1 .. log alpha_i) and log alpha_0
        g = [jnp.exp(self.total(jnp.where(self.col == 0, seg, 0.0), i)
                     + log_alpha[i][0:1, :]) for i in pair]
        to_end = [self.as_column(ratio[C - 1:C, :], i) for i in pair]  # g_C / g_j
        kq = [jnp.concatenate([k[i], q[i]], axis=0) for i in pair]
        keys = [self.rows(k[i], i) for i in pair]
        both = sum(dot_f32(kq[i], keys[i], NT) for i in pair)
        kk, qk = both[:C], both[C:]
        inv = self.inverse(jnp.where(
            self.below, self.pair(*beta) * ratio * kk, 0.0))
        out = []
        for i in pair:
            on_state = dot_f32(kq[i], S[i])
            kS, qS = on_state[:C], on_state[C:]
            rest = v[i] - g[i] * kS
            out.append(_Head(
                g=g[i], to_end=to_end[i], kq=kq[i], keys=keys[i], kS=kS, qS=qS,
                rest=rest, U=dot_f32(inv, self.rows(beta[i] * rest, i)),
                # g_C down a state's rows (a select: Mosaic spreads [1, 1]
                # one way at a time)
                g_last=jnp.where(self.state_row >= 0, g[i][C - 1:C, :], 0.0)))
        return _Pair(ratio=ratio, kk=kk, qk=qk, inv=inv), out


class _Pair(NamedTuple):
    """A pair of heads' ``[C, 2 C]`` matrices, a head a half: ``ratio`` (g_i
    / g_j for j <= i, 0 elsewhere), ``kk``, ``qk`` (k_i . k_j, q_i . k_j),
    ``inv`` ((I + A)^-1)."""
    ratio: jax.Array
    kk: jax.Array
    qk: jax.Array
    inv: jax.Array


class _Head(NamedTuple):
    """A head's chunk: ``g`` [C, 1], ``g_last`` [128, 1] (g_C), ``to_end``
    [C, 1] (g_C / g_j), ``kq`` [2 C, 128] (K over Q), ``keys`` [2 C, 128] (K
    on the head's half of the rows), ``kS``, ``qS`` [C, 256] (K S_0, Q S_0),
    ``rest`` (V - g K S_0) and the pseudo-values ``U``."""
    g: jax.Array
    g_last: jax.Array
    to_end: jax.Array
    kq: jax.Array
    keys: jax.Array
    kS: jax.Array
    qS: jax.Array
    rest: jax.Array
    U: jax.Array


def _params():
    # no ``cost_estimate`` (PERF.md, PR 33). The envs are free to split over
    # cores; a chunk follows the one before it
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _flat(q, k, v):
    """The heads side by side as the layer lays them: [b, T, h K], [b, T, h
    V]."""
    b, T, h, K = q.shape
    return q.reshape(b, T, h * K), k.reshape(b, T, h * K), v.reshape(b, T, -1)


def _blocks(h, K, V, chunk_at):
    """The block of a chunk's keys (whole tiles: the last one reaches past
    the heads' lanes where ``h K`` ends inside a tile), of its values and of
    its gates."""
    at = lambda w: pl.BlockSpec(  # noqa: E731
        (None, CHUNK, w), lambda e, c: (e, chunk_at(c), 0))
    return at(_tiles(h, K)), at(_tiles(h, V)), at(h)


# each kernel a ``jax.jit`` of its own: the linear layers of a policy share
# one trace and one lowering to Mosaic, which is set-up
@functools.partial(jax.jit, static_argnames=("state_dtype", "interpret"))
def _forward(q, k, v, alpha, beta, state_dtype=jnp.float32, interpret=False):
    """Whole chunks from the zero state: q, k [b, T, h, K]; v [b, T, h, V];
    alpha, beta [b, T, h] -> (o [b, T, h, V], the state each chunk closed on
    [b, T / C, h, K, 256], zeros beyond ``V``: the last one is the
    sequence's)."""
    b, T, h, K = q.shape
    V, n = v.shape[-1], T // CHUNK
    vma, (q, k, v, alpha, beta) = vary_alike(*_flat(q, k, v), alpha, beta)

    def kernel(q_ref, k_ref, v_ref, alpha_ref, beta_ref, o_ref, closed_ref, S):
        of = _Chunk(h)

        @pl.when(pl.program_id(1) == 0)
        def _():
            S[...] = jnp.zeros(S.shape, jnp.float32)

        gates, sizes = alpha_ref[...], beta_ref[...]

        def two_heads(p, _):
            at = (2 * p, 2 * p + 1)
            k_h = [of.take(k_ref, i, K) for i in at]
            S_h = [S[i] for i in at]
            both, hds = of.heads(
                [of.take(q_ref, i, K) for i in at], k_h,
                [of.take(v_ref, i, V) for i in at],
                [of.column(gates, i) for i in at],
                [of.column(sizes, i) for i in at], S_h)
            mix = both.ratio * both.qk
            for j, (i, hd) in enumerate(zip(at, hds)):
                of.put(o_ref, i, V, hd.g * hd.qS + dot_f32(mix, of.rows(hd.U, j)))
                new = hd.g_last * S_h[j] + dot_f32(hd.to_end * k_h[j], hd.U, TN)
                new = new.astype(state_dtype).astype(jnp.float32)
                S[i] = new
                closed_ref[i] = new[:K]

        jax.lax.fori_loop(0, h // 2, two_heads, None)

    keys, values, gates = _blocks(h, K, V, lambda c: c)
    o, closed = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, T, h * V), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((b, n, h, K, _WINDOW), jnp.float32, vma=vma)),
        grid=(b, n),
        in_specs=[keys, keys, values, gates, gates],
        out_specs=(
            values,
            pl.BlockSpec((None, None, h, K, _WINDOW), lambda e, c: (e, c, 0, 0, 0))),
        scratch_shapes=[pltpu.VMEM((h, LANE, _WINDOW), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name=FORWARD_KERNEL,
    )(q, k, v, alpha, beta)
    return o.reshape(b, T, h, V), closed


@functools.partial(jax.jit, static_argnames=("state_dtype", "interpret"))
def _backward(q, k, v, alpha, beta, closed, d_o, d_last,
              state_dtype=jnp.float32, interpret=False):
    """The cotangents of :func:`_forward`'s operands from those of ``o`` and
    of the last state [b, h, K, 256], the chunks walked from the last to the
    first with the state's cotangent in fast memory and each chunk's matrices
    made again from the state it opened on: (dq, dk, dv, d log alpha, d beta)
    as the operands lie."""
    b, T, h, K = q.shape
    V, n, C = v.shape[-1], T // CHUNK, CHUNK
    vma, (q, k, v, d_o, alpha, beta, closed, d_last) = vary_alike(
        *_flat(q, k, v), d_o.reshape(b, T, h * V), alpha, beta, closed, d_last)

    def kernel(q_ref, k_ref, v_ref, do_ref, alpha_ref, beta_ref, open_ref,
               dlast_ref, dq_ref, dk_ref, dv_ref, dla_ref, dbeta_ref, dS):
        of = _Chunk(h)
        c = pl.program_id(1)  # c = 0: the LAST chunk

        @pl.when(c == 0)
        def _():
            dS[:, :K, :] = dlast_ref[...]
            if K < LANE:
                dS[:, K:, :] = jnp.zeros((h, LANE - K, _WINDOW), jnp.float32)

        gates, sizes = alpha_ref[...], beta_ref[...]
        row_sum = lambda x: jnp.sum(x, axis=1, keepdims=True)  # noqa: E731
        halves = lambda x, j: x[C:] if j else x[:C]  # noqa: E731

        def opened_on(i):
            # the sequence's first chunk opened on zeros, every other on what
            # the one before it closed on; a state's rows to the tile's edge
            S = jnp.where(c == n - 1, 0.0, open_ref[i])
            return S if K == LANE else jnp.concatenate(
                [S, jnp.zeros((LANE - K, _WINDOW), jnp.float32)], axis=0)

        def two_heads(p, _):
            at = (2 * p, 2 * p + 1)
            k_h = [of.take(k_ref, i, K) for i in at]
            do_h = [of.take(do_ref, i, V) for i in at]
            beta_h = [of.column(sizes, i) for i in at]
            S_h = [opened_on(i) for i in at]
            # through the boundary's rounding (float32: nothing)
            dS_h = [dS[i].astype(state_dtype).astype(jnp.float32) for i in at]
            both, hds = of.heads(
                [of.take(q_ref, i, K) for i in at], k_h,
                [of.take(v_ref, i, V) for i in at],
                [of.column(gates, i) for i in at], beta_h, S_h)
            mix = both.ratio * both.qk
            # S_C = g_C S_0 + (g_C / g K)^T U;  O = g Q S_0 + (ratio Q K^T) U;
            # U = (I + A)^-1 beta (V - g K S_0)
            d_R, d_kout, against_U = [], [], 0.0
            for j, hd in enumerate(hds):
                d_U = (halves(dot_f32(mix, do_h[j], TN), j)
                       + dot_f32(hd.to_end * k_h[j], dS_h[j]))
                d_R.append(halves(dot_f32(both.inv, d_U, TN), j))
                d_kout.append(dot_f32(hd.U, dS_h[j], NT))
                against_U = against_U + dot_f32(
                    jnp.concatenate([do_h[j], d_R[j]], axis=0),
                    of.rows(hd.U, j), NT)
            d_mix = jnp.where(of.live, against_U[:C], 0.0)
            d_A = jnp.where(of.below, -against_U[C:], 0.0)
            # A = beta ratio K K^T below the diagonal
            sizes_both = of.pair(*beta_h)
            d_kk, d_qk = d_A * sizes_both * both.ratio, d_mix * both.ratio
            d_keys = jnp.concatenate([d_kk, d_qk], axis=0)  # as K over Q
            d_L, d_end = [], []
            for j, (i, hd) in enumerate(zip(at, hds)):
                d_rest = beta_h[j] * d_R[j]
                d_kS = -hd.g * d_rest
                g_do = hd.g * do_h[j]
                d_g = (row_sum(do_h[j] * hd.qS) - row_sum(d_rest * hd.kS)
                       + jnp.where(of.at == C - 1,
                                   jnp.sum(dS_h[j] * S_h[j], keepdims=True), 0.0))
                of.set_column(
                    dbeta_ref, i, row_sum(d_R[j] * hd.rest)
                    + of.total(d_A * both.ratio * both.kk, j))
                of.put(dv_ref, i, V, d_rest)
                d_on_state = jnp.concatenate([d_kS, g_do], axis=0)
                from_state = dot_f32(d_on_state, S_h[j], NT)      # dk over dq
                from_keys = dot_f32(d_keys, hd.keys)                # dk over dq
                from_rows = halves(dot_f32(d_keys, hd.kq, TN), j)  # dk
                of.put(dq_ref, i, K, from_state[C:] + from_keys[C:])
                of.put(dk_ref, i, K,
                       from_state[:C] + from_keys[:C] + from_rows
                       + hd.to_end * d_kout[j])
                dS[i] = hd.g_last * dS_h[j] + dot_f32(hd.kq, d_on_state, TN)
                d_L.append(d_g * hd.g)
                d_end.append(row_sum(d_kout[j] * k_h[j]) * hd.to_end)
            # the cotangent of each sum of logarithms: the ratio's, g_i's in
            # column 0 (beside log alpha_0), g_C / g_j's in the last row; then
            # of log alpha_l, which every (i, j) with i >= l > j holds
            d_seg = ((d_A * sizes_both * both.kk + d_mix * both.qk) * both.ratio
                     + jnp.where(of.col == 0, of.pair(*d_L), 0.0)
                     + jnp.where(of.row == C - 1, of.as_row(*d_end), 0.0))
            held = jnp.where(of.below, running_sum(d_seg, up=True), 0.0)
            for j, i in enumerate(at):
                of.set_column(dla_ref, i, of.total(held, j) + jnp.where(
                    of.at == 0, jnp.sum(d_L[j], keepdims=True), 0.0))

        jax.lax.fori_loop(0, h // 2, two_heads, None)

    back = lambda c: n - 1 - c  # noqa: E731
    keys, values, gates = _blocks(h, K, V, back)
    a_key = jax.ShapeDtypeStruct((b, T, h * K), jnp.float32, vma=vma)
    a_gate = jax.ShapeDtypeStruct((b, T, h), jnp.float32, vma=vma)
    dq, dk, dv, dla, dbeta = pl.pallas_call(
        kernel,
        out_shape=(a_key, a_key,
                   jax.ShapeDtypeStruct((b, T, h * V), jnp.float32, vma=vma),
                   a_gate, a_gate),
        grid=(b, n),
        in_specs=[
            keys, keys, values, values, gates, gates,
            # the state the chunk opened on: what the one before closed on
            pl.BlockSpec((None, None, h, K, _WINDOW),
                         lambda e, c: (e, jnp.maximum(back(c) - 1, 0), 0, 0, 0)),
            pl.BlockSpec((None, h, K, _WINDOW), lambda e, c: (e, 0, 0, 0))],
        out_specs=(keys, keys, values, gates, gates),
        scratch_shapes=[pltpu.VMEM((h, LANE, _WINDOW), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name=BACKWARD_KERNEL,
    )(q, k, v, d_o, alpha, beta, closed, d_last)
    return (dq.reshape(b, T, h, K), dk.reshape(b, T, h, K),
            dv.reshape(b, T, h, V), dla, dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kernel_chunked(q, k, v, alpha, beta, state_dtype, interpret):
    return _kernel_chunked_fwd(q, k, v, alpha, beta, state_dtype, interpret)[0]


def _kernel_chunked_fwd(q, k, v, alpha, beta, state_dtype, interpret):
    o, closed = _forward(q, k, v, alpha, beta, state_dtype, interpret)
    return ((o, closed[:, -1, :, :, :v.shape[-1]]),
            (q, k, v, alpha, beta, closed))


def _kernel_chunked_bwd(state_dtype, interpret, res, cotangents):
    q, k, v, alpha, beta, closed = res
    d_o, d_last = cotangents
    d_last = jnp.pad(d_last, ((0, 0),) * 3 + ((0, _WINDOW - v.shape[-1]),))
    dq, dk, dv, dla, dbeta = _backward(
        q, k, v, alpha, beta, closed, d_o, d_last, state_dtype, interpret)
    # d log alpha -> d alpha as the plain form takes it, at the least gate too
    (d_alpha,) = jax.vjp(_log_gate, alpha)[1](dla)
    return dq, dk, dv, d_alpha, dbeta


_kernel_chunked.defvjp(_kernel_chunked_fwd, _kernel_chunked_bwd)


def delta_chunked(q, k, v, alpha, beta, chunk: int = CHUNK,
                  state_dtype=jnp.float32):
    """Whole sequences from the zero state. ``q``, ``k`` [b, T, h, K]; ``v``
    [b, T, h, V]; ``alpha``, ``beta`` [b, T, h], float32 -> (o [b, T, h, V],
    the state after the last position [b, h, K, V]). ``state_dtype``: what
    the state is kept in between chunks (float32; a control's bfloat16). The
    kernels where :func:`kernels_take` the shapes, :func:`delta_chunked_plain`
    anywhere else."""
    if not kernels_take(q, v, chunk):
        return delta_chunked_plain(q, k, v, alpha, beta, chunk, state_dtype)
    T = q.shape[1]
    pad = -T % chunk
    if pad:
        q, k, v, alpha, beta = _whole_chunks(pad, q, k, v, alpha, beta)
    with device_scope(profiling.OP_LINATTN_DELTA_KERNEL):
        o, last = _kernel_chunked(q, k, v, alpha, beta, state_dtype, INTERPRET)
    return o[:, :T], last
