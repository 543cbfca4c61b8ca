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
  another (a scan whose carry is ``H``), each under ``jax.checkpoint``: one
  chunk's ``[Q, Q]`` matrices are live at a time, forward and backward, and
  the backward's residuals are the inputs and the state at the chunk
  boundaries (``T / Q`` states a head, 33.5 MB a sequence a layer at 2,048
  positions of 64 heads of 64 x 128), never a ``[T, heads, P, N]`` array
  and never every chunk's ``[Q, Q]`` matrices at once.

Everything is float32 and every product is at the highest precision, as
``ops/delta_rule.py``'s are and for its reason: the state is the layer's
memory of the whole episode, and the recurrence's products are a fiftieth
of the layer's. Plain ``jax.numpy``: no kernel yet;
``benchmark/layer_metrics/ssd_roofline.py`` is its yardstick.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions a chunk of the sequence form takes (the config's ``chunk_size``)
CHUNK = 128

_HIGHEST = jax.lax.Precision.HIGHEST


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


def ssd_chunked(x, dt, A, B, C, D, chunk: int = CHUNK,
                state_dtype=jnp.float32):
    """Whole sequences from the zero state. ``x`` [b, T, h, P]; ``dt`` [b,
    T, h]; ``A``, ``D`` [h]; ``B``, ``C`` [b, T, g, N], float32 -> (y [b,
    T, h, P], the state after the last position [b, h, P, N]).
    ``state_dtype``: what the state is kept in between chunks (float32; a
    control's bfloat16)."""
    b, T, h, P = x.shape
    g, N = B.shape[2:]
    r = h // g
    Q = min(chunk, T)
    pad = -T % Q
    if pad:  # positions that leave the state as it is: dt 0 decays and writes nothing
        rows = lambda v: jnp.pad(  # noqa: E731
            v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        x, dt, B, C = rows(x), rows(dt), rows(B), rows(C)
    n = (T + pad) // Q
    dot = lambda spec, *ops: jnp.einsum(spec, *ops, precision=_HIGHEST)  # noqa: E731

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
