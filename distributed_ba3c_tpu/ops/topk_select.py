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

Exact for every input (no ``approx_max_k``); nothing here is differentiated.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_SIGN = jnp.uint32(0x80000000)


def ordered_bits(x):
    """float32 -> uint32 that orders as the floats do (-0.0 as 0.0: equal,
    as a sort's comparison has them)."""
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(bits & _SIGN != 0, ~bits, bits | _SIGN)


def _count(mask):
    return jnp.sum(mask, axis=-1, keepdims=True, dtype=jnp.int32)


def select_mask(scores, live, k: int):
    """The top ``k`` of each row's live entries, ties to the lower position."""
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
