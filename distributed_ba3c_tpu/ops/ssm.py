"""The selective scan of a state-space (Mamba, S6) layer, in float32.

For every channel ``c`` of ``d_inner`` and every one of its ``d_state``
states ``n``, over positions ``t``:

    s_t[n, c] = exp(dt_t[c] * A[n, c]) * s_{t-1}[n, c] + dt_t[c] * u_t[c] * B_t[n]
    y_t[c]    = sum_n s_t[n, c] * C_t[n]  +  D[c] * u_t[c]

``A`` is negative (``-exp(A_log)``), so a state decays; ``dt``, ``B`` and
``C`` depend on the input (the selection). Two forms, one recurrence:

- :func:`scan_step`: one position from a state, the rollout's decode step;
- :func:`selective_scan`: a whole sequence from the zero state, the
  learner's. Its positions run in chunks of :data:`SCAN_CHUNK`, each chunk a
  ``jax.checkpoint``: the backward keeps the state at the chunk boundaries
  (``T / SCAN_CHUNK`` states) and recomputes one chunk's states at a time,
  never ``[T, d_state, d_inner]`` a layer (1.34 GB in float32 at 4 x 1,024
  positions of 5,120 channels x 16 states).

The state lies ``[batch, d_state, d_inner]``: the channels on the lanes
(5,120 = 40 x 128), the 16 states on the sublanes. Plain ``jax.numpy``: no
kernel yet; ``benchmark/layer_metrics/ssm_scan_roofline.py`` is its yardstick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: positions a checkpointed chunk of the sequence form runs (the states one
#: chunk's backward holds: 64 x [batch, 16, 5120] float32 = 84 MB at 4 rows)
SCAN_CHUNK = 64
#: positions a loop iteration of the sequence form computes: the body of a
#: position is a handful of small elementwise loops, and the loop's own cost
#: a trip is of their size
SCAN_UNROLL = 8


def scan_step(state, u, dt, A, B, C, D):
    """One position. ``state`` [b, n, c]; ``u``, ``dt`` [b, c]; ``A`` [n, c];
    ``B``, ``C`` [b, n]; ``D`` [c], all float32 -> (state, y [b, c])."""
    decay = jnp.exp(dt[:, None, :] * A)
    state = decay * state + (dt * u)[:, None, :] * B[:, :, None]
    y = jnp.sum(state * C[:, :, None], axis=1) + D * u
    return state, y


def chunk_length(T: int, most: int) -> int:
    """The largest divisor of ``T`` that is at most ``most``."""
    return max(n for n in range(1, min(T, most) + 1) if T % n == 0)


def _run_chunk(A, D, state, xs):
    def one(s, x):
        u, dt, B, C = x
        return scan_step(s, u, dt, A, B, C, D)

    return jax.lax.scan(one, state, xs, unroll=min(SCAN_UNROLL, xs[0].shape[0]))


def selective_scan(u, dt, A, B, C, D, chunk: int = SCAN_CHUNK):
    """Whole sequences from the zero state. ``u``, ``dt`` [b, T, c]; ``A``
    [n, c]; ``B``, ``C`` [b, T, n]; ``D`` [c] -> (y [b, T, c], the state
    after the last position [b, n, c])."""
    b, T, c = u.shape
    L = chunk_length(T, chunk)

    def by_chunk(x):  # [b, T, w] -> [T / L, L, b, w]: time leads a scan
        return jnp.swapaxes(x, 0, 1).reshape(T // L, L, b, x.shape[-1])

    run = jax.checkpoint(_run_chunk)
    # zeros that vary as the inputs do (under shard_map a constant would be
    # typed as the same on every shard, and the scan's carry is not)
    state = jnp.zeros_like(dt[:, 0])[:, None, :] * jnp.zeros_like(A)
    state, y = jax.lax.scan(
        functools.partial(run, A, D), state,
        (by_chunk(u), by_chunk(dt), by_chunk(B), by_chunk(C)),
    )
    return jnp.swapaxes(y.reshape(T, b, c), 0, 1), state
