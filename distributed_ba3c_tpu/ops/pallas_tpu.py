"""What the modules of Pallas TPU kernels share (``decode_attention``,
``sparse_attention``, ``grouped_matmul``, ``topk_select``, ``ssd``,
``delta_rule``): the lane width, which backend runs Mosaic, how a
``pallas_call`` varies under ``shard_map``, and the float32 products and
running sum the two recurrences' kernels are written in.

Each kernel module keeps its own ``INTERPRET`` (a test engages one kernel at
a time through it) and its own rule for the shapes its kernels take
(``kernels_take``, ``tile_of``, ...): that is the module's decision. It
imports :func:`runs_mosaic` by name, so a test that compiles one module's
kernels for a described v5e patches that module's binding and no other's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

LANE = 128  # every tile is whole lanes: the shapes have to be

HIGHEST = jax.lax.Precision.HIGHEST
#: ``dot_general`` dimension numbers of a product with one operand transposed
NT = (((1,), (1,)), ((), ()))  # [m, k] x [n, k] -> [m, n]
TN = (((0,), (0,)), ((), ()))  # [k, m] x [k, n] -> [m, n]


def runs_mosaic() -> bool:
    return jax.default_backend() == "tpu"


def vary_alike(*arrays):
    """(the mesh axes any of ``arrays`` varies over, ``arrays`` all varying
    over those): under ``shard_map`` a ``pallas_call`` has to say over which
    axes its output varies, and its body's operands have to agree."""
    vma = frozenset().union(*(jax.typeof(a).vma for a in arrays))
    return vma, tuple(
        jax.lax.pcast(a, tuple(vma - jax.typeof(a).vma), to="varying")
        if vma - jax.typeof(a).vma else a for a in arrays)


# -- inside a kernel's body -----------------------------------------------------
def dot_f32(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at the highest precision. Mosaic rounds the operands
    of a product that does not ask (the interpreter does not: tier-1 cannot
    tell; ``chip_smoke.py --phase ssd`` does)."""
    return jax.lax.dot_general(
        a, b, dims, precision=HIGHEST, preferred_element_type=jnp.float32)


def running_sum(m, up: bool = False):
    """The running sum of ``m`` [Q, Q] down its rows (``up``: from the last
    row up), by doubling steps: every entry the sum of its own terms and of
    nothing that has to cancel."""
    Q = m.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, m.shape, 0)
    step = 1
    while step < Q:
        if up:  # row t takes row t + step
            m = m + jnp.where(row < Q - step, pltpu.roll(m, Q - step, 0), 0.0)
        else:  # row t takes row t - step
            m = m + jnp.where(row >= step, pltpu.roll(m, step, 0), 0.0)
        step *= 2
    return m
