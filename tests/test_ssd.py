"""The Mamba-2 recurrence (``ops/ssd.py``): its chunked form against its
one-position form iterated, and both against the plain reference's
recurrence (``benchmark/reference/nemotron_h.py``), in float32 on the CPU;
the chunked form's gradients against those through the plain scan; what its
backward keeps."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import nemotron_h as reference  # noqa: E402
from distributed_ba3c_tpu.ops import ssd  # noqa: E402

B, H, P, N = 2, 4, 8, 16
#: where ``dt A`` lies: near 0 (a state that forgets nothing), strongly
#: negative (one that forgets everything: above the diagonal ``L_t - L_s``
#: would overflow an unmasked ``exp``), either by turns
STEPS = {
    "near-zero": lambda u: 1e-4 * u,
    "strongly-negative": lambda u: 40.0 + 40.0 * u,
    "mixed": lambda u: jnp.where(u < 0.5, 1e-3 * u, 20.0 * u),
}
ARGS = ("x", "dt", "A", "B", "C", "D")


def inputs(seed, T, steps="mixed", groups=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (B, T, H, P))
    dt = STEPS[steps](jax.random.uniform(keys[1], (B, T, H)))
    A = -jnp.exp(jax.random.normal(keys[2], (H,)))
    Bm = jax.random.normal(keys[3], (B, T, groups, N))
    Cm = jax.random.normal(keys[4], (B, T, groups, N))
    D = jax.random.normal(keys[5], (H,))
    return x, dt, A, Bm, Cm, D


def stepped(x, dt, A, Bm, Cm, D):
    """``ssd_step`` iterated from the zero state: the plain scan."""
    by_time = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    state, y = jax.lax.scan(
        lambda S, at: ssd.ssd_step(S, at[0], at[1], A, at[2], at[3], D),
        jnp.zeros((B, H, P, N), jnp.float32),
        tuple(by_time(v) for v in (x, dt, Bm, Cm)))
    return by_time(y), state


@functools.lru_cache(maxsize=None)
def _three_forms(chunk):
    """One compiled program a shape: the step sizes' cases share it."""
    return jax.jit(lambda *args: (
        stepped(*args), ssd.ssd_chunked(*args, chunk=chunk),
        reference.recurrence(*args)))


@pytest.mark.parametrize("steps", sorted(STEPS))
@pytest.mark.parametrize("T,chunk,groups", [
    (16, 16, 2), (96, 16, 2), (37, 16, 2), (7, 16, 2), (13, 4, 1), (1, 16, 4)],
    ids=["one-chunk", "many-chunks", "no-multiple-of-16", "shorter-than-a-chunk",
         "one-group-of-every-head", "one-position-a-group-a-head"])
def test_chunked_stepped_and_the_references_recurrence_agree(T, chunk, groups, steps):
    args = inputs(T, T, steps, groups)
    (y_step, s_step), (y_chunk, s_chunk), y_ref = _three_forms(chunk)(*args)
    assert y_chunk.shape == (B, T, H, P) and s_chunk.shape == (B, H, P, N)
    scale = max(float(jnp.abs(y_ref).max()), 1.0)
    np.testing.assert_allclose(y_step, y_ref, atol=1e-4 * scale)
    np.testing.assert_allclose(y_chunk, y_ref, atol=1e-4 * scale)
    np.testing.assert_allclose(
        s_chunk, s_step, atol=1e-4 * max(float(jnp.abs(s_step).max()), 1.0))
    assert float(jnp.abs(y_ref).max()) > 0.1  # and it is not all zeros
    assert bool(jnp.isfinite(y_chunk).all())


def test_heads_that_share_a_group_read_its_b_and_c():
    """After one position from zero, ``H = dt x B^T`` with head ``i``'s ``B``
    its group's (``i // (heads / groups)``), and ``y = H C + D x``."""
    x, dt, A, Bm, Cm, D = (v[:, 0] if v.ndim > 1 else v for v in inputs(3, 1))
    state, y = ssd.ssd_step(jnp.zeros((B, H, P, N)), x, dt, A, Bm, Cm, D)
    of_head = lambda v: jnp.repeat(v, H // v.shape[1], axis=1)  # noqa: E731
    want = (dt[..., None] * x)[..., None] * of_head(Bm)[:, :, None, :]
    np.testing.assert_allclose(state, want, atol=1e-6)
    np.testing.assert_allclose(
        y, jnp.einsum("bhpn,bhn->bhp", want, of_head(Cm)) + D[:, None] * x,
        atol=1e-5)


def _objective(fn):
    def value(*args):
        y, state = fn(*args)
        return jnp.sum(jnp.sin(y)) + jnp.sum(state * state)
    return value


@functools.lru_cache(maxsize=None)
def _both_gradients(T, chunk, steps):
    args = inputs(T + 1, T, steps)
    every = tuple(range(len(ARGS)))
    got = jax.jit(jax.grad(_objective(
        lambda *a: ssd.ssd_chunked(*a, chunk=chunk)), argnums=every))(*args)
    return got, jax.jit(jax.grad(_objective(stepped), argnums=every))(*args)


@pytest.mark.parametrize("arg", range(6), ids=ARGS)
@pytest.mark.parametrize("T,chunk,steps", [
    (80, 16, "mixed"), (21, 8, "near-zero"), (24, 8, "strongly-negative")])
def test_the_chunked_forms_gradient_is_the_plain_scans(T, chunk, steps, arg):
    got, want = (side[arg] for side in _both_gradients(T, chunk, steps))
    assert bool(jnp.isfinite(got).all())  # nothing above the diagonal overflowed
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=1e-4 * max(scale, 1.0))


def test_the_backward_keeps_the_state_at_chunk_boundaries_only():
    """The residuals of the chunked form's backward, read from the jaxpr of
    its ``vjp``: the state as each chunk opened on it (``T / chunk`` states a
    head) and nothing of ``[T, heads, P, N]``."""
    T, chunk = 96, 32
    args = inputs(0, T)
    jaxpr = jax.make_jaxpr(
        lambda *a: jax.vjp(lambda *b: ssd.ssd_chunked(*b, chunk=chunk), *a)[1]
    )(*args)
    shapes = [tuple(v.aval.shape) for v in jaxpr.jaxpr.outvars]
    states = [s for s in shapes if s[-2:] == (P, N)]
    assert states and all(T // chunk in s and T not in s for s in states), states
    # no residual holds every position's state: nothing as large as [T, h, P, N]
    most = max(int(np.prod(s)) for s in shapes)
    assert most < B * T * H * P * N, shapes
    for s in shapes:
        assert not ({P, N} <= set(s) and (T in s or chunk in s)), s
        # nor every chunk's [chunk, chunk] matrices: one chunk's are live at a
        # time and the backward makes them again from the boundary state
        assert s.count(chunk) < 2 or len(s) == 2, s  # (the mask is [chunk, chunk])


def test_a_state_kept_in_bfloat16_is_another_result():
    """The control's precision is no no-op, in either form."""
    args = inputs(5, 64, "near-zero")
    chunked = jax.jit(ssd.ssd_chunked, static_argnames=("chunk", "state_dtype"))
    y, _ = chunked(*args, chunk=8)
    y_low, s_low = chunked(*args, chunk=8, state_dtype=jnp.bfloat16)
    assert s_low.dtype == jnp.float32  # rounded, handed on in float32
    assert 1e-5 < float(jnp.abs(y - y_low).max()) < 0.1
    x, dt, A, Bm, Cm, D = (v[:, 0] if v.ndim > 1 else v for v in args)
    state, _ = ssd.ssd_step(
        jnp.ones((B, H, P, N), jnp.bfloat16), x, dt, A, Bm, Cm, D)
    assert state.dtype == jnp.bfloat16
