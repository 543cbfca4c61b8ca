"""The gated delta rule (``ops/delta_rule.py``): its chunked form against
its one-position form iterated, and both against the plain reference's
recurrence (``benchmark/reference/olmo_hybrid.py``, the state written out
the other way round), in float32 on the CPU; the chunked form's gradients
against those through the plain scan; what its backward keeps."""

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

from benchmark.reference import olmo_hybrid as reference  # noqa: E402
from distributed_ba3c_tpu.ops import delta_rule  # noqa: E402

B, H, K, V = 2, 3, 8, 16
#: where the gates lie: every head near 1 (a state that forgets nothing),
#: near 0 (one that forgets everything), either by turns, and exactly 0
GATES = {
    "near-one": lambda u: 1.0 - 1e-4 * u,
    "near-zero": lambda u: 1e-3 * u + 1e-30,
    "mixed": lambda u: jnp.where(u < 0.5, 1.0 - 1e-3 * u, 0.2 * u),
    "zero": lambda u: jnp.where(u < 0.5, 0.0, u),
}
ARGS = ("q", "k", "v", "alpha", "beta")


def inputs(seed, T, gates="mixed"):
    """Unit keys, queries of length 1/sqrt(K), ``beta`` in (1, 2): past 1
    the rule mirrors what the state held along ``k``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (B, T, H, K))) / np.sqrt(K)
    k = unit(jax.random.normal(keys[1], (B, T, H, K)))
    v = jax.random.normal(keys[2], (B, T, H, V))
    beta = 1.0 + jax.random.uniform(keys[3], (B, T, H))
    alpha = GATES[gates](jax.random.uniform(keys[4], (B, T, H)))
    return q, k, v, alpha, beta


def stepped(q, k, v, alpha, beta):
    """``delta_step`` iterated from the zero state: the plain scan."""
    by_time = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
    state, o = jax.lax.scan(
        lambda S, x: delta_rule.delta_step(S, *x),
        jnp.zeros((B, H, K, V), jnp.float32),
        tuple(by_time(x) for x in (q, k, v, alpha, beta)))
    return by_time(o), state


@functools.lru_cache(maxsize=None)
def _three_forms(chunk):
    """One compiled program a shape: the gates' cases share it."""
    return jax.jit(lambda *args: (
        stepped(*args), delta_rule.delta_chunked(*args, chunk=chunk),
        reference.recurrence(*args)))


@pytest.mark.parametrize("gates", sorted(GATES))
@pytest.mark.parametrize("T,chunk", [
    (64, 64), (256, 64), (100, 64), (7, 64), (13, 4), (1, 64)],
    ids=["one-chunk", "many-chunks", "no-multiple-of-64", "shorter-than-a-chunk",
         "no-multiple-of-4", "one-position"])
def test_chunked_stepped_and_the_references_recurrence_agree(T, chunk, gates):
    args = inputs(T, T, gates)
    (o_step, s_step), (o_chunk, s_chunk), o_ref = _three_forms(chunk)(*args)
    assert o_chunk.shape == (B, T, H, V) and s_chunk.shape == (B, H, K, V)
    np.testing.assert_allclose(o_step, o_ref, atol=1e-4)
    np.testing.assert_allclose(o_chunk, o_ref, atol=1e-4)
    np.testing.assert_allclose(s_chunk, s_step, atol=1e-4)
    assert float(jnp.abs(o_ref).max()) > 0.1  # and it is not all zeros


def test_the_state_is_the_references_transposed():
    """The program's ``[K, V]`` is the reference's ``[V, K]``: after one
    position from zero, ``S = beta k v^T``."""
    q, k, v, alpha, beta = (x[:, 0] for x in inputs(3, 1))
    state, o = delta_rule.delta_step(
        jnp.zeros((B, H, K, V)), q, k, v, alpha, beta)
    want = beta[..., None, None] * k[..., :, None] * v[..., None, :]
    np.testing.assert_allclose(state, want, atol=1e-6)
    np.testing.assert_allclose(o, jnp.einsum("bhkv,bhk->bhv", want, q), atol=1e-6)


def _objective(fn):
    def value(*args):
        o, state = fn(*args)
        return jnp.sum(jnp.sin(o)) + jnp.sum(state * state)
    return value


@functools.lru_cache(maxsize=None)
def _both_gradients(T, chunk):
    args = inputs(T + 1, T)
    every = tuple(range(len(ARGS)))
    got = jax.jit(jax.grad(_objective(
        lambda *a: delta_rule.delta_chunked(*a, chunk=chunk)), argnums=every))(*args)
    return got, jax.jit(jax.grad(_objective(stepped), argnums=every))(*args)


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
@pytest.mark.parametrize("T,chunk", [(200, 64), (24, 8)])
def test_the_chunked_forms_gradient_is_the_plain_scans(T, chunk, arg):
    got, want = (side[arg] for side in _both_gradients(T, chunk))
    scale = float(jnp.abs(want).max())
    assert scale > 1e-3
    np.testing.assert_allclose(got, want, atol=1e-4 * max(scale, 1.0))


def test_the_backward_keeps_the_state_at_chunk_boundaries_only():
    """The residuals of the chunked form's backward, read from the jaxpr of
    its ``vjp``: the state as each chunk opened on it (``T / chunk`` states a
    head) and nothing of ``[T, K, V]`` a head."""
    T, chunk = 256, 64
    args = inputs(0, T)
    jaxpr = jax.make_jaxpr(
        lambda *a: jax.vjp(lambda *b: delta_rule.delta_chunked(*b, chunk=chunk), *a)[1]
    )(*args)
    shapes = [tuple(v.aval.shape) for v in jaxpr.jaxpr.outvars]
    states = [s for s in shapes if s[-2:] == (K, V)]
    # every residual shaped like states is the boundary states, once
    assert states == [(T // chunk, B, H, K, V)]
    # and none holds positions, keys and values at once in another order
    for s in shapes:
        assert not ({K, V} <= set(s) and (T in s or chunk in s)), s


def test_a_state_kept_in_bfloat16_is_another_result():
    """The control's precision is no no-op, in either form."""
    args = inputs(5, 64)
    chunked = jax.jit(delta_rule.delta_chunked,
                      static_argnames=("chunk", "state_dtype"))
    o, _ = chunked(*args, chunk=8)
    o_low, s_low = chunked(*args, chunk=8, state_dtype=jnp.bfloat16)
    assert s_low.dtype == jnp.float32  # rounded, handed on in float32
    assert 1e-4 < float(jnp.abs(o - o_low).max()) < 0.1
    q, k, v, alpha, beta = (x[:, 0] for x in args)
    state, _ = delta_rule.delta_step(
        jnp.ones((B, H, K, V), jnp.bfloat16), q, k, v, alpha, beta)
    assert state.dtype == jnp.bfloat16
