"""The Mamba-2 recurrence (``ops/ssd.py``): its chunked form against its
one-position form iterated, and both against the plain reference's
recurrence (``benchmark/reference/nemotron_h.py``), in float32 on the CPU;
the chunked form's gradients against those through the plain scan; what its
backward keeps. The chunked form's two Pallas kernels under the interpreter
against its plain form at the kernels' shapes cut small, and compiled for a
described v5e at the cell's (nothing runs: ``chip_smoke.py --phase ssd`` is
where Mosaic's arithmetic is held against the plain form's)."""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import nemotron_h as reference  # noqa: E402
from distributed_ba3c_tpu.ops import ssd  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

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


# -- the kernels, interpreted --------------------------------------------------------
#: the kernels' shapes cut small: 2 envs, 16 heads of 64 channels in 2 groups,
#: states of 128 numbers a row, chunks of 128 positions
KB, KH, KP, KG, KN = 2, 16, 64, 2, 128
#: step sizes that try the kernels' arithmetic: ``dt A`` strongly negative
#: (above the diagonal an unmasked exp would overflow), and small steps after
#: large ones (a difference of two running sums would lose them)
KERNEL_STEPS = {
    "strongly-negative": lambda u: 40.0 + 40.0 * u,
    "small-after-large": lambda u: jnp.where(
        jnp.arange(u.shape[1])[None, :, None] % 7 < 5, 3.0 + 3.0 * u, 1e-4 * u),
    "mixed": STEPS["mixed"],
}


def kernel_inputs(seed, T, steps="mixed"):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(keys[0], (KB, T, KH, KP))
    dt = KERNEL_STEPS[steps](jax.random.uniform(keys[1], (KB, T, KH)))
    A = -jnp.exp(jax.random.normal(keys[2], (KH,)))
    Bm, Cm = (jax.random.normal(k, (KB, T, KG, KN)) / 4 for k in keys[3:5])
    return x, dt, A, Bm, Cm, jax.random.normal(keys[5], (KH,))


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(ssd, "INTERPRET", True)


def fresh(form, **kw):
    """``form`` as a function of its own: JAX keeps traces by function and
    shapes, and which path a trace took was read off ``ssd.INTERPRET``."""
    return lambda *args: form(*args, **kw)


def _close(got, want, tol=1e-5):
    """Within float32 rounding of the largest value: the same sums in
    another order."""
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(
        got, want, atol=tol * max(float(jnp.abs(want).max()), 1.0))


@pytest.mark.parametrize("steps", sorted(KERNEL_STEPS))
@pytest.mark.parametrize("T", [256, 300, 128], ids=[
    "two-chunks", "no-whole-chunks", "one-chunk"])
def test_the_kernels_y_and_last_state_are_the_plain_forms(interpreted, T, steps):
    args = kernel_inputs(T, T, steps)
    assert ssd.kernels_take(args[0], args[3])
    y, last = jax.jit(fresh(ssd.ssd_chunked))(*args)
    y_plain, last_plain = jax.jit(ssd.ssd_chunked_plain)(*args)
    assert y.shape == (KB, T, KH, KP) and last.shape == (KB, KH, KP, KN)
    assert float(jnp.abs(y_plain).max()) > 0.1
    _close(y, y_plain)
    _close(last, last_plain)


@pytest.mark.parametrize("boundary", range(3))
def test_the_kernel_keeps_the_state_every_chunk_closed_on(interpreted, boundary):
    """What its backward starts each chunk from: boundary ``c`` is the plain
    form's last state over the first ``c + 1`` chunks."""
    args = kernel_inputs(7, 3 * ssd.CHUNK)
    _, closed = ssd._forward(*args, interpret=True)
    assert closed.shape == (KB, 3, KH, KP, KN)
    upto = (boundary + 1) * ssd.CHUNK
    x, dt, A, Bm, Cm, D = args
    _, want = ssd.ssd_chunked_plain(x[:, :upto], dt[:, :upto], A, Bm[:, :upto],
                                    Cm[:, :upto], D)
    _close(closed[:, boundary], want)


@functools.lru_cache(maxsize=None)
def _kernel_gradients(T, steps):
    args = kernel_inputs(T + 1, T, steps)
    every = tuple(range(len(ARGS)))
    ssd.INTERPRET = True
    try:
        got = jax.jit(jax.grad(
            _objective(fresh(ssd.ssd_chunked)), argnums=every))(*args)
    finally:
        ssd.INTERPRET = False
    return got, jax.jit(
        jax.grad(_objective(ssd.ssd_chunked_plain), argnums=every))(*args)


@pytest.mark.parametrize("arg", range(6), ids=ARGS)
@pytest.mark.parametrize("T,steps", [
    (256, "strongly-negative"), (256, "small-after-large"), (300, "mixed")])
def test_the_backward_kernels_gradient_is_the_plain_forms(T, steps, arg):
    got, want = (side[arg] for side in _kernel_gradients(T, steps))
    assert float(jnp.abs(want).max()) > 0
    _close(got, want, tol=2e-5)


def test_the_kernels_keep_a_bfloat16_state_as_the_plain_form_does(interpreted):
    """The control's rounding at every boundary, forward and backward."""
    args = kernel_inputs(5, 384, "mixed")
    every = tuple(range(len(ARGS)))
    low = lambda form: fresh(form, state_dtype=jnp.bfloat16)  # noqa: E731
    y, last = jax.jit(low(ssd.ssd_chunked))(*args)
    y_plain, last_plain = jax.jit(low(ssd.ssd_chunked_plain))(*args)
    y_float, _ = jax.jit(fresh(ssd.ssd_chunked))(*args)
    _close(y, y_plain)
    _close(last, last_plain)
    assert bool((last.astype(jnp.bfloat16).astype(jnp.float32) == last).all())
    assert 1e-4 < float(jnp.abs(y - y_float).max()) < 0.5  # and is another result
    got = jax.jit(jax.grad(_objective(low(ssd.ssd_chunked)), every))(*args)
    want = jax.jit(jax.grad(_objective(low(ssd.ssd_chunked_plain)), every))(*args)
    for g, w in zip(got, want):
        _close(g, w, tol=2e-5)


@pytest.mark.parametrize("shape,chunk,takes", [
    ((2, 256, 16, 64, 2, 128), 128, True),    # the cell's, cut small
    ((2, 2048, 64, 64, 8, 128), 128, True),   # the cell's
    ((2, 24, 4, 8, 2, 16), 8, False),         # the tiny cut's
    ((2, 256, 16, 64, 2, 128), 64, False),    # a chunk that is no tile's lanes
    ((2, 64, 16, 64, 2, 128), 128, False),    # shorter than a chunk
    ((2, 256, 16, 64, 2, 64), 128, False),    # a state's row off whole lanes
    ((2, 256, 16, 60, 2, 128), 128, False),   # channels off whole sublanes
    ((2, 256, 6, 64, 2, 128), 128, False),    # a group's heads no whole tiles
], ids=["cut-small", "cell", "tiny", "chunk-64", "short", "N-64", "P-60", "odd-heads"])
def test_which_path_runs_is_read_off_the_shapes(monkeypatch, shape, chunk, takes):
    b, T, h, P, g, N = shape
    x = jax.ShapeDtypeStruct((b, T, h, P), jnp.float32)
    Bm = jax.ShapeDtypeStruct((b, T, g, N), jnp.float32)
    assert not ssd.kernels_take(x, Bm, chunk)  # a backend without Mosaic
    monkeypatch.setattr(ssd, "runs_mosaic", lambda: True)
    assert ssd.kernels_take(x, Bm, chunk) == takes
    dt = jax.ShapeDtypeStruct((b, T, h), jnp.float32)
    head = jax.ShapeDtypeStruct((h,), jnp.float32)
    jaxpr = str(jax.make_jaxpr(fresh(ssd.ssd_chunked, chunk=chunk))(
        x, dt, head, Bm, Bm, head))
    assert ("pallas_call" in jaxpr) == takes
    assert ("cumsum" in jaxpr) != takes  # the plain form's running sums, or none


def test_the_plain_form_runs_on_the_cpu_whatever_the_shapes():
    args = kernel_inputs(0, 128)
    assert not ssd.kernels_take(args[0], args[3])
    assert "pallas_call" not in str(jax.make_jaxpr(fresh(ssd.ssd_chunked))(*args))


# -- Mosaic, compiled for a described v5e (nothing runs) ---------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "state_bf16"])
def test_the_kernels_compile_for_a_v5e_at_the_cells_shapes(
        one_chip, monkeypatch, state_dtype):
    """A learner chunk of the cell: 2 envs x 2,048 positions x 64 heads of 64
    channels in 8 groups; both kernels, each under the recurrence's scope."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(ssd, "runs_mosaic", lambda: True)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    b, T, h, P, g, N = 2, 2048, 64, 64, 8, 128
    spec = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    args = (spec(b, T, h, P), spec(b, T, h), spec(h), spec(b, T, g, N),
            spec(b, T, g, N), spec(h))
    try:
        text = jax.jit(jax.grad(
            _objective(fresh(ssd.ssd_chunked, state_dtype=state_dtype)),
            argnums=tuple(range(6)))).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        ssd._forward.clear_cache()
        ssd._backward.clear_cache()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2
    forward, backward = sorted(calls, key=lambda line: "transpose(" in line)
    assert f"/{ssd.FORWARD_KERNEL}/" in forward and "transpose(" not in forward
    assert f"/{ssd.BACKWARD_KERNEL}/" in backward
    for call in calls:  # the scope that says the kernels ran
        assert f"{profiling.SSD_CHUNKS}" in call.split("op_name=")[1].split('"')[1]
