"""The gated delta rule (``ops/delta_rule.py``): its chunked form against
its one-position form iterated, and both against the plain reference's
recurrence (``benchmark/reference/olmo_hybrid.py``, the state written out
the other way round), in float32 on the CPU; the chunked form's gradients
against those through the plain scan; what its backward keeps. The chunked
form's two Pallas kernels under the interpreter against its plain form at the
cell's heads, and compiled for a described v5e at the cell's shapes (nothing
runs: ``chip_smoke.py --phase delta`` is where Mosaic's arithmetic is held
against the plain form's)."""

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

from benchmark.reference import olmo_hybrid as reference  # noqa: E402
from distributed_ba3c_tpu.ops import delta_rule  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

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


# -- the kernels, interpreted --------------------------------------------------------
#: the cell's heads, fewer of them: 2 envs, 4 heads (two pairs) of 96 keys
#: and 192 values (the second head's keys straddle a tile's edge, the
#: fourth's block ends past the heads' lanes), chunks of 64 positions
KB, KH, KK, KV = 2, 4, 96, 192
#: gates that try the kernels' arithmetic: near the least one (above the
#: diagonal an unmasked exp would overflow, and a chunk forgets what it opened
#: on), one near 1 after one near 0 (a difference of two running sums would
#: lose it), all near 1 (a state that forgets nothing)
KERNEL_GATES = {
    "near-least": lambda u: delta_rule.LEAST_GATE * (1.0 + 9.0 * u),
    "near-one-after-near-zero": lambda u: jnp.where(
        jnp.arange(u.shape[1])[None, :, None] % 7 < 2, 1e-6 * (1.0 + u),
        1.0 - 1e-4 * u),
    "near-one": GATES["near-one"],
    # a tenth each under the least gate (0: no gradient reaches it), exactly
    # on it (the clamp's tie: half a gradient) and half of it; the rest near 1
    "at-least": lambda u: jnp.where(
        u < 0.1, 0.0, jnp.where(u < 0.2, delta_rule.LEAST_GATE, jnp.where(
            u < 0.3, 0.5 * delta_rule.LEAST_GATE, 1.0 - 1e-3 * u))),
}
#: step sizes: in (0, 1], and in (1, 2) (past 1 the rule mirrors)
KERNEL_BETAS = {"to-one": lambda u: 1.0 - 0.999 * u, "past-one": lambda u: 1.0 + 0.999 * u}


def kernel_inputs(seed, T, gates="near-one-after-near-zero", betas="past-one",
                  dims=(KB, KH, KK, KV)):
    b, h, K, V = dims
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (b, T, h, K))) / np.sqrt(K)
    k = unit(jax.random.normal(keys[1], (b, T, h, K)))
    v = jax.random.normal(keys[2], (b, T, h, V))
    beta = KERNEL_BETAS[betas](jax.random.uniform(keys[3], (b, T, h)))
    alpha = KERNEL_GATES[gates](jax.random.uniform(keys[4], (b, T, h)))
    return q, k, v, alpha, beta


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(delta_rule, "INTERPRET", True)


def fresh(form, **kw):
    """``form`` as a function of its own: JAX keeps traces by function and
    shapes, and which path a trace took was read off ``delta_rule.INTERPRET``."""
    return lambda *args: form(*args, **kw)


def _close(got, want, tol=1e-5):
    """Within float32 rounding of the largest value: the same sums in
    another order."""
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(
        got, want, atol=tol * max(float(jnp.abs(want).max()), 1.0))


@pytest.mark.parametrize("betas", sorted(KERNEL_BETAS))
@pytest.mark.parametrize("gates", sorted(KERNEL_GATES))
@pytest.mark.parametrize("T", [128, 150, 64], ids=[
    "two-chunks", "no-whole-chunks", "one-chunk"])
def test_the_kernels_o_and_last_state_are_the_plain_forms(interpreted, T, gates, betas):
    args = kernel_inputs(T, T, gates, betas)
    assert delta_rule.kernels_take(args[0], args[2])
    o, last = jax.jit(fresh(delta_rule.delta_chunked))(*args)
    o_plain, last_plain = jax.jit(delta_rule.delta_chunked_plain)(*args)
    assert o.shape == (KB, T, KH, KV) and last.shape == (KB, KH, KK, KV)
    assert float(jnp.abs(o_plain).max()) > 0.01
    _close(o, o_plain)
    _close(last, last_plain)


@pytest.mark.parametrize("h,K,V", [
    (4, 64, 160),   # heads from lanes 0, 32, 64, 96 of a tile
    (4, 40, 152),   # this many heads of 152 end within their windows (six do not)
    (2, 128, 256),  # the widest: a head fills its window
    (6, 8, 136),    # the narrowest; the sixth head starts on a tile's last lanes
], ids=["V-160", "V-152", "V-256", "V-136"])
def test_the_kernels_at_every_width_they_take_are_the_plain_form(interpreted, h, K, V):
    """Every head is read through a window of two tiles from the tile it
    starts in: at each width ``kernels_take`` lets through, whole heads, so
    ``o``, the last state and every gradient leaf are the plain form's."""
    args = kernel_inputs(V, 150, dims=(1, h, K, V))
    assert delta_rule.kernels_take(args[0], args[2])
    every = tuple(range(len(ARGS)))

    def all_seven(form):
        return jax.jit(lambda *a: (
            form(*a), jax.grad(_objective(form), argnums=every)(*a)))(*args)

    (o, last), grads = all_seven(fresh(delta_rule.delta_chunked))
    (o_plain, last_plain), grads_plain = all_seven(delta_rule.delta_chunked_plain)
    assert float(jnp.abs(o_plain).max()) > 0.01
    _close(o, o_plain)
    _close(last, last_plain)
    for name, got, want in zip(ARGS, grads, grads_plain):
        assert float(jnp.abs(want).max()) > 0, name
        _close(got, want, tol=2e-5)


@pytest.mark.parametrize("boundary", range(3))
def test_the_kernel_keeps_the_state_every_chunk_closed_on(interpreted, boundary):
    """What its backward starts each chunk from: boundary ``c`` is the plain
    form's last state over the first ``c + 1`` chunks, zeros beyond a head's
    values."""
    args = kernel_inputs(7, 3 * delta_rule.CHUNK, "near-one")
    _, closed = delta_rule._forward(*args, interpret=True)
    assert closed.shape == (KB, 3, KH, KK, 256)
    upto = (boundary + 1) * delta_rule.CHUNK
    _, want = delta_rule.delta_chunked_plain(*(x[:, :upto] for x in args))
    _close(closed[:, boundary, :, :, :KV], want)
    assert not bool(closed[..., KV:].any())


@functools.lru_cache(maxsize=None)
def _kernel_gradients(T, gates, betas):
    args = kernel_inputs(T + 1, T, gates, betas)
    every = tuple(range(len(ARGS)))
    delta_rule.INTERPRET = True
    try:
        got = jax.jit(jax.grad(
            _objective(fresh(delta_rule.delta_chunked)), argnums=every))(*args)
    finally:
        delta_rule.INTERPRET = False
    return got, jax.jit(jax.grad(
        _objective(delta_rule.delta_chunked_plain), argnums=every))(*args)


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
@pytest.mark.parametrize("T,gates,betas", [
    (128, "near-least", "past-one"), (150, "near-one-after-near-zero", "past-one"),
    (128, "near-one", "to-one"), (150, "at-least", "past-one")])
def test_the_backward_kernels_gradient_is_the_plain_forms(T, gates, betas, arg):
    got, want = (side[arg] for side in _kernel_gradients(T, gates, betas))
    assert float(jnp.abs(want).max()) > 0
    if (gates, ARGS[arg]) == ("at-least", "alpha"):
        return _the_least_gates_gradient_is_the_plain_forms(
            kernel_inputs(T + 1, T, gates, betas)[arg], got, want)
    _close(got, want, tol=2e-5)


def _the_least_gates_gradient_is_the_plain_forms(alpha, got, want):
    """``d alpha`` where gates lie on and under ``LEAST_GATE``, the regime
    on its own: a gate's gradient is a sum of terms that each hold the gate
    as a factor, over the gate, so at the least float32 some terms flush to
    zero, not the same ones in both forms. Held there: none under the least
    gate, the clamp's half ON it in both forms (not the whole: the two would
    stand the gradient's own size apart), and ``d log alpha`` itself, which
    is what the kernel writes, within rounding everywhere."""
    least = delta_rule.LEAST_GATE
    assert bool(jnp.isfinite(got).all())
    under, on = alpha < least, alpha == least
    assert int(under.sum()) > 100 and int(on.sum()) > 50
    assert not bool(jnp.where(under, got, 0.0).any())
    assert not bool(jnp.where(under, want, 0.0).any())
    at_most = lambda x, where: float(jnp.abs(jnp.where(where, x, 0.0)).max())  # noqa: E731
    assert at_most(got - want, on) < 0.25 * at_most(want, on)
    _close(jnp.where(on, 0.0, got), jnp.where(on, 0.0, want), tol=2e-5)
    _close(got * alpha, want * alpha, tol=2e-5)


def test_the_kernels_keep_a_bfloat16_state_as_the_plain_form_does(interpreted):
    """The control's rounding at every boundary, forward and backward. Two
    float32 sums a rounding apart now and then round to neighbouring
    bfloat16 values (0.4 % of the entries here, each 2^-8 of itself off), so
    the two forms agree to that and not to float32 rounding."""
    args = kernel_inputs(5, 192, "near-one")
    every = tuple(range(len(ARGS)))
    low = lambda form: fresh(form, state_dtype=jnp.bfloat16)  # noqa: E731
    o, last = jax.jit(low(delta_rule.delta_chunked))(*args)
    o_plain, last_plain = jax.jit(low(delta_rule.delta_chunked_plain))(*args)
    o_float, _ = jax.jit(fresh(delta_rule.delta_chunked))(*args)
    _close(o, o_plain, tol=1e-3)
    _close(last, last_plain, tol=2.0 ** -7)
    assert float(jnp.mean(last != last_plain)) < 0.02
    assert bool((last.astype(jnp.bfloat16).astype(jnp.float32) == last).all())
    # and is another result, further from float32 than the two forms lie apart
    assert (float(jnp.abs(o - o_float).mean())
            > 10 * float(jnp.abs(o - o_plain).mean()) > 0)
    got = jax.jit(jax.grad(_objective(low(delta_rule.delta_chunked)), every))(*args)
    want = jax.jit(jax.grad(
        _objective(low(delta_rule.delta_chunked_plain)), every))(*args)
    for g, w in zip(got, want):
        _close(g, w, tol=2e-3)


@pytest.mark.parametrize("shape,chunk,takes", [
    ((2, 128, 4, 96, 192), 64, True),      # the cell's heads, fewer
    ((2, 2048, 10, 96, 192), 64, True),    # the cell's
    ((2, 24, 2, 8, 16), 8, False),         # the tiny cut's
    ((2, 256, 4, 96, 192), 128, False),    # a chunk that is not 64
    ((2, 40, 4, 96, 192), 64, False),      # shorter than a chunk
    ((2, 128, 3, 96, 192), 64, False),     # heads that are no pairs
    ((2, 128, 4, 100, 192), 64, False),    # keys off whole sublanes
    ((2, 128, 4, 96, 128), 64, False),     # values no wider than a tile
    ((2, 128, 4, 192, 192), 64, False),    # keys wider than a tile
    ((2, 128, 4, 64, 160), 64, True),      # other widths whose heads the windows hold
    ((2, 128, 4, 40, 152), 64, True),
    ((2, 128, 6, 40, 152), 64, False),     # the sixth head of 152 ends past its window
    ((2, 128, 2, 96, 200), 64, False),     # the second head of 200 does
    ((2, 128, 16, 96, 192), 64, True),     # as many of the cell's heads as fast memory holds
    ((2, 128, 18, 96, 192), 64, False),    # more states and blocks than it holds
    ((2, 128, 14, 128, 256), 64, False),
], ids=["fewer-heads", "cell", "tiny", "chunk-128", "short", "odd-heads", "K-100",
        "V-128", "K-192", "V-160", "V-152", "V-152-six-heads", "V-200", "16-heads",
        "18-heads", "14-widest-heads"])
def test_which_path_runs_is_read_off_the_shapes(monkeypatch, shape, chunk, takes):
    b, T, h, K, V = shape
    q = jax.ShapeDtypeStruct((b, T, h, K), jnp.float32)
    v = jax.ShapeDtypeStruct((b, T, h, V), jnp.float32)
    assert not delta_rule.kernels_take(q, v, chunk)  # a backend without Mosaic
    monkeypatch.setattr(delta_rule, "runs_mosaic", lambda: True)
    assert delta_rule.kernels_take(q, v, chunk) == takes
    gate = jax.ShapeDtypeStruct((b, T, h), jnp.float32)
    jaxpr = str(jax.make_jaxpr(fresh(delta_rule.delta_chunked, chunk=chunk))(
        q, q, v, gate, gate))
    assert ("pallas_call" in jaxpr) == takes
    assert ("triangular_solve" in jaxpr) != takes  # the plain form's solve, or none


def test_the_plain_form_runs_on_the_cpu_whatever_the_shapes():
    args = kernel_inputs(0, 128)
    assert not delta_rule.kernels_take(args[0], args[2])
    assert "pallas_call" not in str(
        jax.make_jaxpr(fresh(delta_rule.delta_chunked))(*args))


# -- Mosaic, compiled for a described v5e (nothing runs) ---------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_for_a_v5e(one_chip, monkeypatch, dims, state_dtype=jnp.float32):
    """The text of both kernels' step, gradients of every operand, compiled
    afresh for the described chip at ``dims`` (b, T, h, K, V)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(delta_rule, "runs_mosaic", lambda: True)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    b, T, h, K, V = dims
    spec = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.float32, sharding=one_chip)
    args = (spec(b, T, h, K), spec(b, T, h, K), spec(b, T, h, V), spec(b, T, h),
            spec(b, T, h))
    assert delta_rule.kernels_take(args[0], args[2])
    try:
        return jax.jit(jax.grad(
            _objective(fresh(delta_rule.delta_chunked, state_dtype=state_dtype)),
            argnums=tuple(range(5)))).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        delta_rule._forward.clear_cache()
        delta_rule._backward.clear_cache()


@pytest.mark.parametrize("h,K,V", [
    (4, 64, 160), (4, 40, 152), (2, 128, 256), (6, 8, 136),
    # the most heads whose states and blocks fast memory holds (16 of 128 x
    # 144, 18 of the cell's and 14 of 128 x 256 overran it on the chip's compiler)
    (14, 128, 144), (16, 96, 192), (12, 128, 256)])
def test_the_kernels_compile_for_a_v5e_at_every_width_they_take(
        one_chip, monkeypatch, h, K, V):
    """Mosaic takes what ``kernels_take`` lets through: the narrowest and the
    widest heads, keys that fill a tile (no rows to pad), heads that start
    anywhere in a tile, as many heads as fast memory holds (a learner chunk's
    length: the compiler's limit on a kernel's scope showed there)."""
    text = _compiled_for_a_v5e(one_chip, monkeypatch, (2, 2048, h, K, V))
    assert text.count("tpu_custom_call") == 2 and "triangular" not in text


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "state_bf16"])
def test_the_kernels_compile_for_a_v5e_at_the_cells_shapes(
        one_chip, monkeypatch, state_dtype):
    """A learner chunk of the cell: 2 envs x 2,048 positions x 10 heads of 96
    keys and 192 values; both kernels, each under the rule's kernel scope."""
    text = _compiled_for_a_v5e(
        one_chip, monkeypatch, (2, 2048, 10, 96, 192), state_dtype)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2
    forward, backward = sorted(calls, key=lambda line: "transpose(" in line)
    assert f"/{delta_rule.FORWARD_KERNEL}/" in forward and "transpose(" not in forward
    assert f"/{delta_rule.BACKWARD_KERNEL}/" in backward
    for call in calls:  # the scope that says the kernels ran
        assert profiling.DELTA_CHUNKS in call.split("op_name=")[1].split('"')[1]
    assert "triangular" not in text and "while" not in text  # nothing of the plain form
