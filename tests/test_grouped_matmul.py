"""The Pallas grouped product (ops/grouped_matmul.py) under Pallas's
interpreter against ``jax.lax.ragged_dot``: the three forms in value, the
gradients of both operands, groups that are empty or start and end inside a
tile, rows outside every group poisoned, the path chosen from backend and
shapes, and the kernels compiled at the cell's widths for a described v5e.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distributed_ba3c_tpu.ops import grouped_matmul as gm  # noqa: E402
from distributed_ba3c_tpu.ops import moe  # noqa: E402

M, K, N, G = 512, 256, 384, 4
TILES = (128, 128, 128)  # four row tiles, so groups can start and end inside
#: group sizes over M = 512 rows in tiles of 128
SIZES = {
    "on-tile-edges": (128, 256, 0, 128),
    "an-empty-group": (130, 0, 200, 61),
    "inside-one-tile": (20, 30, 40, 10),
    "one-group-has-all": (0, 0, 512, 0),
    "nothing-held": (0, 0, 0, 0),
    "ends-inside-a-tile": (127, 1, 129, 200),
}


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(gm, "INTERPRET", True)


def _operands(dtype, k=K, n=N, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(keys[0], (M, k), dtype)
    rhs = (jax.random.normal(keys[1], (G, k, n)) / 16).astype(dtype)
    pull = jax.random.normal(keys[2], (M, n), dtype)
    return lhs, rhs, pull


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


def _held(sizes):
    return (jnp.arange(M) < sum(sizes))[:, None]


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 0.04}


def _calls(fn, *args):
    """{primitive: equations of it} in ``fn``'s program, each place a
    jitted function is called counted (a kernel's own body is not the
    program's)."""
    found = {}

    def walk(jaxpr):
        for e in jaxpr.eqns:
            found[e.primitive.name] = found.get(e.primitive.name, 0) + 1
            if e.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(e.params):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


# -- the three forms in value ---------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", SIZES)
@pytest.mark.parametrize("form", ["forward", "dx", "dw"])
def test_a_form_is_ragged_dots(form, case, dtype):
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    lhs, rhs, pull = _operands(dtype)
    held = _held(SIZES[case])
    if form == "forward":
        got = gm.gmm(lhs, rhs, sizes, TILES, interpret=True)
        want = jax.lax.ragged_dot(lhs, rhs, sizes)
    elif form == "dx":  # pull [M, N] x rhs [G, K, N] read transposed
        got = gm.gmm(pull, rhs, sizes, TILES, transpose_rhs=True, interpret=True)
        want = jax.lax.ragged_dot(pull, rhs.swapaxes(1, 2), sizes)
    else:
        got = gm.tgmm(lhs, pull, sizes, TILES, interpret=True)
        want = jax.vjp(lambda r: jax.lax.ragged_dot(lhs, r, sizes), rhs)[1](pull)[0]
        held = True  # every group's matrix is written, an empty group's zeros
    assert got.dtype == dtype == want.dtype  # bf16 out of bf16 in
    scale = max(float(jnp.abs(want.astype(jnp.float32)).max()), 1.0)
    assert _gap(jnp.where(held, got, 0), jnp.where(held, want, 0)) <= TOL[dtype] * scale


@pytest.mark.parametrize("tiles", [(128, 256, 384), (256, 128, 128), (512, 256, 128)])
def test_the_tiles_do_not_change_the_product(tiles):
    sizes = jnp.asarray(SIZES["an-empty-group"], jnp.int32)
    lhs, rhs, pull = _operands(jnp.float32)
    held = _held(SIZES["an-empty-group"])
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    got = gm.gmm(lhs, rhs, sizes, tiles, interpret=True)
    assert _gap(jnp.where(held, got, 0), want) < 2e-5 * float(jnp.abs(want).max())
    d_want = jax.vjp(lambda r: jax.lax.ragged_dot(lhs, r, sizes), rhs)[1](pull)[0]
    d_got = gm.tgmm(lhs, pull, sizes, tiles, interpret=True)
    assert _gap(d_got, d_want) < 2e-5 * float(jnp.abs(d_want).max())


@pytest.mark.parametrize("k,n,chunk", [(512, 1024, 512), (256, 768, 256), (384, 384, 128)])
def test_the_inner_loops_chunks_cover_the_tile(k, n, chunk):
    """A kernel works on its tile ``chunk`` columns (dW: rows of the sum) at
    a time in a loop: whole tiles of each width a chunk can take."""
    assert gm._chunk(n) == chunk
    sizes = jnp.asarray(SIZES["ends-inside-a-tile"], jnp.int32)
    lhs, rhs, pull = _operands(jnp.float32, k, n)
    held = _held(SIZES["ends-inside-a-tile"])
    want, pull_back = jax.vjp(lambda l, r: jax.lax.ragged_dot(l, r, sizes), lhs, rhs)
    d_lhs, d_rhs = pull_back(jnp.where(held, pull, 0))
    got = gm.gmm(lhs, rhs, sizes, (128, k, n), interpret=True)
    assert _gap(jnp.where(held, got, 0), want) < 2e-5 * float(jnp.abs(want).max())
    got = gm.gmm(pull, rhs, sizes, (128, n, k), transpose_rhs=True, interpret=True)
    assert _gap(jnp.where(held, got, 0), d_lhs) < 2e-5 * float(jnp.abs(d_lhs).max())
    got = gm.tgmm(lhs, pull, sizes, (128, k, n), interpret=True)
    assert _gap(got, d_rhs) < 2e-5 * float(jnp.abs(d_rhs).max())


# -- gradients of both operands, through grouped_dot ----------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("k,n", [(128, 1856), (1856, 128)],
                         ids=["columns-off-the-lane", "contracted-off-the-lane"])
def test_at_fourteen_and_a_half_lanes_the_kernel_is_ragged_dot(interpreted, k, n, dtype):
    """Forward, dx and dW through ``grouped_dot`` at a width of 1,856 under
    the interpreter: three kernels, no ``ragged_dot``, its values (forward
    and dx in bfloat16 to one place: float32 sums rounded once)."""
    m = 256
    sizes = jnp.asarray((100, 0, 120), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(k), 3)
    lhs = jax.random.normal(keys[0], (m, k), dtype)
    rhs = (jax.random.normal(keys[1], (3, k, n)) / 16).astype(dtype)
    held = (jnp.arange(m) < 220)[:, None]
    pull = jnp.where(held, jax.random.normal(keys[2], (m, n), dtype), 0)

    def all_three(dot):
        def run(lhs, rhs):
            out, pull_back = jax.vjp(lambda l, r: dot(l, r, sizes), lhs, rhs)
            d_lhs, d_rhs = pull_back(pull)
            return jnp.where(held, out, 0), jnp.where(held, d_lhs, 0), d_rhs
        return run

    calls = _calls(all_three(gm.grouped_dot), lhs, rhs)
    assert calls["pallas_call"] == 3 and "ragged_dot_general" not in calls
    with jax.default_matmul_precision("highest"):
        got = jax.jit(all_three(gm.grouped_dot))(lhs, rhs)
        want = jax.jit(all_three(jax.lax.ragged_dot))(lhs, rhs)
    for name, a, b in zip(("forward", "dx", "dw"), got, want, strict=True):
        assert np.isfinite(np.asarray(a.astype(jnp.float32))).all(), name
        scale = max(float(jnp.abs(b.astype(jnp.float32)).max()), 1.0)
        assert _gap(a, b) <= TOL[dtype] * scale, name
        if dtype == jnp.bfloat16 and name != "dw":
            # float32 sums rounded once on both sides: the order of a sum's
            # terms moves a result by one place of bfloat16 at most
            assert _gap(a, b) <= scale / 128, name


def _loss(dot, sizes, pull, transpose_rhs=False):
    held = _held(tuple(int(s) for s in sizes))

    def loss(lhs, rhs):
        out = dot(lhs, rhs, sizes)
        # as ops/moe.py:_block holds them: zero outside every group
        out = jnp.where(held, out, 0)
        return jnp.sum(out.astype(jnp.float32) * pull.astype(jnp.float32))

    return loss


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["an-empty-group", "inside-one-tile",
                                  "ends-inside-a-tile", "nothing-held"])
@pytest.mark.parametrize("transpose_rhs", [False, True], ids=["rhs", "rhs-transposed"])
def test_both_gradients_are_ragged_dots(interpreted, transpose_rhs, case, dtype):
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    lhs, rhs, pull = _operands(dtype)
    held = _held(SIZES[case])
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
        ref = lambda l, r, s: jax.lax.ragged_dot(l, r.swapaxes(1, 2), s)  # noqa: E731
    else:
        ref = jax.lax.ragged_dot
    ours = lambda l, r, s: gm.grouped_dot(l, r, s, transpose_rhs=transpose_rhs)  # noqa: E731
    calls = _calls(jax.grad(_loss(ours, sizes, pull), (0, 1)), lhs, rhs)
    assert calls["pallas_call"] == 3 and "ragged_dot_general" not in calls
    got = jax.jit(jax.grad(_loss(ours, sizes, pull), (0, 1)))(lhs, rhs)
    want = jax.jit(jax.grad(_loss(ref, sizes, pull), (0, 1)))(lhs, rhs)
    assert got[0].dtype == got[1].dtype == dtype
    for name, a, b, rows in (("lhs", *map(lambda g: g[0], (got, want)), held),
                             ("rhs", *map(lambda g: g[1], (got, want)), True)):
        scale = max(float(jnp.abs(b.astype(jnp.float32)).max()), 1.0)
        assert _gap(jnp.where(rows, a, 0), jnp.where(rows, b, 0)) <= TOL[dtype] * scale, name


# -- group sizes clipped to a window, as ops/moe.py:_block clips them ------------
@pytest.mark.parametrize("lo", [0, 512, 1024])
def test_groups_clipped_to_a_blocks_window(interpreted, lo):
    """1,200 sorted rows of 4 experts in blocks of 512: block ``lo // 512``
    holds each expert's rows that fall in ``[lo, lo + 512)``."""
    counts = jnp.asarray([300, 250, 0, 650], jnp.int32)
    ends = jnp.cumsum(counts)
    sizes = jnp.clip(ends, lo, lo + M) - jnp.clip(ends - counts, lo, lo + M)
    assert int(sizes.sum()) == min(M, 1200 - lo)
    lhs, rhs, pull = _operands(jnp.float32)
    held = (jnp.arange(M) < sizes.sum())[:, None]
    got = gm.grouped_dot(lhs, rhs, sizes)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    assert _gap(jnp.where(held, got, 0), want) < 2e-5 * float(jnp.abs(want).max())


# -- rows outside every group, poisoned -----------------------------------------
@pytest.mark.parametrize("case", ["an-empty-group", "inside-one-tile", "nothing-held"])
def test_poisoned_rows_outside_every_group_reach_no_gradient(interpreted, case):
    """NaN in the rows of both operands of dW that no group holds, and in
    what the kernel leaves unwritten (the interpreter's fresh buffers are
    NaN): the groups' rows of the product and of d(lhs), and all of d(rhs),
    are ragged_dot's with those rows at zero."""
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    lhs, rhs, pull = _operands(jnp.float32)
    held = _held(SIZES[case])
    poison = lambda x: jnp.where(held, x, jnp.nan)  # noqa: E731
    out = gm.grouped_dot(poison(lhs), rhs, sizes)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    assert _gap(jnp.where(held, out, 0), want) < 2e-5 * max(float(jnp.abs(want).max()), 1)
    if sum(SIZES[case]) < M:
        assert not np.isfinite(np.asarray(out[sum(SIZES[case]):])).any()
    _, pull_back = jax.vjp(
        lambda l, r: gm.grouped_dot(l, r, sizes), poison(lhs), rhs)
    d_lhs, d_rhs = pull_back(poison(pull))
    w_lhs, w_rhs = jax.vjp(lambda l, r: jax.lax.ragged_dot(l, r, sizes), lhs, rhs)[1](
        jnp.where(held, pull, 0))
    assert np.isfinite(np.asarray(d_rhs)).all()
    assert _gap(d_rhs, w_rhs) < 2e-5 * max(float(jnp.abs(w_rhs).max()), 1)
    assert _gap(jnp.where(held, d_lhs, 0), w_lhs) < 2e-5 * max(
        float(jnp.abs(w_lhs).max()), 1)


def _plain_share(z, experts, weights, w1, w3, w2):
    out = jnp.zeros(z.shape, jnp.float32)
    for e in range(w1.shape[0]):
        share = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        y = (jax.nn.silu(z @ w1[e]) * (z @ w3[e])) @ w2[e]
        out = out + share[:, None] * y
    return out


@pytest.mark.parametrize("held_rows", [70, 128, 150], ids=lambda r: f"{r}-of-128")
def test_a_block_keeps_poison_out_of_the_layer(interpreted, monkeypatch, held_rows):
    """``_block``'s ``here`` masks with the kernel under them. 128 tokens,
    2 of 8 experts held, a block of 128 sorted rows; the tokens routed to no
    held expert carry NaN, and the kernel's unwritten rows are NaN: the
    layer's value and all five gradients are finite and the reference's."""
    monkeypatch.setattr(moe, "DENSE_ROWS", 0)
    monkeypatch.setattr(moe, "ROW_TILE", 128)
    n, d, f = 128, 128, 256
    assert moe.block_rows(n, 2, 2, 8) == 128
    experts = np.stack([2 + np.arange(n) % 3, 5 + np.arange(n) % 3], 1)
    for i in range(held_rows):
        experts[i // 2, i % 2] = (i // 2 + i) % 2  # held: experts 0 and 1
    experts = jnp.asarray(experts, jnp.int32)
    assert int((experts < 2).sum()) == held_rows
    routed_here = np.asarray((experts < 2).any(1))
    keys = jax.random.split(jax.random.PRNGKey(4), 6)
    z = jax.random.normal(keys[0], (n, d))
    weights = jax.nn.softmax(jax.random.normal(keys[1], (n, 2)))
    w1, w3 = (jax.random.normal(k, (2, d, f)) / 11 for k in keys[2:4])
    w2 = jax.random.normal(keys[4], (2, f, d)) / 16
    pull = jax.random.normal(keys[5], (n, d))

    def ours(z, weights, w1, w3, w2):
        out, counts, ran = moe.expert_ffn(
            z, moe.Routing(experts, weights), w1, w3, w2, 0, 8)
        return jnp.sum(out * pull), (out, counts, ran)

    def theirs(z, weights, w1, w3, w2):
        out = _plain_share(z, experts, weights, w1, w3, w2)
        return jnp.sum(out * pull), out

    poisoned = jnp.where(routed_here[:, None], z, jnp.nan)
    clean = jnp.where(routed_here[:, None], z, 0.0)
    moe._sorted_rows.clear_cache()
    calls = _calls(jax.grad(lambda *a: ours(*a)[0], range(5)),
                   poisoned, weights, w1, w3, w2)
    assert calls["pallas_call"] == 12 and "ragged_dot_general" not in calls
    with jax.default_matmul_precision("highest"):
        (_, (out, counts, ran)), got = jax.jit(jax.value_and_grad(
            ours, argnums=range(5), has_aux=True))(poisoned, weights, w1, w3, w2)
        (_, want_out), want = jax.jit(jax.value_and_grad(
            theirs, argnums=range(5), has_aux=True))(clean, weights, w1, w3, w2)
    moe._sorted_rows.clear_cache()
    assert int(counts.sum()) == held_rows and int(ran) == (held_rows > 128)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    for name, a, b in zip(("z", "weights", "w1", "w3", "w2"), got, want, strict=True):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(
            a, b, atol=1e-4 * float(jnp.abs(b).max()), err_msg=name)


def _plain_relu2_share(z, experts, weights, w1, w2):
    out = jnp.zeros(z.shape, jnp.float32)
    for e in range(w1.shape[0]):
        share = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        out = out + share[:, None] * (jnp.square(jax.nn.relu(z @ w1[e])) @ w2[e])
    return out


@pytest.mark.parametrize("form", ["sorted-rows", "every-token"])
def test_the_two_matrix_expert_is_a_plain_product_over_every_token(monkeypatch, form):
    """``w3`` None: ``W2 relu(W1 z)^2``, the layer's value and its four
    gradients against every held expert computing every token, in the sorted
    rows' blocks and in the ``DENSE_ROWS`` form alike."""
    n, d, f = 96, 32, 24  # a width off every lane: ragged_dot on the CPU
    monkeypatch.setattr(moe, "DENSE_ROWS", 0 if form == "sorted-rows" else 256)
    monkeypatch.setattr(moe, "ROW_TILE", 32)
    monkeypatch.setattr(moe, "HELD_ROWS_MARGIN", -0.5)  # blocks too small: a loop
    keys = jax.random.split(jax.random.PRNGKey(9), 6)
    experts = jax.random.randint(keys[0], (n, 3), 0, 8)  # held: 0 and 1 of 8
    z = jax.random.normal(keys[1], (n, d))
    weights = jax.nn.softmax(jax.random.normal(keys[2], (n, 3)))
    w1 = jax.random.normal(keys[3], (2, d, f)) / 6
    w2 = jax.random.normal(keys[4], (2, f, d)) / 5
    pull = jax.random.normal(keys[5], (n, d))

    def ours(z, weights, w1, w2):
        out, counts, ran = moe.expert_ffn(
            z, moe.Routing(experts, weights), w1, None, w2, 0, 8)
        return jnp.sum(out * pull), (out, counts, ran)

    def theirs(z, weights, w1, w2):
        out = _plain_relu2_share(z, experts, weights, w1, w2)
        return jnp.sum(out * pull), out

    moe._sorted_rows.clear_cache()
    with jax.default_matmul_precision("highest"):
        (_, (out, counts, ran)), got = jax.jit(jax.value_and_grad(
            ours, argnums=range(4), has_aux=True))(z, weights, w1, w2)
        (_, want_out), want = jax.jit(jax.value_and_grad(
            theirs, argnums=range(4), has_aux=True))(z, weights, w1, w2)
    moe._sorted_rows.clear_cache()
    assert int(counts.sum()) == int((experts < 2).sum()) > 32
    block = moe.block_rows(n, 3, 2, 8)
    assert block == 64 < int(counts.sum())
    # blocks beyond the first: one where the rows are sorted, none where
    # every held expert computes every token
    assert int(ran) == ((int(counts.sum()) - 1) // block if form == "sorted-rows" else 0)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    for name, a, b in zip(("z", "weights", "w1", "w2"), got, want, strict=True):
        np.testing.assert_allclose(
            a, b, atol=1e-4 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("form,tokens", [("sorted-rows", 512), ("every-token", 64)])
def test_the_expert_form_is_read_off_the_operands(form, tokens):
    """Three matrices: three grouped products (or batched ones) forward and a
    ``logistic`` (silu), as before there was a second form; ``w3`` None: two,
    and a ``max`` and a square in its place."""
    d, f = 32, 24
    z = jnp.zeros((tokens, d))
    routing = moe.Routing(jnp.zeros((tokens, 2), jnp.int32), jnp.ones((tokens, 2)))
    w1 = w3 = jnp.zeros((2, d, f))
    w2 = jnp.zeros((2, f, d))
    product = "ragged_dot_general" if form == "sorted-rows" else "dot_general"
    moe._sorted_rows.clear_cache()
    gated = _calls(lambda *a: moe.expert_ffn(z, routing, *a, 0, 8), w1, w3, w2)
    plain = _calls(lambda a, b: moe.expert_ffn(z, routing, a, None, b, 0, 8), w1, w2)
    moe._sorted_rows.clear_cache()
    extra = gated[product] - plain[product]
    assert extra == 1 and plain[product] >= 2
    assert gated["logistic"] == 1 and "logistic" not in plain
    assert "square" in plain or "integer_pow" in plain
    assert "square" not in gated and "integer_pow" not in gated


# -- which path runs is read off the backend and the shapes ----------------------
@pytest.mark.parametrize("k,n,kernel", [
    (256, 384, True), (64, 384, False), (256, 32, False), (200, 384, False)])
def test_the_kernel_runs_only_on_whole_lanes(interpreted, k, n, kernel):
    lhs, rhs, _ = _operands(jnp.bfloat16, k, n)
    sizes = jnp.asarray(SIZES["an-empty-group"], jnp.int32)
    calls = _calls(lambda l, r: gm.grouped_dot(l, r, sizes), lhs, rhs)
    assert ("pallas_call" in calls) is kernel
    assert ("ragged_dot_general" in calls) is not kernel
    assert (gm._tiles_of(lhs, rhs, False) is not None) is kernel


def test_rows_off_a_tile_take_ragged_dot(interpreted):
    lhs = jnp.zeros((200, K), jnp.bfloat16)
    rhs = jnp.zeros((G, K, N), jnp.bfloat16)
    assert gm._tiles_of(lhs, rhs, False) is None


@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_off_the_tpu_the_product_is_ragged_dot(transpose_rhs):
    assert jax.default_backend() != "tpu" and not gm.INTERPRET
    lhs, rhs, _ = _operands(jnp.float32)
    sizes = jnp.asarray(SIZES["an-empty-group"], jnp.int32)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
    fn = lambda l, r: gm.grouped_dot(l, r, sizes, transpose_rhs=transpose_rhs)  # noqa: E731
    calls = _calls(fn, lhs, rhs)
    assert "ragged_dot_general" in calls and "pallas_call" not in calls
    assert _gap(fn(lhs, rhs), want) == 0.0  # the reference semantics, zeros and all


def test_on_a_tpu_the_product_is_the_kernel(monkeypatch):
    monkeypatch.setattr(gm.jax, "default_backend", lambda: "tpu")
    lhs, rhs, _ = _operands(jnp.bfloat16)
    assert gm._tiles_of(lhs, rhs, False) is not None
    assert gm._tiles_of(lhs[:, :64], rhs[:, :64], False) is None


# -- the tiles are a function of the shapes --------------------------------------
CELL = dict(m=5120, d=2048, f=1792)


@pytest.mark.parametrize("form,k,n", [
    ("forward", 2048, 1792), ("forward", 1792, 2048), ("dw", 2048, 1792),
    ("dw", 1792, 2048), ("forward", 128, 128), ("dw", 256, 384),
    ("forward", 8192, 8192), ("dw", 8192, 8192), ("forward", 16384, 128)])
def test_tiles_divide_the_shapes_and_fit(form, k, n):
    tm, tk, tn = gm.tiling(form, CELL["m"], k, n)
    assert CELL["m"] % tm == 0 and k % tk == 0 and n % tn == 0
    assert tm % 128 == tk % 128 == tn % 128 == 0
    assert gm._vmem_bytes(form, tm, tk, tn, 2) <= gm.VMEM_BUDGET
    assert tm == gm.ROW_TILE == 256
    if k <= 2048:  # the cell's: a whole expert matrix at once
        assert (tk, tn) == (k, n)


@pytest.mark.parametrize("m,k,n", [(5120, 64, 1792), (5120, 2048, 32), (100, 128, 128)])
def test_no_tiles_off_whole_lanes(m, k, n):
    assert gm.tiling("forward", m, k, n) is None


@pytest.mark.parametrize("rows,d,f", [(5120, 2048, 1792), (10240, 2048, 768)],
                         ids=["lfm2-cell", "sparse-attention-cell"])
def test_the_cells_that_were_there_keep_their_tiles(rows, d, f):
    """What ``tiling`` returned at the two expert cells' shapes before a
    width off whole lanes was taken: a whole expert matrix at once."""
    assert gm.tiling("forward", rows, d, f) == (256, d, f)
    assert gm.tiling("forward", rows, f, d) == (256, f, d)
    assert gm.tiling("dw", rows, d, f) == (256, d, f)
    assert gm.tiling("dw", rows, f, d) == (256, f, d)
    assert gm._chunk(f) == 256 and gm._chunk(d) == 512
    assert gm._last_chunk(f) == gm._last_chunk(d) == 0


def test_a_width_off_whole_lanes_is_one_tile():
    """1,856 = 14.5 lanes (the Mamba-2 hybrid's expert width): the dimension
    whole, columns or contracted, the inner loop ending on a chunk of 320;
    dW, whose float32 sum of a whole matrix does not fit, takes the other
    dimension in thirds."""
    m, d, f = 1024, 2688, 1856
    assert gm._widths(f) == [f] and gm._widths(d)[0] == d
    assert gm._widths(200) == gm._widths(64) == gm._widths(32) == []
    assert gm.tiling("forward", m, d, f) == (256, d, f)
    assert gm.tiling("forward", m, f, d) == (256, f, d)
    assert gm.tiling("dw", m, d, f) == (256, 896, f)
    assert gm.tiling("dw", m, f, d) == (256, f, 896)
    for form, k, n in (("forward", d, f), ("forward", f, d), ("dw", d, f), ("dw", f, d)):
        assert gm._vmem_bytes(form, *gm.tiling(form, m, k, n), 2) <= gm.VMEM_BUDGET
    assert (gm._chunk(f), gm._last_chunk(f)) == (512, 320)
    assert 3 * 512 + 320 == f


# -- Mosaic compiles the kernels at the cell's widths (no chip attached) ---------
@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.timeout(300)
@pytest.mark.parametrize("m,k,n", [
    (5120, 2048, 1792), (5120, 1792, 2048), (1024, 2688, 1856), (1024, 1856, 2688)],
    ids=["d-f", "f-d", "d-f-off-the-lane", "f-d-off-the-lane"])
def test_the_three_forms_compile_for_a_v5e_at_the_cells_shapes(
        one_chip, no_compile_cache, monkeypatch, m, k, n):
    monkeypatch.setattr(gm, "runs_mosaic", lambda: True)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def all_three(lhs, rhs, sizes, pull):
        out, pull_back = jax.vjp(lambda l, r: gm.grouped_dot(l, r, sizes), lhs, rhs)
        return out, pull_back(pull)

    text = jax.jit(all_three).lower(
        shape(m, k), shape(8, k, n), shape(8, dtype=jnp.int32), shape(m, n)
    ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "ragged-dot" not in text  # the name the compiler gives ragged_dot's kernels
