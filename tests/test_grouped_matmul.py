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
@pytest.mark.parametrize("k,n", [(2048, 1792), (1792, 2048)], ids=["d-f", "f-d"])
def test_the_three_forms_compile_for_a_v5e_at_the_cells_shapes(
        one_chip, no_compile_cache, monkeypatch, k, n):
    monkeypatch.setattr(gm, "_backend_runs_mosaic", lambda: True)
    m = CELL["m"]

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
