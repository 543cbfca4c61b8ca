"""The learner's attention under a selection's mask (ops/sparse_attention.py):
the Pallas kernels under Pallas's interpreter against ``layers.attend`` under
``live & chosen`` and against ``jax.grad`` of the masked-dense form, the
probabilities summed over the heads, a row with no selected key in a tile,
key tiles past the diagonal poisoned, the path chosen from backend and
shapes, and the kernels compiled for a described v5e at ``keye-vl2``'s cell:
alone, and in a learner chunk that holds no score matrix.
"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from distributed_ba3c_tpu.models import layers  # noqa: E402
from distributed_ba3c_tpu.ops import decode_attention  # noqa: E402
from distributed_ba3c_tpu.ops import grouped_matmul  # noqa: E402
from distributed_ba3c_tpu.ops import sparse_attention as sa  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402

# 2 query heads a K/V head; three tiles of 128 positions a side
B, T, H, KV, D, TILE = 2, 384, 4, 2, 128, 128
SCALE = D ** -0.5
AT = np.arange(T)
LIVE = AT[None, :] <= AT[:, None]
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 0.02}
DTYPES = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])


@pytest.fixture
def interpreted(monkeypatch):
    """The kernels, interpreted, in tiles of 128."""
    monkeypatch.setattr(sa, "INTERPRET", True)
    monkeypatch.setattr(sa, "TILE", TILE)


def _operands(dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(keys[0], (B, T, H, D), dtype)
    k = jax.random.normal(keys[1], (B, T, KV, D), dtype)
    v = jax.random.normal(keys[2], (B, T, KV, D), dtype)
    weights = jax.random.normal(keys[3], (B, T, H * D), jnp.float32)
    return q, k, v, weights


def _half(seed=0):
    """A selection a query at a time: about half of the keys it could see,
    its own position always."""
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.random((B, T, T)) < 0.5) | np.eye(T, dtype=bool))


def _none_in_the_diagonals_tile():
    """Queries of the second and third tile select keys of the first tile
    alone: the tiles in between and the diagonal's hold none of theirs."""
    chosen = np.broadcast_to(LIVE, (B, T, T)).copy()
    chosen[:, TILE:, TILE:] = False
    return jnp.asarray(chosen)


def _none_in_the_first_tile():
    """Queries past the first tile select no key of it: the running maximum
    is still ``-inf`` when the first tile has been walked."""
    chosen = np.broadcast_to(LIVE, (B, T, T)).copy()
    chosen[:, TILE:, :TILE] = False
    return jnp.asarray(chosen)


SELECTIONS = {
    "none-given": lambda: None,
    "every-live-key": lambda: jnp.asarray(np.broadcast_to(LIVE, (B, T, T))),
    "half": _half,
    "its-own-position-alone": lambda: jnp.asarray(
        np.broadcast_to(np.eye(T, dtype=bool), (B, T, T))),
    "none-in-the-diagonals-tile": _none_in_the_diagonals_tile,
    "none-in-the-first-tile": _none_in_the_first_tile,
}


def _mask(chosen):
    live = jnp.asarray(LIVE)[None]
    return live if chosen is None else live & chosen


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


def _weighed(fn, weights):
    """d(sum(out * weights)) / d(q, k, v) of ``fn(q, k, v) -> out``."""
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * weights), (0, 1, 2))


# -- the kernels in value -----------------------------------------------------------
@DTYPES
@pytest.mark.parametrize("case", SELECTIONS)
def test_the_forward_is_attend_under_the_mask(interpreted, case, dtype):
    q, k, v, _ = _operands(dtype)
    chosen = SELECTIONS[case]()
    assert sa.tile_of(q, k) == TILE
    got, _ = sa.attend_selected(q, k, v, chosen, SCALE)
    want = layers.attend(q, k, v, _mask(chosen), dtype, scale=SCALE)
    assert got.dtype == jnp.float32 and got.shape == (B, T, H * D)
    assert np.isfinite(np.asarray(got)).all()
    assert _gap(got, want) <= TOL[dtype] * max(float(jnp.abs(want).max()), 1.0)


@DTYPES
@pytest.mark.parametrize("case", ["none-given", "half", "none-in-the-diagonals-tile",
                                  "none-in-the-first-tile"])
def test_the_gradients_are_the_masked_dense_forms(interpreted, case, dtype):
    q, k, v, weights = _operands(dtype, seed=1)
    chosen = SELECTIONS[case]()
    got = _weighed(
        lambda q, k, v: sa.attend_selected(q, k, v, chosen, SCALE)[0], weights)(q, k, v)
    want = _weighed(
        lambda q, k, v: layers.attend(q, k, v, _mask(chosen), dtype, scale=SCALE),
        weights)(q, k, v)
    for name, g, w in zip("qkv", got, want, strict=True):
        assert g.dtype == dtype and g.shape == w.shape, name
        assert np.isfinite(np.asarray(g, np.float32)).all(), name
        assert _gap(g, w) <= TOL[dtype] * float(jnp.abs(w).max()), name


@DTYPES
@pytest.mark.parametrize("case", ["none-given", "half", "none-in-the-first-tile"])
def test_the_heads_sum_is_the_probabilities_summed(interpreted, case, dtype):
    q, k, v, _ = _operands(dtype, seed=2)
    chosen = SELECTIONS[case]()
    _, got = sa.attend_selected(q, k, v, chosen, SCALE)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, jnp.repeat(k, H // KV, axis=2),
                        preferred_element_type=jnp.float32) * SCALE
    probs = jax.nn.softmax(jnp.where(_mask(chosen)[:, None], scores, -jnp.inf), -1)
    want = probs.sum(axis=1)
    assert got.dtype == jnp.float32 and got.shape == (B, T, T)
    assert _gap(got, want) <= 1e-5 * H
    np.testing.assert_allclose(np.asarray(got).sum(-1), H, rtol=1e-5)
    # nothing outside the mask, the tiles past the diagonal included
    assert not np.asarray(got)[~np.broadcast_to(np.asarray(_mask(chosen)), got.shape)].any()


def test_no_gradient_passes_the_heads_sum(interpreted):
    q, k, v, _ = _operands(jnp.float32, seed=3)
    grads = jax.grad(
        lambda q, k, v: jnp.sum(jnp.square(
            sa.attend_selected(q, k, v, _half(), SCALE)[1])), (0, 1, 2))(q, k, v)
    assert all(not np.asarray(g).any() for g in grads)


@DTYPES
def test_a_row_with_no_selected_key_in_a_tile_weighs_nothing_there(interpreted, dtype):
    """The second tile's queries select the first tile's keys alone: K and V
    of every other tile are poisoned with 1e30 where a query of theirs could
    see them, and weigh nothing (a NaN there would show too: 0 * 1e30 is 0,
    ``exp(-inf - -inf)`` is not)."""
    q, k, v, weights = _operands(dtype, seed=4)
    chosen = _none_in_the_diagonals_tile()
    rows = slice(TILE, 2 * TILE)
    row = jnp.arange(T)[None, :, None, None]
    poison = lambda x: jnp.where(row >= TILE, 1e30, x.astype(jnp.float32)).astype(dtype)  # noqa: E731
    got, shared = sa.attend_selected(q, poison(k), poison(v), chosen, SCALE)
    want = layers.attend(q, k, v, _mask(chosen), dtype, scale=SCALE)
    assert np.isfinite(np.asarray(got)).all()
    assert _gap(got[:, rows], want[:, rows]) <= TOL[dtype] * float(jnp.abs(want).max())
    assert not np.asarray(shared)[:, rows, TILE:].any()
    dq, _, _ = _weighed(
        lambda q, k, v: sa.attend_selected(q, k, v, chosen, SCALE)[0],
        weights)(q, poison(k), poison(v))
    assert np.isfinite(np.asarray(dq[:, rows], np.float32)).all()


@DTYPES
def test_key_tiles_past_the_diagonal_are_neither_read_nor_weighed(interpreted, dtype):
    """NaN in every K and V row past the first tile: the first tile's queries
    never fetch them (a product with a probability of 0 would still be NaN).
    In the diagonal's own tile a key after the query is masked whatever the
    selection says of it: 1e30 there would win every maximum."""
    q, k, v, _ = _operands(dtype, seed=5)
    row = jnp.arange(T)[None, :, None, None]
    nan_past = lambda x: jnp.where(row >= TILE, jnp.nan, x.astype(jnp.float32)).astype(dtype)  # noqa: E731
    everything = jnp.ones((B, T, T), bool)  # a selection that names later keys
    got, shared = sa.attend_selected(q, nan_past(k), nan_past(v), everything, SCALE)
    want = layers.attend(q, k, v, _mask(None), dtype, scale=SCALE)
    first = slice(0, TILE)
    assert np.isfinite(np.asarray(got[:, first])).all()
    assert _gap(got[:, first], want[:, first]) <= TOL[dtype] * float(jnp.abs(want).max())
    assert np.isfinite(np.asarray(shared[:, first])).all()
    # inside the diagonal's tile: query 0 sees key 0 alone
    loud = lambda x: jnp.where(row >= 1, 1e30, x.astype(jnp.float32)).astype(dtype)  # noqa: E731
    got, _ = sa.attend_selected(q, loud(k), loud(v), everything, SCALE)
    np.testing.assert_allclose(
        np.asarray(got[:, 0]).reshape(B, KV, H // KV, D),
        np.broadcast_to(np.asarray(v[:, 0], np.float32)[:, :, None], (B, KV, H // KV, D)),
        rtol=TOL[dtype])


@pytest.mark.parametrize("tile", [128, 384])
def test_the_tile_does_not_change_the_value(monkeypatch, tile):
    monkeypatch.setattr(sa, "INTERPRET", True)
    monkeypatch.setattr(sa, "TILE", tile)
    q, k, v, weights = _operands(jnp.float32, seed=6)
    chosen = _half(1)
    assert sa.tile_of(q, k) == tile
    got, shared = sa.attend_selected(q, k, v, chosen, SCALE)
    want, want_shared = sa._dense(q, k, v, chosen, SCALE)
    assert _gap(got, want) <= 1e-5 * float(jnp.abs(want).max())
    assert _gap(shared, want_shared) <= 1e-5 * H


# -- which path runs ------------------------------------------------------------------
def _kernels(fn, *args) -> int:
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


def test_off_the_tpu_the_op_is_the_masked_dense_form():
    q, k, v, _ = _operands(jnp.float32)
    chosen = _half()
    assert jax.default_backend() == "cpu" and sa.tile_of(q, k) is None
    attend = lambda q, k, v: sa.attend_selected(q, k, v, chosen, SCALE)  # noqa: E731
    assert _kernels(attend, q, k, v) == 0
    out, shared = attend(q, k, v)
    np.testing.assert_array_equal(
        out, layers.attend(q, k, v, _mask(chosen), jnp.float32, scale=SCALE))
    assert sa.tiles_visited_share(q, k) == 1.0


@pytest.mark.parametrize("shape,tile", [
    ((B, T, H, D), 384), ((B, 4096, 32, 128), 512), ((B, 1024, H, D), 512),
    ((B, 640, H, D), 128), ((B, T, H, 64), None), ((B, 100, H, D), None),
    ((B, 32, H, 16), None)], ids=str)
def test_the_tile_is_read_off_the_shapes(monkeypatch, shape, tile):
    monkeypatch.setattr(sa, "runs_mosaic", lambda: True)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((*shape[:2], shape[2] // 2, shape[3]), jnp.bfloat16)
    assert sa.tile_of(q, k) == tile
    n = shape[1] // tile if tile else None
    assert sa.tiles_visited_share(q, k) == (1.0 if tile is None else (n + 1) / (2 * n))


def test_the_kernels_are_one_forward_one_sum_and_two_backward(interpreted):
    q, k, v, weights = _operands(jnp.float32)
    chosen = _half()
    attend = lambda q, k, v: sa.attend_selected(q, k, v, chosen, SCALE)  # noqa: E731
    assert _kernels(attend, q, k, v) == 2
    # differentiated: the forward once (no recomputation), dK/dV and dQ; the
    # heads' sum is not differentiated and, unused, is no part of it
    assert _kernels(_weighed(lambda *a: attend(*a)[0], weights), q, k, v) == 4


# -- compiled for a described v5e -----------------------------------------------------
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


@pytest.fixture
def on_a_tpu(monkeypatch):
    """Every module of kernels takes its TPU path (the code asks the backend,
    which is the CPU here)."""
    for module in (sa, decode_attention, grouped_matmul):
        monkeypatch.setattr(module, "runs_mosaic", lambda: True)


_KERNELS = ("sparse_attend_forward", "sparse_attend_shared",
            "sparse_attend_backward_kv", "sparse_attend_backward_q")


def _kernel_names(text):
    return re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', text)


@pytest.mark.timeout(300)
@pytest.mark.parametrize("selected", [True, False], ids=["selection", "none"])
def test_the_kernels_compile_for_a_v5e_at_the_cells_shapes(
        one_chip, no_compile_cache, on_a_tpu, selected):
    """[2, 4096, 32, 128] against [2, 4096, 4, 128], bfloat16, in tiles of
    512: Mosaic takes the four kernels (tilings, fast memory) as the chip's
    compiler would."""
    placed = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    q = placed((2, 4096, 32, 128), jnp.bfloat16)
    k = placed((2, 4096, 4, 128), jnp.bfloat16)
    chosen = placed((2, 4096, 4096), jnp.bool_)
    weights = placed((2, 4096, 4096), jnp.float32)
    assert sa.tile_of(q, k) == 512

    def both(q, k, v, chosen, weights):
        def weighed(q, k, v):
            out, shared = sa.attend_selected(
                q, k, v, chosen if selected else None, 128 ** -0.5)
            return jnp.sum(out * weights), shared
        return jax.value_and_grad(weighed, (0, 1, 2), has_aux=True)(q, k, v)

    text = jax.jit(both).lower(q, k, k, chosen, weights).compile().as_text()
    names = _kernel_names(text)
    assert sorted(n.split("/")[-2] for n in names) == sorted(_KERNELS), names
    assert [profiling.is_backward(n) for n in sorted(names, key=lambda n: n.split("/")[-2])
            ] == [True, True, False, False]
    # the selection goes in a byte a pair, transposed once for dK/dV
    assert bool(re.search(r"= s8\[2,4096,4096\]", text)) == selected
    assert not _SCORES.search(text)


@pytest.mark.timeout(300)
def test_the_kernels_compile_ungrouped_and_without_a_selection(
        one_chip, no_compile_cache, on_a_tpu):
    """The linear-attention hybrid's full layer (models/olmo_hybrid.py): [2,
    2048, 10, 128] against as many key/value heads, no selection: the first
    cell to run that form."""
    placed = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    q = placed((2, 2048, 10, 128), jnp.bfloat16)
    weights = placed((2, 2048, 1280), jnp.float32)
    assert sa.tile_of(q, q) == 512

    def both(q, k, v, weights):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            sa.attend_selected(q, k, v, None, 128 ** -0.5)[0] * weights),
            (0, 1, 2))(q, k, v)

    text = jax.jit(both).lower(q, q, q, weights).compile().as_text()
    # the heads' sum is not asked for, so its kernel is not in the program
    assert sorted(n.split("/")[-2] for n in _kernel_names(text)) == sorted(
        set(_KERNELS) - {"sparse_attend_shared"})
    assert not re.search(r"= (?:f32|bf16)\[2,10,(?:512|2048),\d{3,4}\]", text)


#: an instruction that makes a score matrix of the cell's chunk: every head's
#: [queries, keys] in float32 or bfloat16, the heads grouped or not
_SCORES = re.compile(
    r"= (?:f32|bf16)\[2,(?:4,8|32),(?:512|4096),\d{3,4}\]\S* [\w\-]+\(")


def test_the_masked_dense_form_writes_every_heads_scores(one_chip, no_compile_cache):
    """What the learner's chunk must not hold, seen where it is: the
    masked-dense form of one block of 512 queries, compiled for the v5e."""
    placed = lambda shape, dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one_chip)
    q, k = placed((2, 512, 32, 128), jnp.bfloat16), placed((2, 512, 4, 128), jnp.bfloat16)
    text = jax.jit(lambda q, k, v, c: sa._dense(q, k, v, c, 0.1)).lower(
        q, k, k, placed((2, 512, 512), jnp.bool_)).compile().as_text()
    assert _SCORES.search(text)


@pytest.mark.timeout(900)
def test_the_compiled_learner_chunk_holds_no_score_matrix(
        one_chip, no_compile_cache, on_a_tpu):
    """``keye-vl2``'s unroll of a learner's chunk (2 envs x 4,096 positions,
    the published widths, one of the held layers) differentiated and compiled
    for the v5e: the four kernels under ``op_attn_sparse`` (the forward and
    the heads' sum twice: the layer is recomputed in the backward), and no
    buffer of [.., 32 heads, queries, keys] extent, float32 or bfloat16."""
    from distributed_ba3c_tpu.models import policy
    from distributed_ba3c_tpu.models.keye_vl2 import KeyeVL2

    model = KeyeVL2(max_positions=4096, layer_ids=(0,))
    placed = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = placed(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one_chip)
    assert model.learner_tiles_visited_share() == 9 / 16

    def total(params, tokens):
        with profiling.device_scope(profiling.LEARNER):
            out, aux = model.unroll(params, tokens)
            return (jnp.sum(jax.nn.log_softmax(out.logits)[..., 0]) + jnp.sum(out.value)
                    + jnp.sum(aux[policy.LOSS_TERMS]["indexer_kl"]))

    text = jax.jit(jax.value_and_grad(total)).lower(
        params, tokens).compile().as_text()
    names = [n for n in _kernel_names(text) if "sparse_attend" in n]
    assert sorted(n.split("/")[-2] for n in names) == sorted(
        _KERNELS + _KERNELS[:2]), names
    scope = f"{profiling.LEARNER}/{profiling.OP_ATTN_SPARSE}"
    assert scope in profiling.ALL_SCOPES
    assert {profiling.scope_of(n) for n in names} == {scope}, names
    made = [m.group(0) for m in _SCORES.finditer(text)]
    assert not made, made[:4]
