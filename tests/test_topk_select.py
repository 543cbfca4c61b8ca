"""``ops/topk_select.py:select_mask`` in its three regimes, each against a
stable sort bit for bit: ``live`` returned where the shapes say every live
key fits, ``live`` returned and the searches not executed where a count says
so, the two searches otherwise, in ``jax.numpy`` and in the Pallas kernel
(interpreted here; compiled for a described v5e, not run)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P, SingleDeviceSharding

from distributed_ba3c_tpu.models.keye_vl2 import KeyeVL2, cut_fields
from distributed_ba3c_tpu.ops import topk_select
from distributed_ba3c_tpu.ops.topk_select import select_mask
from distributed_ba3c_tpu.utils import profiling

#: the cell's decode row block and its top-k; a block of the learner's
CELL, TOPK, LEARNER = (16, 4096), 2048, (2, 512, 2560)


def by_sort(scores, live, k):
    """The ``min(k, live)`` live entries of largest score of each row, a tie
    to the lower position: a stable sort, a row at a time."""
    scores, live = np.asarray(scores), np.asarray(live)
    flat_s, flat_l = scores.reshape(-1, scores.shape[-1]), live.reshape(-1, live.shape[-1])
    want = np.zeros(flat_s.shape, bool)
    for r, (row, alive) in enumerate(zip(flat_s, flat_l, strict=True)):
        order = np.lexsort((np.arange(len(row)), -(row + 0.0), ~alive))
        want[r, order[:min(k, int(alive.sum()))]] = True
    return (want & flat_l).reshape(live.shape)


def up_to(counts, n):
    """live [len(counts), n]: row ``r`` holds its first ``counts[r]`` entries."""
    return np.arange(n)[None, :] < np.asarray(counts)[:, None]


def selected(scores, live, k):
    return np.asarray(jax.jit(lambda s, a: select_mask(s, a, k))(scores, live))


@pytest.fixture
def searches_run(monkeypatch):
    """A list that grows by one each time regime 3 is EXECUTED (a callback
    inside the ``cond``'s branch: a branch not taken calls nothing)."""
    ran = []
    inner = topk_select._run_searches

    def counted(scores, live, k):
        jax.debug.callback(lambda: ran.append(1))
        return inner(scores, live, k)

    monkeypatch.setattr(topk_select, "_run_searches", counted)
    return ran


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(topk_select, "INTERPRET", True)
    topk_select._kernel_searches.clear_cache()
    yield
    topk_select._kernel_searches.clear_cache()


def loops_in(jaxpr):
    """Names of the control-flow primitives anywhere in a jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("while", "cond", "scan", "pallas_call"):
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += loops_in(sub)
    return found


# -- the boundary between the regimes, against the sort ---------------------------
@pytest.mark.parametrize("n,k", [(64, 8), (256, 128), (384, 100)])
@pytest.mark.parametrize("surplus", [0, 1], ids=["k_live", "k_plus_1_live"])
def test_exactly_k_and_k_plus_one_live(searches_run, n, k, surplus):
    rng = np.random.default_rng(n + surplus)
    scores = rng.normal(size=(4, n)).astype(np.float32)
    live = np.zeros((4, n), bool)
    for row in live:
        row[rng.choice(n, k + surplus, replace=False)] = True
    got = selected(scores, live, k)
    np.testing.assert_array_equal(got, by_sort(scores, live, k))
    assert (got.sum(-1) == k).all()
    jax.effects_barrier()
    # k live: every row fits, nothing searched; k + 1: one entry has to go
    assert len(searches_run) == surplus


@pytest.mark.parametrize("n,k", [(8, 8), (5, 8), (128, 2048), (512, 512)])
def test_a_row_no_longer_than_k_is_live_and_emits_no_op(n, k):
    rng = np.random.default_rng(n)
    scores = rng.normal(size=(3, n)).astype(np.float32)
    live = rng.random((3, n)) < 0.6
    np.testing.assert_array_equal(selected(scores, live, k), live)
    np.testing.assert_array_equal(live, by_sort(scores, live, k))
    jaxpr = jax.make_jaxpr(lambda s, a: select_mask(s, a, k))(scores, live)
    assert not jaxpr.eqns and not loops_in(jaxpr.jaxpr)


@pytest.mark.parametrize("shape,k", [((6, 37), 8), ((16, 512), 256), (CELL, TOPK)])
def test_where_every_row_fits_the_searches_are_not_executed(searches_run, shape, k):
    rng = np.random.default_rng(1)
    scores = rng.normal(size=shape).astype(np.float32)
    live = up_to(rng.integers(0, k + 1, shape[0]), shape[1])
    live[0] = up_to([k], shape[1])[0]                 # a row exactly full
    fn = jax.jit(lambda s, a: select_mask(s, a, k))
    assert "cond" in loops_in(jax.make_jaxpr(fn)(scores, live).jaxpr)
    got = np.asarray(fn(scores, live))
    jax.effects_barrier()
    np.testing.assert_array_equal(got, live)
    np.testing.assert_array_equal(got, by_sort(scores, live, k))
    assert searches_run == []
    # the same program, a row one entry fuller: now they run, once
    live[-1] = up_to([k + 1], shape[1])[0]
    got = np.asarray(fn(scores, live))
    jax.effects_barrier()
    np.testing.assert_array_equal(got, by_sort(scores, live, k))
    assert searches_run == [1]


@pytest.mark.parametrize("shape,k", [((2, 64), 8), ((8, 512), 100), (CELL, TOPK)])
def test_one_fitting_and_one_overflowing_row_in_a_batch(searches_run, shape, k):
    rng = np.random.default_rng(2)
    scores = rng.normal(size=shape).astype(np.float32)
    counts = np.where(np.arange(shape[0]) % 2 == 0, k // 2, shape[1])
    live = up_to(counts, shape[1])
    got = selected(scores, live, k)
    jax.effects_barrier()
    np.testing.assert_array_equal(got, by_sort(scores, live, k))
    np.testing.assert_array_equal(got[0], live[0])
    assert got[1].sum() == k and searches_run == [1]


# -- ties and special values at the cell's width -------------------------------------
def special_rows(rng, shape):
    scores = rng.normal(size=shape).astype(np.float32)
    flat = scores.reshape(-1, shape[-1])
    n = shape[-1]
    flat[0] = 0.0                                       # every entry ties
    flat[1, ::2], flat[1, 1::2] = -0.0, 0.0             # the two zeros are one
    flat[2] = np.round(flat[2])                         # ties everywhere
    flat[3, : n // 2] = -np.inf
    flat[4, 5:n // 2 + 9] = flat[4].max() + 1.0         # a tie across the k-th place
    flat[5] = -np.inf                                   # every entry ties at the bottom
    flat[6, ::3] = np.inf
    return scores


def test_all_equal_scores_keep_the_first_k_positions_at_the_cells_width():
    scores = np.full(CELL, 0.25, np.float32)
    live = np.ones(CELL, bool)
    live[1, ::2] = False                                # 2,048 live: fits
    live[2, :100] = False
    got = selected(scores, live, TOPK)
    np.testing.assert_array_equal(got, by_sort(scores, live, TOPK))
    assert got[0, :TOPK].all() and not got[0, TOPK:].any()
    assert got[2, 100:100 + TOPK].all() and got[2].sum() == TOPK


@pytest.mark.parametrize("seed", range(3))
def test_zeros_infinities_and_ties_at_the_cells_width(seed):
    rng = np.random.default_rng(seed)
    scores = special_rows(rng, CELL)
    live = rng.random(CELL) < (0.55, 0.8, 1.0)[seed]
    live[7] = up_to([TOPK - 1], CELL[1])[0]             # fewer live than k
    got = selected(scores, live, TOPK)
    np.testing.assert_array_equal(got, by_sort(scores, live, TOPK))


@pytest.mark.parametrize("lead", [(), (5,), (3, 4), (2, 3, 4)],
                         ids=lambda s: f"rank{len(s)}")
def test_leading_axes_of_any_rank(lead):
    rng = np.random.default_rng(len(lead))
    shape = lead + (96,)
    scores = np.round(rng.normal(size=shape) * 4).astype(np.float32)
    live = rng.random(shape) < 0.8
    got = selected(scores, live, 24)
    assert got.shape == shape
    np.testing.assert_array_equal(got, by_sort(scores, live, 24))


# -- as its callers call it ------------------------------------------------------------
@pytest.mark.parametrize("lo,keys,k", [(0, 8, 8), (8, 16, 8), (24, 32, 8)])
def test_under_checkpoint_with_the_learners_static_argument(lo, keys, k):
    """``models/keye_vl2.py:_select``'s shape of call: a block of queries
    from ``lo`` over keys ``[0, keys)``, ``lo`` static, under
    ``jax.checkpoint`` and differentiated (nothing passes the selection)."""
    rng = np.random.default_rng(lo)
    index = jnp.asarray(rng.normal(size=(2, keys - lo, keys)).astype(np.float32))

    def block(index, weight, lo):
        at = lo + jnp.arange(index.shape[1])[:, None]
        live = jnp.broadcast_to(jnp.arange(keys)[None, :] <= at, index.shape)
        chosen = select_mask(jax.lax.stop_gradient(index), live, k)
        return jnp.sum(jnp.where(chosen, index * weight, 0.0)), chosen

    run = jax.jit(jax.grad(
        jax.checkpoint(block, static_argnums=(2,)), has_aux=True),
        static_argnums=(2,))
    grad, chosen = run(index, jnp.float32(3.0), lo)
    at = lo + np.arange(keys - lo)[:, None]
    live = np.broadcast_to(np.arange(keys)[None, :] <= at, index.shape)
    want = by_sort(index, live, k)
    np.testing.assert_array_equal(np.asarray(chosen), want)
    np.testing.assert_array_equal(np.asarray(grad), np.where(want, 3.0, 0.0))


@pytest.mark.parametrize("counts", [(4, 8, 3, 8, 2, 8, 1, 8), (20, 8, 64, 3, 9, 8, 8, 40)],
                         ids=["every_shard_fits", "some_shards_overflow"])
def test_under_shard_map_each_shard_decides_for_its_own_rows(counts):
    devices = np.asarray(jax.devices()[:8])
    if devices.size < 8:
        pytest.skip("needs the 8-device CPU mesh of tests/conftest.py")
    mesh = Mesh(devices, ("data",))
    rng = np.random.default_rng(3)
    scores = rng.normal(size=(16, 64)).astype(np.float32)
    live = up_to(np.repeat(counts, 2), 64)
    # ``live`` built inside, unvarying, as the decode builds it from a
    # position that varies; and handed in, varying
    inside = jax.jit(jax.shard_map(
        lambda s, c: select_mask(s, jnp.arange(64)[None, :] < c[:, None], 8),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data")))
    handed = jax.jit(jax.shard_map(
        lambda s, a: select_mask(s, a, 8),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P("data")))
    want = by_sort(scores, live, 8)
    np.testing.assert_array_equal(
        np.asarray(inside(scores, np.repeat(counts, 2))), want)
    np.testing.assert_array_equal(np.asarray(handed(scores, live)), want)
    # a row no longer than k, unvarying ``live``: still the shards' own mask
    short = jax.jit(jax.shard_map(
        lambda s: select_mask(s, jnp.ones(s.shape, bool), 64),
        mesh=mesh, in_specs=P("data"), out_specs=P("data")))
    assert np.asarray(short(scores)).all()


# -- the kernel ------------------------------------------------------------------------
@pytest.mark.parametrize("shape,k,blocks", [
    (CELL, TOPK, 1), (LEARNER, TOPK, 16), ((24, 256), 100, 1), ((2, 64, 384), 129, 1)])
def test_the_interpreted_kernel_is_the_jax_numpy_form(interpreted, shape, k, blocks):
    rng = np.random.default_rng(4)
    scores = special_rows(rng, shape)
    scores.reshape(-1, shape[-1])[8, 3::5] = np.nan     # no order a sort agrees on
    rows, n = int(np.prod(shape[:-1])), shape[-1]
    if shape == LEARNER:  # as the learner's block has it: a causal edge
        live = np.broadcast_to(
            np.arange(n)[None, :] <= (n - 512) + np.arange(512)[:, None], shape).copy()
    else:
        live = rng.random(shape) < 0.8
        live.reshape(rows, n)[7] = up_to([k - 1], n)[0]
    block = topk_select.block_rows(rows, n)
    assert block is not None and rows // block == blocks
    jaxpr = jax.make_jaxpr(lambda s, a: select_mask(s, a, k))(scores, live)
    assert "pallas_call" in loops_in(jaxpr.jaxpr)
    got = selected(scores, live, k)
    want = np.asarray(jax.jit(functools.partial(topk_select._searches, k=k))(
        jnp.asarray(scores), jnp.asarray(live)))
    np.testing.assert_array_equal(got, want)
    sane = ~np.isnan(scores).any(-1)
    np.testing.assert_array_equal(got[sane], by_sort(scores, live, k)[sane])


@pytest.mark.parametrize("rows,n,block", [
    (16, 4096, 16), (1024, 2560, 64), (1024, 4096, 64), (1024, 3072, 64),
    (6, 4096, None), (16, 4000, None), (8, 128, 8), (24, 256, 24)])
def test_a_block_is_whole_tiles_of_whole_lanes_that_fit(interpreted, rows, n, block):
    assert topk_select.block_rows(rows, n) == block


def test_off_the_tpu_and_at_odd_widths_the_searches_are_jax_numpy():
    scores = jnp.zeros(CELL)
    live = jnp.ones(CELL, bool)
    assert topk_select.block_rows(*CELL) is None       # this backend is a CPU
    found = loops_in(jax.make_jaxpr(
        lambda s, a: select_mask(s, a, TOPK))(scores, live).jaxpr)
    # a ``fori_loop`` of a static count is a ``scan`` in the jaxpr
    assert "pallas_call" not in found and found.count("scan") == 2


def test_on_a_tpu_the_searches_are_one_kernel_under_the_radix_scope(monkeypatch):
    monkeypatch.setattr(topk_select, "runs_mosaic", lambda: True)
    topk_select._kernel_searches.clear_cache()
    scores = jax.ShapeDtypeStruct(CELL, jnp.float32)
    live = jax.ShapeDtypeStruct(CELL, jnp.bool_)
    jaxpr = jax.make_jaxpr(lambda s, a: select_mask(s, a, TOPK))(scores, live)
    found = loops_in(jaxpr.jaxpr)
    # both loops inside the kernel's body, none beside it
    assert found.count("pallas_call") == 1 and found.count("cond") == 1
    assert found.count("scan") == 2
    (cond,) = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    inside = [loops_in(branch.jaxpr) for branch in cond.params["branches"]]
    assert sorted(map(len, inside)) == [0, 3]            # one branch is ``live``
    topk_select._kernel_searches.clear_cache()


def test_the_radix_scope_is_the_selects_and_this_policys():
    assert profiling.OP_INDEXER_SELECT_RADIX in profiling.KEYE_VL2_LAYERS
    assert profiling.OP_INDEXER_SELECT_RADIX.startswith(profiling.OP_INDEXER_SELECT + "/")
    name = ("jit(multi_step)/rollout/while/body/policy/op_indexer/select/cond/"
            "branch_1_fun/radix/jit(_kernel_searches)/select_radix/pallas_call")
    assert profiling.scope_of(name) == "rollout/policy/op_indexer/select/radix"
    name = "jit(multi_step)/rollout/while/body/policy/op_indexer/select/cond"
    assert profiling.scope_of(name) == "rollout/policy/op_indexer/select"


# -- what the policy reports of it ---------------------------------------------------
@pytest.mark.parametrize("fields,decode,learner", [
    ({}, 0.5, 0.5),                                      # the cell: 4,096 over 2,048
    ({"max_positions": 2048}, 0.0, 0.0),                 # no longer than the top-k
    ({"max_positions": 8192}, 0.75, 0.75),
    ({**cut_fields("tiny"), "max_positions": 32}, 0.75, 0.75),
    ({"max_positions": 2304}, 256 / 2304, 1 / 6),           # six blocks of 384
])
def test_the_shares_of_selections_that_run_their_searches(fields, decode, learner):
    model = KeyeVL2(**fields)
    assert model.decode_selects_run_share() == pytest.approx(decode)
    assert model.learner_selects_run_share() == pytest.approx(learner)


def test_runs_searches_is_the_regimes_own_predicate():
    assert not topk_select.runs_searches(2048, 2048, 2048)    # regime 1
    assert not topk_select.runs_searches(4096, 2048, 2048)    # regime 2
    assert topk_select.runs_searches(4096, 2049, 2048)        # regime 3


# -- Mosaic, compiled for a described v5e (nothing runs) ---------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("shape", [CELL, LEARNER, (2, 512, 4096)],
                         ids=["decode", "learner_2560", "learner_4096"])
def test_the_kernel_compiles_for_a_v5e_at_the_cells_shapes(
        one_chip, monkeypatch, shape):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(topk_select, "runs_mosaic", lambda: True)
    topk_select._kernel_searches.clear_cache()
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        args = (jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip),
                jax.ShapeDtypeStruct(shape, jnp.bool_, sharding=one_chip))
        text = jax.jit(
            lambda s, a: select_mask(s, a, TOPK)).lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
        topk_select._kernel_searches.clear_cache()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and "op_name" in calls[0]
    assert "/radix/" in calls[0] and "conditional(" in text
