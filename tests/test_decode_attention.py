"""A decode step's attention over the rows up to a length
(ops/decode_attention.py): the Pallas kernel under Pallas's interpreter
against ``layers.attend`` under the mask ``row < length``, lengths on and
beside a block's edge and another for every env, rows past the length
poisoned, the same under a selection (``row < length`` AND ``kept``: every
live row, half of them, none in the first live block, one, the boundary
block's alone; unselected rows poisoned), the path chosen from backend and
shapes, the block from the shapes, the program without a selection the one
it was, and the two policies' decodes compiled for a described v5e: the
kernel under the attention scopes and no whole K/V buffer moved in the loop.
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
from distributed_ba3c_tpu.models.phi4_flash import Phi4Flash  # noqa: E402
from distributed_ba3c_tpu.ops import decode_attention as da  # noqa: E402
from distributed_ba3c_tpu.utils import profiling  # noqa: E402
from distributed_ba3c_tpu.utils.profiling import device_scope  # noqa: E402

# 4 queries a K/V pair, as the hybrid's pairs have; four blocks of 128 rows
B, H, G, W, ROWS, BLOCK = 6, 8, 2, 128, 512, 128
SCALE = 0.125
LENGTHS = {
    "one": (1,) * B,
    "a-block-less-one": (BLOCK - 1,) * B,
    "a-block": (BLOCK,) * B,
    "a-block-and-one": (BLOCK + 1,) * B,
    "the-whole-buffer": (ROWS,) * B,
    "every-env-another": (1, BLOCK - 1, BLOCK, BLOCK + 1, 300, ROWS),
}
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 0.01}


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(da, "INTERPRET", True)


@pytest.fixture
def blocks_of_128(monkeypatch):
    """Four blocks a buffer, whatever the type's bytes a row."""
    monkeypatch.setattr(da, "block_rows", lambda rows, row_bytes: BLOCK)


def _operands(dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, H, W), dtype)
    k = jax.random.normal(keys[1], (B, ROWS, G * W), dtype)
    v = jax.random.normal(keys[2], (B, ROWS, G * W), dtype)
    return q, k, v


def _masked(q, k, v, length, kept=None):
    """``layers.attend`` over whole buffers under the mask."""
    rows = k.shape[1]
    groups = k.shape[2] // q.shape[2]
    mask = jnp.arange(rows)[None, None, :] < length[:, None, None]
    if kept is not None:
        mask = mask & kept[:, None, :]
    shape = (k.shape[0], rows, groups, q.shape[2])
    return layers.attend(q[:, None], k.reshape(shape), v.reshape(shape), mask,
                         v.dtype, scale=SCALE).reshape(q.shape)


def _gap(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


def _has_kernel(fn, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(fn)(*args))


def _kernel_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, those of the functions it
    calls included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _kernel_calls(sub)
    return found


# -- the kernel in value ----------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", LENGTHS)
def test_the_kernel_is_attend_under_the_mask(interpreted, blocks_of_128, case, dtype):
    q, k, v = _operands(dtype)
    length = jnp.asarray(LENGTHS[case], jnp.int32)
    assert da._block_of(q, k) == BLOCK
    got = da.decode_attend(q, k, v, length, SCALE)
    want = _masked(q, k, v, length)
    assert got.dtype == jnp.float32 and got.shape == (B, H, W)
    assert _gap(got, want) <= TOL[dtype] * max(float(jnp.abs(want).max()), 1.0)


@pytest.mark.parametrize("kib,block", [(128, 128), (256, 256), (768, 512)])
def test_the_block_does_not_change_the_value(interpreted, monkeypatch, kib, block):
    monkeypatch.setattr(da, "BLOCK_BYTES", kib * 2**10)
    q, k, v = _operands(jnp.float32, seed=1)
    assert da._block_of(q, k) == block
    length = jnp.asarray(LENGTHS["every-env-another"], jnp.int32)
    want = _masked(q, k, v, length)
    assert _gap(da.decode_attend(q, k, v, length, SCALE), want) <= 2e-6 * float(
        jnp.abs(want).max())


# -- rows at or past the length, poisoned -----------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["one", "a-block-less-one", "a-block",
                                  "a-block-and-one", "every-env-another"])
def test_rows_past_the_length_are_not_read_nor_weighed(
        interpreted, blocks_of_128, case, dtype):
    """NaN in every row of the blocks wholly past an env's length (they are
    not fetched: a product with a probability of 0 would still be NaN);
    1e30 in the rows past it inside the block that holds the boundary (they
    are masked: their scores would win every maximum)."""
    q, k, v = _operands(dtype, seed=2)
    length = jnp.asarray(LENGTHS[case], jnp.int32)
    row = jnp.arange(ROWS)[None, :, None]
    live_blocks = (length[:, None, None] + BLOCK - 1) // BLOCK * BLOCK
    poison = lambda x: jnp.where(  # noqa: E731
        row >= live_blocks, jnp.nan,
        jnp.where(row >= length[:, None, None], 1e30, x.astype(jnp.float32))
    ).astype(dtype)
    got = da.decode_attend(q, poison(k), poison(v), length, SCALE)
    want = _masked(q, k, v, length)
    assert np.isfinite(np.asarray(got)).all()
    assert _gap(got, want) <= TOL[dtype] * max(float(jnp.abs(want).max()), 1.0)
    if case != "a-block":  # and the plain path does read them
        assert not np.isfinite(np.asarray(
            _masked(q, poison(k), poison(v), length))).all()


# -- under a selection: ``row < length`` AND ``kept`` -------------------------------
_ROW = np.arange(ROWS)[None, :]


def _every_live_row(length, rng):
    return np.ones((B, ROWS), bool)


def _a_random_half(length, rng):
    kept = rng.random((B, ROWS)) < 0.5
    kept[np.arange(B), length - 1] = True  # at least one under every length
    return kept


def _none_in_the_first_live_block(length, rng):
    return (_ROW >= BLOCK) & (rng.random((B, ROWS)) < 0.5) | (
        _ROW == length[:, None] - 1)


def _one_row(length, rng):
    return _ROW == rng.integers(0, length)[:, None]


def _the_boundary_block_alone(length, rng):
    return _ROW // BLOCK == ((length - 1) // BLOCK)[:, None]


#: name -> (a length an env, the selection of (lengths, rng) [B, ROWS] bool)
SELECTIONS = {
    "every-live-row": (LENGTHS["every-env-another"], _every_live_row),
    "a-random-half": (LENGTHS["every-env-another"], _a_random_half),
    # every env's first block is live and holds no selected row; rows are
    # selected past the length too, and in blocks wholly past it
    "none-in-the-first-live-block": (
        (BLOCK + 1, 200, 2 * BLOCK, 2 * BLOCK + 1, 300, ROWS),
        _none_in_the_first_live_block),
    "one-row": ((1, BLOCK, BLOCK + 1, 300, 3 * BLOCK + 7, ROWS), _one_row),
    "the-boundary-block-alone": (
        LENGTHS["every-env-another"], _the_boundary_block_alone),
}


def _selection(case):
    length, select = SELECTIONS[case]
    length = np.asarray(length, np.int32)
    kept = select(length, np.random.default_rng(7))
    assert (kept & (_ROW < length[:, None])).any(axis=1).all()
    return jnp.asarray(length), jnp.asarray(kept)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", SELECTIONS)
def test_the_masked_kernel_is_attend_under_live_and_kept(
        interpreted, blocks_of_128, case, dtype):
    q, k, v = _operands(dtype, seed=3)
    length, kept = _selection(case)
    fn = lambda *a: da.decode_attend(q, k, v, length, SCALE, *a)  # noqa: E731
    assert _has_kernel(fn, kept)
    got = fn(kept)
    want = _masked(q, k, v, length, kept)
    assert got.dtype == jnp.float32 and got.shape == (B, H, W)
    assert np.isfinite(np.asarray(got)).all()
    assert _gap(got, want) <= TOL[dtype] * max(float(jnp.abs(want).max()), 1.0)
    if case == "every-live-row":  # one more term in the mask, always true
        assert _gap(got, fn()) == 0.0
    elif case == "none-in-the-first-live-block":
        live = np.asarray(kept) & (_ROW < np.asarray(length)[:, None])
        assert not live[:, :BLOCK].any() and live.any(axis=1).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", SELECTIONS)
def test_unselected_rows_and_rows_past_the_length_are_not_read_nor_weighed(
        interpreted, blocks_of_128, case, dtype):
    """NaN in every row of the blocks wholly past an env's length (not
    fetched); 1e30 in the rows past the length inside the boundary's block
    and in every live row the selection leaves out (masked: their scores
    would win every maximum), selected or not past the length."""
    q, k, v = _operands(dtype, seed=4)
    length, kept = _selection(case)
    row = jnp.arange(ROWS)[None, :, None]
    live_blocks = (length[:, None, None] + BLOCK - 1) // BLOCK * BLOCK
    attended = (row < length[:, None, None]) & kept[:, :, None]
    poison = lambda x: jnp.where(  # noqa: E731
        row >= live_blocks, jnp.nan,
        jnp.where(attended, x.astype(jnp.float32), 1e30)).astype(dtype)
    got = da.decode_attend(q, poison(k), poison(v), length, SCALE, kept)
    want = _masked(q, k, v, length, kept)
    assert np.isfinite(np.asarray(got)).all()
    assert _gap(got, want) <= TOL[dtype] * max(float(jnp.abs(want).max()), 1.0)
    # and the plain path does read them
    assert not np.isfinite(np.asarray(
        _masked(q, poison(k), poison(v), length, kept))).all()


# -- which path runs is read off the backend and the shapes -----------------------
@pytest.mark.parametrize("selection", [None, "a-random-half"])
def test_off_the_tpu_the_op_is_attend_under_the_mask(selection):
    assert jax.default_backend() != "tpu" and not da.INTERPRET
    q, k, v = _operands(jnp.float32)
    length = jnp.asarray(LENGTHS["every-env-another"], jnp.int32)
    kept = () if selection is None else _selection(selection)[1:]
    fn = lambda q, k, v: da.decode_attend(q, k, v, length, SCALE, *kept)  # noqa: E731
    assert not _has_kernel(fn, q, k, v)
    assert _gap(fn(q, k, v), _masked(q, k, v, length, *kept)) == 0.0


@pytest.mark.parametrize("selected", [False, True], ids=["length", "selection"])
@pytest.mark.parametrize("heads,width,rows,kernel", [
    (8, 128, 512, True), (16, 128, 256, True), (2, 128, 128, True),
    (8, 64, 512, False), (8, 128, 200, False), (8, 16, 24, False),
    (32, 128, 512, False)],  # 16 queries a group: more than a tile's rows
    ids=lambda x: str(x))
def test_the_kernel_runs_only_on_whole_lanes_and_whole_blocks(
        interpreted, heads, width, rows, kernel, selected):
    q = jnp.zeros((2, heads, width), jnp.bfloat16)
    k = v = jnp.zeros((2, rows, 2 * width), jnp.bfloat16)
    length = jnp.ones(2, jnp.int32)
    kept = (jnp.ones((2, rows), bool),) if selected else ()
    assert _has_kernel(
        lambda q, k, v: da.decode_attend(q, k, v, length, SCALE, *kept),
        q, k, v) is kernel
    assert (da._block_of(q, k) is not None) is kernel
    # the rows in the blocks a call fetches: the live blocks', or all
    block = da._block_of(q, k)
    lengths = np.asarray([1, rows // 2, rows // 2 + 1, rows])
    want = -(-lengths // block) * block if kernel else [rows] * 4
    assert da.rows_read(q, k, lengths).tolist() == list(want)


# -- without a selection the program is the one it was -------------------------------
@pytest.mark.parametrize("rows", [1024, 512], ids=["shared-kv", "ring"])
def test_without_a_selection_the_call_has_the_four_operands_it_had(
        monkeypatch, rows):
    """The hybrid's two shapes: one ``pallas_call`` of (length, q, K, V)
    over a grid of every block; a selection is a fifth operand, ``[B, 1,
    rows]`` int32, and another kernel (its body guards a block with no
    selected row) whose grid ends at the furthest env's last live block: a
    bound of its own, the call's first operand."""
    monkeypatch.setattr(da, "runs_mosaic", lambda: True)
    q = jax.ShapeDtypeStruct((32, 40, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((32, rows, 1280), jnp.bfloat16)
    n = jax.ShapeDtypeStruct((32,), jnp.int32)
    kept = jax.ShapeDtypeStruct((32, rows), jnp.bool_)
    bare = jax.make_jaxpr(
        lambda q, k, v, n: da.decode_attend(q, k, v, n, SCALE))(q, k, k, n)
    operands = lambda call: [  # noqa: E731
        (x.aval.shape, x.aval.dtype) for x in call.invars]
    four = [((32,), jnp.int32), ((32, 80, 1280), jnp.bfloat16),
            ((32, rows, 1280), jnp.bfloat16), ((32, rows, 1280), jnp.bfloat16)]
    assert str(bare).count("pallas_call") == 1
    (call,) = _kernel_calls(bare.jaxpr)
    assert operands(call) == four
    masked = jax.make_jaxpr(
        lambda q, k, v, n, m: da.decode_attend(q, k, v, n, SCALE, m))(q, k, k, n, kept)
    (call,) = _kernel_calls(masked.jaxpr)
    assert operands(call) == [((), jnp.int32)] + four + [((32, 1, rows), jnp.int32)]
    assert call.params["grid_mapping"].num_dynamic_grid_bounds == 1


def test_on_a_tpu_the_op_is_the_kernel(monkeypatch):
    monkeypatch.setattr(da.jax, "default_backend", lambda: "tpu")
    q, k = jnp.zeros((2, H, W), jnp.bfloat16), jnp.zeros((2, ROWS, G * W), jnp.bfloat16)
    assert da._block_of(q, k) == ROWS  # 256 KB a buffer an env: one block
    assert da._block_of(q[:, :, :64], k[:, :, :G * 64]) is None


# -- the block is a function of the shapes ----------------------------------------
@pytest.mark.parametrize("rows,row_bytes,block", [
    (1024, 2560, 256),   # the hybrid's shared K/V: ten pairs of 128 in bf16
    (512, 2560, 256),    # its ring
    (1024, 256, 1024), (4096, 2560, 256), (384, 2560, 128), (1024, 8192, None),
    (100, 256, None)])
def test_a_block_divides_the_rows_and_fits(rows, row_bytes, block):
    assert da.block_rows(rows, row_bytes) == block
    if block:
        assert rows % block == 0 and block % 128 == 0
        assert block * row_bytes <= da.BLOCK_BYTES


# -- Mosaic compiles it at the cell's shapes (no chip attached) -------------------
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
@pytest.mark.parametrize("envs,heads,rows,width,selected", [
    (32, 40, 1024, 1280, False), (32, 40, 512, 1280, False),
    (16, 32, 4096, 512, True), (32, 10, 2048, 1280, False)],
    ids=["shared-kv", "ring", "sparse", "ten-heads-ungrouped"])
def test_the_kernel_compiles_for_a_v5e_at_the_cells_shapes(
        one_chip, no_compile_cache, monkeypatch, envs, heads, rows, width, selected):
    monkeypatch.setattr(da, "runs_mosaic", lambda: True)

    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    kept = (shape(envs, rows, dtype=jnp.bool_),) if selected else ()
    text = jax.jit(
        lambda q, k, v, n, *kept: da.decode_attend(q, k, v, n, SCALE, *kept)).lower(
        shape(envs, heads, 128), shape(envs, rows, width), shape(envs, rows, width),
        shape(envs, dtype=jnp.int32), *kept).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


#: an instruction that moves a whole K/V buffer of the cell (or a quarter of
#: one: the compiler stages a buffer through fast memory in four slices)
_MOVES = re.compile(
    r"bf16\[(?:32|16|8),(?:1024|512),1280\]\S* "
    r"(?:copy|copy-start|slice-start|transpose)\(")
_ATTENTION = (profiling.OP_ATTN_WINDOW, profiling.OP_ATTN_FULL,
              profiling.OP_ATTN_CROSS)


def _loop_body(text):
    """The lines of the computation that holds the kernel calls: the scan's
    body (the compiler's own asynchronous copies carry no ``op_name`` that
    would say where they stand)."""
    lines, body = text.splitlines(), []
    for line in lines:
        if line.startswith(("%", "ENTRY")) and line.rstrip().endswith("{"):
            body = []
        body.append(line)
        if line.startswith("}") and any("tpu_custom_call" in b for b in body):
            return body
    return []


@pytest.fixture(scope="module")
def compiled_decode(one_chip, no_compile_cache):
    """The hybrid's decode at the cell's size (32 envs, 1,024 positions, the
    published widths, the bf16 snapshot) in a scan under the trainer's
    scopes, compiled for the described v5e."""
    model = Phi4Flash(max_positions=1024)
    placed = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = placed(jax.eval_shape(
        lambda key: model.rollout_params(model.init_params(key)),
        jax.random.PRNGKey(0)))
    carry = placed(jax.eval_shape(lambda: model.init_carry(32)))
    tokens = jax.ShapeDtypeStruct((1024, 32), jnp.int32, sharding=one_chip)
    fresh = jax.ShapeDtypeStruct((1024, 32), jnp.bool_, sharding=one_chip)

    def episode(params, carry, tokens, fresh):
        def one(carry, x):
            with device_scope(profiling.ROLLOUT_POLICY):
                out, carry = model.step(params, x[0], carry, x[1])
            return carry, out.value

        with device_scope(profiling.ROLLOUT):
            return jax.lax.scan(one, carry, (tokens, fresh))

    before = da.runs_mosaic
    da.runs_mosaic = lambda: True
    try:
        return jax.jit(episode, donate_argnums=1).lower(
            params, carry, tokens, fresh).compile().as_text()
    finally:
        da.runs_mosaic = before


@pytest.mark.timeout(600)
@pytest.mark.parametrize("layer", _ATTENTION)
def test_the_compiled_decode_holds_the_kernel_under_an_attention_scope(
        compiled_decode, layer):
    names = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"', compiled_decode)
    assert len(names) == 3
    scope = profiling.policy_scope(
        profiling.ROLLOUT_POLICY, f"{layer}/{profiling.DECODE_ATTEND}")
    assert scope in profiling.ALL_SCOPES
    assert sum(profiling.scope_of(n) == scope for n in names) == 1, names


@pytest.mark.timeout(600)
def test_the_compiled_decode_moves_no_whole_buffer_in_its_loop(compiled_decode):
    """The scatter that writes a position updates in place, and the kernel
    reads the buffers where they lie: no copy, relayout or staging through
    fast memory of a whole K/V buffer an iteration (PERF.md, PR 31 and 33)."""
    body = _loop_body(compiled_decode)
    assert len(body) > 100 and any("decode_attend" in line for line in body)
    moved = [line.strip()[:160] for line in body if _MOVES.search(line)]
    assert not moved, moved
    scatters = [line for line in compiled_decode.splitlines()
                if re.search(r"= bf16\[32,(1024|512),1280\]\S* scatter\(", line)]
    assert len(scatters) == 4  # K and V of the ring and of the shared buffer


# -- the sparse-attention policy's decode: the kernel, and no whole buffer moved ----
#: an instruction of the loop's body that MAKES a whole cache of the cell: K or
#: V ([16, 4096, 512], also seen as [16, 4096, 4, 128]) or the indexer's keys
#: ([16, 4096, 64]). All but the in-place scatter that writes a position is a
#: copy, a relayout or a staging through fast memory, alone or in a fusion
_WHOLE_CACHE = re.compile(
    r"^\s*(?:ROOT )?%\S+ = bf16\[16,4096,(512|64|4,128)\]\S* ([\w\-]+)\((.*)")


def _whole_caches_made(body, widths):
    """[(opcode, is an in-place scatter)] of the body's instructions that
    make a whole cache of one of ``widths``."""
    made = []
    for line in body:
        m = _WHOLE_CACHE.match(line)
        if m and m.group(1) in widths and m.group(2) not in (
                "get-tuple-element", "parameter", "bitcast"):
            made.append((m.group(2), bool(re.search(r'/scatter"', m.group(3)))))
    return made


@pytest.fixture(scope="module")
def compiled_sparse_decode(one_chip, no_compile_cache):
    """``keye-vl2``'s decode at the cell's size (16 envs, 4,096 positions, the
    published widths, the bf16 snapshot) in a scan under the trainer's
    scopes, compiled for the described v5e."""
    from distributed_ba3c_tpu.models.keye_vl2 import KeyeVL2

    model = KeyeVL2(max_positions=4096)
    placed = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)
    params = placed(jax.eval_shape(
        lambda key: model.rollout_params(model.init_params(key)),
        jax.random.PRNGKey(0)))
    carry = placed(jax.eval_shape(lambda: model.init_carry(16)))
    tokens = jax.ShapeDtypeStruct((4096, 16), jnp.int32, sharding=one_chip)
    fresh = jax.ShapeDtypeStruct((4096, 16), jnp.bool_, sharding=one_chip)

    def episode(params, carry, tokens, fresh):
        def one(carry, x):
            with device_scope(profiling.ROLLOUT_POLICY):
                out, carry = model.step(params, x[0], carry, x[1])
            return carry, out.value

        with device_scope(profiling.ROLLOUT):
            return jax.lax.scan(one, carry, (tokens, fresh))

    before = da.runs_mosaic
    da.runs_mosaic = lambda: True
    try:
        return jax.jit(episode, donate_argnums=1).lower(
            params, carry, tokens, fresh).compile().as_text()
    finally:
        da.runs_mosaic = before


def _scan_body(text):
    """The lines of the decode scan's body: the computation the program's
    largest ``while`` names as its ``body``."""
    bodies = re.findall(r" while\(.*?body=(%[\w.\-]+)", text)
    blocks = {}
    for name in set(bodies):
        start = text.index(f"\n{name} (")
        blocks[name] = text[start:text.index("\n}\n", start)].splitlines()
    return max(blocks.values(), key=len, default=[])


@pytest.mark.timeout(600)
def test_the_sparse_decode_moves_no_whole_buffer_in_its_loop(compiled_sparse_decode):
    """The three scatters a layer (K, V, the indexer's key) update in place,
    the kernel reads K and V where they lie, block by block up to the
    position, and the scores' product the indexer's keys: no copy, relayout
    or staging of a whole cache an iteration. With the K/V heads split into
    an axis of their own (``layers.attend`` on the reshaped buffers) the
    compiler relaid both whole buffers out every step; gathered into copies
    the selected rows cost 3.68 ms a step (PERF.md section 6, PR 34)."""
    body = _scan_body(compiled_sparse_decode)
    assert len(body) > 100
    # K and V of four layers: the eight scatters and nothing else
    assert _whole_caches_made(body, ("512", "4,128")) == [("fusion", True)] * 8
    # nor a part of one anywhere in the program: reading whole buffers under
    # the mask, the compiler staged one of the eight through fast memory in
    # four slices every step, in a region of its own beside the body
    assert not re.search(
        r"bf16\[\d+,4096,512\]\S* slice-(?:start|done)\(", compiled_sparse_decode)
    # the indexer's keys (8 MB, read whole every step by construction): the
    # four scatters; in some layers the compiler also stages that cache
    # through fast memory for the scores' product (slices in, a copy back: 16
    # MB moved for 8 read; three layers of four before the kernel stood
    # beside them, all four or none with it, by the kernel's grid), which is
    # its choice and is held here so that it does not grow unseen
    keys = _whole_caches_made(body, ("64",))
    staged = keys.count(("custom-call", False))
    assert staged <= 4 and sorted(keys) == sorted(
        [("fusion", True)] * 4
        + [("custom-call", False), ("copy-done", False)] * staged)
    # no copy of the selected rows, and no sort of a row of scores: the
    # selection is a mask (the router's top 8 of 128 is the only sort left)
    assert not re.search(r"= bf16\[16,2048,512\]", compiled_sparse_decode)
    assert not re.search(r"\[16,4096\]\S*\) sort\(", compiled_sparse_decode)
    # the kernel, under ``op_attn_sparse/decode_attend``, once a layer
    names = re.findall(
        r'custom_call_target="tpu_custom_call".*?op_name="([^"]*)"',
        compiled_sparse_decode)
    scope = profiling.policy_scope(
        profiling.ROLLOUT_POLICY, profiling.OP_ATTN_SPARSE_DECODE)
    assert scope in profiling.ALL_SCOPES
    assert [profiling.scope_of(n) for n in names] == [scope] * 4, names
    assert any("decode_attend" in line for line in body)
