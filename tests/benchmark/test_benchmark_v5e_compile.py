"""Compile ``fused.step`` at the cells' own sizes for a described v5e chip.

No chip is attached: the TPU's compiler is installed here and compiles for a
chip that is described. Guards the cells' programs, and that they fit 16 GB,
on every later PR at no chip time. The topology is described inside a
module-scoped fixture, in this one file, and the compile runs in the test's
own process with the persistent cache off around it.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _compile_cell(topo, cell_name):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark.spec import Benchmark
    from distributed_ba3c_tpu import cli
    from distributed_ba3c_tpu.envs import jaxenv
    from distributed_ba3c_tpu.fused.loop import (
        FusedState, create_fused_state, make_fused_step)
    from distributed_ba3c_tpu.models.a3c import BA3CNet
    from distributed_ba3c_tpu.ops.gradproc import make_optimizer
    from distributed_ba3c_tpu.parallel.mesh import make_mesh

    bench = Benchmark()
    cell = bench.cell(cell_name)
    config = bench.config(cell["config"])
    args = cli.make_parser().parse_args(list(config["argv"]) + list(cell["argv"]))
    cfg = cli.build_config(args)
    model = BA3CNet(num_actions=cfg.num_actions, fc_units=cfg.fc_units)
    opt = make_optimizer(cfg.learning_rate, cfg.adam_epsilon, cfg.grad_clip_norm)
    env = jaxenv.get_env("pong")
    chips = cell["chips"]
    mesh = make_mesh(num_data=chips, devices=topo.devices[:chips])
    n_envs = cfg.batch_size // args.rollout_len * chips
    step = make_fused_step(model, opt, cfg, mesh, env, args.rollout_len,
                           grad_chunk_samples=args.grad_chunk_samples)
    shapes = jax.eval_shape(
        lambda k: create_fused_state(k, model, cfg, opt, env, n_envs, n_shards=chips),
        jax.random.PRNGKey(0))
    rep, bat = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def placed(tree, sharding):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)

    state = FusedState(
        train=placed(shapes.train, rep), env_state=placed(shapes.env_state, bat),
        obs_stack=placed(shapes.obs_stack, bat), key=placed(shapes.key, bat),
        ep_return=placed(shapes.ep_return, bat), ep_count=placed(shapes.ep_count, bat),
        ep_return_sum=placed(shapes.ep_return_sum, bat))
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    compiled = step.audit_jit.lower(state, scalar, scalar).compile()
    return n_envs, compiled


@pytest.mark.timeout(900)
@pytest.mark.parametrize("cell_name,envs,min_gb", [
    ("fused-pong-256x20", 256, 4.5),
    ("fused-pong-4096x20", 4096, 4.5),
])
def test_fused_step_compiles_for_a_v5e_and_fits(topo, no_compile_cache, cell_name,
                                               envs, min_gb):
    n_envs, compiled = _compile_cell(topo, cell_name)
    assert n_envs == envs
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{cell_name}: {total / 1e9:.2f} GB does not fit"
    # a cell this size is no toy: it fills a fair part of the chip
    assert total > min_gb * 1e9, f"{cell_name}: only {total / 1e9:.2f} GB"


@pytest.mark.timeout(900)
def test_four_chip_step_shards_the_batch_and_reduces_once(topo, no_compile_cache):
    n_envs, compiled = _compile_cell(topo, "fused-pong-4chip-1024x20")
    assert n_envs == 1024
    text = compiled.as_text()
    assert "all-reduce" in text
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < HBM_BYTES
