"""Shape/dtype tests for the BA3C convnet."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ba3c_tpu.config import BA3CConfig
from distributed_ba3c_tpu.models import BA3CNet


def test_forward_shapes_and_dtypes():
    cfg = BA3CConfig(num_actions=6)
    model = BA3CNet(num_actions=cfg.num_actions)
    params = model.init(jax.random.key(0), jnp.zeros((1, *cfg.state_shape), jnp.uint8))
    state = jnp.zeros((8, *cfg.state_shape), jnp.uint8)
    out = model.apply(params, state)
    assert out.logits.shape == (8, 6)
    assert out.value.shape == (8,)
    assert out.logits.dtype == jnp.float32
    assert out.value.dtype == jnp.float32


#: every parameter leaf of the one conv path at the published widths and 6
#: actions: what a checkpoint under runs/ holds, so what must not move
LEAVES = {
    ("Conv_0", "kernel"): (5, 5, 4, 32),
    ("Conv_0", "bias"): (32,),
    ("Conv_1", "kernel"): (5, 5, 32, 32),
    ("Conv_1", "bias"): (32,),
    ("Conv_2", "kernel"): (4, 4, 32, 64),
    ("Conv_2", "bias"): (64,),
    ("Conv_3", "kernel"): (3, 3, 64, 64),
    ("Conv_3", "bias"): (64,),
    ("Dense_0", "kernel"): (6400, 512),
    ("Dense_0", "bias"): (512,),
    ("Dense_1", "kernel"): (512, 6),
    ("Dense_1", "bias"): (6,),
    ("Dense_2", "kernel"): (512, 1),
    ("Dense_2", "bias"): (1,),
    ("PReLU_0", "alpha"): (),
}


@pytest.fixture(scope="module")
def param_shapes():
    model = BA3CNet(num_actions=6)
    return jax.eval_shape(
        lambda: model.init(
            jax.random.key(0), jnp.zeros((1, 84, 84, 4), jnp.uint8)
        )
    )["params"]


@pytest.mark.parametrize("path", LEAVES, ids="/".join)
def test_param_leaf_name_shape_and_dtype(param_shapes, path):
    module, name = path
    leaf = param_shapes[module][name]
    assert leaf.shape == LEAVES[path]
    assert leaf.dtype == jnp.float32


def test_no_other_param_leaves(param_shapes):
    assert {
        (m, n) for m, sub in param_shapes.items() for n in sub
    } == set(LEAVES)


def test_declared_fields_are_the_architecture_only():
    """One conv path: BA3CNet declares no field that selects another."""
    flax_adds = {"parent", "name"}  # on every nn.Module
    assert [
        f.name for f in dataclasses.fields(BA3CNet) if f.name not in flax_adds
    ] == [
        "num_actions", "fc_units", "conv_features", "conv_kernels",
        "pooled_layers", "compute_dtype",
    ]


def test_uint8_and_prescaled_inputs_agree():
    model = BA3CNet(num_actions=4)
    key = jax.random.key(1)
    params = model.init(key, jnp.zeros((1, 84, 84, 4), jnp.uint8))
    state_u8 = jax.random.randint(key, (2, 84, 84, 4), 0, 256, jnp.int32).astype(jnp.uint8)
    out_u8 = model.apply(params, state_u8)
    out_f = model.apply(params, state_u8.astype(jnp.bfloat16) / 255.0)
    np.testing.assert_allclose(
        np.asarray(out_u8.logits), np.asarray(out_f.logits), atol=2e-2
    )
