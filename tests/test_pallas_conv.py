"""Pallas fused conv blocks (ops/pallas_conv.py) vs the XLA reference.

Runs in interpreter mode on the CPU backend — asked for by name
(``interpret=True`` / ``conv_backend="pallas-interpret"``), never guessed —
so CI needs no TPU; the Mosaic-compiled kernel is compared with the XLA
block on the chip by chip_smoke.py. These tests pin CORRECTNESS.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ba3c_tpu.ops.pallas_conv import (
    ConvSpec,
    ba3c_specs,
    conv_block,
    conv_block_fwd,
    pack_bias,
    pack_weights,
    reference_block,
    supported,
)


@pytest.fixture(scope="module")
def rng():
    return np.random.default_rng(0)


def test_ba3c_specs_chain():
    specs = ba3c_specs()
    assert [(s.H, s.W, s.Ci, s.Co) for s in specs] == [
        (84, 84, 4, 32),
        (42, 42, 32, 32),
        (21, 21, 32, 64),
        (10, 10, 64, 64),
    ]
    assert [s.Ho for s in specs] == [42, 21, 10, 10]
    # conv0's P*Ci=16 lane granularity is not Mosaic-compilable; the rest are
    assert [supported(s) for s in specs] == [False, True, True, True]


def test_fwd_matches_reference_all_blocks(rng):
    specs = ba3c_specs()
    x = jnp.asarray(rng.integers(0, 256, (2, 84, 84 * 4), dtype=np.uint8))
    for i, s in enumerate(specs):
        w = jnp.asarray(
            rng.normal(0, 0.1, (s.kh, s.kw, s.Ci, s.Co)), jnp.float32
        )
        b = jnp.asarray(rng.normal(0, 0.05, (s.Co,)), jnp.float32)
        ref = reference_block(x, w, b, s)
        if supported(s):
            got = conv_block_fwd(
                x, pack_weights(w, s), pack_bias(b, s), s, interpret=True
            )
            err = jnp.max(
                jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32))
            )
            scale = jnp.max(jnp.abs(ref.astype(jnp.float32))) + 1e-6
            assert err / scale < 2e-2, (i, float(err), float(scale))
        x = ref  # chain the stack through the reference path


def test_batch_padding(rng):
    """B not divisible by the batch tile pads and trims correctly."""
    s = ba3c_specs()[1]
    B = s.bt + 1
    x = jnp.asarray(
        np.abs(rng.normal(0, 0.5, (B, s.H, s.W * s.Ci))), jnp.bfloat16
    )
    w = jnp.asarray(rng.normal(0, 0.1, (s.kh, s.kw, s.Ci, s.Co)), jnp.float32)
    b = jnp.zeros((s.Co,), jnp.float32)
    got = conv_block_fwd(
        x, pack_weights(w, s), pack_bias(b, s), s, interpret=True
    )
    assert got.shape == (B, s.Ho, s.Wo * s.Co)
    ref = reference_block(x, w, b, s)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref.astype(jnp.float32)))) < 0.1


def test_model_pallas_backend_value_and_grad(rng):
    """BA3CNet(conv_backend='pallas') matches the XLA model: fwd + grads."""
    from distributed_ba3c_tpu.models.a3c import BA3CNet

    x = jnp.asarray(rng.integers(0, 256, (2, 84, 84, 4), dtype=np.uint8))
    m_x = BA3CNet(num_actions=4)
    m_p = BA3CNet(num_actions=4, conv_backend="pallas-interpret")
    params = m_x.init(jax.random.PRNGKey(0), x)["params"]
    # identical param trees (names/shapes interchangeable)
    out_x = m_x.apply({"params": params}, x)
    out_p = m_p.apply({"params": params}, x)
    assert np.allclose(out_x.logits, out_p.logits, atol=0.15), (
        np.max(np.abs(np.asarray(out_x.logits) - np.asarray(out_p.logits)))
    )

    def loss(m, p):
        out = m.apply({"params": p}, x)
        return jnp.sum(out.logits**2) + jnp.sum(out.value**2)

    g_x = jax.grad(lambda p: loss(m_x, p))(params)
    g_p = jax.grad(lambda p: loss(m_p, p))(params)
    key = lambda kv: str(kv[0])  # noqa: E731
    for (kx, vx), (kp, vp) in zip(
        sorted(jax.tree_util.tree_leaves_with_path(g_x), key=key),
        sorted(jax.tree_util.tree_leaves_with_path(g_p), key=key),
        strict=True,
    ):
        scale = np.max(np.abs(np.asarray(vx))) + 1e-3
        assert np.max(np.abs(np.asarray(vx) - np.asarray(vp))) / scale < 0.2, kx
