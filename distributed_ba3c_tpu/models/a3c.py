"""The BA3C policy/value convnet.

Reference equivalent: ``Model._build_graph`` in ``src/train.py`` (SURVEY.md
§2.1 #2) — the Tensorpack train-atari architecture:

    input uint8 [B, 84, 84, FRAME_HISTORY] / 255
    Conv 32@5x5 -> MaxPool 2 -> Conv 32@5x5 -> MaxPool 2
    Conv 64@4x4 -> MaxPool 2 -> Conv 64@3x3
    FC 512 + PReLU
    -> policy logits [B, A]    (FC A)
    -> value [B]               (FC 1)

TPU-native design decisions:
- NHWC layout, bfloat16 compute / float32 params (MXU-friendly; convs at these
  sizes map onto the MXU as implicit GEMMs).
- uint8 states cross the host->device boundary; the /255 cast happens on
  device, so PCIe/ICI traffic is 1 byte per pixel (the reference ships uint8
  over ZMQ for the same reason).
- One module serves both the learner (value+logits) and the actor serving path
  (vmapped under jit in predict/server.py).
"""

from __future__ import annotations

from typing import NamedTuple, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.models.layers import PReLU


class PolicyValue(NamedTuple):
    logits: jax.Array  # [B, A] float32
    value: jax.Array   # [B] float32


#: stacks an inference forward of this network takes at once when a shard
#: carries many envs (the fused rollout's policy forward and the bootstrap
#: under ``returns``; never the learner). PERF.md, PR 25: on the v5e this
#: conv stack's forward costs more a sample the larger its batch, so a large
#: env batch runs as sequential forwards of this many. Measured for this
#: network alone: a policy that carries state is not split this way.
FORWARD_SUB_BATCH = 256


def forward_sub_batch(n_envs: int) -> int | None:
    """Stacks a forward of a shard's ``n_envs`` env batch: None is all at
    once (under two sub-batches' worth, or no divisor of ``n_envs`` in
    [FORWARD_SUB_BATCH / 2, FORWARD_SUB_BATCH]), else the largest such
    divisor. Read off the shape alone, so every program that traces the
    rollout at one shape splits it the same way."""
    top = FORWARD_SUB_BATCH
    if n_envs < 2 * top:
        return None
    for size in range(top, (top - 1) // 2, -1):
        if n_envs % size == 0:
            return size
    return None


def conv_layout(model: "BA3CNet") -> Tuple[Tuple[int, int, bool], ...]:
    """The conv stack's (features, kernel, pooled) triples — the ONE
    layout description shared by :meth:`BA3CNet.__call__` and the
    quantized mirror forward (distributed_ba3c_tpu/quantize/), so the
    int8 program can never drift from the f32 architecture it
    quantizes."""
    return tuple(
        zip(
            model.conv_features,
            model.conv_kernels,
            model.pooled_layers,
            strict=True,
        )
    )


class BA3CNet(nn.Module):
    """Policy/value network with the reference's conv stack."""

    num_actions: int
    fc_units: int = 512
    conv_features: Sequence[int] = (32, 32, 64, 64)
    conv_kernels: Sequence[int] = (5, 5, 4, 3)
    # maxpool after first 3 conv layers, as in the reference stack
    pooled_layers: Tuple[bool, ...] = (True, True, True, False)
    compute_dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, state: jax.Array) -> PolicyValue:
        """state: [B, H, W, C] uint8 (or float already scaled)."""
        if state.dtype == jnp.uint8:
            x = state.astype(self.compute_dtype) / 255.0
        else:
            x = state.astype(self.compute_dtype)

        for i, (feats, k, pooled) in enumerate(conv_layout(self)):
            x = nn.Conv(
                features=feats,
                kernel_size=(k, k),
                padding="SAME",
                dtype=self.compute_dtype,
                param_dtype=jnp.float32,
                name=f"Conv_{i}",
            )(x)
            x = nn.relu(x)
            if pooled:
                x = nn.max_pool(x, window_shape=(2, 2), strides=(2, 2))

        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.fc_units, dtype=self.compute_dtype, param_dtype=jnp.float32)(x)
        x = PReLU()(x)

        logits = nn.Dense(
            self.num_actions, dtype=jnp.float32, param_dtype=jnp.float32
        )(x.astype(jnp.float32))
        value = nn.Dense(1, dtype=jnp.float32, param_dtype=jnp.float32)(
            x.astype(jnp.float32)
        )[:, 0]
        return PolicyValue(logits=logits, value=value)
