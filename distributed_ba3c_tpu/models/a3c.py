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


def _conv_spec(x: jax.Array, features: int, k: int, pooled: bool):
    """The ONE ConvSpec construction shared by the gate and the executed
    block, so they can never diverge (ops/pallas_conv.py)."""
    from distributed_ba3c_tpu.ops.pallas_conv import ConvSpec

    return ConvSpec(
        H=x.shape[1], W=x.shape[2], Ci=x.shape[3], Co=features,
        kh=k, kw=k, pool=pooled, scale_uint8=False,
    )


class _PallasConvBlock(nn.Module):
    """conv+bias+relu(+2x2 maxpool) as one fused Pallas kernel.

    Param names/shapes match ``nn.Conv`` ('kernel' [k,k,ci,co], 'bias'
    [co]). ``interpret`` runs the kernel in the Pallas interpreter — the
    CPU tests ask for it by name (``conv_backend="pallas-interpret"``); it
    is never guessed from the back-end, so on a chip "pallas" always means
    the Mosaic-compiled kernel.
    """

    spec: object  # ConvSpec (static)
    interpret: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from distributed_ba3c_tpu.ops.pallas_conv import conv_block

        s = self.spec
        B = x.shape[0]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (s.kh, s.kw, s.Ci, s.Co), jnp.float32,
        )
        bias = self.param("bias", nn.initializers.zeros, (s.Co,), jnp.float32)
        y = conv_block(
            x.astype(jnp.bfloat16).reshape(B, s.H, s.W * s.Ci),
            kernel, bias, s,
            self.interpret,
        )
        return y.reshape(B, s.Ho, s.Wo, s.Co)


class BA3CNet(nn.Module):
    """Policy/value network with the reference's conv stack."""

    num_actions: int
    fc_units: int = 512
    conv_features: Sequence[int] = (32, 32, 64, 64)
    conv_kernels: Sequence[int] = (5, 5, 4, 3)
    # maxpool after first 3 conv layers, as in the reference stack
    pooled_layers: Tuple[bool, ...] = (True, True, True, False)
    compute_dtype: jnp.dtype = jnp.bfloat16
    # lane-packing factor per conv layer (models/packed_conv.py). MEASURED
    # NEUTRAL on v5e (PERF.md: the net is HBM-roofline-bound, and XLA's conv
    # emitter already packs output lanes) — kept as tested infrastructure
    # for backends where the GEMM shape does bind. 0/1 = plain nn.Conv.
    # Numerically EXACT either way (value- and gradient-tested).
    conv_pack: Tuple[int, ...] = (0, 0, 0, 0)
    # "xla" (default), "pallas" or "pallas-interpret": fused Pallas
    # conv+relu+pool blocks where the geometry allows (ops/pallas_conv.py —
    # blocks whose P*Ci is a 128-multiple, i.e. the 32/64-channel layers;
    # conv0's Ci=4 cannot). The kernels compile under the installed Mosaic
    # and match the XLA block on the v5e (chip_smoke.py checks both on every
    # run); measured SLOWER than XLA in an earlier round on other code
    # (patch-assembly relayout outweighs the 4x MXU lane-occupancy win), so
    # the default stays XLA and ROADMAP D1 queues the removal. Checkpoints
    # are interchangeable (same param names/shapes).
    conv_backend: str = "xla"

    @nn.compact
    def __call__(self, state: jax.Array) -> PolicyValue:
        """state: [B, H, W, C] uint8 (or float already scaled)."""
        if state.dtype == jnp.uint8:
            x = state.astype(self.compute_dtype) / 255.0
        else:
            x = state.astype(self.compute_dtype)

        for i, ((feats, k, pooled), pack) in enumerate(
            zip(conv_layout(self), self.conv_pack, strict=True)
        ):
            # explicit name "Conv_i" for ALL branches: PackedConv and
            # _PallasConvBlock own nn.Conv-shaped params, so checkpoints
            # stay interchangeable between configurations
            if self.conv_backend in ("pallas", "pallas-interpret"):
                from distributed_ba3c_tpu.ops.pallas_conv import supported

                if self.compute_dtype != jnp.bfloat16:
                    # the Pallas block is bf16-only: running XLA convs
                    # under the kernel's name would hide which one ran
                    raise ValueError(
                        f"conv_backend={self.conv_backend!r} computes in "
                        f"bfloat16, not {self.compute_dtype}"
                    )

                spec = _conv_spec(x, feats, k, pooled)
                if supported(spec):
                    x = _PallasConvBlock(
                        spec=spec,
                        interpret=self.conv_backend == "pallas-interpret",
                        name=f"Conv_{i}",
                    )(x)
                    continue  # relu+pool fused inside the block
            if pack and pack > 1:
                from distributed_ba3c_tpu.models.packed_conv import PackedConv

                x = PackedConv(
                    features=feats,
                    kernel_size=k,
                    pack=pack,
                    dtype=self.compute_dtype,
                    param_dtype=jnp.float32,
                    name=f"Conv_{i}",
                )(x)
            else:
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
