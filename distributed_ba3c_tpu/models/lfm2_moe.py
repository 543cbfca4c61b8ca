"""LFM2-8B-A1B (``model_type lfm2_moe``) as a token-sequence policy.

Published (LiquidAI/LFM2-8B-A1B ``config.json``): hidden 2048, 24 layers of
``conv`` and ``full_attention`` operators (3 : 1 after the two leading
layers), the first ``num_dense_layers`` = 2 with a dense SwiGLU of 7168 and
the rest with 32 routed experts of 1792, 4 a token, no shared expert.
Every layer is

    h = x + Op(RMSNorm(x))        y = h + FFN(RMSNorm(h))        eps 1e-5

- Op ``conv`` (gated short convolution, ``conv_L_cache`` 3, no bias):
  ``[b, c, u] = split3(W_in z)``; ``v = b * u``; ``s_t = k_0 v_t + k_1
  v_{t-1} + k_2 v_{t-2}`` (depthwise, causal, zero before the episode);
  ``out = W_out (c * s)``. Carried while decoding: ``v_{t-1}, v_{t-2}``.
- Op ``full_attention``: 32 query heads over 8 key/value heads of 64;
  RMSNorm with a learned gain over each head's 64 on ``q`` and on ``k``;
  RoPE (theta 1e6, rotate-half) over the whole head; causal
  ``softmax(q k^T / 8)``; ``W_o``. Carried: ``k, v`` of past positions.
- FFN dense: ``W_2 (silu(W_1 z) * W_3 z)``. FFN experts: ``ops/moe.py``.
- Final RMSNorm; logits over the vocabulary ids held here are the
  embedding's rows times ``h`` (tied). The value head, a float32 ``Dense(1)``
  on the same ``h``, is the trainer's own and no part of the published model.

The widths are the defaults below and are never cut. What IS cut is how
much of the model one chip holds (``benchmark/configs/lfm2-8b-a1b-recall-
fused-a2c.json`` has the arithmetic): which published layers (``layer_ids``
with their operator and FFN kinds), how many experts of each layer
(``experts_held`` from ``expert_offset``) and how many vocabulary ids
(``num_actions``). ``--model_cut`` names such a cut (:data:`CUTS`).

Precision: float32 parameters, residual stream, norms, router, softmax and
heads' outputs; bfloat16 matrix operands (``compute_dtype``) with float32
accumulation. The policy protocol is models/policy.py's.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.models import layers, sequence
from distributed_ba3c_tpu.models.layers import rms_norm, rope
from distributed_ba3c_tpu.ops import moe
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

CONV, ATTN = "conv", "full_attention"
DENSE, EXPERTS = "dense", "experts"
#: spread of the seeded ``use_expert_bias`` buffer: small beside the gaps
#: between router scores, as a bias that exists to even the load out is
EXPERT_BIAS_SCALE = 0.01

#: ``--model_cut``: what one chip holds, the default first. ``chip-share-4``:
#: one of 4 chips that share each layer (8 of 32 experts; the vocabulary
#: slice is the env's action space), published layer 0 and the whole period
#: 2-5. ``tiny``: every mechanism at a size a CPU test runs.
CUTS = {
    "chip-share-4": {},
    "tiny": dict(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=8, num_experts_per_tok=2, experts_held=2,
        layer_ids=(0, 2, 3), layer_kinds=((CONV, DENSE), (ATTN, EXPERTS),
                                          (CONV, EXPERTS)),
    ),
}


cut_fields = functools.partial(sequence.cut_fields, CUTS)


class Carry(NamedTuple):
    """What decoding carries from one position to the next, an env a row."""

    pos: jax.Array       # [B] int32 position in the episode
    conv: Tuple          # per conv layer (v_{t-1}, v_{t-2}), each [B, d] f32
    kv: Tuple            # per attention layer (k, v), each [B, P, KV, D]


@dataclasses.dataclass(frozen=True)
class LFM2MoE(sequence.SequencePolicy):
    num_actions: int = 16384            # vocabulary ids held (of 65,536)
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # -- the chip's share ---------------------------------------------------
    layer_ids: Tuple[int, ...] = (0, 2, 3, 4, 5)
    layer_kinds: Tuple[Tuple[str, str], ...] = (
        (CONV, DENSE), (ATTN, EXPERTS), (CONV, EXPERTS), (CONV, EXPERTS),
        (CONV, EXPERTS),
    )
    experts_held: int = 8
    expert_offset: int = 0
    # -- how it is run ------------------------------------------------------
    max_positions: int = 256            # K/V cache rows: the episode length
    compute_dtype: jnp.dtype = jnp.bfloat16

    #: the router with its bias and the conv's taps stay float32
    float32_leaves = ("router", "expert_bias", "conv_taps")
    final_norm_eps = property(lambda self: self.norm_eps)

    def __post_init__(self):
        assert len(self.layer_ids) == len(self.layer_kinds)
        assert self.conv_L_cache == 3, "the short conv is written for 3 taps"
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert 0 < self.experts_held <= self.num_experts

    # -- parameters -----------------------------------------------------------
    def _init_layer(self, i: int, init):
        """Held layer ``i``'s seeded leaves: normal kernels scaled by
        1/sqrt(fan_in), unit gains. ``expert_bias`` is the published buffer:
        it only chooses, so its gradient is identically zero and Adam never
        moves it."""
        d, f, fe = self.hidden_size, self.intermediate_size, self.moe_intermediate_size
        hq = self.num_attention_heads * self.head_dim
        hkv = self.num_key_value_heads * self.head_dim
        normal, ones = init.normal, init.ones
        op, ffn = self.layer_kinds[i]
        layer = {"op_norm": ones(d), "ffn_norm": ones(d)}
        if op == CONV:
            layer.update(conv_in=normal((d, 3 * d), d),
                         conv_taps=normal((self.conv_L_cache, d), self.conv_L_cache),
                         conv_out=normal((d, d), d))
        else:
            layer.update(wq=normal((d, hq), d), wk=normal((d, hkv), d),
                         wv=normal((d, hkv), d), wo=normal((hq, d), hq),
                         q_norm=ones(self.head_dim), k_norm=ones(self.head_dim))
        if ffn == DENSE:
            layer.update(w1=normal((d, f), d), w3=normal((d, f), d),
                         w2=normal((f, d), f))
        else:
            e = self.experts_held
            layer.update(
                router=normal((d, self.num_experts), d),
                expert_bias=EXPERT_BIAS_SCALE * jax.random.normal(
                    next(init.keys), (self.num_experts,), jnp.float32),
                w1=normal((e, d, fe), d), w3=normal((e, d, fe), d),
                w2=normal((e, fe, d), fe))
        return layer

    # -- pieces shared by the decode step and the unroll -----------------------
    def _ffn(self, p, ffn: str, h):
        """h [N, d] float32 -> (h + FFN(RMSNorm(h)), None or (tokens routed
        to each held expert, the chosen expert ids [N, k], the blocks of
        sorted rows the layer ran beyond its first))."""
        if ffn == DENSE:
            with device_scope(profiling.FFN_DENSE):
                z = rms_norm(h, p["ffn_norm"], self.norm_eps)
                return h + layers.swiglu(
                    z, p["w1"], p["w3"], p["w2"], self.compute_dtype), None
        with device_scope(profiling.MOE):
            z = rms_norm(h, p["ffn_norm"], self.norm_eps)
            routing = moe.route(
                z, p["router"], p["expert_bias"], self.num_experts_per_tok,
                self.norm_topk_prob, self.routed_scaling_factor,
            )
            out, counted = moe.held_experts(
                z, routing, p, self.compute_dtype, self.expert_offset,
                self.num_experts)
            return h + out, counted

    def _qkv(self, p, z, positions):
        """z [..., d] -> q [..., H, D], k, v [..., KV, D] in the compute
        type: per-head RMSNorm on q and k, then RoPE at ``positions``."""
        D, cd = self.head_dim, self.compute_dtype
        # the three products leave in the compute type
        q = self._mm(z, p["wq"], cd).reshape(*z.shape[:-1], -1, D)
        k = self._mm(z, p["wk"], cd).reshape(*z.shape[:-1], -1, D)
        v = self._mm(z, p["wv"], cd).reshape(*z.shape[:-1], -1, D)
        q = rope(rms_norm(q, p["q_norm"], self.norm_eps), positions, self.rope_theta)
        k = rope(rms_norm(k, p["k_norm"], self.norm_eps), positions, self.rope_theta)
        return q.astype(cd), k.astype(cd), v

    def _attend(self, q, k, v, mask):
        return layers.attend(q, k, v, mask, self.compute_dtype)

    def epoch_stats(self, metrics: dict) -> dict:
        """An epoch's scalars from the step's metrics of this policy."""
        return moe.load_stats(metrics)

    # -- the rollout's decode step ---------------------------------------------
    def init_carry(self, batch: int) -> Carry:
        d = self.hidden_size
        kv_shape = (batch, self.max_positions, self.num_key_value_heads,
                    self.head_dim)

        def zeros():  # a buffer each: the step donates its state
            return jnp.zeros((batch, d), jnp.float32)

        return Carry(
            pos=jnp.zeros((batch,), jnp.int32),
            conv=tuple((zeros(), zeros()) for op, _ in self.layer_kinds if op == CONV),
            kv=tuple(
                (jnp.zeros(kv_shape, self.compute_dtype),
                 jnp.zeros(kv_shape, self.compute_dtype))
                for op, _ in self.layer_kinds if op == ATTN
            ),
        )

    def step(self, params, obs, carry: Carry, fresh):
        pos, keep = sequence.decode_opening(carry.pos, fresh)
        keep = keep.astype(jnp.float32)[:, None]
        x = self._embed(params, obs)
        conv_out, kv_out = [], []
        conv_in, kv_in = iter(carry.conv), iter(carry.kv)
        rows = jnp.arange(obs.shape[0])
        for i, (op, ffn) in enumerate(self.layer_kinds):
            p = params[self.layer_name(i)]
            if op == CONV:
                with device_scope(profiling.OP_CONV):
                    v1, v2 = next(conv_in)
                    v1, v2 = v1 * keep, v2 * keep
                    z = rms_norm(x, p["op_norm"], self.norm_eps)
                    b, c, u = jnp.split(self._mm(
                        z, p["conv_in"], self.compute_dtype).astype(jnp.float32), 3, -1)
                    v = b * u
                    taps = p["conv_taps"]
                    s = taps[0] * v + taps[1] * v1 + taps[2] * v2
                    h = x + self._mm(c * s, p["conv_out"], jnp.float32)
                    conv_out.append((v, v1))
            else:
                with device_scope(profiling.OP_ATTN):
                    k_cache, v_cache = next(kv_in)
                    z = rms_norm(x, p["op_norm"], self.norm_eps)
                    q, k, v = self._qkv(p, z[:, None, :], pos[:, None])
                    k_cache = sequence.write_row(rows, k_cache, pos, k[:, 0])
                    v_cache = sequence.write_row(rows, v_cache, pos, v[:, 0])
                    mask = jnp.arange(self.max_positions)[None, None, :] <= pos[:, None, None]
                    a = self._attend(q, k_cache, v_cache, mask)[:, 0]
                    h = x + self._mm(a, p["wo"], jnp.float32)
                    kv_out.append((k_cache, v_cache))
            x, _ = self._ffn(p, ffn, h)
        return self._head(params, x), Carry(
            pos=pos + 1, conv=tuple(conv_out), kv=tuple(kv_out))

    # -- the learner's unroll ----------------------------------------------------
    def _layer_unroll(self, i: int, p, x):
        """One layer over whole episodes: x [B, T, d] float32."""
        op, ffn = self.layer_kinds[i]
        B, T, d = x.shape
        if op == CONV:
            with device_scope(profiling.OP_CONV):
                z = rms_norm(x, p["op_norm"], self.norm_eps)
                b, c, u = jnp.split(self._mm(
                    z, p["conv_in"], self.compute_dtype).astype(jnp.float32), 3, -1)
                v = b * u
                padded = jnp.pad(v, ((0, 0), (2, 0), (0, 0)))
                taps = p["conv_taps"]
                s = (taps[0] * v + taps[1] * padded[:, 1:T + 1]
                     + taps[2] * padded[:, :T])
                h = x + self._mm(c * s, p["conv_out"], jnp.float32)
        else:
            with device_scope(profiling.OP_ATTN):
                z = rms_norm(x, p["op_norm"], self.norm_eps)
                positions = jnp.arange(T)[None, :]
                q, k, v = self._qkv(p, z, positions)
                mask = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None]
                h = x + self._mm(self._attend(q, k, v, mask), p["wo"], jnp.float32)
        y, routed = self._ffn(p, ffn, h.reshape(B * T, d))
        return y.reshape(B, T, d), routed

    def unroll(self, params, tokens, with_routes: bool = False):
        """Whole episodes from a reset: ``tokens`` [B, T] int32 ->
        (PolicyValue with logits [B, T, A] and value [B, T], aux). ``aux``
        counts the tokens routed to each held expert of each expert layer
        (``moe_tokens_per_expert``) and the blocks of sorted rows each ran
        beyond its first (``moe_overflow_blocks`` [expert layers]: 0 unless
        more rows were routed here than ``ops/moe.py``'s bound) and, asked,
        names every token's chosen experts (``routes`` [expert layers, B,
        T, k])."""
        routed = moe.RoutedLayers(*tokens.shape)
        return self._unroll(params, tokens, self._layer_unroll, routed.take,
                            lambda: routed.aux(with_routes))
