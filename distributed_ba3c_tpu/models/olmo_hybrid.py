"""Olmo-Hybrid-7B (``model_type olmo_hybrid``) as a token-sequence policy:
gated delta-rule linear attention three layers in four beside full
attention, one chip holding a third of every mixer's heads.

Published (allenai/Olmo-Hybrid-7B ``config.json``): 32 layers in the period
``linear, linear, linear, full`` (``layer_types``), hidden 3840, SwiGLU
11008 in every layer, RMSNorm (eps 1e-6), 30 heads of every kind, an untied
head over 100,352 ids, no rotary embedding (``rope_theta null``: the linear
layers carry position). A layer is

    h = x + RMSNorm(Mixer(x))        x' = h + RMSNorm(W_down(silu(W_gate h) * W_up h))

(a norm on each sub-block's OUTPUT, none on its input), then a final
RMSNorm, the head and the trainer's float32 value head. The mixer by
``layer_types[i]``:

- ``linear_attention`` (the gated delta rule of arXiv:2412.06464; 30 heads
  with keys of 96 and values of 192, ``linear_conv_kernel_dim`` 4,
  ``linear_allow_neg_eigval``): ``[q; k; v] = silu(conv4(W_qkv x))``
  (causal, depthwise, zero before the episode), ``z = W_z x``, ``b = W_b
  x``, ``a = W_a x``; a head's ``q <- q / |q| / sqrt(96)``, ``k <- k /
  |k|``; ``beta = 2 sigmoid(b)``, ``alpha = exp(-exp(A_log) softplus(a +
  dt_bias))``; the recurrence of ``ops/delta_rule.py`` on a float32 state
  ``[96, 192]`` a head gives ``o``; out ``W_o (RMSNorm_192(o) * silu(z))``.
- ``full_attention``: 30 heads of 128 with as many key/value heads, no
  bias; ``q`` and ``k`` each through an RMSNorm with a gain over the whole
  projection (all heads together); causal softmax at ``1/sqrt(128)``.

The widths are the defaults below and are never cut. What IS cut is how
much one chip holds (``benchmark/configs/olmo-hybrid-7b-recall-fused-a2c.
json`` has the arithmetic and what is assumed beyond the config): which
published layers (``layer_ids``), how many vocabulary ids (``num_actions``)
and HOW MANY HEADS of every mixer: a stage's chips share a layer by heads,
so this chip's ``W_qkv``, ``W_z``, ``W_a``, ``W_b``, conv channels, ``A_log``,
``dt_bias`` and rows of ``W_o`` are those of its heads, and the mixer's
output is this chip's part of the sum. A linear head's norms and gate are
its own, so its share is exact; the full layer's ``q`` / ``k`` norm spans
the heads, and its mean of squares is taken over the heads held
(:meth:`OlmoHybrid.full_mixer` takes the whole layer's sums of squares
where someone has added them up: a test; no cell runs such a mesh).
``--model_cut`` names a cut (:data:`CUTS`).

Precision: float32 parameters, residual stream, norms, gates, conv, the
delta rule and its state, softmax and heads' outputs; bfloat16 matrix
operands with float32 accumulation (``models/layers.py:mm``); the K/V cache
bfloat16. The policy protocol is models/policy.py's.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.models import layers, sequence
from distributed_ba3c_tpu.models.layers import rms_norm
from distributed_ba3c_tpu.ops import delta_rule, sparse_attention
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

LINEAR, FULL = "linear_attention", "full_attention"
#: ``layer_types`` as published: 32 layers, every fourth one full attention
LAYER_TYPES = (LINEAR, LINEAR, LINEAR, FULL) * 8
#: the seeded gates: ``exp(A_log)`` uniform in [A_MIN, A_MAX], ``softplus(
#: dt_bias)`` log-uniform in [DT_MIN, DT_MAX] (the family's start)
A_MIN, A_MAX = 1.0, 16.0
DT_MIN, DT_MAX = 1e-3, 1e-1
#: under the square root of a head's ``|q|`` and ``|k|``
L2_EPS = 1e-6
#: ``--model_cut``: what one chip holds, the default first. ``head-share-3``:
#: one of 3 chips that share each layer by heads (10 of 30 of every mixer),
#: published layers 0-3 (one whole period); the vocabulary slice is the
#: env's action space. ``tiny``: every mechanism at a size a CPU test runs,
#: 2 heads of an uncut 6 of both kinds, the learner's delta rule in chunks
#: of 8.
CUTS = {
    "head-share-3": {},
    "tiny": dict(
        hidden_size=64, intermediate_size=96, num_attention_heads=2,
        head_dim=16, linear_num_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=16, delta_chunk=8,
    ),
}


cut_fields = functools.partial(sequence.cut_fields, CUTS)


class Carry(NamedTuple):
    """What decoding carries from one position to the next, an env a row.
    ``fresh`` resets ``pos`` and zeroes a linear layer's state and conv
    tail; the K/V buffers keep their bytes and are masked by the position
    (nothing at or past it is read)."""

    pos: jax.Array   # [B] int32 position in the episode
    linear: Tuple    # per linear layer (the state [B, H, K, V] f32: constant
                     # in the episode's length; the conv's last three inputs
                     # [B, 3, H (2K + V)] f32; the last position's gates
                     # [B, H] f32, for the gauges)
    kv: Tuple        # per full layer (k, v), each [B, P, H * D]: a
                     # position's heads side by side in one row of whole lanes


@dataclasses.dataclass(frozen=True)
class OlmoHybrid(sequence.SequencePolicy):
    num_actions: int = 12544            # vocabulary ids held (of 100,352)
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_attention_heads: int = 10       # held, of the published 30; as many
    head_dim: int = 128                 # key/value heads
    linear_num_heads: int = 10          # held, of 30 key and 30 value heads
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    rms_norm_eps: float = 1e-6
    layer_types: Tuple[str, ...] = LAYER_TYPES
    # -- the chip's share ---------------------------------------------------
    layer_ids: Tuple[int, ...] = (0, 1, 2, 3)
    # -- how it is run ------------------------------------------------------
    max_positions: int = 2048           # K/V rows: the episode length
    delta_chunk: int = delta_rule.CHUNK  # positions a chunk of the learner's
                                         # delta rule takes
    compute_dtype: jnp.dtype = jnp.bfloat16
    state_dtype: jnp.dtype = jnp.float32  # the delta rule's state (a
                                          # control keeps it in bfloat16)

    head_table = "head"
    #: the conv's taps stay float32 (``A_log`` and ``dt_bias`` are vectors)
    float32_leaves = ("conv_w",)
    final_norm_eps = property(lambda self: self.rms_norm_eps)

    def __post_init__(self):
        assert self.linear_conv_kernel_dim == 4, "the causal conv is written for 4 taps"
        assert set(self.layer_kinds) <= {LINEAR, FULL}

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(self.layer_types[i] for i in self.layer_ids)

    @property
    def conv_width(self) -> int:
        """Channels of a linear layer's conv: every held head's q, k and v."""
        return self.linear_num_heads * (
            2 * self.linear_key_head_dim + self.linear_value_head_dim)

    # -- parameters -----------------------------------------------------------
    def _init_layer(self, i: int, init):
        """Held layer ``i``'s seeded leaves: normal kernels scaled by
        1/sqrt(fan_in), unit gains; ``exp(A_log)`` uniform in [1, 16],
        ``dt_bias`` the inverse softplus of step sizes log-uniform in [1e-3,
        1e-1]."""
        d, f = self.hidden_size, self.intermediate_size
        H, K, V = (self.linear_num_heads, self.linear_key_head_dim,
                   self.linear_value_head_dim)
        hq = self.num_attention_heads * self.head_dim
        taps = self.linear_conv_kernel_dim
        normal, uniform, ones = init.normal, init.uniform, init.ones
        layer = {"mix_norm": ones(d), "ffn_norm": ones(d),
                 "w_gate": normal((d, f), d), "w_up": normal((d, f), d),
                 "w_down": normal((f, d), f)}
        if self.layer_kinds[i] == LINEAR:
            step = jnp.exp(uniform((H,), math.log(DT_MIN), math.log(DT_MAX)))
            layer.update(
                wqkv=normal((d, self.conv_width), d),
                wz=normal((d, H * V), d), wa=normal((d, H), d),
                wb=normal((d, H), d),
                conv_w=normal((taps, self.conv_width), taps),
                A_log=jnp.log(uniform((H,), A_MIN, A_MAX)),
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                o_norm=ones(V), wo=normal((H * V, d), H * V))
        else:
            layer.update(
                wq=normal((d, hq), d), wk=normal((d, hq), d),
                wv=normal((d, hq), d), wo=normal((hq, d), hq),
                q_norm=ones(hq), k_norm=ones(hq))
        return layer

    # -- pieces shared by the decode step and the unroll -----------------------
    def _after(self, p, x, mixed):
        """x [N, d] and its mixer's output -> the layer's output: both
        sub-blocks' norms lie on their OUTPUT, before the residual add."""
        eps = self.rms_norm_eps
        h = x + rms_norm(mixed, p["mix_norm"], eps)
        with device_scope(profiling.FFN_DENSE):
            return h + rms_norm(layers.swiglu(
                h, p["w_gate"], p["w_up"], p["w_down"], self.compute_dtype),
                p["ffn_norm"], eps)

    def _linear_in(self, p, x):
        """x [..., d] -> (the conv's input [..., H (2K + V)], z [..., H, V],
        alpha, beta [..., H]), float32."""
        with device_scope(profiling.OP_LINATTN_IN_PROJ):
            u = self._mm(x, p["wqkv"])
            z = self._mm(x, p["wz"]).reshape(
                *x.shape[:-1], self.linear_num_heads, self.linear_value_head_dim)
            rate = jax.nn.softplus(self._mm(x, p["wa"]) + p["dt_bias"])
            alpha = jnp.exp(-jnp.exp(p["A_log"]) * rate)
            # in (0, 2): past 1 the rule mirrors what the state held along k
            beta = 2.0 * jax.nn.sigmoid(self._mm(x, p["wb"]))
            return u, z, alpha, beta

    def _linear_heads(self, u):
        """The conv's output [..., H (2K + V)] -> q, k [..., H, K] (unit
        length, q over ``sqrt(K)``), v [..., H, V]."""
        H, K = self.linear_num_heads, self.linear_key_head_dim
        q, k, v = (x.reshape(*u.shape[:-1], H, -1)
                   for x in jnp.split(u, (H * K, 2 * H * K), -1))
        unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
        return unit(q) / math.sqrt(K), unit(k), v

    def _linear_out(self, p, o, z):
        """o, z [..., H, V] -> this chip's part of the mixer's output."""
        with device_scope(profiling.OP_LINATTN_OUT):
            gated = rms_norm(o, p["o_norm"], self.rms_norm_eps) * jax.nn.silu(z)
            return self._mm(gated.reshape(*o.shape[:-2], -1), p["wo"])

    def linear_mixer(self, p, x):
        """A linear-attention layer's mixer over whole episodes from a reset:
        x [B, T, d] float32 -> this chip's part of its output [B, T, d]."""
        with device_scope(profiling.OP_LINATTN):
            u, z, alpha, beta = self._linear_in(p, x)
            with device_scope(profiling.OP_LINATTN_CONV):
                u = jax.nn.silu(layers.causal_conv(p["conv_w"], u))
            q, k, v = self._linear_heads(u)
            with device_scope(profiling.OP_LINATTN_DELTA):
                o, _ = delta_rule.delta_chunked(
                    q, k, v, alpha, beta, self.delta_chunk, self.state_dtype)
            return self._linear_out(p, o, z)

    @staticmethod
    def _squares(q, k):
        """(the sum of squares of q, of k, each [B, T, 1]; their width)."""
        return (jnp.sum(q * q, -1, keepdims=True),
                jnp.sum(k * k, -1, keepdims=True), q.shape[-1])

    def qk_sums(self, p, x):
        """What a full layer's q / k norm needs of the heads held here, x [B,
        T, d]: chips that share the layer would add these up."""
        return self._squares(self._mm(x, p["wq"]), self._mm(x, p["wk"]))

    def _qkv(self, p, x, sums=None):
        """x [B, T, d] -> q, k, v [B, T, H, D] in the compute type; q and k
        through their RMSNorm over the whole projection. ``sums``: (the sum
        of squares of ``W_q x`` [B, T, 1], of ``W_k x``, the width they are
        over) where they span more heads than are held here."""
        q, k = self._mm(x, p["wq"]), self._mm(x, p["wk"])
        q_sum, k_sum, width = sums or self._squares(q, k)
        eps = self.rms_norm_eps
        q = q * jax.lax.rsqrt(q_sum / width + eps) * p["q_norm"]
        k = k * jax.lax.rsqrt(k_sum / width + eps) * p["k_norm"]
        heads = lambda y: y.reshape(  # noqa: E731
            *y.shape[:-1], -1, self.head_dim).astype(self.compute_dtype)
        return heads(q), heads(k), heads(self._mm(x, p["wv"]))

    def full_mixer(self, p, x, sums=None):
        """A full-attention layer's mixer over whole episodes, causal: x [B,
        T, d] float32 -> this chip's part of its output [B, T, d]."""
        with device_scope(profiling.OP_ATTN_FULL):
            q, k, v = self._qkv(p, x, sums)
            out, _ = sparse_attention.attend_selected(
                q, k, v, None, 1.0 / math.sqrt(self.head_dim))
            return self._mm(out, p["wo"])

    # -- the rollout's decode step ---------------------------------------------
    def init_carry(self, batch: int) -> Carry:
        H, K, V = (self.linear_num_heads, self.linear_key_head_dim,
                   self.linear_value_head_dim)
        kv_shape = (batch, self.max_positions,
                    self.num_attention_heads * self.head_dim)
        f32 = jnp.float32
        # a buffer each: the step donates its state
        return Carry(
            pos=jnp.zeros((batch,), jnp.int32),
            linear=tuple(
                (jnp.zeros((batch, H, K, V), self.state_dtype),
                 jnp.zeros((batch, self.linear_conv_kernel_dim - 1,
                            self.conv_width), f32),
                 jnp.zeros((batch, H), f32))
                for kind in self.layer_kinds if kind == LINEAR),
            kv=tuple(
                tuple(jnp.zeros(kv_shape, self.compute_dtype) for _ in range(2))
                for kind in self.layer_kinds if kind == FULL),
        )

    def carry_bytes(self) -> Tuple[int, ...]:
        """Bytes of carry an env, by kind: (the delta rule's states, the
        convs' tails, the K/V buffers, the position and the last gates)."""
        def kinds(carry):
            states, tails, gates = (
                [layer[i] for layer in carry.linear] for i in range(3))
            return states, tails, carry.kv, (carry.pos, gates)

        return self._carry_bytes(kinds)

    def carry_gauges(self, carry: Carry) -> dict:
        """What the trainer reports of the carry at an update's end: its
        bytes an env by kind (a constant of the shapes), the largest ``|S|``
        of the delta rule's states (with ``beta`` up to 2 a state can grow,
        and an overflow shows here before it shows in the loss) and the mean
        gate ``alpha`` of the rollout's last position."""
        states = [jnp.max(jnp.abs(s.astype(jnp.float32))) for s, _, _ in carry.linear]
        gates = [jnp.mean(g) for _, _, g in carry.linear]
        zero = jnp.float32(0.0)
        return {
            "carry_bytes_per_env": jnp.asarray(self.carry_bytes(), jnp.float32),
            "linattn_state_absmax": jnp.max(jnp.stack(states)) if states else zero,
            "linattn_gate_mean": jnp.mean(jnp.stack(gates)) if gates else zero,
        }

    def epoch_stats(self, metrics: dict) -> dict:
        """An epoch's scalars from the step's metrics of this policy."""
        return {
            "linattn_state_absmax": float(metrics["linattn_state_absmax"]),
            "linattn_gate_mean": float(metrics["linattn_gate_mean"]),
            "carry_bytes_per_env": float(metrics["carry_bytes_per_env"].sum()),
        }

    def step(self, params, obs, carry: Carry, fresh):
        pos, keep = sequence.decode_opening(carry.pos, fresh)
        rows = jnp.arange(obs.shape[0])
        x = self._embed(params, obs)
        linear_in, kv_in = iter(carry.linear), iter(carry.kv)
        linear_out, kv_out = [], []
        for i, kind in enumerate(self.layer_kinds):
            p = params[self.layer_name(i)]
            if kind == LINEAR:
                with device_scope(profiling.OP_LINATTN):
                    state, tail, _ = next(linear_in)
                    state = state * keep[:, None, None, None].astype(state.dtype)
                    tail = tail * keep[:, None, None]
                    u, z, alpha, beta = self._linear_in(p, x)
                    with device_scope(profiling.OP_LINATTN_CONV):
                        taps = p["conv_w"]
                        conv, tail = layers.conv_step(taps[0] * u, taps, u, tail)
                    q, k, v = self._linear_heads(jax.nn.silu(conv))
                    with device_scope(profiling.OP_LINATTN_DELTA):
                        state, o = delta_rule.delta_step(
                            state, q, k, v, alpha, beta)
                    mixed = self._linear_out(p, o, z)
                    linear_out.append((state, tail, alpha))
            else:
                with device_scope(profiling.OP_ATTN_FULL):
                    caches = next(kv_in)
                    mixed, caches = self._decode_attention(
                        p, self._qkv(p, x[:, None, :]), caches, rows, pos)
                    kv_out.append(caches)
            x = self._after(p, x, mixed)
        return self._head(params, x), Carry(
            pos=pos + 1, linear=tuple(linear_out), kv=tuple(kv_out))

    # -- the learner's unroll ----------------------------------------------------
    def _layer_unroll(self, i: int, p, x):
        """One layer over whole episodes: x [B, T, d] float32 -> (the same,
        None: it counts nothing)."""
        B, T, d = x.shape
        mixer = self.linear_mixer if self.layer_kinds[i] == LINEAR else self.full_mixer
        return self._after(
            p, x.reshape(B * T, d), mixer(p, x).reshape(B * T, d)
        ).reshape(B, T, d), None

    def unroll(self, params, tokens):
        """Whole episodes from a reset: ``tokens`` [B, T] int32 ->
        (PolicyValue with logits [B, T, A] and value [B, T], aux). ``aux``
        is empty: this policy counts nothing in its learner."""
        return self._unroll(params, tokens, self._layer_unroll)
