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
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.models import layers
from distributed_ba3c_tpu.models.a3c import PolicyValue
from distributed_ba3c_tpu.models.layers import rms_norm
from distributed_ba3c_tpu.ops import decode_attention, delta_rule, sparse_attention
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

LINEAR, FULL = "linear_attention", "full_attention"
#: ``layer_types`` as published: 32 layers, every fourth one full attention
LAYER_TYPES = (LINEAR, LINEAR, LINEAR, FULL) * 8
VALUE_INIT_SCALE = 0.01
#: the seeded gates: ``exp(A_log)`` uniform in [A_MIN, A_MAX], ``softplus(
#: dt_bias)`` log-uniform in [DT_MIN, DT_MAX] (the family's start)
A_MIN, A_MAX = 1.0, 16.0
DT_MIN, DT_MAX = 1e-3, 1e-1
#: under the square root of a head's ``|q|`` and ``|k|``
L2_EPS = 1e-6
#: ``--model_cut``: what one chip holds. ``head-share-3``: one of 3 chips
#: that share each layer by heads (10 of 30 of every mixer), published
#: layers 0-3 (one whole period); the vocabulary slice is the env's action
#: space. ``tiny``: every mechanism at a size a CPU test runs, 2 heads of
#: an uncut 6 of both kinds, the learner's delta rule in chunks of 8.
CUTS = {
    "head-share-3": {},
    "tiny": dict(
        hidden_size=64, intermediate_size=96, num_attention_heads=2,
        head_dim=16, linear_num_heads=2, linear_key_head_dim=8,
        linear_value_head_dim=16, delta_chunk=8,
    ),
}


def cut_fields(cut: str | None) -> dict:
    cut = cut or "head-share-3"
    if cut not in CUTS:
        raise ValueError(f"unknown --model_cut {cut!r}; have {sorted(CUTS)}")
    return dict(CUTS[cut])


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
class OlmoHybrid:
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

    carries_state = True

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

    def for_env(self, env) -> "OlmoHybrid":
        """This policy over ``env``'s action space and episode length."""
        return dataclasses.replace(
            self, num_actions=env.num_actions, max_positions=env.episode_length
        )

    def layer_name(self, i: int) -> str:
        return f"layer_{self.layer_ids[i]}"

    # -- parameters -----------------------------------------------------------
    def init_params(self, rng):
        """Seeded float32 parameters, ``{layer: {leaf: array}}``: normal
        kernels scaled by 1/sqrt(fan_in), unit gains; ``exp(A_log)`` uniform
        in [1, 16], ``dt_bias`` the inverse softplus of step sizes
        log-uniform in [1e-3, 1e-1]."""
        d, f = self.hidden_size, self.intermediate_size
        H, K, V = (self.linear_num_heads, self.linear_key_head_dim,
                   self.linear_value_head_dim)
        hq = self.num_attention_heads * self.head_dim
        taps = self.linear_conv_kernel_dim
        keys = iter(jax.random.split(rng, 16 * len(self.layer_ids) + 4))

        def normal(shape, fan_in):
            return jax.random.normal(next(keys), shape, jnp.float32) / math.sqrt(fan_in)

        def uniform(shape, low, high):
            return low + (high - low) * jax.random.uniform(
                next(keys), shape, jnp.float32)

        ones = lambda n: jnp.ones((n,), jnp.float32)  # noqa: E731
        params = {"embed": {"table": normal((self.num_actions, d), d)}}
        for i, kind in enumerate(self.layer_kinds):
            layer = {"mix_norm": ones(d), "ffn_norm": ones(d),
                     "w_gate": normal((d, f), d), "w_up": normal((d, f), d),
                     "w_down": normal((f, d), f)}
            if kind == LINEAR:
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
            params[self.layer_name(i)] = layer
        params["final"] = {"norm": ones(d)}
        params["head"] = {"table": normal((self.num_actions, d), d)}
        # a value head that starts near zero, as actor-critic code starts it
        params["value"] = {"kernel": VALUE_INIT_SCALE * normal((d, 1), d),
                           "bias": jnp.zeros((1,), jnp.float32)}
        return params

    def rollout_params(self, params):
        """The matrices in the compute type, once for a whole rollout. Gains,
        the conv's taps, ``A_log``, ``dt_bias`` and the value head stay
        float32."""
        return layers.matrices_in(params, self.compute_dtype, keep=("conv_w",))

    # -- pieces shared by the decode step and the unroll -----------------------
    def _mm(self, x, w, out_dtype=jnp.float32):
        return layers.mm(x, w, self.compute_dtype, out_dtype)

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
        T = x.shape[1]
        taps = self.linear_conv_kernel_dim
        with device_scope(profiling.OP_LINATTN):
            u, z, alpha, beta = self._linear_in(p, x)
            with device_scope(profiling.OP_LINATTN_CONV):
                padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
                u = jax.nn.silu(sum(
                    p["conv_w"][k] * padded[:, taps - 1 - k:taps - 1 - k + T]
                    for k in range(taps)))
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

    def _head(self, params, x):
        """x [N, d] float32 -> PolicyValue over the held vocabulary."""
        with device_scope(profiling.HEAD):
            h = rms_norm(x, params["final"]["norm"], self.rms_norm_eps)
            logits, value = layers.tied_head(
                h, params["head"]["table"], params["value"], self.compute_dtype)
            return PolicyValue(logits=logits, value=value)

    def _embed(self, params, tokens):
        return layers.embed_rows(
            params["embed"]["table"], tokens, self.compute_dtype)

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
        shapes = jax.eval_shape(lambda: self.init_carry(1))
        size = lambda tree: sum(  # noqa: E731
            x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))
        states, tails, gates = (
            [layer[i] for layer in shapes.linear] for i in range(3))
        return (size(states), size(tails), size(shapes.kv),
                size(shapes.pos) + size(gates))

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
        """One token an env: ``obs`` [B] int32, ``fresh`` [B] bool (the
        token opens an episode: forget the last one first)."""
        B = obs.shape[0]
        pos = jnp.where(fresh, 0, carry.pos)
        keep = ~fresh
        rows = jnp.arange(B)
        x = self._embed(params, obs)
        linear_in, kv_in = iter(carry.linear), iter(carry.kv)
        linear_out, kv_out = [], []

        def write(cache, new):  # in place: one row an env
            return cache.at[rows, pos].set(
                new.reshape(B, -1), indices_are_sorted=True, unique_indices=True)

        for i, kind in enumerate(self.layer_kinds):
            p = params[self.layer_name(i)]
            if kind == LINEAR:
                with device_scope(profiling.OP_LINATTN):
                    state, tail, _ = next(linear_in)
                    state = state * keep[:, None, None, None].astype(state.dtype)
                    tail = tail * keep[:, None, None]
                    u, z, alpha, beta = self._linear_in(p, x)
                    with device_scope(profiling.OP_LINATTN_CONV):
                        taps = p["conv_w"]  # taps[k] weighs the input k back
                        conv = taps[0] * u + sum(
                            taps[k] * tail[:, k - 1]
                            for k in range(1, self.linear_conv_kernel_dim))
                        tail = jnp.concatenate([u[:, None], tail[:, :-1]], 1)
                    q, k, v = self._linear_heads(jax.nn.silu(conv))
                    with device_scope(profiling.OP_LINATTN_DELTA):
                        state, o = delta_rule.delta_step(
                            state, q, k, v, alpha, beta)
                    mixed = self._linear_out(p, o, z)
                    linear_out.append((state, tail, alpha))
            else:
                with device_scope(profiling.OP_ATTN_FULL):
                    k_cache, v_cache = next(kv_in)
                    q, k, v = self._qkv(p, x[:, None, :])
                    k_cache, v_cache = write(k_cache, k), write(v_cache, v)
                    out = decode_attention.decode_attend(
                        q[:, 0], k_cache, v_cache, pos + 1,
                        1.0 / math.sqrt(self.head_dim))
                    mixed = self._mm(out.reshape(B, -1), p["wo"])
                    kv_out.append((k_cache, v_cache))
            x = self._after(p, x, mixed)
        return self._head(params, x), Carry(
            pos=pos + 1, linear=tuple(linear_out), kv=tuple(kv_out))

    # -- the learner's unroll ----------------------------------------------------
    def _layer_unroll(self, i: int, p, x):
        """One layer over whole episodes: x [B, T, d] float32 -> the same."""
        B, T, d = x.shape
        mixer = self.linear_mixer if self.layer_kinds[i] == LINEAR else self.full_mixer
        return self._after(
            p, x.reshape(B * T, d), mixer(p, x).reshape(B * T, d)
        ).reshape(B, T, d)

    def unroll(self, params, tokens):
        """Whole episodes from a reset: ``tokens`` [B, T] int32 ->
        (PolicyValue with logits [B, T, A] and value [B, T], aux). ``aux``
        is empty: this policy counts nothing in its learner."""
        B, T = tokens.shape
        x = self._embed(params, tokens)
        for i in range(len(self.layer_kinds)):
            # a layer is recomputed in the backward, as in the other
            # sequence policies
            layer = jax.checkpoint(lambda p, x, i=i: self._layer_unroll(i, p, x))
            x = layer(params[self.layer_name(i)], x)
        out = self._head(params, x.reshape(B * T, -1))
        return PolicyValue(
            logits=out.logits.reshape(B, T, -1), value=out.value.reshape(B, T)
        ), {}
