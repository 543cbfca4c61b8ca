"""NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type nemotron_h``) as a
token-sequence policy: Mamba-2 (SSD) layers, sigmoid-routed relu² experts
beside a shared expert, one grouped-query attention layer in nine; one chip
holding a sixteenth of every expert layer's routed experts.

Published (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 ``config.json``):
hidden 2688, 52 blocks whose kinds ``hybrid_override_pattern`` spells
(``M`` Mamba-2, ``E`` experts, ``*`` attention: 23 : 23 : 6), RMSNorm (eps
1e-5), an untied head over 131,072 ids. A block is

    x <- x + Mixer(RMSNorm(x))                 ONE mixer a block

then a final RMSNorm, the head and the trainer's float32 value head. The
mixer by the pattern's letter at the block's published index:

- ``M`` (64 heads of 64 channels, state 128, 8 groups of ``B`` / ``C``,
  conv of 4 taps with a bias, chunks of 128): ``[z; xBC; dt] = W_in u``
  (4,096 + 6,144 + 64); ``xBC <- silu(conv4(xBC) + b)`` (causal, depthwise,
  zero before the episode); ``[x; B; C] = xBC``; ``dt <- softplus(dt +
  dt_bias)``; ``A = -exp(A_log)``; the recurrence of ``ops/ssd.py`` on a
  float32 state ``[64, 128]`` a head gives ``y``; out ``W_out
  RMSNorm_512(y * silu(z))``, the gate first and the statistic over each
  group's 512 channels.
- ``E`` (128 routed experts of 1,856, 6 a token, one shared expert of
  3,712): ``ops/moe.py:route`` (sigmoid scores, a bias that only chooses,
  the chosen scores normalised and scaled by 2.5) and ``expert_ffn`` in its
  two-matrix form ``W2 relu(W1 u)^2``; the shared expert, which every token
  takes, is a dense product of the same form here.
- ``*``: 32 query heads over 2 key/value heads of 128, no bias, no rotary
  embedding (the Mamba layers carry position), causal softmax at
  ``1/sqrt(128)``.

The widths are the defaults below and are never cut. What IS cut is how
much one chip holds (``benchmark/configs/nemotron3-nano-30b-a3b-recall-
fused-a2c.json`` has the arithmetic and what is assumed beyond the config):
which published blocks (``layer_ids``; their kinds come from the pattern),
how many routed experts of each expert layer (``experts_held`` from
``expert_offset``: the router still scores all ``n_routed_experts``, and an
assignment to an absent expert adds nothing here) and how many vocabulary
ids (``num_actions``). ``--model_cut`` names a cut (:data:`CUTS`).

Precision: float32 parameters, residual stream, norms, router, ``dt``,
decays, conv, the recurrence and its state, softmax and heads' outputs;
bfloat16 matrix operands with float32 accumulation (``models/layers.py:
mm``); the K/V cache bfloat16. The policy protocol is models/policy.py's.
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
from distributed_ba3c_tpu.ops import moe, sparse_attention, ssd
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
#: ``hybrid_override_pattern`` as published: 52 blocks
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
#: the seeded start of a Mamba-2 layer: ``exp(A_log)`` uniform in [A_MIN,
#: A_MAX], ``dt_bias`` the inverse softplus of a step size log-uniform in
#: [time_step_min, time_step_max] floored at time_step_floor (the config's
#: three keys shape this start and nothing else)
A_MIN, A_MAX = 1.0, 16.0
#: spread of the seeded choosing bias: small beside the gaps between router
#: scores, as a bias that exists to even the load out is
EXPERT_BIAS_SCALE = 0.01
#: query heads a K/V head that ``ops/sparse_attention.py``'s kernels take in
#: one tile (the sparse-attention policy's grouping, which the chip has run)
KERNEL_QUERY_HEADS = 8
#: room in a block of the experts' sorted rows over an even router's share
#: (``ops/moe.py:block_rows``): a capacity factor of 2, what sparse-expert
#: layers conventionally give a router that load balancing has not yet
#: evened out. A chip holds 8 of 128 experts: 768 expected rows a block an
#: env of a learner chunk. This model's SEEDED router loads those 8 with
#: 0.57-1.83 of their even share by the seed and the block (14 seeds at a
#: chunk of one env, my chip runs, PR 44: ``silu``'s positive mean gives the
#: residual stream a part every token shares, which an untrained router
#: scores alike for all of them; the published ``e_score_correction_bias``
#: is trained against exactly that). At the shared margin of a quarter a
#: block past 1.33 ran a second pass in every chunk and the update took
#: 1-2 % longer on a third of the seeds. PERF.md section 7 has what was
#: tried in its place.
EXPERT_ROWS_MARGIN = 1.0
#: ``--model_cut``: what one chip holds, the default first.
#: ``chip-share-16``: one of 16 chips that share each layer expert parallel
#: (8 of 128 routed experts; the vocabulary slice is the env's action
#: space), published blocks 0-8 (one whole period, 4 Mamba-2 : 4 expert : 1
#: attention). ``tiny``: every mechanism at a size a CPU test runs, 2 of 32
#: experts (sixteen shares), an expert width off whole lanes as the
#: published one is.
CUTS = {
    "chip-share-16": {},
    "tiny": dict(
        hidden_size=64, mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=16,
        n_groups=2, chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, n_routed_experts=32, experts_held=2, num_experts_per_tok=3,
        moe_intermediate_size=24, moe_shared_expert_intermediate_size=48,
        layer_ids=(0, 1, 4, 5, 6),
    ),
}


cut_fields = functools.partial(sequence.cut_fields, CUTS)


class Carry(NamedTuple):
    """What decoding carries from one position to the next, an env a row.
    ``fresh`` resets ``pos`` and zeroes a Mamba-2 layer's state and conv
    tail; the K/V buffers keep their bytes and are masked by the position
    (nothing at or past it is read)."""

    pos: jax.Array   # [B] int32 position in the episode
    mamba: Tuple     # per Mamba-2 layer (the state [B, h, P, N] f32: constant
                     # in the episode's length; the conv's last three inputs
                     # [B, 3, h P + 2 g N] f32; the last position's step
                     # sizes [B, h] f32, for the gauges)
    kv: Tuple        # per attention layer (k, v), each [B, P, KV * D]: a
                     # position's K/V heads side by side in one row


@dataclasses.dataclass(frozen=True)
class NemotronH(sequence.SequencePolicy):
    num_actions: int = 16384            # vocabulary ids held (of 131,072)
    hidden_size: int = 2688
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = ssd.CHUNK
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 128         # the router's width, as published
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    layer_norm_epsilon: float = 1e-5
    hybrid_override_pattern: str = PATTERN
    # -- the chip's share ---------------------------------------------------
    layer_ids: Tuple[int, ...] = tuple(range(9))
    experts_held: int = 8
    expert_offset: int = 0
    # -- how it is run ------------------------------------------------------
    max_positions: int = 2048           # K/V rows: the episode length
    compute_dtype: jnp.dtype = jnp.bfloat16
    state_dtype: jnp.dtype = jnp.float32  # the recurrence's state (a
                                          # control keeps it in bfloat16)

    head_table = "head"
    #: the conv's taps and the router stay float32 (the conv's bias,
    #: ``A_log``, ``D``, ``dt_bias`` and the router's bias are vectors)
    float32_leaves = ("conv_w", "router")
    final_norm_eps = property(lambda self: self.layer_norm_epsilon)

    def __post_init__(self):
        assert self.conv_kernel == 4, "the causal conv is written for 4 taps"
        assert set(self.layer_kinds) <= {MAMBA, EXPERTS, ATTENTION}
        assert self.mamba_num_heads % self.n_groups == 0
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert 0 < self.experts_held <= self.n_routed_experts

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(self.hybrid_override_pattern[i] for i in self.layer_ids)

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_width(self) -> int:
        """Channels of a Mamba-2 layer's conv: ``x`` and every group's ``B``
        and ``C``."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    # -- parameters -----------------------------------------------------------
    def _init_layer(self, i: int, init):
        """Held block ``i``'s seeded leaves: normal kernels scaled by
        1/sqrt(fan_in), unit gains, ``D`` 1; ``exp(A_log)`` uniform in [1,
        16], ``dt_bias`` the inverse softplus of step sizes log-uniform in
        [time_step_min, time_step_max] floored at time_step_floor.
        ``expert_bias`` only chooses, so its gradient is identically zero
        and Adam never moves it."""
        d, h = self.hidden_size, self.mamba_num_heads
        hq = self.num_attention_heads * self.head_dim
        hkv = self.num_key_value_heads * self.head_dim
        fe, fs = self.moe_intermediate_size, self.moe_shared_expert_intermediate_size
        taps, width = self.conv_kernel, self.conv_width
        normal, uniform, ones = init.normal, init.uniform, init.ones
        kind = self.layer_kinds[i]
        layer = {"norm": ones(d)}
        if kind == MAMBA:
            step = jnp.maximum(jnp.exp(uniform(
                (h,), math.log(self.time_step_min),
                math.log(self.time_step_max))), self.time_step_floor)
            layer.update(
                in_proj=normal((d, self.d_inner + width + h), d),
                conv_w=normal((taps, width), taps),
                conv_b=normal((width,), taps),
                A_log=jnp.log(uniform((h,), A_MIN, A_MAX)), D=ones(h),
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                gate_norm=ones(self.d_inner),
                out_proj=normal((self.d_inner, d), self.d_inner))
        elif kind == ATTENTION:
            layer.update(
                wq=normal((d, hq), d), wk=normal((d, hkv), d),
                wv=normal((d, hkv), d), wo=normal((hq, d), hq))
        else:
            e = self.experts_held
            layer.update(
                router=normal((d, self.n_routed_experts), d),
                expert_bias=EXPERT_BIAS_SCALE * jax.random.normal(
                    next(init.keys), (self.n_routed_experts,), jnp.float32),
                w1=normal((e, d, fe), d), w2=normal((e, fe, d), fe),
                shared_w1=normal((d, fs), d), shared_w2=normal((fs, d), fs))
        return layer

    # -- pieces shared by the decode step and the unroll -----------------------
    def _mamba_in(self, p, x):
        """x [..., d] float32 -> (z [..., h P], the conv's input [..., h P +
        2 g N], the step sizes dt [..., h]), float32."""
        with device_scope(profiling.OP_MAMBA2_IN_PROJ):
            u = rms_norm(x, p["norm"], self.layer_norm_epsilon)
            z, xbc, dt = jnp.split(
                self._mm(u, p["in_proj"]),
                (self.d_inner, self.d_inner + self.conv_width), -1)
            return z, xbc, jax.nn.softplus(dt + p["dt_bias"])

    def _mamba_heads(self, xbc):
        """The conv's output [..., h P + 2 g N] -> x [..., h, P], B, C [...,
        g, N]."""
        g, N = self.n_groups, self.ssm_state_size
        x, B, C = jnp.split(xbc, (self.d_inner, self.d_inner + g * N), -1)
        lead = xbc.shape[:-1]
        return (x.reshape(*lead, self.mamba_num_heads, self.mamba_head_dim),
                B.reshape(*lead, g, N), C.reshape(*lead, g, N))

    def _mamba_out(self, p, y, z):
        """y [..., h, P], z [..., h P] -> the mixer's output [..., d]: the
        gate first, then the RMSNorm over each group's channels, ``W_out``."""
        with device_scope(profiling.OP_MAMBA2_OUT):
            lead = z.shape[:-1]
            gated = (y.reshape(*lead, -1) * jax.nn.silu(z)).reshape(
                *lead, self.n_groups, -1)
            normed = rms_norm(
                gated, p["gate_norm"].reshape(self.n_groups, -1),
                self.layer_norm_epsilon)
            return self._mm(normed.reshape(*lead, -1), p["out_proj"])

    def mamba_mixer(self, p, x):
        """A Mamba-2 block's mixer over whole episodes from a reset: x [B,
        T, d] float32 -> [B, T, d]."""
        with device_scope(profiling.OP_MAMBA2):
            z, xbc, dt = self._mamba_in(p, x)
            with device_scope(profiling.OP_MAMBA2_CONV):
                xbc = jax.nn.silu(
                    p["conv_b"] + layers.causal_conv(p["conv_w"], xbc))
            heads, B, C = self._mamba_heads(xbc)
            with device_scope(profiling.OP_MAMBA2_SSD):
                y, _ = ssd.ssd_chunked(
                    heads, dt, -jnp.exp(p["A_log"]), B, C, p["D"],
                    self.chunk_size, self.state_dtype)
            return self._mamba_out(p, y, z)

    def _qkv(self, p, x):
        """x [B, T, d] float32 -> q [B, T, H, D], k, v [B, T, KV, D] in the
        compute type (no bias, no norm, no rotary embedding)."""
        u = rms_norm(x, p["norm"], self.layer_norm_epsilon)
        heads = lambda y: y.reshape(*y.shape[:-1], -1, self.head_dim)  # noqa: E731
        cd = self.compute_dtype
        return (heads(self._mm(u, p["wq"], cd)), heads(self._mm(u, p["wk"], cd)),
                heads(self._mm(u, p["wv"], cd)))

    def attention_mixer(self, p, x):
        """The attention block's mixer over whole episodes, causal: x [B, T,
        d] float32 -> [B, T, d]."""
        with device_scope(profiling.OP_ATTN_FULL):
            q, k, v = self._qkv(p, x)
            # the kernels hold a K/V head's query heads in one tile: 16 of
            # 128 lanes ran their backward out of fast memory, so each K/V
            # head is laid down twice and serves 8 (its gradient adds up)
            copies = max(1, q.shape[2] // k.shape[2] // KERNEL_QUERY_HEADS)
            out, _ = sparse_attention.attend_selected(
                q, jnp.repeat(k, copies, axis=2), jnp.repeat(v, copies, axis=2),
                None, 1.0 / math.sqrt(self.head_dim))
            return self._mm(out, p["wo"])

    def shared_expert(self, p, u):
        """u [N, d] float32, normed -> the shared expert's ``W2 relu(W1
        u)^2`` [N, d] float32: every token takes it, every chip computes it."""
        with device_scope(profiling.MOE_SHARED):
            hidden = jnp.square(jax.nn.relu(self._mm(u, p["shared_w1"])))
            return self._mm(hidden, p["shared_w2"])

    def experts_mixer(self, p, x):
        """An expert block's mixer: x [N, d] float32 -> (this chip's part of
        the routed experts' sum plus the shared expert [N, d], (tokens
        routed to each held expert, the chosen expert ids [N, k], the blocks
        of sorted rows the layer ran beyond its first))."""
        with device_scope(profiling.MOE):
            u = rms_norm(x, p["norm"], self.layer_norm_epsilon)
            routing = moe.route(
                u, p["router"], p["expert_bias"], self.num_experts_per_tok,
                self.norm_topk_prob, self.routed_scaling_factor)
            out, counted = moe.held_experts(
                u, routing, p, self.compute_dtype, self.expert_offset,
                self.n_routed_experts, EXPERT_ROWS_MARGIN)
            return out + self.shared_expert(p, u), counted

    # -- the rollout's decode step ---------------------------------------------
    def init_carry(self, batch: int) -> Carry:
        h, P, N = self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size
        kv_shape = (batch, self.max_positions,
                    self.num_key_value_heads * self.head_dim)
        f32 = jnp.float32
        # a buffer each: the step donates its state
        return Carry(
            pos=jnp.zeros((batch,), jnp.int32),
            mamba=tuple(
                (jnp.zeros((batch, h, P, N), self.state_dtype),
                 jnp.zeros((batch, self.conv_kernel - 1, self.conv_width), f32),
                 jnp.zeros((batch, h), f32))
                for kind in self.layer_kinds if kind == MAMBA),
            kv=tuple(
                tuple(jnp.zeros(kv_shape, self.compute_dtype) for _ in range(2))
                for kind in self.layer_kinds if kind == ATTENTION),
        )

    def carry_bytes(self) -> Tuple[int, ...]:
        """Bytes of carry an env, by kind: (the recurrence's states, the
        convs' tails, the K/V buffers, the position and the last step sizes)."""
        def kinds(carry):
            states, tails, steps = (
                [layer[i] for layer in carry.mamba] for i in range(3))
            return states, tails, carry.kv, (carry.pos, steps)

        return self._carry_bytes(kinds)

    def carry_gauges(self, carry: Carry) -> dict:
        """What the trainer reports of the carry at an update's end: its
        bytes an env by kind (a constant of the shapes), the largest ``|H|``
        of the recurrence's states (a decay near 1 over a long episode lets
        a state grow, and an overflow shows here before it shows in the
        loss) and the mean step size ``dt`` of the rollout's last position."""
        states = [jnp.max(jnp.abs(s.astype(jnp.float32))) for s, _, _ in carry.mamba]
        steps = [jnp.mean(dt) for _, _, dt in carry.mamba]
        zero = jnp.float32(0.0)
        return {
            "carry_bytes_per_env": jnp.asarray(self.carry_bytes(), jnp.float32),
            "ssm_state_absmax": jnp.max(jnp.stack(states)) if states else zero,
            "ssm_dt_mean": jnp.mean(jnp.stack(steps)) if steps else zero,
        }

    def epoch_stats(self, metrics: dict) -> dict:
        """An epoch's scalars from the step's metrics of this policy."""
        return {
            "ssm_state_absmax": float(metrics["ssm_state_absmax"]),
            "ssm_dt_mean": float(metrics["ssm_dt_mean"]),
            "carry_bytes_per_env": float(metrics["carry_bytes_per_env"].sum()),
            **moe.load_stats(metrics),
        }

    def step(self, params, obs, carry: Carry, fresh):
        pos, keep = sequence.decode_opening(carry.pos, fresh)
        rows = jnp.arange(obs.shape[0])
        x = self._embed(params, obs)
        mamba_in, kv_in = iter(carry.mamba), iter(carry.kv)
        mamba_out, kv_out = [], []
        for i, kind in enumerate(self.layer_kinds):
            p = params[self.layer_name(i)]
            if kind == MAMBA:
                with device_scope(profiling.OP_MAMBA2):
                    state, tail, _ = next(mamba_in)
                    state = state * keep[:, None, None, None].astype(state.dtype)
                    tail = tail * keep[:, None, None]
                    z, xbc, dt = self._mamba_in(p, x)
                    with device_scope(profiling.OP_MAMBA2_CONV):
                        taps = p["conv_w"]
                        conv, tail = layers.conv_step(
                            p["conv_b"] + taps[0] * xbc, taps, xbc, tail)
                    heads, Bm, Cm = self._mamba_heads(jax.nn.silu(conv))
                    with device_scope(profiling.OP_MAMBA2_SSD):
                        state, y = ssd.ssd_step(
                            state, heads, dt, -jnp.exp(p["A_log"]), Bm, Cm, p["D"])
                    mixed = self._mamba_out(p, y, z)
                    mamba_out.append((state, tail, dt))
            elif kind == ATTENTION:
                with device_scope(profiling.OP_ATTN_FULL):
                    caches = next(kv_in)
                    mixed, caches = self._decode_attention(
                        p, self._qkv(p, x[:, None, :]), caches, rows, pos)
                    kv_out.append(caches)
            else:
                mixed, _ = self.experts_mixer(p, x)
            x = x + mixed
        return self._head(params, x), Carry(
            pos=pos + 1, mamba=tuple(mamba_out), kv=tuple(kv_out))

    # -- the learner's unroll ----------------------------------------------------
    def _layer_unroll(self, i: int, p, x):
        """One block over whole episodes: x [B, T, d] float32 -> (the same,
        None or what an expert block counts)."""
        kind = self.layer_kinds[i]
        if kind == EXPERTS:
            B, T, d = x.shape
            mixed, routed = self.experts_mixer(p, x.reshape(B * T, d))
            return x + mixed.reshape(B, T, d), routed
        mixer = self.mamba_mixer if kind == MAMBA else self.attention_mixer
        return x + mixer(p, x), None

    def unroll(self, params, tokens, with_routes: bool = False):
        """Whole episodes from a reset: ``tokens`` [B, T] int32 ->
        (PolicyValue with logits [B, T, A] and value [B, T], aux). ``aux``
        counts the tokens routed to each held expert of each expert block
        (``moe_tokens_per_expert``) and the blocks of sorted rows each ran
        beyond its first (``moe_overflow_blocks``) and, asked, names every
        token's chosen experts (``routes`` [expert blocks, B, T, k])."""
        routed = moe.RoutedLayers(*tokens.shape)
        # a block's weights, and the head's, are tied to their input (see
        # ``sequence.with_its_input``: without it this step does not fit the chip)
        return self._unroll(
            params, tokens, self._layer_unroll, routed.take,
            lambda: routed.aux(with_routes), tie=sequence.with_its_input)
