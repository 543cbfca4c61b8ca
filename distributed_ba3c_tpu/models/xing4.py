"""Xing4.0-29B-A4B (``model_type xing4_0``) as a token-sequence policy: a
DeepSeek-V3-shaped decoder (latent attention, sigmoid-routed SwiGLU experts
beside a shared one, leading dense layers, YaRN) whose residual path is
manifold-constrained hyper-connections over 4 streams; one chip holding an
eighth of every expert layer's routed experts and an eighth of every
attention's heads.

Published (XingChen-AGI/Xing4.0-29B-A4B ``config.json``): hidden 3584, 40
layers (the first ``first_k_dense_replace`` = 2 with a dense SwiGLU of 9216,
the rest with 64 routed experts of 1024, 4 a token, beside 1 shared expert),
32 heads, RMSNorm (eps 1e-6), an untied head over 131,072 ids. A token's
residual is ``hc_mult`` = 4 streams ``X`` (the embedding replicated into
each; their sum goes to the final norm), and a layer is two sub-blocks, each
under ``ops/hyper_connection.py``'s three mappings of the token's own
streams:

    u = sum_j H_pre[j] X[j];   y = f(RMSNorm(u));
    X[i] <- sum_j H_res[i, j] X[j] + H_post[i] y

``H_res`` the Sinkhorn projection (``hc_sinkhorn_iters`` 20, ``hc_eps``
1e-6) of logits clamped to ``mhc_h_res_clamp_min/max``. ``f`` is

- latent attention (MLA, arXiv:2405.04434): ``c_q = RMSNorm(z W_qa)`` (768),
  ``[q_nope | q_rope]_h = (c_q W_qb)_h`` (128 + 64 a head); ``[c | k_r] = z
  W_kva`` (512 + 64, ONE row a position for every head), ``c <-
  RMSNorm(c)``; RoPE (rotate-half, YaRN's frequencies) on ``q_rope`` and
  ``k_r``; ``[k_nope | v]_h = (c W_kvb)_h`` (128 + 128); scores ``s_att
  (q_nope . k_nope + q_rope . k_r)``, causal softmax, ``W_o``; ``s_att =
  192^-0.5 m^2``, ``m = 0.1 ln(64) + 1``. **Two forms of the one layer**:
  the unroll expands ``k_nope`` and ``v`` from the latent (``op_mla/expand``);
  the decode step never forms them: with ``W_kvb,h = [W^K_h | W^V_h]`` it
  attends over the cache's latent rows with ``q~_h = W^K_h q_nope_h`` (512)
  and applies ``(W^V_h)^T`` to the attended latent (``op_mla/absorb``).
- then the dense SwiGLU (published layers 0-1), or ``ops/moe.py:route``
  (sigmoid scores over all 64, a bias that only chooses, the 4 chosen scores
  normalised and scaled by 2) with ``expert_ffn`` in its three-matrix form,
  plus the shared expert, a dense SwiGLU of 1024 every token takes.

The widths are the defaults below and are never cut. What IS cut is how
much one chip holds (``benchmark/configs/xing4-29b-a4b-recall-fused-a2c.
json`` has the arithmetic and what is assumed beyond the config): which
published layers (``layer_ids``), how many of every attention's heads
(``heads_held``: ``W_qb``'s and ``W_kvb``'s columns and
``W_o``'s rows of those heads; both down-projections and their norms whole,
since every head reads the same latents), how many routed experts
(``experts_held`` from ``expert_offset``) and how many vocabulary ids
(``num_actions``). ``--model_cut`` names a cut (:data:`CUTS`).

**The carry** (the eighth kind, docs/policy_protocol.md): ``pos``, then a
layer's latent rows ``[B, P, 640]`` bfloat16: ``[c (512) | k_r (64) | 64
zeros]``, the 576 numbers padded to whole lanes so that
``ops/decode_attention.py:decode_attend`` runs unchanged with the buffer as
its keys AND as its values (one group of the held query heads, 640 lanes; the
attended latent is the output's first 512 lanes). 1,280 bytes a row where
the numbers are 1,152; the kernel fetches a row twice (as key, as value).
Both are later work's (PERF.md section 7).

Precision: float32 parameters, residual streams, mHC mappings (their
projection at the highest precision), norms, router, scores, softmax and
heads' outputs; bfloat16 matrix operands with float32 accumulation
(``models/layers.py:mm``); the latent cache bfloat16. Multi-token prediction
(``num_nextn_predict_layers`` 1) is no part of this policy: a training-time
likelihood term the A2C step has no place for.
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
from distributed_ba3c_tpu.ops import decode_attention, moe
from distributed_ba3c_tpu.ops import hyper_connection as hc
from distributed_ba3c_tpu.ops.pallas_tpu import LANE
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

DENSE, EXPERTS = "dense", "experts"
ATTN, FFN = "attn", "ffn"  # a layer's two sub-blocks: the prefix of their leaves
#: spread of the seeded ``e_score_correction_bias``: small beside the gaps
#: between router scores, as a bias that exists to even the load out is
EXPERT_BIAS_SCALE = 0.01
#: the seeded start of a sub-block's mappings. The paper starts the three
#: gates ``a`` at 0.01 and trains them up; seeded weights are never trained,
#: and at 0.01 the mappings are the same for every token to four places (20
#: Sinkhorn iterations and 5 then agree to 2e-5: nothing a comparison could
#: hold the projection to). So the start is where the token's own streams
#: move the mappings as a trained model's do: ``a_pre`` = ``a_post`` = 1,
#: ``a_res`` = 2; ``b_res`` twice the identity (``H_res`` starts with half
#: its weight on the diagonal), ``b_pre`` so that ``H_pre`` starts round
#: ``1/n``, ``b_post`` 0 (``H_post`` round 1)
HC_GATES = (1.0, 1.0, 2.0)
HC_RES_DIAGONAL = 2.0
HC_LEAVES = ("phi", "alpha", "b_pre", "b_post", "b_res")
#: queries a block of the unroll's attention (plain XLA): the float32
#: scores of a block against the keys up to its end, not ``T x T`` a head
QUERY_BLOCK = 512
#: room in a block of the experts' sorted rows over an even router's share
#: (``ops/moe.py:block_rows``): a capacity factor of 2, as ``nemotron-h``
#: chose it for the same seeded sigmoid router over a ``silu`` residual
#: (PERF.md section 6, PR 44: 0.57-1.83 of the even share by the seed)
EXPERT_ROWS_MARGIN = 1.0
#: ``--model_cut``: what one chip holds, the default first.
#: ``ep8-heads8-vocab8``: one of 8 chips that share each layer (8 of 64
#: routed experts; 4 of 32 heads; the vocabulary slice is the env's action
#: space), published layers 0 and 2-5 (one leading dense layer, four expert
#: layers). With 8 heads (four ways) the step compiled to 16.2 GB for a
#: described v5e at a learner chunk of one env: the configuration's file has
#: the ladder. ``tiny``: every mechanism
#: at a size a CPU test runs, 2 of 16 experts and 2 of 8 heads, a YaRN whose
#: original length an episode of 24 crosses.
CUTS = {
    "ep8-heads8-vocab8": {},
    "tiny": dict(
        hidden_size=32, intermediate_size=48, moe_intermediate_size=24,
        num_attention_heads=8, heads_held=2, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        n_routed_experts=16, experts_held=2, num_experts_per_tok=3,
        rope_theta=100.0, rope_factor=4.0, rope_original_positions=8,
        layer_ids=(0, 2, 3),
    ),
}

cut_fields = functools.partial(sequence.cut_fields, CUTS)


class Carry(NamedTuple):
    """What decoding carries from one position to the next, an env a row.
    ``fresh`` resets ``pos``; the latent rows keep their bytes and are
    masked by the position (nothing at or past it is read)."""

    pos: jax.Array   # [B] int32 position in the episode
    latent: Tuple    # per layer [B, P, row_width]: ``[c | k_r | zeros]`` a
                     # position, after the latent's norm and the RoPE


@dataclasses.dataclass(frozen=True)
class Xing4(sequence.SequencePolicy):
    num_actions: int = 16384            # vocabulary ids held (of 131,072)
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_attention_heads: int = 32       # as published; ``heads_held`` here
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64          # the router's width, as published
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    first_k_dense_replace: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # ``rope_scaling`` (type yarn)
    rope_factor: float = 64.0
    rope_original_positions: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0    # ``mscale`` equal to it: cos, sin x 1
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # -- the chip's share ---------------------------------------------------
    layer_ids: Tuple[int, ...] = (0, 2, 3, 4, 5)
    heads_held: int = 4
    experts_held: int = 8
    expert_offset: int = 0
    # -- how it is run ------------------------------------------------------
    max_positions: int = 2048           # latent rows: the episode length
    compute_dtype: jnp.dtype = jnp.bfloat16
    stream_dtype: jnp.dtype = jnp.float32  # the residual streams and their
                                           # mappings (a control: bfloat16)

    head_table = "head"
    #: the router and what the mappings are computed from stay float32 (the
    #: choosing bias, the gates and the other biases are vectors)
    float32_leaves = ("router",) + tuple(
        f"{sub}_hc_{leaf}" for sub in (ATTN, FFN) for leaf in ("phi", "b_res"))
    final_norm_eps = property(lambda self: self.rms_norm_eps)

    def __post_init__(self):
        assert 0 < self.heads_held <= self.num_attention_heads
        assert 0 < self.experts_held <= self.n_routed_experts
        assert self.qk_rope_head_dim % 2 == 0

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(DENSE if i < self.first_k_dense_replace else EXPERTS
                     for i in self.layer_ids)

    @property
    def row_width(self) -> int:
        """Lanes of a cache row: the latent and the shared key, to whole lanes."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // LANE) * LANE

    @property
    def attention_scale(self) -> float:
        """``(nope + rope)^-0.5`` times YaRN's correction of it."""
        return layers.yarn_attention_scale(
            self.rope_factor, self.rope_mscale_all_dim) / math.sqrt(
                self.qk_nope_head_dim + self.qk_rope_head_dim)

    def rope_frequencies(self):
        return layers.yarn_inv_freq(
            self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
            self.rope_original_positions, self.rope_beta_fast,
            self.rope_beta_slow)

    # -- parameters -----------------------------------------------------------
    def _init_layer(self, i: int, init):
        """Held layer ``i``'s seeded leaves: normal kernels scaled by
        1/sqrt(fan_in), unit gains, the mappings' start of :data:`HC_GATES`.
        ``expert_bias`` only chooses, so its gradient is identically zero
        and Adam never moves it."""
        d, n = self.hidden_size, self.hc_mult
        nope, rot, v = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                        self.v_head_dim)
        rq, rkv, H = self.q_lora_rank, self.kv_lora_rank, self.heads_held
        normal, ones = init.normal, init.ones
        layer = {}
        for sub in (ATTN, FFN):
            layer.update({
                f"{sub}_norm": ones(d),
                f"{sub}_hc_phi": normal((2 * n + n * n, n * d), n * d),
                f"{sub}_hc_alpha": jnp.asarray(HC_GATES, jnp.float32),
                f"{sub}_hc_b_pre": jnp.full((n,), -math.log(n - 1.0), jnp.float32),
                f"{sub}_hc_b_post": init.zeros(n),
                f"{sub}_hc_b_res": HC_RES_DIAGONAL * jnp.eye(n, dtype=jnp.float32),
            })
        layer.update(
            wq_a=normal((d, rq), d), q_norm=ones(rq),
            wq_b=normal((rq, H * (nope + rot)), rq),
            wkv_a=normal((d, rkv + rot), d), kv_norm=ones(rkv),
            wkv_b=normal((rkv, H * (nope + v)), rkv),
            wo=normal((H * v, d), H * v))
        if self.layer_kinds[i] == DENSE:
            f = self.intermediate_size
            layer.update(w1=normal((d, f), d), w3=normal((d, f), d),
                         w2=normal((f, d), f))
        else:
            e, fe = self.experts_held, self.moe_intermediate_size
            fs = fe * self.n_shared_experts
            layer.update(
                router=normal((d, self.n_routed_experts), d),
                expert_bias=EXPERT_BIAS_SCALE * jax.random.normal(
                    next(init.keys), (self.n_routed_experts,), jnp.float32),
                w1=normal((e, d, fe), d), w3=normal((e, d, fe), d),
                w2=normal((e, fe, d), fe),
                shared_w1=normal((d, fs), d), shared_w3=normal((d, fs), d),
                shared_w2=normal((fs, d), fs))
        return layer

    # -- the residual path -------------------------------------------------------
    def streams_in(self, x):
        """The embedding's rows [..., d] as every stream's start: a tuple of
        ``n`` arrays (hyper-connections' own start)."""
        return (x.astype(self.stream_dtype),) * self.hc_mult

    def streams_out(self, x):
        """The streams' sum [..., d] float32: what the final norm reads."""
        return sum(s.astype(jnp.float32) for s in x)

    def _hyper(self, p, sub: str, x, f):
        """One sub-block under its mappings: ``x`` n streams [N, d], ``f(u
        [N, d] float32) -> (y [N, d], extra)`` -> (the streams after it,
        extra, the mixing matrices [n, n, N])."""
        leaves = {leaf: p[f"{sub}_hc_{leaf}"] for leaf in HC_LEAVES}
        with device_scope(profiling.HYPER_CONN):
            with device_scope(profiling.HYPER_CONN_MAPPINGS):
                h = hc.mappings(
                    x, leaves, self.hc_sinkhorn_iters, self.hc_eps,
                    (self.mhc_h_res_clamp_min, self.mhc_h_res_clamp_max),
                    self.stream_dtype)
            with device_scope(profiling.HYPER_CONN_MIX):
                u = hc.read(x, h).astype(jnp.float32)
        y, extra = f(u)
        with device_scope(profiling.HYPER_CONN):
            with device_scope(profiling.HYPER_CONN_MIX):
                x = hc.write(x, h, y.astype(self.stream_dtype))
        return x, extra, h.res

    # -- pieces shared by the decode step and the unroll -----------------------
    def _queries(self, p, z, positions):
        """z [B, T, d] float32, normed -> (q_nope [B, T, H, nope], q_rope
        [B, T, H, rope]) float32, the second rotated at ``positions``."""
        with device_scope(profiling.OP_MLA_Q):
            c_q = rms_norm(self._mm(z, p["wq_a"]), p["q_norm"], self.rms_norm_eps)
            q = self._mm(c_q, p["wq_b"]).reshape(
                *z.shape[:-1], self.heads_held, -1)
            q_nope, q_rope = jnp.split(q, (self.qk_nope_head_dim,), -1)
            return q_nope, rope(q_rope, positions, self.rope_theta,
                                self.rope_frequencies())

    def _latent(self, p, z, positions):
        """z [B, T, d] float32, normed -> (the latent c [B, T, 512] through
        its norm, the shared key k_r [B, T, 64] rotated), float32: what a
        cache row holds."""
        with device_scope(profiling.OP_MLA_KV_LATENT):
            c, k_r = jnp.split(
                self._mm(z, p["wkv_a"]), (self.kv_lora_rank,), -1)
            k_r = rope(k_r[..., None, :], positions, self.rope_theta,
                       self.rope_frequencies())[..., 0, :]
            return rms_norm(c, p["kv_norm"], self.rms_norm_eps), k_r

    def _kv_up(self, p):
        """``W_kvb`` as (``W^K`` [512, H, nope], ``W^V`` [512, H, v]) in the
        compute type."""
        w = p["wkv_b"].astype(self.compute_dtype).reshape(
            self.kv_lora_rank, self.heads_held, -1)
        return jnp.split(w, (self.qk_nope_head_dim,), -1)

    def _attend_blocks(self, q, k, v):
        """Causal attention over whole episodes, :data:`QUERY_BLOCK` queries
        at a time against the keys up to the block's end, each block
        recomputed in the backward: q, k [B, T, H, nope + rope], v [B, T, H,
        v] -> [B, T, H * v] float32."""
        T = q.shape[1]
        block = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

        @jax.checkpoint
        def one(q, k, v, mask):
            return layers.attend(q, k, v, mask, self.compute_dtype,
                                 self.attention_scale)

        out = []
        for lo in range(0, T, block):
            hi = lo + block
            mask = (jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None])[None]
            out.append(one(q[:, lo:hi], k[:, :hi], v[:, :hi], mask))
        return jnp.concatenate(out, axis=1)

    def attention(self, p, u):
        """The attention sub-block over whole episodes from a reset, the
        EXPANDED form: u [B, T, d] float32 -> [B, T, d]."""
        cd = self.compute_dtype
        with device_scope(profiling.OP_MLA):
            z = rms_norm(u, p["attn_norm"], self.rms_norm_eps)
            positions = jnp.arange(u.shape[1])[None, :]
            q_nope, q_rope = self._queries(p, z, positions)
            c, k_r = self._latent(p, z, positions)
            with device_scope(profiling.OP_MLA_EXPAND):
                w_k, w_v = self._kv_up(p)
                k_nope = jnp.einsum("btr,rhn->bthn", c.astype(cd), w_k,
                                    preferred_element_type=cd)
                v = jnp.einsum("btr,rhv->bthv", c.astype(cd), w_v,
                               preferred_element_type=cd)
            with device_scope(profiling.OP_MLA_ATTEND):
                q = jnp.concatenate([q_nope, q_rope], -1).astype(cd)
                k = jnp.concatenate([k_nope, jnp.broadcast_to(
                    k_r[:, :, None, :].astype(cd),
                    (*k_nope.shape[:-1], k_r.shape[-1]))], -1)
                out = self._attend_blocks(q, k, v)
            with device_scope(profiling.OP_MLA_OUT):
                return self._mm(out, p["wo"])

    def feed_forward(self, kind: str, p, u):
        """The feed-forward sub-block: u [N, d] float32 -> (out [N, d], None
        or what an expert layer counts: the tokens routed to each held
        expert, the chosen expert ids [N, k], the blocks of sorted rows the
        layer ran beyond its first)."""
        cd = self.compute_dtype
        if kind == DENSE:
            with device_scope(profiling.FFN_DENSE):
                z = rms_norm(u, p["ffn_norm"], self.rms_norm_eps)
                return layers.swiglu(z, p["w1"], p["w3"], p["w2"], cd), None
        with device_scope(profiling.MOE):
            z = rms_norm(u, p["ffn_norm"], self.rms_norm_eps)
            routing = moe.route(
                z, p["router"], p["expert_bias"], self.num_experts_per_tok,
                self.norm_topk_prob, self.routed_scaling_factor)
            out, counted = moe.held_experts(
                z, routing, p, cd, self.expert_offset, self.n_routed_experts,
                EXPERT_ROWS_MARGIN)
            with device_scope(profiling.MOE_SHARED):
                # every token takes it, every chip computes it
                shared = layers.swiglu(
                    z, p["shared_w1"], p["shared_w3"], p["shared_w2"], cd)
            return out + shared, counted

    # -- the rollout's decode step ---------------------------------------------
    def init_carry(self, batch: int) -> Carry:
        shape = (batch, self.max_positions, self.row_width)
        # a buffer each: the step donates its state
        return Carry(
            pos=jnp.zeros((batch,), jnp.int32),
            latent=tuple(jnp.zeros(shape, self.compute_dtype)
                         for _ in self.layer_ids))

    def carry_bytes(self) -> Tuple[int, ...]:
        """Bytes of carry an env, by kind: (the latent rows' buffers, their
        padding to whole lanes included; the position)."""
        return self._carry_bytes(lambda carry: (carry.latent, carry.pos))

    def carry_gauges(self, carry: Carry) -> dict:
        """What the trainer reports of the carry at an update's end: its
        bytes an env by kind (a constant of the shapes)."""
        del carry
        return {"carry_bytes_per_env": jnp.asarray(
            self.carry_bytes(), jnp.float32)}

    def epoch_stats(self, metrics: dict) -> dict:
        """An epoch's scalars from the step's metrics of this policy."""
        gap_sum, mappings = (float(x) for x in metrics["mhc_doubly_stochastic_gap"])
        return {
            "carry_bytes_per_env": float(metrics["carry_bytes_per_env"].sum()),
            "mhc_doubly_stochastic_gap": gap_sum / max(mappings, 1.0),
            **moe.load_stats(metrics),
        }

    def attention_step(self, p, u, cache, rows, pos):
        """The attention sub-block one position an env, the ABSORBED form:
        this position's row written into ``cache`` [B, P, row_width], the
        queries over the latent rows up to it; no key or value of a head is
        formed. u [B, d] float32 -> ([B, d] float32, the cache)."""
        cd, B, r = self.compute_dtype, u.shape[0], self.kv_lora_rank
        with device_scope(profiling.OP_MLA):
            z = rms_norm(u, p["attn_norm"], self.rms_norm_eps)[:, None, :]
            q_nope, q_rope = self._queries(p, z, pos[:, None])
            c, k_r = self._latent(p, z, pos[:, None])
            pad = self.row_width - r - k_r.shape[-1]
            with device_scope(profiling.OP_MLA_KV_LATENT):
                row = jnp.pad(jnp.concatenate([c, k_r], -1)[:, 0],
                              ((0, 0), (0, pad))).astype(cd)
                cache = sequence.write_row(rows, cache, pos, row)
            with device_scope(profiling.OP_MLA_ABSORB):
                w_k, w_v = self._kv_up(p)
                q_latent = jnp.einsum(
                    "bhn,rhn->bhr", q_nope[:, 0].astype(cd), w_k,
                    preferred_element_type=jnp.float32)
            with device_scope(profiling.OP_MLA_ATTEND):
                q = jnp.pad(jnp.concatenate([q_latent, q_rope[:, 0]], -1),
                            ((0, 0), (0, 0), (0, pad))).astype(cd)
                # the buffer is the keys and the values: the attended
                # latent is the first ``r`` lanes of what comes back
                attended = decode_attention.decode_attend(
                    q, cache, cache, pos + 1, self.attention_scale)[..., :r]
            with device_scope(profiling.OP_MLA_ABSORB):
                out = jnp.einsum("bhr,rhv->bhv", attended.astype(cd), w_v,
                                 preferred_element_type=jnp.float32)
            with device_scope(profiling.OP_MLA_OUT):
                return self._mm(out.reshape(B, -1), p["wo"]), cache

    def step(self, params, obs, carry: Carry, fresh):
        pos, _ = sequence.decode_opening(carry.pos, fresh)
        rows = jnp.arange(obs.shape[0])
        x = self.streams_in(self._embed(params, obs))
        latent = []
        for i, (kind, cache) in enumerate(
                zip(self.layer_kinds, carry.latent, strict=True)):
            p = params[self.layer_name(i)]
            x, cache, _ = self._hyper(
                p, ATTN, x,
                lambda u, p=p, cache=cache: self.attention_step(
                    p, u, cache, rows, pos))
            latent.append(cache)
            x, _, _ = self._hyper(
                p, FFN, x,
                lambda u, p=p, kind=kind: self.feed_forward(kind, p, u))
        return self._head(params, self.streams_out(x)), Carry(
            pos=pos + 1, latent=tuple(latent))

    # -- the learner's unroll ----------------------------------------------------
    def _layer_unroll(self, i: int, p, x):
        """One layer over whole episodes: x n streams [B, T, d] -> (the same,
        (None or what an expert layer counts, the sum over its tokens and its
        two sub-blocks of ``H_res``'s distance from doubly stochastic))."""
        B, T, d = x[0].shape
        x, _, res_a = self._hyper(
            p, ATTN, tuple(s.reshape(B * T, d) for s in x),
            lambda u: (self.attention(p, u.reshape(B, T, d)).reshape(
                B * T, d), None))
        x, routed, res_f = self._hyper(
            p, FFN, x, lambda u: self.feed_forward(self.layer_kinds[i], p, u))
        with device_scope(profiling.HYPER_CONN):
            gap = sum(jnp.sum(hc.doubly_stochastic_gap(
                jax.lax.stop_gradient(res).astype(jnp.float32)))
                for res in (res_a, res_f))
        return tuple(s.reshape(B, T, d) for s in x), (routed, gap)

    def unroll(self, params, tokens, with_routes: bool = False):
        """Whole episodes from a reset: ``tokens`` [B, T] int32 ->
        (PolicyValue with logits [B, T, A] and value [B, T], aux). ``aux``
        counts the tokens routed to each held expert of each expert layer
        (``moe_tokens_per_expert``), the blocks of sorted rows each ran
        beyond its first (``moe_overflow_blocks``) and how far the mixing
        matrices were from doubly stochastic (``mhc_doubly_stochastic_gap``
        [2]: the sum over tokens and sub-blocks of the largest ``|row or
        column sum - 1|``, and how many were summed: the mean is the first
        over the second) and, asked, names every token's chosen experts
        (``routes`` [expert layers, B, T, k])."""
        routed = moe.RoutedLayers(*tokens.shape)
        gaps = []

        def took(second):
            routed.take(second[0])
            gaps.append(second[1])

        def aux():
            mappings = 2.0 * len(gaps) * tokens.size
            return dict(
                routed.aux(with_routes),
                mhc_doubly_stochastic_gap=jnp.stack(
                    [sum(gaps), jnp.float32(mappings)]))

        # a layer's weights, and the head's, are tied to their input
        # (``sequence.with_its_input``: the step does not fit without)
        return self._unroll(params, tokens, self._layer_unroll, took, aux,
                            tie=sequence.with_its_input)
