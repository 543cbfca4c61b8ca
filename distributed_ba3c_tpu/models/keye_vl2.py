"""Keye-VL-2.0-30B-A3B's language model (``model_type KeyeVL2``) as a
token-sequence policy: 128 routed experts top-8 beside grouped-query
attention that reads only the keys a learned indexer selects.

Published (Kwai-Keye/Keye-VL-2.0-30B-A3B ``config.json``): 48 layers of one
kind, hidden 2048, 32 query heads over 4 key/value heads of 128, RMSNorm
(eps 1e-6), RoPE theta 1e7, 128 experts of width 768 with 8 a token
(``norm_topk_prob``), no shared expert, no dense layer, an untied head over
151,936 ids, and ``sa_config``: an indexer of 16 heads of 64 over one key
head that keeps ``topk`` 2,048 keys a query. For position ``t`` with
residual ``x_t``, ``z = RMSNorm(x_t)``:

- **main attention**: ``q = RoPE(RMSNorm_head(W_q z))`` [32, 128], ``k =
  RoPE(RMSNorm_head(W_k z))`` [4, 128], ``v = W_v z``; one K/V head serves
  8 query heads; scale ``128^-0.5``; no bias;
- **indexer** (DeepSeek-V3.2-Exp's, reading ``stop_gradient(z)``): ``q^I =
  RoPE(W_q^I z)`` [16, 64], ``k^I = RoPE(LayerNorm(W_k^I z))`` [64], ``w =
  W_w z * 16^-0.5 * 64^-0.5``; ``I_{t,s} = sum_j w_{t,j} ReLU(q^I_{t,j} .
  k^I_s)`` for ``s <= t``, float32;
- **selection**: ``S_t`` = the ``min(t + 1, 2048)`` positions ``s <= t`` of
  largest ``I_{t,s}``, exactly, a tie to the lower position; up to position
  2,047 that is every past position;
- ``o_t = W_o concat_h softmax_{s in S_t}(q_h . k_s * scale) v_s``; ``x_t +=
  o_t``; then ``z' = RMSNorm(x_t)`` and the experts of ``ops/moe.py``
  (``softmax`` scoring, no bias): ``x_t += sum_i w_i W2_i (silu(W1_i z') *
  W3_i z')`` over the chosen ``i`` held here;
- final RMSNorm, an untied head over the ids held here, and the trainer's
  float32 value head;
- **the indexer's loss**: ``L_I = mean_t KL(p_t || softmax_{s in S_t}
  I_{t,s})``, ``p_t`` the main attention's probabilities summed over the 32
  heads and normalised over ``S_t``, under ``stop_gradient``. The selection
  is no function a gradient passes: the indexer's leaves (``idx_*``) are
  trained by ``L_I`` alone and every other leaf by the A2C loss alone. The
  unroll hands ``indexer_loss_coef * L_I``, one a layer, to the trainer
  under ``policy.LOSS_TERMS``.

The decode step scores the live rows of the indexer's key cache, takes the
exact top-k as a mask (``ops/topk_select.py``: the unroll's own function, so
the two choose alike) and attends over its K/V buffers under that mask,
where they lie and up to the position: ``ops/decode_attention.py`` with
``length = pos + 1`` and the selection as ``kept``. On a TPU at whole-lane
widths that is a Pallas kernel that fetches the blocks of 512 rows that hold
a live row (over an episode from a reset 56 % of the buffers' rows) and
masks inside them; anywhere else ``layers.attend`` over the whole buffers
under ``live & kept``. It does NOT fetch the selected rows: a row is 1 KB,
so from position 2,048 on they are 262,144 separate fetches a decode step
(16 envs x 2,048 rows x K and V x 4 layers); gathered by XLA into ``[B,
2048, 512]`` copies they cost 3.68 ms of a 5.05 ms decode step (73 GB/s; the
top-k's sort another 0.14 ms; my chip runs, PR 34). Nor can the selection
skip a block: past the top-k half or more of the live rows are kept, so no
block of 128 rows or more is empty. The unroll takes the indexer in blocks
of ``q_chunk_size`` 512 queries, each over the keys its mask can reach (its
scores, the exact top-k as a mask, ``ops/topk_select.py``: no sort), and the
main attention of a layer in one call under the whole selection
(``ops/sparse_attention.py``): on a TPU at whole-lane widths blocked Pallas
kernels that keep a tile's scores in fast memory, visit the tiles up to the
diagonal and are differentiated from each row's log-sum-exp; anywhere else the
masked-dense form. ``L_I`` reads the main attention's probabilities summed
over the heads, which the same call hands over. The selection saves no
product: past the top-k no tile is empty of selected keys.

The widths are the defaults below and are never cut. What IS cut is how
much one chip holds (``benchmark/configs/keye-vl2-30b-a3b-recall-fused-
a2c.json``): which published layers (``layer_ids``), how many experts of
each (``experts_held`` from ``expert_offset``) and how many vocabulary ids
(``num_actions``). ``--model_cut`` names such a cut (:data:`CUTS`).

Precision: float32 parameters, residual stream, norms, router, softmaxes,
indexer scores and heads' outputs; bfloat16 matrix operands with float32
accumulation; the K/V and indexer-key caches bfloat16 (the published
indexer cache is float8, which a v5e has not). The policy protocol is
models/policy.py's.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ba3c_tpu.models import sequence
from distributed_ba3c_tpu.models.layers import layer_norm, rms_norm, rope
from distributed_ba3c_tpu.models.policy import LOSS_TERMS
from distributed_ba3c_tpu.ops import (
    decode_attention, moe, sparse_attention, ssm, topk_select)
from distributed_ba3c_tpu.ops.topk_select import select_mask
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

#: the leaves of a layer that only ``L_I`` trains
INDEXER_LEAVES = ("idx_wq", "idx_wk", "idx_k_norm", "idx_k_norm_b", "idx_ww")
#: ``--model_cut``: what one chip holds, the default first. ``chip-share-8``:
#: one of 8 chips that share each layer (16 of 128 experts; the vocabulary
#: slice is the env's action space), published layers 0-3. ``tiny``: every
#: mechanism at a size a CPU test runs (a top-k of 8, blocks of 8 queries).
CUTS = {
    "chip-share-8": {},
    "tiny": dict(
        hidden_size=64, moe_intermediate_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_experts=16,
        num_experts_per_tok=2, experts_held=2, indexer_num_heads=2,
        indexer_head_dim=8, index_topk=8, q_chunk_size=8, layer_ids=(0, 1),
    ),
}


cut_fields = functools.partial(sequence.cut_fields, CUTS)


class Carry(NamedTuple):
    """What decoding carries from one position to the next, an env a row.
    ``fresh`` resets ``pos``; the buffers keep their bytes and are masked by
    the position (nothing at or past it is read)."""

    pos: jax.Array      # [B] int32 position in the episode
    kv: Tuple           # per layer (k, v), each [B, P, KV * D]: a position's
                        # K/V heads side by side in one row of whole lanes;
                        # attended over at the selected rows only
    index_keys: Tuple   # per layer the indexer's keys [B, P, Di]: read whole
                        # (under the mask of the position) every step


@dataclasses.dataclass(frozen=True)
class KeyeVL2(sequence.SequencePolicy):
    num_actions: int = 18992            # vocabulary ids held (of 151,936)
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    index_topk: int = 2048
    q_chunk_size: int = 512             # queries a block of the unroll takes
    indexer_loss_coef: float = 1.0
    # -- the chip's share ---------------------------------------------------
    layer_ids: Tuple[int, ...] = (0, 1, 2, 3)
    experts_held: int = 16
    expert_offset: int = 0
    # -- how it is run ------------------------------------------------------
    max_positions: int = 4096           # cache rows: the episode length
    compute_dtype: jnp.dtype = jnp.bfloat16

    head_table = "head"
    #: the router stays float32
    float32_leaves = ("router",)
    final_norm_eps = property(lambda self: self.rms_norm_eps)

    def __post_init__(self):
        assert self.num_attention_heads % self.num_key_value_heads == 0
        assert 0 < self.experts_held <= self.num_experts

    # -- parameters -----------------------------------------------------------
    def _init_layer(self, i: int, init):
        """A held layer's seeded leaves (every layer is of one kind): normal
        kernels scaled by 1/sqrt(fan_in), unit gains, a zero bias."""
        d, fe, D = self.hidden_size, self.moe_intermediate_size, self.head_dim
        hq, hkv = self.num_attention_heads * D, self.num_key_value_heads * D
        hi, di = self.indexer_num_heads, self.indexer_head_dim
        e = self.experts_held
        normal, ones = init.normal, init.ones
        return dict(
            attn_norm=ones(d), ffn_norm=ones(d),
            wq=normal((d, hq), d), wk=normal((d, hkv), d),
            wv=normal((d, hkv), d), wo=normal((hq, d), hq),
            q_norm=ones(D), k_norm=ones(D),
            idx_wq=normal((d, hi * di), d), idx_wk=normal((d, di), d),
            idx_k_norm=ones(di), idx_k_norm_b=init.zeros(di),
            idx_ww=normal((d, hi), d),
            router=normal((d, self.num_experts), d),
            w1=normal((e, d, fe), d), w3=normal((e, d, fe), d),
            w2=normal((e, fe, d), fe))

    # -- pieces shared by the decode step and the unroll -----------------------
    def _qkv(self, p, z, positions):
        """z [B, T, d] -> q [B, T, H, D], k, v [B, T, KV, D] in the compute
        type: per-head RMSNorm on q and k, then RoPE at ``positions``."""
        D, eps, cd = self.head_dim, self.rms_norm_eps, self.compute_dtype
        heads = lambda w: self._mm(z, w).reshape(*z.shape[:-1], -1, D)  # noqa: E731
        q = rope(rms_norm(heads(p["wq"]), p["q_norm"], eps), positions, self.rope_theta)
        k = rope(rms_norm(heads(p["wk"]), p["k_norm"], eps), positions, self.rope_theta)
        return q.astype(cd), k.astype(cd), heads(p["wv"]).astype(cd)

    def _index_qkw(self, p, z, positions):
        """The indexer's side of ``z`` [B, T, d], which it reads under
        ``stop_gradient``: (q^I [B, T, Hi, Di], k^I [B, T, Di], both in the
        compute type; the heads' weights [B, T, Hi] float32, scaled)."""
        z = jax.lax.stop_gradient(z)
        hi, di, cd = self.indexer_num_heads, self.indexer_head_dim, self.compute_dtype
        q = rope(self._mm(z, p["idx_wq"]).reshape(*z.shape[:-1], hi, di),
                 positions, self.rope_theta)
        k = layer_norm(self._mm(z, p["idx_wk"]), p["idx_k_norm"],
                       p["idx_k_norm_b"], self.rms_norm_eps)
        k = rope(k[..., None, :], positions, self.rope_theta)[..., 0, :]
        w = self._mm(z, p["idx_ww"]) * (hi ** -0.5 * di ** -0.5)
        return q.astype(cd), k.astype(cd), w

    @staticmethod
    def _index_scores(q, k, w):
        """``I = sum_j w_j relu(q_j . k_s)``: q [B, Tq, Hi, Di], k [B, Tk, Di],
        w [B, Tq, Hi] -> [B, Tq, Tk] float32."""
        with device_scope(profiling.OP_INDEXER_SCORES):
            dots = jnp.einsum("bqjd,bsd->bqjs", q, k,
                              preferred_element_type=jnp.float32)
            index = jnp.sum(jax.nn.relu(dots) * w[..., None], axis=2)
            # one zero: -0.0 and 0.0 tie, whichever way a top-k compares
            return jnp.where(index == 0, 0.0, index)

    def _ffn(self, p, h):
        """h [N, d] float32 -> (h + this chip's part of the experts'
        FFN(RMSNorm(h)), (tokens routed to each held expert, the chosen
        expert ids [N, k], the blocks of sorted rows run beyond the first))."""
        with device_scope(profiling.MOE):
            z = rms_norm(h, p["ffn_norm"], self.rms_norm_eps)
            routing = moe.route(
                z, p["router"], None, self.num_experts_per_tok,
                self.norm_topk_prob, scoring="softmax")
            out, counted = moe.held_experts(
                z, routing, p, self.compute_dtype, self.expert_offset,
                self.num_experts)
            return h + out, counted

    # -- the rollout's decode step ---------------------------------------------
    def init_carry(self, batch: int) -> Carry:
        kv_shape = (batch, self.max_positions,
                    self.num_key_value_heads * self.head_dim)
        idx_shape = (batch, self.max_positions, self.indexer_head_dim)
        cd = self.compute_dtype
        n = len(self.layer_ids)
        # a buffer each: the step donates its state
        return Carry(
            pos=jnp.zeros((batch,), jnp.int32),
            kv=tuple((jnp.zeros(kv_shape, cd), jnp.zeros(kv_shape, cd))
                     for _ in range(n)),
            index_keys=tuple(jnp.zeros(idx_shape, cd) for _ in range(n)),
        )

    def carry_bytes(self) -> Tuple[int, ...]:
        """Bytes of carry an env, by kind: (``kv``, ``index_keys``, ``pos``)."""
        return self._carry_bytes(lambda c: (c.kv, c.index_keys, c.pos))

    def carry_gauges(self, carry: Carry) -> dict:
        del carry  # a constant of the shapes
        return {"carry_bytes_per_env": jnp.asarray(self.carry_bytes(), jnp.float32)}

    def decode_rows_read_share(self) -> float:
        """Of the K/V buffers' rows, the share that lies in the blocks the
        decode fetches, over an episode from a reset: a function of the
        shapes and of where the decode runs (1.0 where no kernel does)."""
        q = jax.ShapeDtypeStruct(
            (1, self.num_attention_heads, self.head_dim), self.compute_dtype)
        k = jax.eval_shape(lambda: self.init_carry(1)).kv[0][0]
        read = decode_attention.rows_read(
            q, k, np.arange(1, self.max_positions + 1))
        return float(np.mean(read)) / self.max_positions

    def learner_tiles_visited_share(self) -> float:
        """Of the dense ``T x T`` square's (query tile, key tile) pairs, the
        share the learner's attention visits over an episode: a function of
        the shapes and of where the unroll runs (1.0 where no kernel does)."""
        q, k = (jax.ShapeDtypeStruct(
            (1, self.max_positions, heads, self.head_dim), self.compute_dtype)
            for heads in (self.num_attention_heads, self.num_key_value_heads))
        return sparse_attention.tiles_visited_share(q, k)

    def decode_selects_run_share(self) -> float:
        """Of an episode's decode steps from a reset, the share whose row can
        hold more than the top-k live keys, so that the selection's searches
        run (``ops/topk_select.py``; at the others the mask is ``live``): a
        function of the shapes."""
        P = self.max_positions
        return float(np.mean([
            topk_select.runs_searches(P, pos + 1, self.index_topk)
            for pos in range(P)]))

    def learner_selects_run_share(self) -> float:
        """The same of the unroll's blocks of queries, each over the keys up
        to its last query."""
        T = self.max_positions
        size = ssm.chunk_length(T, self.q_chunk_size)
        return float(np.mean([
            topk_select.runs_searches(hi, hi, self.index_topk)
            for hi in range(size, T + 1, size)]))

    def epoch_stats(self, metrics: dict) -> dict:
        """An epoch's scalars from the step's metrics of this policy."""
        live = float(np.sum(metrics["dsa_keys_live"]))
        return {
            **moe.load_stats(metrics),
            # of the keys a query could see, the share its indexer kept
            "dsa_kept_share": float(np.sum(metrics["dsa_keys_selected"])) / max(live, 1.0),
            # of the K/V buffers' rows, the share in blocks the decode fetched
            "dsa_decode_rows_read_share": self.decode_rows_read_share(),
            # of the T x T (query tile, key tile) pairs, the share the
            # learner's attention visited
            "dsa_learner_tiles_visited_share": self.learner_tiles_visited_share(),
            # of the decode steps, and of the learner's blocks of queries,
            # the share whose selection ran its searches
            "dsa_decode_selects_run_share": self.decode_selects_run_share(),
            "dsa_learner_selects_run_share": self.learner_selects_run_share(),
            "indexer_kl": float(np.sum(metrics["indexer_kl"])),
            "carry_bytes_per_env": float(np.sum(metrics["carry_bytes_per_env"])),
        }

    def step(self, params, obs, carry: Carry, fresh):
        # nothing of this carry is zeroed where ``fresh``: the position
        # masks every buffer, so the opening is the position alone
        pos = jnp.where(fresh, 0, carry.pos)
        rows = jnp.arange(obs.shape[0])
        at = pos[:, None]
        live = jnp.arange(self.max_positions)[None, :] <= at
        x = self._embed(params, obs)
        kv_out, idx_out = [], []

        def write(cache, new):
            return sequence.write_row(rows, cache, pos, new)

        for i, ((k_cache, v_cache), i_cache) in enumerate(
                zip(carry.kv, carry.index_keys, strict=True)):
            p = params[self.layer_name(i)]
            with device_scope(profiling.OP_ATTN_SPARSE):
                z = rms_norm(x, p["attn_norm"], self.rms_norm_eps)[:, None, :]
                q, k, v = self._qkv(p, z, at)
                k_cache, v_cache = write(k_cache, k), write(v_cache, v)
            with device_scope(profiling.OP_INDEXER):
                qi, ki, w = self._index_qkw(p, z, at)
                i_cache = write(i_cache, ki)
                scores = self._index_scores(qi, i_cache, w)[:, 0]
                with device_scope(profiling.OP_INDEXER_SELECT):
                    kept = select_mask(scores, live, self.index_topk)
            with device_scope(profiling.OP_ATTN_SPARSE):
                h = x + self._attend_rows(p, q, k_cache, v_cache, pos, kept)
            x, _ = self._ffn(p, h)
            kv_out.append((k_cache, v_cache))
            idx_out.append(i_cache)
        return self._head(params, x), Carry(
            pos=pos + 1, kv=tuple(kv_out), index_keys=tuple(idx_out))

    # -- the learner's unroll ----------------------------------------------------
    def _select(self, qi, ki, w, lo: int):
        """The indexer over queries ``[lo, lo + len(qi))`` of an episode and
        keys ``[0, lo + len(qi))``: qi [B, Tq, Hi, Di], ki [B, Tk, Di], w [B,
        Tq, Hi] -> (the index scores [B, Tq, Tk] float32, the selection [B,
        Tq, Tk] bool, keys selected, keys live)."""
        B, Tq = qi.shape[:2]
        Tk = ki.shape[1]
        at_q = lo + jnp.arange(Tq)[:, None]
        live = jnp.broadcast_to(jnp.arange(Tk)[None, :] <= at_q, (B, Tq, Tk))
        with device_scope(profiling.OP_INDEXER):
            index = self._index_scores(qi, ki, w)
            with device_scope(profiling.OP_INDEXER_SELECT):
                chosen = select_mask(
                    jax.lax.stop_gradient(index), live, self.index_topk)
        count = lambda m: jnp.sum(m, dtype=jnp.int32)  # noqa: E731
        return index, chosen, count(chosen), count(live)

    def _index_loss(self, index, chosen, shared):
        """``sum_t KL_t`` of a block of queries: the index scores and the
        selection [B, Tq, Tk] against the main attention's probabilities
        summed over the heads, ``shared`` [B, Tq, Tk]."""
        with device_scope(profiling.OP_INDEXER), device_scope(
                profiling.OP_INDEXER_LOSS):
            # the main attention's distribution over the selected keys, all
            # heads together; each head's sums to one, so theirs to H
            target = jax.lax.stop_gradient(shared) / self.num_attention_heads
            log_q = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), axis=-1)
            there = chosen & (target > 0)
            return jnp.sum(jnp.where(
                there, target * (jnp.log(jnp.where(there, target, 1.0))
                                 - jnp.where(there, log_q, 0.0)), 0.0))

    def _layer_unroll(self, i: int, p, x, with_selection: bool):
        """One layer over whole episodes: x [B, T, d] float32 -> (x, (the
        layer's ``L_I``, keys selected, keys live, what the experts' layer
        counted, the selection [B, T, T] or None))."""
        B, T, d = x.shape
        positions = jnp.arange(T)[None, :]
        with device_scope(profiling.OP_ATTN_SPARSE):
            z = rms_norm(x, p["attn_norm"], self.rms_norm_eps)
            q, k, v = self._qkv(p, z, positions)
        with device_scope(profiling.OP_INDEXER):
            qi, ki, w = self._index_qkw(p, z, positions)
        # the indexer in blocks of queries, each over the keys its mask can
        # reach; a block's [B, Tq, Hi, Tk] float32 dots are recomputed in the
        # backward, not kept
        size = ssm.chunk_length(T, self.q_chunk_size)
        select = jax.checkpoint(self._select, static_argnums=(3,))
        blocks, selected, live = [], 0, 0
        for lo in range(0, T, size):
            hi = lo + size
            index, chosen, sel_b, live_b = select(
                qi[:, lo:hi], ki[:, :hi], w[:, lo:hi], lo)
            blocks.append((lo, hi, index, chosen))
            selected, live = selected + sel_b, live + live_b
        chosen = jnp.concatenate(
            [jnp.pad(c, ((0, 0), (0, 0), (0, T - hi))) for _, hi, _, c in blocks],
            axis=1)
        with device_scope(profiling.OP_ATTN_SPARSE):
            out, shared = sparse_attention.attend_selected(
                q, k, v, chosen, 1.0 / math.sqrt(self.head_dim))
            h = x + self._mm(out, p["wo"])
        loss = jax.checkpoint(self._index_loss)
        kl = sum(loss(index, c, shared[:, lo:hi, :hi]) for lo, hi, index, c in blocks)
        y, routed = self._ffn(p, h.reshape(B * T, d))
        return y.reshape(B, T, d), (kl / (B * T), selected, live, routed,
                                    chosen if with_selection else None)

    def unroll(self, params, tokens, with_routes: bool = False):
        """Whole episodes from a reset: ``tokens`` [B, T] int32 ->
        (PolicyValue with logits [B, T, A] and value [B, T], aux). ``aux``
        counts, a layer: the tokens routed to each held expert
        (``moe_tokens_per_expert``), the blocks of sorted rows run beyond
        the first (``moe_overflow_blocks``), the keys the queries could see
        and those their indexer kept (``dsa_keys_live``,
        ``dsa_keys_selected``); under ``LOSS_TERMS`` it hands the trainer
        ``indexer_kl``: ``indexer_loss_coef * L_I``, a layer. Asked, it also
        names every token's chosen experts (``routes`` [layers, B, T, k])
        and every query's selected keys (``selected`` [layers, B, T, T / 8]
        uint8: the mask's bits, ``jnp.packbits`` along the keys)."""
        routed = moe.RoutedLayers(*tokens.shape)
        kls, selected, live, masks = [], [], [], []

        def took(counted):
            kl, n_sel, n_live, of_experts, mask = counted
            kls.append(kl)
            selected.append(n_sel)
            live.append(n_live)
            routed.take(of_experts)
            masks.append(mask)

        def aux():
            out = {
                **routed.aux(),
                "dsa_keys_selected": jnp.stack(selected),
                "dsa_keys_live": jnp.stack(live),
                LOSS_TERMS: {"indexer_kl": self.indexer_loss_coef * jnp.stack(kls)},
            }
            if with_routes:
                out["routes"] = jnp.stack(routed.routes)
                out["selected"] = jnp.packbits(jnp.stack(masks), axis=-1)
            return out

        return self._unroll(
            params, tokens,
            lambda i, p, x: self._layer_unroll(i, p, x, with_routes), took, aux)
