"""Phi-4-mini-flash-reasoning (``model_type phi4flash``, the SambaY
decoder-hybrid-decoder of arXiv:2507.06607) as a token-sequence policy.

Published (microsoft/Phi-4-mini-flash-reasoning ``config.json``): 32 layers,
hidden 2560, 40 query heads over 20 key/value heads of 64, SwiGLU 10240 in
every layer, LayerNorm (eps 1e-5, gain and bias), a tied embedding of
200,064 ids, ``sliding_window`` 512, ``mb_per_layer`` 2, no positional
encoding of any kind (the state-space layers carry position). Every layer is

    h = x + Mixer_i(LN(x))        x' = h + W_down(silu(W_gate LN(h)) * W_up LN(h))

then a final LN and the tied head. The mixer by published layer index ``i``
of ``n`` = 32 (:func:`kind_of`):

- ``i`` even, ``i <= n/2``: **Mamba** (``d_inner`` 5120, ``d_state`` 16,
  ``d_conv`` 4, ``dt_rank`` 160). ``[u, z] = W_in a``; ``u = silu(conv4(u) +
  b_c)`` causal, depthwise; ``[dt, B, C] = W_x u``; ``dt = softplus(W_dt dt +
  b_dt)``; ``A = -exp(A_log)``; the selective scan of ``ops/ssm.py`` gives
  ``y``; out ``W_out (y * silu(z))``. Layer ``n/2`` also hands ``m = y``
  (before the gate) to the layers after it.
- ``i`` odd, ``i < n/2``: **window attention** over the last 512 positions,
  self included; ``i = n/2 + 1``: **full attention** over every past
  position, whose K and V are kept for the layers after it. Both are
  differential attention (arXiv:2410.05258): ``[q, k, v] = W_qkv a + b``;
  query heads pair up (even, odd) into 20 pairs, K/V heads into 10, a K/V
  pair serving two query pairs; ``o = (softmax(q_1 k_1^T / 8) - lam
  softmax(q_2 k_2^T / 8)) [v_1 ; v_2]``, ``o = RMSNorm_128(o) (1 -
  lam_init)``, ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
  ``lam_init = 0.8 - 0.6 exp(-0.3 i)``; then ``W_o`` with a bias.
- ``i`` even, ``i > n/2``: **gated memory unit**, ``W_2 (silu(W_1 a) *
  m_t)`` with ``m_t`` layer ``n/2``'s ``y`` at the same position.
- ``i`` odd, ``i > n/2 + 1``: **cross attention**: queries only (``W_q``,
  ``W_o``), the same differential attention over layer ``n/2 + 1``'s keys
  and values, causal over every past position.

So a layer's output depends on what another layer produced at the same
position: the layer loop of :meth:`Phi4Flash.step` and of
:meth:`Phi4Flash.unroll` threads two side channels beside the residual
stream (``m``; the shared K/V). And the carry holds four kinds of state side
by side (:class:`Carry`).

The widths are the defaults below and are never cut. What IS cut is which
published layers one chip holds (``layer_ids``) and how many vocabulary ids
(``num_actions``); ``--model_cut`` names such a cut (:data:`CUTS`;
``benchmark/configs/phi4-mini-flash-recall-fused-a2c.json`` has the
arithmetic and lists what is assumed beyond the published config).

Precision: float32 parameters, residual stream, norms, softmax, conv, the
state-space state and its scan, heads' outputs; bfloat16 matrix operands
with float32 accumulation (``models/layers.py:mm``). A K/V pair is stored
128 wide, ``[k_1 ; k_2]`` and ``[v_1 ; v_2]``: a query of 64 is laid beside
64 zeros on its own half, so one masked grouped-query attention
(``layers.attend``) over lane-wide rows gives both softmaxes of a pair. A
decode step attends over the rows of its carry's buffers up to the position
and no further (``ops/decode_attention.py``: a Pallas kernel on a TPU at
whole-lane widths, ``layers.attend`` under the mask anywhere else). The
policy protocol is models/policy.py's.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from distributed_ba3c_tpu.models import layers, sequence
from distributed_ba3c_tpu.models.layers import layer_norm, rms_norm
from distributed_ba3c_tpu.ops import decode_attention, ssm
from distributed_ba3c_tpu.utils import profiling
from distributed_ba3c_tpu.utils.profiling import device_scope

MAMBA, WINDOW, FULL, GMU, CROSS = (
    "mamba", "window_attention", "full_attention", "memory_unit",
    "cross_attention")
#: spread of the four learned vectors of a differential attention's ``lam``
LAMBDA_INIT_SCALE = 0.1
#: the seeded step sizes: ``softplus(b_dt)`` log-uniform between these
DT_MIN, DT_MAX = 1e-3, 1e-1
#: query positions a block of the learner's attention takes at once: the
#: scores of a block, ``[envs, 40, block, keys]`` float32, are what a
#: backward holds (168 MB at 4 envs x 256 x 1,024), and a block reads only
#: the keys its mask can reach
ATTN_QUERY_BLOCK = 256
#: ``--model_cut``: what one chip holds, the default first. ``stage-14-19``:
#: published layers 14-19 (Mamba, window, Mamba that hands on ``m``, full
#: that hands on K/V, memory unit, cross: every kind, contiguous), the
#: vocabulary slice the env's action space. ``tiny``: every mechanism at a
#: size a CPU test runs.
CUTS = {
    "stage-14-19": {},
    "tiny": dict(
        hidden_size=64, intermediate_size=96, num_attention_heads=8,
        num_key_value_heads=4, head_dim=8, d_inner=128, d_state=4, dt_rank=4,
        sliding_window=8, num_hidden_layers=8, layer_ids=(2, 3, 4, 5, 6, 7),
    ),
}


cut_fields = functools.partial(sequence.cut_fields, CUTS)


def kind_of(i: int, n: int) -> str:
    """The mixer of published layer ``i`` of ``n`` (``mb_per_layer`` 2)."""
    half = n // 2
    if i <= half:
        return MAMBA if i % 2 == 0 else WINDOW
    if i == half + 1:
        return FULL
    return GMU if i % 2 == 0 else CROSS


def lambda_init(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


class Carry(NamedTuple):
    """What decoding carries from one position to the next, an env a row:
    four kinds of state side by side, and the position. ``fresh`` resets
    ``pos`` and zeroes ``ssm``; ``ring`` and ``shared_kv`` keep their bytes
    and are masked by the position (nothing at or past it is read)."""

    pos: jax.Array     # [B] int32 position in the episode
    ssm: Tuple         # per Mamba layer (state [B, n, c] f32, the last
                       # d_conv - 1 inputs of the conv [B, 3, c] f32)
    ring: Tuple        # per window layer (k, v), each [B, window, KV/2 * 2D]
                       # (a position's pairs side by side in one row):
                       # position p lies in slot p % window
    shared_kv: Tuple   # () or the full layer's (k, v), each [B, P, KV/2 * 2D]:
                       # written by that layer, read by it and every cross layer


@dataclasses.dataclass(frozen=True)
class Phi4Flash(sequence.SequencePolicy):
    num_actions: int = 25008            # vocabulary ids held (of 200,064)
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    head_dim: int = 64
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    num_hidden_layers: int = 32         # published: places the kinds
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    # -- the chip's share ---------------------------------------------------
    layer_ids: Tuple[int, ...] = (14, 15, 16, 17, 18, 19)
    # -- how it is run ------------------------------------------------------
    max_positions: int = 1024           # shared K/V rows: the episode length
    compute_dtype: jnp.dtype = jnp.bfloat16

    #: the conv's taps and ``A_log`` stay float32
    float32_leaves = ("conv_w", "A_log")
    final_norm_eps = property(lambda self: self.layer_norm_eps)

    def __post_init__(self):
        assert self.d_conv == 4, "the causal conv is written for 4 taps"
        assert self.num_key_value_heads % 2 == 0, "K/V heads pair up"
        assert self.num_attention_heads % self.num_key_value_heads == 0
        kinds = self.layer_kinds
        if GMU in kinds and MAMBA not in kinds[:kinds.index(GMU)]:
            raise ValueError("a memory unit needs the state-space layer that "
                             "hands on its output among the layers held")
        if CROSS in kinds and FULL not in kinds[:kinds.index(CROSS)]:
            raise ValueError("a cross layer needs the full-attention layer "
                             "whose K/V it reads among the layers held")

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return tuple(kind_of(i, self.num_hidden_layers) for i in self.layer_ids)

    @property
    def memory_layer(self) -> int:
        """Index (among the held) of the Mamba layer whose ``y`` the memory
        units read: the last one before them."""
        kinds = self.layer_kinds
        last = kinds.index(GMU) if GMU in kinds else len(kinds)
        return max((i for i in range(last) if kinds[i] == MAMBA), default=-1)

    # -- parameters -----------------------------------------------------------
    def init_params(self, rng):
        """The scaffold's, and the final LayerNorm's zero bias."""
        params = super().init_params(rng)
        params["final"]["norm_b"] = jnp.zeros((self.hidden_size,), jnp.float32)
        return params

    def _init_layer(self, i: int, init):
        """Held layer ``i``'s seeded leaves: normal kernels scaled by
        1/sqrt(fan_in), unit gains, zero biases; ``A_log`` the family's
        ``log(1 .. d_state)``, ``D`` ones, ``dt_bias`` the inverse softplus
        of step sizes log-uniform in [1e-3, 1e-1]."""
        d, f, c = self.hidden_size, self.intermediate_size, self.d_inner
        D = self.head_dim
        hq, hkv = self.num_attention_heads * D, self.num_key_value_heads * D
        normal, ones, zeros = init.normal, init.ones, init.zeros
        kind = self.layer_kinds[i]
        layer = {"mix_norm": ones(d), "mix_norm_b": zeros(d),
                 "ffn_norm": ones(d), "ffn_norm_b": zeros(d),
                 "w_gate": normal((d, f), d), "w_up": normal((d, f), d),
                 "w_down": normal((f, d), f)}
        if kind == MAMBA:
            step = jnp.exp(
                jax.random.uniform(next(init.keys), (c,), jnp.float32)
                * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
            layer.update(
                in_proj=normal((d, 2 * c), d),
                conv_w=normal((self.d_conv, c), self.d_conv),
                conv_b=zeros(c),
                x_proj=normal((c, self.dt_rank + 2 * self.d_state), c),
                dt_proj=normal((self.dt_rank, c), self.dt_rank),
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                A_log=jnp.broadcast_to(jnp.log(jnp.arange(
                    1, self.d_state + 1, dtype=jnp.float32)), (c, self.d_state)),
                D=ones(c), out_proj=normal((c, d), c))
        elif kind == GMU:
            layer.update(gmu_in=normal((d, c), d), gmu_out=normal((c, d), c))
        else:
            if kind == CROSS:
                layer.update(wq=normal((d, hq), d), bq=zeros(hq))
            else:
                layer.update(wqkv=normal((d, hq + 2 * hkv), d),
                             bqkv=zeros(hq + 2 * hkv))
            layer.update(wo=normal((hq, d), hq), bo=zeros(d),
                         sub_norm=ones(2 * D))
            for name in ("lam_q1", "lam_k1", "lam_q2", "lam_k2"):
                layer[name] = LAMBDA_INIT_SCALE * jax.random.normal(
                    next(init.keys), (D,), jnp.float32)
        return layer

    # -- pieces shared by the decode step and the unroll -----------------------
    def _ffn(self, p, h):
        """h [N, d] float32 -> h + SwiGLU(LN(h))."""
        with device_scope(profiling.FFN_DENSE):
            z = layer_norm(h, p["ffn_norm"], p["ffn_norm_b"], self.layer_norm_eps)
            return h + layers.swiglu(
                z, p["w_gate"], p["w_up"], p["w_down"], self.compute_dtype)

    def _mix_norm(self, p, x):
        return layer_norm(x, p["mix_norm"], p["mix_norm_b"], self.layer_norm_eps)

    def _ssm_in(self, p, x):
        """x [..., d] -> (u before the conv, z), each [..., c] float32."""
        with device_scope(profiling.OP_SSM_IN_PROJ):
            return jnp.split(self._mm(self._mix_norm(p, x), p["in_proj"]), 2, -1)

    def _ssm_select(self, p, u):
        """u [..., c] after the conv -> (dt [..., c], B, C [..., n]) and ``A``
        [n, c]: what the scan is run with."""
        R, n = self.dt_rank, self.d_state
        low, B, C = jnp.split(self._mm(u, p["x_proj"]), (R, R + n), -1)
        dt = jax.nn.softplus(self._mm(low, p["dt_proj"]) + p["dt_bias"])
        return dt, B, C, -jnp.exp(p["A_log"]).T

    def _ssm_out(self, p, y, z):
        with device_scope(profiling.OP_SSM_OUT_PROJ):
            return self._mm(y * jax.nn.silu(z), p["out_proj"])

    def _qkv(self, p, a):
        """a [B, T, d] -> q [B, T, H, D], k, v [B, T, KV/2, 2D] (pairs side
        by side), in the compute type."""
        B, T, _ = a.shape
        D = self.head_dim
        hq, hkv = self.num_attention_heads * D, self.num_key_value_heads * D
        q, k, v = jnp.split(
            self._mm(a, p["wqkv"]) + p["bqkv"], (hq, hq + hkv), -1)
        cd = self.compute_dtype
        return (q.reshape(B, T, -1, D).astype(cd),
                k.reshape(B, T, -1, 2 * D).astype(cd),
                v.reshape(B, T, -1, 2 * D).astype(cd))

    def _q(self, p, a):
        B, T, _ = a.shape
        q = self._mm(a, p["wq"]) + p["bq"]
        return q.reshape(B, T, -1, self.head_dim).astype(self.compute_dtype)

    def _on_halves(self, q):
        """q [B, Tq, H, D] -> [B, Tq, H, 2D]: head 2p of a pair reads the
        first halves of a K/V pair, head 2p+1 the second, so each query is
        laid on its own half beside zeros."""
        B, Tq, H, D = q.shape
        halves = jnp.eye(2, dtype=q.dtype)[:, :, None]
        return (q.reshape(B, Tq, H // 2, 2, 1, D) * halves).reshape(B, Tq, H, 2 * D)

    def _diff_out(self, p, i: int, out):
        """What follows the two softmaxes of held layer ``i``'s pairs: out
        [B, Tq, H * 2D] float32 (a head's softmax over ``[v_1 ; v_2]``) ->
        their difference under ``lam``, the sub-norm, ``W_o``: [B, Tq, d]."""
        B, Tq, _ = out.shape
        D = self.head_dim
        out = out.reshape(B, Tq, -1, 2, 2 * D)
        start = lambda_init(self.layer_ids[i])
        lam = (jnp.exp(jnp.sum(p["lam_q1"] * p["lam_k1"]))
               - jnp.exp(jnp.sum(p["lam_q2"] * p["lam_k2"])) + start)
        o = out[..., 0, :] - lam * out[..., 1, :]
        o = rms_norm(o, p["sub_norm"], self.layer_norm_eps) * (1.0 - start)
        return self._mm(o.reshape(B, Tq, -1), p["wo"]) + p["bo"]

    def _diff_attend(self, p, i: int, q, k, v, mask):
        """Differential attention of held layer ``i``. q [B, Tq, H, D]; k, v
        [B, Tk, KV/2, 2D]; mask [B or 1, Tq, Tk] -> [B, Tq, d] float32."""
        out = layers.attend(self._on_halves(q), k, v, mask, self.compute_dtype,
                            scale=1.0 / math.sqrt(self.head_dim))
        return self._diff_out(p, i, out)

    def _diff_decode(self, p, i: int, q, k, v, length):
        """The same for a decode step's one query an env, over rows ``[0,
        length)`` of an env's buffers. q [B, 1, H, D]; k, v [B, rows, KV/2 *
        2D]; length [B] -> [B, d] float32."""
        B = q.shape[0]
        out = decode_attention.decode_attend(
            self._on_halves(q)[:, 0], k, v, length,
            scale=1.0 / math.sqrt(self.head_dim))
        return self._diff_out(p, i, out.reshape(B, 1, -1))[:, 0]

    # -- the rollout's decode step ---------------------------------------------
    def _kv_shape(self, batch: int, rows: int):
        # a position's pairs side by side in ONE row of whole lanes: the
        # scatter that writes a position and the kernel that reads blocks
        # of positions (ops/decode_attention.py) then share the default
        # layout, and a row is all data. Read off the program compiled for
        # a v5e: with pairs an axis of their own the compiler kept the
        # buffers positions-major for the scatter, ten pairs in tiles of
        # sixteen, and copied them whole into the kernel's order every step
        # (PR 33; PR 31 met the same copy the other way round)
        return (batch, rows, self.num_key_value_heads * self.head_dim)

    def init_carry(self, batch: int) -> Carry:
        kinds = self.layer_kinds
        c, n = self.d_inner, self.d_state

        def kv(rows):  # a buffer each: the step donates its state
            return tuple(jnp.zeros(self._kv_shape(batch, rows), self.compute_dtype)
                         for _ in range(2))

        return Carry(
            pos=jnp.zeros((batch,), jnp.int32),
            ssm=tuple((jnp.zeros((batch, n, c), jnp.float32),
                       jnp.zeros((batch, self.d_conv - 1, c), jnp.float32))
                      for k in kinds if k == MAMBA),
            ring=tuple(kv(self.sliding_window) for k in kinds if k == WINDOW),
            shared_kv=kv(self.max_positions) if FULL in kinds else (),
        )

    def carry_bytes(self) -> Tuple[int, ...]:
        """Bytes of carry an env, by kind: (``ssm``, ``ring``, ``shared_kv``,
        ``pos``)."""
        return self._carry_bytes(lambda c: (c.ssm, c.ring, c.shared_kv, c.pos))

    def carry_gauges(self, carry: Carry) -> dict:
        """What the trainer reports of the carry at an update's end: its
        bytes an env by kind (a constant of the shapes) and the largest
        ``|s|`` of the state-space states (a scan that overflows shows here
        before it shows in the loss)."""
        states = [jnp.max(jnp.abs(state)) for state, _ in carry.ssm]
        return {
            "carry_bytes_per_env": jnp.asarray(self.carry_bytes(), jnp.float32),
            "ssm_state_absmax": (jnp.max(jnp.stack(states)) if states
                                 else jnp.float32(0.0)),
        }

    def epoch_stats(self, metrics: dict) -> dict:
        """An epoch's scalars from the step's metrics of this policy."""
        return {
            "ssm_state_absmax": float(metrics["ssm_state_absmax"]),
            "carry_bytes_per_env": float(metrics["carry_bytes_per_env"].sum()),
        }

    def step(self, params, obs, carry: Carry, fresh):
        pos, keep = sequence.decode_opening(carry.pos, fresh)
        keep = keep.astype(jnp.float32)[:, None, None]
        rows = jnp.arange(obs.shape[0])
        x = self._embed(params, obs)
        ssm_in, ring_in = iter(carry.ssm), iter(carry.ring)
        ssm_out, ring_out = [], []
        shared_kv = carry.shared_kv
        memory = None
        write = functools.partial(sequence.write_row, rows)

        for i, kind in enumerate(self.layer_kinds):
            p = params[self.layer_name(i)]
            if kind == MAMBA:
                with device_scope(profiling.OP_SSM):
                    state, tail = next(ssm_in)
                    state, tail = state * keep, tail * keep
                    u, z = self._ssm_in(p, x)
                    with device_scope(profiling.OP_SSM_CONV):
                        taps = p["conv_w"]
                        conv, tail = layers.conv_step(
                            taps[0] * u + p["conv_b"], taps, u, tail)
                        u = jax.nn.silu(conv)
                    dt, Bs, Cs, A = self._ssm_select(p, u)
                    with device_scope(profiling.OP_SSM_SCAN):
                        state, y = ssm.scan_step(state, u, dt, A, Bs, Cs, p["D"])
                    if i == self.memory_layer:
                        memory = y
                    h = x + self._ssm_out(p, y, z)
                    ssm_out.append((state, tail))
            elif kind == GMU:
                with device_scope(profiling.OP_GMU):
                    gate = jax.nn.silu(self._mm(self._mix_norm(p, x), p["gmu_in"]))
                    h = x + self._mm(gate * memory, p["gmu_out"])
            elif kind == WINDOW:
                with device_scope(profiling.OP_ATTN_WINDOW):
                    k_ring, v_ring = next(ring_in)
                    q, k, v = self._qkv(p, self._mix_norm(p, x)[:, None, :])
                    slot = pos % self.sliding_window
                    k_ring, v_ring = write(k_ring, slot, k), write(v_ring, slot, v)
                    # slots up to the position are this episode's; from
                    # position window - 1 on every slot is one of the last
                    # ``window`` positions
                    h = x + self._diff_decode(
                        p, i, q, k_ring, v_ring,
                        jnp.minimum(pos + 1, self.sliding_window))
                    ring_out.append((k_ring, v_ring))
            else:
                scope = (profiling.OP_ATTN_FULL if kind == FULL
                         else profiling.OP_ATTN_CROSS)
                with device_scope(scope):
                    a = self._mix_norm(p, x)[:, None, :]
                    if kind == FULL:
                        q, k, v = self._qkv(p, a)
                        shared_kv = (write(shared_kv[0], pos, k),
                                     write(shared_kv[1], pos, v))
                    else:
                        q = self._q(p, a)
                    h = x + self._diff_decode(p, i, q, *shared_kv, pos + 1)
            x = self._ffn(p, h)
        return self._head(params, x), Carry(
            pos=pos + 1, ssm=tuple(ssm_out), ring=tuple(ring_out),
            shared_kv=shared_kv)

    # -- the learner's unroll ----------------------------------------------------
    def _attend_blocks(self, p, i: int, q, k, v, window):
        """Causal (``window``: banded) differential attention over whole
        episodes, a block of query positions at a time, each block over the
        keys its mask can reach. q [B, T, H, D]; k, v [B, T, KV/2, 2D]."""
        T = q.shape[1]
        size = ssm.chunk_length(T, ATTN_QUERY_BLOCK)
        block = jax.checkpoint(
            lambda p, q, k, v, mask: self._diff_attend(p, i, q, k, v, mask))
        out = []
        for lo in range(0, T, size):
            hi = lo + size
            first = 0 if window is None else max(0, lo - window + 1)
            at_q = jnp.arange(lo, hi)[:, None]
            at_k = jnp.arange(first, hi)[None, :]
            mask = at_k <= at_q
            if window is not None:
                mask &= at_k > at_q - window
            out.append(block(p, q[:, lo:hi], k[:, first:hi], v[:, first:hi],
                             mask[None]))
        return jnp.concatenate(out, axis=1)

    def _layer_unroll(self, i: int, p, x, memory, shared_kv):
        """One layer over whole episodes: x [B, T, d] float32; ``memory``
        and ``shared_kv`` are the side channels as the layers before left
        them. -> (x, (memory, shared_kv))."""
        kind = self.layer_kinds[i]
        B, T, d = x.shape
        if kind == MAMBA:
            with device_scope(profiling.OP_SSM):
                u, z = self._ssm_in(p, x)
                with device_scope(profiling.OP_SSM_CONV):
                    u = jax.nn.silu(
                        p["conv_b"] + layers.causal_conv(p["conv_w"], u))
                dt, Bs, Cs, A = self._ssm_select(p, u)
                with device_scope(profiling.OP_SSM_SCAN):
                    y, _ = ssm.selective_scan(u, dt, A, Bs, Cs, p["D"])
                if i == self.memory_layer:
                    memory = y
                h = x + self._ssm_out(p, y, z)
        elif kind == GMU:
            with device_scope(profiling.OP_GMU):
                gate = jax.nn.silu(self._mm(self._mix_norm(p, x), p["gmu_in"]))
                h = x + self._mm(gate * memory, p["gmu_out"])
        elif kind == WINDOW:
            with device_scope(profiling.OP_ATTN_WINDOW):
                q, k, v = self._qkv(p, self._mix_norm(p, x))
                h = x + self._attend_blocks(p, i, q, k, v, self.sliding_window)
        else:
            scope = (profiling.OP_ATTN_FULL if kind == FULL
                     else profiling.OP_ATTN_CROSS)
            with device_scope(scope):
                a = self._mix_norm(p, x)
                if kind == FULL:
                    q, *shared_kv = self._qkv(p, a)
                else:
                    q = self._q(p, a)
                h = x + self._attend_blocks(p, i, q, *shared_kv, None)
        y = self._ffn(p, h.reshape(B * T, d))
        return y.reshape(B, T, d), (memory, tuple(shared_kv))

    def unroll(self, params, tokens):
        """Whole episodes from a reset: ``tokens`` [B, T] int32 ->
        (PolicyValue with logits [B, T, A] and value [B, T], aux). ``aux``
        is empty: this policy counts nothing in its learner. The two side
        channels go from layer to layer beside the residual stream."""
        return self._unroll(
            params, tokens, self._layer_unroll, side=(None, ()))
