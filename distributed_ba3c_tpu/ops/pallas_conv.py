"""Pallas TPU kernels for the BA3C conv stack: fused conv+bias+relu+maxpool.

STATUS — default OFF, and no flag selects it (ROADMAP D1 queues the
removal). Under the installed stack (jax 0.9.0, libtpu 0.0.34) all three
supported geometries compile with Mosaic on the v5e and match the XLA block
to one bf16 ulp (PR 21; chip_smoke.py repeats the check on every run). Its
speed has NOT been re-measured there. An A/B in an earlier round, on other
code (chained in-jit loops, B=4096), read:

    XLA conv1 block (conv+bias+relu+pool)      2.52 us/sample
    this kernel, VPU-assembled patches          4.17-4.75
    this kernel, DMA-engine-assembled patches   7.34

The hypothesis was sound — XLA's conv emitter fills only 32 of the MXU's
128 output lanes on this net (a ~11 us/sample fwd+bwd floor), while the
packed GEMM here fills all 128 (a ~1.1 us/sample conv1 floor) and fuses
bias/relu/pool so the pre-pool activation never touches HBM. What kills it
is im2col patch ASSEMBLY: reorganizing [W*Ci] lanes into overlapping
[G, P*Ci] patch rows is a lane<->sublane relayout that costs more on the
VPU (or the DMA engines) than the MXU occupancy saves at these small
shapes. Mosaic constraints hit along the way, for the record: lane-split
reshapes require 128-multiples (conv0's P*Ci=16 is unreachable), sublane
DMA slices require 8-aligned offsets, and sub-tile flattens relayout unless
the collapsed dim is 16-aligned (hence G=16 here).

Do NOT re-try without new evidence; the remaining ideas (input-channel
padding to 32, space-to-depth, Toeplitz row-GEMMs, stride-2 shifted convs)
are analyzed and rejected in PERF.md.

Reference equivalent: the conv layers of ``Model._build_graph`` in
``src/train.py`` (SURVEY.md §2.1 #2) — re-designed as TPU kernels, not
translated.

The GEMM formulation (lane packing, same algebra as models/packed_conv.py
but fused): a stride-1 SAME conv computing P adjacent output columns per
GEMM row fills P*Co of the MXU's 128 output lanes (P=4, Co=32 -> exactly
128 for the 32-channel layers). For output row y and column group j
(covering columns j*P .. j*P+P-1):

    patch[y, j]  = xpad[y:y+kh, j*P : j*P+2P, :]          (K = kh*2P*Ci)
    out[y, j, (p, co)] = patch[y, j] . Wp[:, (p, co)]

with Wp[ky, q, ci, (p, co)] = W[ky, q-p, ci, co] (zero outside 0<=q-p<kw),
which is exact for kw <= P+1 (all BA3C kernels: 5,5,4,3 with P=4).

Layout notes (Mosaic):
- All HBM-visible tensors are [B, H, W*C] with the (W, C) pair flattened
  into the lane dimension — W*C is 336..1344 lanes, well-tiled, and the
  flattened layout makes every im2col/pool step a *lane slice* instead of
  a gather.
- The 2x2 maxpool runs in the packed layout: with P even, column pairs
  (2t, 2t+1) live in adjacent Co-lane chunks of the same group, so x-pooling
  is a lane-chunk max and y-pooling a sublane-pair max; the pooled packed
  layout [Ho, G, (P/2)*Co] flattens back to [Ho, (Wc/2)*Co] with no
  permutation.
- Numerics match the flax path op-for-op: bf16 GEMM with f32 accumulation,
  round to bf16, add bf16 bias, relu, pool — the same order nn.Conv +
  nn.relu + nn.max_pool produce under XLA.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Static geometry of one fused conv block."""

    H: int            # input height
    W: int            # input width
    Ci: int           # input channels
    Co: int           # output channels
    kh: int
    kw: int
    pool: bool        # 2x2/2 maxpool after relu
    scale_uint8: bool  # input is uint8; cast and multiply by 1/255
    P: int = 4        # lane-packing factor (even, >= kw-1)
    bt: int = 4       # batch tile per grid step

    # geometry validity is a query, not an invariant: callers gate on
    # supported(); the kernel entry point re-asserts it

    # ---- derived geometry ----
    @property
    def ph(self) -> int:  # top row pad (XLA SAME convention)
        return (self.kh - 1) // 2

    @property
    def pw(self) -> int:  # left col pad
        return (self.kw - 1) // 2

    @property
    def Wc(self) -> int:  # logical width padded up to a multiple of P
        return -(-self.W // self.P) * self.P

    @property
    def G(self) -> int:  # column groups
        return self.Wc // self.P

    @property
    def Hp(self) -> int:  # padded rows held in VMEM
        return self.H + self.kh - 1

    @property
    def Wp(self) -> int:  # padded cols held in VMEM (patch j spans [jP, jP+2P))
        return self.Wc + self.P

    @property
    def K(self) -> int:  # GEMM contraction size
        return self.kh * 2 * self.P * self.Ci

    @property
    def N(self) -> int:  # GEMM output lanes
        return self.P * self.Co

    @property
    def Ho(self) -> int:
        return self.H // 2 if self.pool else self.H

    @property
    def Wo(self) -> int:
        return self.W // 2 if self.pool else self.W

    @property
    def in_dtype(self):
        return jnp.uint8 if self.scale_uint8 else jnp.bfloat16


def ba3c_specs(
    frame_history: int = 4,
    conv_features: Tuple[int, ...] = (32, 32, 64, 64),
    conv_kernels: Tuple[int, ...] = (5, 5, 4, 3),
    batch_tiles: Tuple[int, ...] = (4, 4, 8, 16),
) -> Tuple[ConvSpec, ...]:
    """The four BA3C conv blocks (84x84xhist uint8 in, 10x10x64 out)."""
    specs = []
    h = w = 84
    ci = frame_history
    pooled = (True, True, True, False)
    for i, (co, k, pool, bt) in enumerate(
        zip(conv_features, conv_kernels, pooled, batch_tiles, strict=True)
    ):
        s = ConvSpec(
            H=h, W=w, Ci=ci, Co=co, kh=k, kw=k,
            pool=pool, scale_uint8=(i == 0), bt=bt,
        )
        specs.append(s)
        h, w, ci = s.Ho, s.Wo, co
    return tuple(specs)


# --------------------------------------------------------------------------
# weight packing (host-side jnp; cached by jit as a constant-folded prologue)
# --------------------------------------------------------------------------

def pack_weights(w: jax.Array, s: ConvSpec) -> jax.Array:
    """[kh, kw, Ci, Co] -> [K, P*Co] bf16 shifted-stack (see module doc)."""
    wp = jnp.zeros((s.kh, 2 * s.P, s.Ci, s.P, s.Co), w.dtype)
    for p in range(s.P):
        wp = wp.at[:, p : p + s.kw, :, p, :].set(w)
    return wp.reshape(s.K, s.N).astype(jnp.bfloat16)


def pack_bias(b: jax.Array, s: ConvSpec) -> jax.Array:
    """[Co] -> [1, P*Co] bf16, tiled per packed column."""
    return jnp.tile(b, (s.P,)).reshape(1, s.N).astype(jnp.bfloat16)


# --------------------------------------------------------------------------
# in-kernel building blocks (shared with the VJP kernels)
# --------------------------------------------------------------------------

def _load_padded(x, s: ConvSpec):
    """[bt, H, W*Ci] raw input -> [bt, Hp, Wp*Ci] bf16 zero-padded."""
    if s.scale_uint8:
        # Mosaic has no uint8->bf16 cast; hop through int32/f32 (VPU-cheap)
        x = x.astype(jnp.int32).astype(jnp.float32) * (1.0 / 255.0)
        x = x.astype(jnp.bfloat16)
    else:
        x = x.astype(jnp.bfloat16)
    lpad = s.pw * s.Ci
    rpad = s.Wp * s.Ci - s.W * s.Ci - lpad
    return jnp.pad(
        x, ((0, 0), (s.ph, s.kh - 1 - s.ph), (lpad, rpad))
    )


def _im2col_segs(xp, s: ConvSpec):
    """[bt, Hp, Wp*Ci] -> 2*kh segments [bt*H*G, PCi], K-ordered (ky, h).

    Never materializes the concatenated patch matrix: a 10-way lane concat
    is pure VPU relayout cost (measured 3x slower than XLA). Instead each
    (ky, h) segment feeds its own K=PCi matmul and the products accumulate
    in f32 — identical MXU slot count, zero shuffling. Requires PCi to be a
    multiple of 128 for the lane-split reshape (all 32/64-channel blocks).
    """
    bt = xp.shape[0]
    PCi = s.P * s.Ci
    segs = []
    for ky in range(s.kh):
        row = xp[:, ky : ky + s.H, :]                       # [bt, H, Wp*Ci]
        for h in (0, 1):
            seg = row[:, :, h * PCi : (s.G + h) * PCi]
            segs.append(seg.reshape(bt * s.H * s.G, PCi))
    return segs


def _matmul_segs(segs, w_ref, s: ConvSpec):
    """sum_t segs[t] @ w[t*PCi:(t+1)*PCi, :] with f32 accumulation."""
    PCi = s.P * s.Ci
    acc = None
    for t, seg in enumerate(segs):
        part = jnp.dot(
            seg,
            w_ref[t * PCi : (t + 1) * PCi, :],
            preferred_element_type=jnp.float32,
        )
        acc = part if acc is None else acc + part
    return acc                                              # [M, N] f32


def _pool_packed(acts, s: ConvSpec):
    """[bt, H, G, P*Co] relu'd acts -> pooled [bt, Ho, G, (P/2)*Co].

    x-pooling: adjacent column pairs live in adjacent Co-lane chunks of the
    same group (P even), so it's a lane-chunk max. y-pooling: split the row
    dim (a non-minor dim — Mosaic-legal reshape) and max the pair.
    """
    bt = acts.shape[0]
    cols = [
        jnp.maximum(
            acts[..., (2 * t) * s.Co : (2 * t + 1) * s.Co],
            acts[..., (2 * t + 1) * s.Co : (2 * t + 2) * s.Co],
        )
        for t in range(s.P // 2)
    ]
    ap = jnp.concatenate(cols, axis=-1)                     # [bt,H,G,(P/2)Co]
    ap = ap[:, : 2 * s.Ho].reshape(bt, s.Ho, 2, s.G, (s.P // 2) * s.Co)
    return jnp.maximum(ap[:, :, 0], ap[:, :, 1])            # [bt,Ho,G,(P/2)Co]


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, b_ref, y_ref, *, s: ConvSpec):
    bt = s.bt
    xp = _load_padded(x_ref[:], s)
    segs = _im2col_segs(xp, s)
    acts = _matmul_segs(segs, w_ref, s).astype(jnp.bfloat16)
    acts = jnp.maximum(acts + b_ref[:], jnp.bfloat16(0.0))
    acts = acts.reshape(bt, s.H, s.G, s.N)
    # output stays in the 4D packed layout [bt, Ho, G, lanes]; the wrapper
    # flattens/trims it with a free XLA reshape outside the kernel (lane
    # merges of sub-128 chunks are not Mosaic-legal in-kernel)
    if s.pool:
        y_ref[:] = _pool_packed(acts, s)
    else:
        y_ref[:] = acts


def _pad_batch(x: jax.Array, bt: int):
    B = x.shape[0]
    Bp = -(-B // bt) * bt
    if Bp != B:
        x = jnp.pad(x, ((0, Bp - B),) + ((0, 0),) * (x.ndim - 1))
    return x, B, Bp


def conv_block_fwd(
    x: jax.Array,
    w_packed: jax.Array,
    b_packed: jax.Array,
    s: ConvSpec,
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused conv+bias+relu(+pool). x: [B, H, W*Ci] (uint8 for block 0)."""
    assert supported(s), s
    x, B, Bp = _pad_batch(x, s.bt)
    # packed 4D output: pooled [Bp, Ho, G, (P/2)Co] or plain [Bp, H, G, P*Co]
    out_lanes = (s.P // 2 if s.pool else s.P) * s.Co
    y = pl.pallas_call(
        partial(_fwd_kernel, s=s),
        grid=(Bp // s.bt,),
        in_specs=[
            pl.BlockSpec(
                (s.bt, s.H, s.W * s.Ci), lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((s.K, s.N), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, s.N), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (s.bt, s.Ho, s.G, out_lanes), lambda i: (i, 0, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct(
            (Bp, s.Ho, s.G, out_lanes), jnp.bfloat16
        ),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=2 * Bp * s.H * s.G * s.K * s.N,
            bytes_accessed=x.size * x.dtype.itemsize
            + Bp * s.Ho * s.G * out_lanes * 2,
            transcendentals=0,
        ),
    )(x, w_packed, b_packed)
    # flatten the packed (G, lanes) pair and trim width padding — free in XLA
    y = y.reshape(Bp, s.Ho, s.G * out_lanes)[:B, :, : s.Wo * s.Co]
    return y


# --------------------------------------------------------------------------
# XLA reference path (tests + CPU fallback); identical op order
# --------------------------------------------------------------------------

def supported(s: ConvSpec) -> bool:
    """Mosaic-compilable geometry: lane-split reshapes need 128-multiples."""
    return (s.P * s.Ci) % 128 == 0 and s.kw <= s.P + 1 and s.P % 2 == 0


def _primal(x, w, b, s: ConvSpec, interpret: bool):
    return conv_block_fwd(
        x, pack_weights(w, s), pack_bias(b, s), s, interpret=interpret
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def conv_block(x, w, b, s: ConvSpec, interpret: bool = False):
    """Trainable fused block: Pallas forward, XLA-vjp backward.

    The backward recomputes the reference forward for its VJP — fine for
    the default-off status of this backend; a Pallas backward was designed
    (unpool-scatter + packed dW/dx GEMMs) but not built once the forward
    A/B came back negative (PERF.md).
    """
    return _primal(x, w, b, s, interpret)


def _cb_fwd(x, w, b, s, interpret):
    return _primal(x, w, b, s, interpret), (x, w, b)


def _cb_bwd(s, interpret, res, g):
    x, w, b = res
    _, vjp = jax.vjp(
        lambda xx, ww, bb: reference_block(xx, ww, bb, s), x, w, b
    )
    return vjp(g.astype(jnp.bfloat16))


conv_block.defvjp(_cb_fwd, _cb_bwd)


def reference_block(
    x: jax.Array, w: jax.Array, b: jax.Array, s: ConvSpec
) -> jax.Array:
    """x: [B, H, W*Ci] -> [B, Ho, Wo*Co], plain XLA ops, same op order."""
    B = x.shape[0]
    x = x.reshape(B, s.H, s.W, s.Ci)
    if s.scale_uint8:
        x = x.astype(jnp.bfloat16) * jnp.bfloat16(1.0 / 255.0)
    else:
        x = x.astype(jnp.bfloat16)
    y = jax.lax.conv_general_dilated(
        x,
        w.astype(jnp.bfloat16),
        window_strides=(1, 1),
        padding=[(s.ph, s.kh - 1 - s.ph), (s.pw, s.kw - 1 - s.pw)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    y = jnp.maximum(y + b.astype(jnp.bfloat16), jnp.bfloat16(0.0))
    if s.pool:
        # reshape-max instead of reduce_window: identical values, and it
        # reverse-differentiates cleanly inside the custom-vjp backward
        # (reduce_window's linearization fails there on the TPU backend)
        y = y[:, : 2 * s.Ho, : 2 * s.Wo, :].reshape(
            B, s.Ho, 2, s.Wo, 2, s.Co
        )
        y = jnp.max(jnp.max(y, axis=4), axis=2)
    return y.reshape(B, s.Ho, s.Wo * s.Co)
