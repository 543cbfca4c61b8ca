"""The quantized serving/actor forward and its publish-time table build.

Two pieces, mirroring the bf16 rung's split exactly:

- :func:`quantize_params` is the PUBLISH step (the overlap prep-cast /
  ``BatchedPredictor._put_policy`` cast, int8 edition): f32 params in,
  int8 serving table out — per-out-channel symmetric weight scales, int8
  kernels, f32 biases, plus the frozen per-tensor activation scale from
  the :class:`~distributed_ba3c_tpu.quantize.spec.QuantSpec`. One small
  jittable pass, amortized over a whole publish interval.
- :func:`make_quant_apply` is the FORWARD: a plain-lax mirror of
  ``BA3CNet.__call__`` built from the shared
  :func:`~distributed_ba3c_tpu.models.a3c.conv_layout` seam (the two
  cannot drift), with two arms:

  * ``int8`` (dequant-free): activations fake-quantize to the int8 grid,
    the conv/dot runs int8 x int8 -> int32 on the MXU-native path
    (``preferred_element_type=int32``), and ONE f32 epilogue folds
    ``act_scale * w_scale`` into the bias add. This is the arm the audit
    entries ``predict.server_int8``/``fused.actor_int8`` pin (T1 proves
    every conv operand is int8).
  * ``folded`` (the no-int8-conv fallback): the conv runs on the int8
    kernel VALUES carried in bf16 (integers <= 127 are exact in bf16)
    with unquantized bf16 activations, and the f32 epilogue applies the
    weight scale — same quantized weights, no int8 compute required.

The policy/value heads and the PReLU stay f32 in both arms (the
models/a3c.py contract): log mu(a|s) keeps its precision and V-trace's
measured-lag correction absorbs the behavior-policy quantization drift.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from distributed_ba3c_tpu.models.a3c import PolicyValue, conv_layout
from distributed_ba3c_tpu.quantize.spec import QuantSpec

#: forward arms: ``auto`` resolves per-backend at build time
QUANT_ARMS = ("auto", "int8", "folded")

_DIMENSION_NUMBERS = ("NHWC", "HWIO", "NHWC")

#: cached int8-conv capability probe result per backend
_INT8_CONV_OK: dict = {}


def quant_layer_names(model) -> tuple:
    """The layers the int8 rung quantizes: the conv stack + the big FC
    (``Dense_0``). The heads (``Dense_1``/``Dense_2``) and ``PReLU_0``
    stay f32 — they are tiny, and they own the precision of the
    log-prob/value record V-trace corrects against."""
    return tuple(
        f"Conv_{i}" for i in range(len(conv_layout(model)))
    ) + ("Dense_0",)


def int8_conv_supported(backend: str = "") -> bool:
    """Can this backend compile an int8 x int8 -> int32 conv?

    Probed ONCE per backend with a 1-pixel conv; the result is cached.
    The CPU client and the v5e (jax 0.9.0, libtpu 0.0.34: measured PR 21)
    both support it; the probe exists so the ``auto`` arm degrades to
    ``folded`` instead of crashing on a backend that doesn't — LOUDLY: on
    a TPU that is a kernel giving way to a bf16 reference."""
    backend = backend or jax.default_backend()
    ok = _INT8_CONV_OK.get(backend)
    if ok is None:
        try:
            x = jnp.zeros((1, 2, 2, 1), jnp.int8)
            w = jnp.zeros((1, 1, 1, 1), jnp.int8)
            jax.jit(
                lambda a, b: lax.conv_general_dilated(
                    a, b, (1, 1), "SAME",
                    dimension_numbers=_DIMENSION_NUMBERS,
                    preferred_element_type=jnp.int32,
                )
            )(x, w).block_until_ready()
            ok = True
        except Exception as e:  # whatever the compiler raises means "no"
            from distributed_ba3c_tpu.utils import logger

            logger.warn(
                "backend %s refused an int8 x int8 -> int32 conv (%r): the "
                "`auto` quant arm resolves to the bf16 `folded` reference, "
                "not int8 compute",
                backend, e,
            )
            ok = False
        _INT8_CONV_OK[backend] = ok
    return ok


def _resolve_arm(arm: str) -> str:
    if arm not in QUANT_ARMS:
        raise ValueError(f"quant arm must be one of {QUANT_ARMS}, got {arm!r}")
    if arm == "auto":
        return "int8" if int8_conv_supported() else "folded"
    return arm


def _weight_scale(kernel: jax.Array) -> jax.Array:
    """Per-OUT-CHANNEL symmetric scale: absmax over every other axis,
    mapped so the channel's largest weight lands exactly on +/-127. A
    zero-range channel (all-zero weights — freshly initialized biases'
    neighbors, pruned channels) gets scale 1.0: its quantized weights
    are exactly 0 either way, and the scale stays finite (no NaN/inf
    anywhere downstream)."""
    absmax = jnp.max(jnp.abs(kernel), axis=tuple(range(kernel.ndim - 1)))
    return jnp.where(absmax > 0, absmax / 127.0, 1.0).astype(jnp.float32)


def _quantize_tensor(x: jax.Array, scale) -> jax.Array:
    return jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)


def quantize_params(params, spec: QuantSpec):
    """f32 param pytree -> the int8 serving table (jittable; ``spec`` is
    static — close over it or ``functools.partial`` it before jit).

    Quantized layers become ``{kernel_q int8, w_scale f32[co], bias f32,
    act_scale f32[]}``; every other layer (the f32 heads, PReLU) passes
    through untouched. The act scale rides IN the table so the compiled
    forward depends only on avals, never on spec values — one program
    serves every calibration."""
    missing = sorted(set(spec.act_scales) - set(params))
    if missing:
        raise ValueError(
            f"quant spec names layers absent from params: {missing}"
        )
    out = {}
    for name, leaves in params.items():
        if name not in spec.act_scales:
            out[name] = leaves
            continue
        kernel = jnp.asarray(leaves["kernel"], jnp.float32)
        w_scale = _weight_scale(kernel)
        out[name] = {
            "kernel_q": _quantize_tensor(kernel, w_scale),
            "w_scale": w_scale,
            "bias": jnp.asarray(leaves["bias"], jnp.float32),
            "act_scale": jnp.asarray(spec.act_scales[name], jnp.float32),
        }
    return out


def _conv_int8(x: jax.Array, p: dict) -> jax.Array:
    xq = _quantize_tensor(x, p["act_scale"])
    y = lax.conv_general_dilated(
        xq, p["kernel_q"], (1, 1), "SAME",
        dimension_numbers=_DIMENSION_NUMBERS,
        preferred_element_type=jnp.int32,
    )
    # ONE f32 epilogue: int32 accumulator * (s_act * s_w[co]) + bias
    return y.astype(jnp.float32) * (p["act_scale"] * p["w_scale"]) + p["bias"]


def _conv_folded(x: jax.Array, p: dict) -> jax.Array:
    y = lax.conv_general_dilated(
        x.astype(jnp.bfloat16), p["kernel_q"].astype(jnp.bfloat16),
        (1, 1), "SAME",
        dimension_numbers=_DIMENSION_NUMBERS,
        preferred_element_type=jnp.float32,
    )
    return y * p["w_scale"] + p["bias"]


def _dense_int8(x: jax.Array, p: dict) -> jax.Array:
    xq = _quantize_tensor(x, p["act_scale"])
    y = lax.dot_general(
        xq, p["kernel_q"], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return y.astype(jnp.float32) * (p["act_scale"] * p["w_scale"]) + p["bias"]


def _dense_folded(x: jax.Array, p: dict) -> jax.Array:
    y = lax.dot_general(
        x.astype(jnp.bfloat16), p["kernel_q"].astype(jnp.bfloat16),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return y * p["w_scale"] + p["bias"]


def make_quant_apply(model, arm: str = "auto") -> Callable:
    """Build ``apply(qparams, states) -> PolicyValue``, the quantized
    mirror of ``model.apply({'params': p}, states)``.

    The layout comes from :func:`conv_layout` — the same triples the f32
    forward executes — so adding/resizing a conv layer updates both
    programs from one place."""
    layout = conv_layout(model)
    arm = _resolve_arm(arm)
    conv = _conv_int8 if arm == "int8" else _conv_folded
    dense = _dense_int8 if arm == "int8" else _dense_folded

    def apply_fn(qparams, state: jax.Array) -> PolicyValue:
        x = state.astype(jnp.float32)
        if state.dtype == jnp.uint8:
            x = x / 255.0
        for i, (_feats, _k, pooled) in enumerate(layout):
            x = nn.relu(conv(x, qparams[f"Conv_{i}"]))
            if pooled:
                x = nn.max_pool(x, window_shape=(2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = dense(x, qparams["Dense_0"])
        alpha = qparams["PReLU_0"]["alpha"].astype(x.dtype)
        x = jnp.where(x >= 0, x, alpha * x)
        logits = x @ qparams["Dense_1"]["kernel"] + qparams["Dense_1"]["bias"]
        value = (x @ qparams["Dense_2"]["kernel"]
                 + qparams["Dense_2"]["bias"])[:, 0]
        return PolicyValue(logits=logits, value=value)

    apply_fn.quant_arm = arm
    return apply_fn


def make_quant_fwd_sample(model, greedy: bool = False,
                          arm: str = "auto") -> Callable:
    """The int8 action server's compiled program: quantized forward + the
    SAME on-device sampling + single-fetch packing contract as
    ``predict.server.make_fwd_sample`` ([3, B] greedy / [4, B] sampling,
    f32) — the scheduler's ``_unpack`` serves either without knowing the
    table's precision. Module-level so the audit registry traces the
    same function the live predictor jits (entry ``predict.server_int8``)."""
    qapply = make_quant_apply(model, arm=arm)

    def fwd_sample(qparams, states, key):
        out = qapply(qparams, states)
        if greedy:
            actions = jnp.argmax(out.logits, axis=-1)
        else:
            actions = jax.random.categorical(key, out.logits, axis=-1)
        actions = actions.astype(jnp.int32)
        log_probs = jax.nn.log_softmax(out.logits, axis=-1)
        logp = jnp.take_along_axis(log_probs, actions[:, None], axis=-1)[:, 0]
        rows = [actions.astype(jnp.float32), out.value, logp]
        if not greedy:
            rows.append(jnp.argmax(out.logits, axis=-1).astype(jnp.float32))
        return jnp.stack(rows)

    fwd_sample.quant_arm = qapply.quant_arm
    return fwd_sample
