"""Operations and bytes of BA3CNet's layers, counted from shapes.

The counts are what the algorithm needs, not what a compiler emits: one
rollout forward an env-step; for the learner one forward, the weight
gradient of every layer and the input gradient of every layer but the first
(nothing is differentiated with respect to the frames). A recomputed forward
or a relayout copy is work the program chose and is not counted.
"""

from __future__ import annotations

from typing import List


def conv_layers(cfg: dict) -> List[dict]:
    """Per conv layer: output side, channels in/out, kernel, MACs a sample."""
    side, c_in = cfg["image_size"], cfg["frame_history"]
    out = []
    for feats, k, pooled in zip(
        cfg["conv_features"], cfg["conv_kernels"], cfg["pooled_layers"], strict=True
    ):
        out.append({
            "side": side, "c_in": c_in, "c_out": feats, "kernel": k,
            "macs": side * side * k * k * c_in * feats,
        })
        c_in, side = feats, side // 2 if pooled else side
    return out


def dense_layers(cfg: dict) -> List[dict]:
    last = conv_layers(cfg)[-1]
    side = last["side"] // 2 if cfg["pooled_layers"][-1] else last["side"]
    flat = side * side * last["c_out"]
    fc, acts = cfg["fc_units"], cfg["num_actions"]
    return [
        {"n_in": flat, "n_out": fc, "macs": flat * fc},
        {"n_in": fc, "n_out": acts, "macs": fc * acts},
        {"n_in": fc, "n_out": 1, "macs": fc},
    ]


def forward_macs(cfg: dict) -> int:
    """Multiply-accumulates of one forward of one sample."""
    return sum(l["macs"] for l in conv_layers(cfg) + dense_layers(cfg))


def conv_flops_per_env_step(cfg: dict) -> int:
    """Convolution FLOPs a fused env-step needs: rollout forward, learner
    forward, dW of each conv and dx of every conv but the first."""
    layers = conv_layers(cfg)
    fwd = sum(l["macs"] for l in layers)
    dx = sum(l["macs"] for l in layers[1:])
    return 2 * (fwd + fwd + fwd + dx)


def flops_per_env_step(cfg: dict) -> int:
    """All matrix FLOPs a fused env-step needs (convs and dense layers)."""
    dense = sum(l["macs"] for l in dense_layers(cfg))
    # rollout forward, learner forward, dW, dx (the fc's input has a dx)
    return conv_flops_per_env_step(cfg) + 2 * 4 * dense


def conv_bytes_per_env_step(cfg: dict, act_bytes: int = 2) -> int:
    """Least HBM traffic of those convolutions, each reading its operands
    and writing its result once at the compute type's width (weights are
    shared by a batch and left out; the first layer reads uint8 frames)."""
    total = 0
    for i, l in enumerate(conv_layers(cfg)):
        pixels = l["side"] * l["side"]
        x = pixels * l["c_in"] * (1 if i == 0 else act_bytes)
        y = pixels * l["c_out"] * act_bytes
        # rollout fwd and learner fwd: read x, write y; dW: read x and dy;
        # dx (not for layer 0): read dy, write dx
        total += 2 * (x + y) + (x + y)
        if i > 0:
            total += y + pixels * l["c_in"] * act_bytes
    return total


def bytes_per_env_step(cfg: dict, act_bytes: int = 2) -> int:
    """Least HBM traffic of the convolutions and the dense layers."""
    total = conv_bytes_per_env_step(cfg, act_bytes)
    for l in dense_layers(cfg):
        # two forwards, dW and dx, each reading and writing activations once
        total += 4 * (l["n_in"] + l["n_out"]) * act_bytes
    return total
