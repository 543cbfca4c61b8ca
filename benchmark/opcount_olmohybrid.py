"""Parameters, operations and bytes of Olmo-Hybrid-7B's layers as one chip
holds them, counted from the configuration's shapes (the heads of every
mixer that the chip holds, the feed-forwards whole).

The counts are what the algorithm needs, not what a compiler emits. A token
costs one rollout forward (a decode step) and, in the learner, one forward,
the weight gradient of every matrix and the input gradient of every matrix
(the embedding's lookup has neither product). A recomputed forward
(rematerialisation) is work the program chose and is not counted. The full
layer's products against its keys and values are counted at the mean
context an episode of ``T`` positions gives it, ``(T + 1) / 2``. The delta
rule is counted as its recurrence: three products a state a position (what
the state held along ``k``, the rank-one write, the read-out by ``q``); the
chunked form spends more products than that to run them on the matrix unit,
which is its choice.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.reference.olmo_hybrid import FULL, LINEAR

#: positions between two states the learner's delta rule must keep
CHUNK = 64


def layers(cfg: dict) -> List[dict]:
    """Per held layer: its kind, its mixer's parameters and matrix MACs a
    token, and its feed-forward's (every layer: SwiGLU, two RMSNorms)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    H, K, V = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
               cfg["linear_value_head_dim"])
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    out = []
    for i in cfg["held"]["layers"]:
        kind = cfg["layer_types"][i]
        if kind == LINEAR:
            width = H * (2 * K + V)  # the conv's channels: q, k and v
            macs = d * width + d * H * V + 2 * d * H + H * V * d
            # conv taps, A_log, dt_bias, the per-head norm's gain
            params = macs + cfg["linear_conv_kernel_dim"] * width + 2 * H + V
        else:
            macs = 4 * d * hq
            params = macs + 2 * hq  # the gains of the q and the k norm
        out.append({"layer": i, "kind": kind, "mixer_macs": macs,
                    "mixer_params": params, "ffn_macs": 3 * d * f,
                    "ffn_params": 3 * d * f, "norm_params": 2 * d})
    return out


def params_held(cfg: dict) -> int:
    """Parameters this chip holds (embedding, untied head, the final norm
    and the value head counted)."""
    d = cfg["hidden_size"]
    body = sum(l["mixer_params"] + l["ffn_params"] + l["norm_params"]
               for l in layers(cfg))
    return 2 * cfg["vocab_size"] * d + body + d + (d + 1)


def delta_rule_macs(cfg: dict) -> int:
    """MACs a position of ONE linear layer's recurrence needs, all held heads."""
    return 3 * cfg["linear_num_key_heads"] * (
        cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"])


def forward_macs(cfg: dict, episode: int) -> Dict[str, float]:
    """MACs of one forward of one token, by part: the linear mixers'
    projections, their recurrences (``delta``), the full mixers'
    projections, their products against keys and values at the mean context
    (``context``), the feed-forwards, the head."""
    rows = layers(cfg)
    by_kind = lambda kind: sum(  # noqa: E731
        l["mixer_macs"] for l in rows if l["kind"] == kind)
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    return {
        "linear": by_kind(LINEAR),
        "delta": delta_rule_macs(cfg) * _linear_layers(cfg),
        "attention": by_kind(FULL),
        "context": 2 * hq * (episode + 1) / 2 * sum(
            l["kind"] == FULL for l in rows),
        "ffn": sum(l["ffn_macs"] for l in rows),
        "head": cfg["vocab_size"] * cfg["hidden_size"],
    }


def flops_per_env_step(cfg: dict, episode: int) -> float:
    """FLOPs a fused env-step (one token) needs: the rollout's forward, the
    learner's forward, dW and dx of every product."""
    return 2 * 4 * sum(forward_macs(cfg, episode).values())


def decode_weight_bytes(cfg: dict, weight_bytes: int = 2) -> float:
    """Bytes of weights one decode step reads: every parameter held, at the
    rollout snapshot's width."""
    return params_held(cfg) * weight_bytes


def decode_carry_bytes(cfg: dict, carry_bytes_per_env, envs: int,
                       episode: int) -> float:
    """Bytes of carry one decode step must move, the mean over an episode.
    ``carry_bytes_per_env``: the program's own count by kind (the delta
    rule's states; the convs' tails; the K/V buffers; the position and the
    last gates). States, tails and the small leaves are read and written
    whole; the K/V is read up to ``t + 1`` of its rows, and one row is
    written."""
    states, tails, kv, small = (float(x) for x in carry_bytes_per_env)
    kv_read = (episode + 1) / 2 / episode
    return envs * (2 * states + 2 * tails + kv * (kv_read + 1 / episode)
                   + 2 * small)


def _linear_layers(cfg: dict) -> int:
    return sum(l["kind"] == LINEAR for l in layers(cfg))


def delta_rule_flops(cfg: dict, tokens: float) -> float:
    """FLOPs the learner's delta rules need over ``tokens`` positions, all
    linear layers: the recurrence forward and twice that backward."""
    return 2 * 3 * delta_rule_macs(cfg) * _linear_layers(cfg) * tokens


def delta_rule_bytes(cfg: dict, tokens: float, act_bytes: int = 4) -> float:
    """Least HBM traffic of the learner's delta rules over ``tokens``
    positions, all linear layers: forward reads ``q``, ``k`` (``K`` wide a
    head), ``v`` (``V``), the two gates, and writes ``o``; backward reads
    those five and ``do`` and writes five gradients; the state at a chunk's
    boundary (``K x V`` a head every 64 positions) is written once and read
    once. Inside a chunk the state need not leave the chip's fast memory."""
    H, K, V = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
               cfg["linear_value_head_dim"])
    a_token = (2 * K + 2 * V + 2) + (2 * K + 2 * V + 2) + (2 * K + V + 2)
    boundary = 2 * K * V / CHUNK
    return tokens * _linear_layers(cfg) * H * (a_token + boundary) * act_bytes
