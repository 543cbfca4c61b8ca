"""Parameters, operations and bytes of Phi-4-mini-flash-reasoning's layers
as one chip holds them, counted from the configuration's shapes.

The counts are what the algorithm needs, not what a compiler emits. A token
costs one rollout forward (a decode step) and, in the learner, one forward,
the weight gradient of every matrix and the input gradient of every matrix
(the embedding's lookup has neither product). A recomputed forward
(rematerialisation) is work the program chose and is not counted. An
attention layer's products against its keys and values are counted at the
mean context an episode of ``T`` positions gives it: ``min(t + 1, window)``
positions a window layer, ``t + 1`` a full or cross layer.
"""

from __future__ import annotations

from typing import Dict, List

from benchmark.reference.phi4_flash import CROSS, FULL, GMU, MAMBA, WINDOW, kind_of

ATTENTION = (WINDOW, FULL, CROSS)


def layers(cfg: dict) -> List[dict]:
    """Per held layer: its kind, its mixer's parameters and matrix MACs a
    token, and its feed-forward's (every layer: SwiGLU, two LayerNorms)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    ssm = cfg["state_space"]
    c, n, R = ssm["d_inner"], ssm["d_state"], ssm["dt_rank"]
    D = d // cfg["num_attention_heads"]
    hq, hkv = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    out = []
    for i in cfg["held"]["layers"]:
        kind = kind_of(i, cfg["published"]["num_hidden_layers"])
        if kind == MAMBA:
            macs = d * 2 * c + c * (R + 2 * n) + R * c + c * d
            # conv taps and bias, dt bias, A_log, D
            params = macs + ssm["d_conv"] * c + c + c + c * n + c
        elif kind == GMU:
            macs = params = 2 * d * c
        else:
            macs = (d * hq if kind == CROSS else d * (hq + 2 * hkv)) + hq * d
            # biases of both products, four vectors of lam, the norm's gain
            params = macs + (hq if kind == CROSS else hq + 2 * hkv) + d + 4 * D + 2 * D
        out.append({"layer": i, "kind": kind, "mixer_macs": macs,
                    "mixer_params": params, "ffn_macs": 3 * d * f,
                    "ffn_params": 3 * d * f, "norm_params": 4 * d})
    return out


def params_held(cfg: dict) -> int:
    """Parameters this chip holds (the final norm and the value head counted)."""
    d = cfg["hidden_size"]
    body = sum(l["mixer_params"] + l["ffn_params"] + l["norm_params"]
               for l in layers(cfg))
    return cfg["vocab_size"] * d + body + 2 * d + (d + 1)


def mean_context(kind: str, episode: int, window: int) -> float:
    """Positions a token of an episode reads in an attention layer, mean."""
    if kind == WINDOW:
        return sum(min(t + 1, window) for t in range(episode)) / episode
    return (episode + 1) / 2


def forward_macs(cfg: dict, episode: int) -> Dict[str, float]:
    """MACs of one forward of one token, by part. ``scan``: the recurrence
    itself, three products a state (decay, input, read-out) of ``c x n``
    states; ``context``: 40 query heads of 64 against their keys and 40
    softmaxes against 128-wide value pairs, at the mean context."""
    rows = layers(cfg)
    d = cfg["hidden_size"]
    ssm = cfg["state_space"]
    by_kind = lambda kinds: sum(  # noqa: E731
        l["mixer_macs"] for l in rows if l["kind"] in kinds)
    return {
        "mamba": by_kind((MAMBA,)),
        "scan": sum(3 * ssm["d_inner"] * ssm["d_state"]
                    for l in rows if l["kind"] == MAMBA),
        "attention": by_kind(ATTENTION),
        "context": sum(
            3 * d * mean_context(l["kind"], episode, cfg["sliding_window"])
            for l in rows if l["kind"] in ATTENTION),
        "memory_unit": by_kind((GMU,)),
        "ffn": sum(l["ffn_macs"] for l in rows),
        "head": cfg["vocab_size"] * d,
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
    ``carry_bytes_per_env``: the program's own count by kind (state-space
    state and conv tail; the window layers' rings; the shared K/V; the
    position). The state-space state and tail are read and written whole;
    a ring is read up to ``min(t + 1, window)`` of its rows and the shared
    K/V up to ``t + 1`` of its rows, the latter once a reader (the full
    layer and every cross layer); a written K/V row is one of ``window`` or
    ``episode``."""
    ssm, ring, shared, pos = (float(x) for x in carry_bytes_per_env)
    window = cfg["sliding_window"]
    kinds = [l["kind"] for l in layers(cfg)]
    readers = sum(k in (FULL, CROSS) for k in kinds)
    ring_read = mean_context(WINDOW, episode, window) / window
    shared_read = mean_context(FULL, episode, window) / episode
    an_env = (2 * ssm + ring * (ring_read + 1 / window)
              + shared * (readers * shared_read + 1 / episode) + 2 * pos)
    return envs * an_env


def scan_bytes(cfg: dict, tokens: float, act_bytes: int = 4) -> float:
    """Least HBM traffic of the learner's selective scans over ``tokens``
    positions, all state-space layers: forward reads ``u``, ``dt`` (``c``
    wide), ``B``, ``C`` (``n`` wide) and writes ``y``; backward reads those
    four and ``dy`` and writes their four gradients. The state never needs
    to leave the chip's fast memory."""
    ssm = cfg["state_space"]
    c, n = ssm["d_inner"], ssm["d_state"]
    a_token = (3 * c + 2 * n) + (3 * c + 2 * n) + (2 * c + 2 * n)
    n_layers = sum(l["kind"] == MAMBA for l in layers(cfg))
    return tokens * n_layers * a_token * act_bytes
