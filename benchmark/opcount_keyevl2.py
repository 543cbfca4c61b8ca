"""Parameters, operations and bytes of Keye-VL-2.0-30B-A3B's language-model
layers as one chip holds them, counted from the configuration's shapes.

The counts are what the algorithm needs, not what a compiler emits. A token
costs one rollout forward (a decode step) and, in the learner, one forward,
the weight gradient of every matrix and the input gradient of every matrix.
A recomputed forward is work the program chose and is not counted. The main
attention's products are counted at the mean SELECTION an episode of ``T``
positions gives (``min(t + 1, topk)`` keys: the selection is the
algorithm, whatever a masked-dense learner multiplies besides); the
indexer's scores at the mean CONTEXT (every live key is scored); an expert
layer by the (token, held expert) visits the program counted, a layer, or
by ``top_k * held / experts`` a token where no count is at hand.
"""

from __future__ import annotations

from typing import Dict, Optional


def layer(cfg: dict) -> Dict[str, int]:
    """One held layer's parameters by part (every layer is of one kind)."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {
        # q, k, v, o; the two per-head gains
        "attention": d * hq + 2 * d * hkv + hq * d + 2 * D,
        # W_q^I, W_k^I, W_w; the key norm's gain and bias
        "indexer": d * hi * di + d * di + d * hi + 2 * di,
        "router": d * cfg["published"]["num_experts"],
        "expert": 3 * d * cfg["moe_intermediate_size"],
        "norms": 2 * d,
    }


def params_held(cfg: dict) -> int:
    """Parameters this chip holds: the layers, embedding and untied head
    over the held ids, the final norm and the value head."""
    d = cfg["hidden_size"]
    a = layer(cfg)
    body = (a["attention"] + a["indexer"] + a["router"] + a["norms"]
            + cfg["num_experts"] * a["expert"])
    return (len(cfg["held"]["layers"]) * body + 2 * cfg["vocab_size"] * d
            + d + (d + 1))


def mean_selection(episode: int, topk: int) -> float:
    """Keys a query reads in the main attention, mean over an episode."""
    return sum(min(t + 1, topk) for t in range(episode)) / episode


def mean_context(episode: int) -> float:
    """Live keys a query's indexer scores, mean over an episode."""
    return (episode + 1) / 2


def forward_macs(cfg: dict, episode: int,
                 visits_per_token: Optional[float] = None) -> Dict[str, float]:
    """MACs of one forward of one token, by part, all held layers.
    ``visits_per_token``: (token, held expert) visits a token a layer, as
    counted; None: an even router's ``top_k * held / experts``."""
    d, D = cfg["hidden_size"], cfg["head_dim"]
    H = cfg["num_attention_heads"]
    sa = cfg["sa_config"]
    a, n = layer(cfg), len(cfg["held"]["layers"])
    if visits_per_token is None:
        visits_per_token = (cfg["num_experts_per_tok"] * cfg["num_experts"]
                            / cfg["published"]["num_experts"])
    return {
        "attention": n * (a["attention"] - 2 * D),
        # q . k and probs . v over the selected keys, 32 heads of 128
        "selected": n * 2 * H * D * mean_selection(episode, sa["topk"]),
        "indexer": n * (a["indexer"] - 2 * sa["indexer_head_dim"]),
        # 16 heads of 64 against every live key
        "index_scores": n * sa["indexer_num_heads"] * sa["indexer_head_dim"]
        * mean_context(episode),
        "router": n * a["router"],
        "experts": n * visits_per_token * a["expert"],
        "head": cfg["vocab_size"] * d,
    }


def flops_per_env_step(cfg: dict, episode: int,
                       visits_per_token: Optional[float] = None) -> float:
    """FLOPs a fused env-step (one token) needs: the rollout's forward, the
    learner's forward, dW and dx of every product."""
    return 2 * 4 * sum(forward_macs(cfg, episode, visits_per_token).values())


def decode_weight_bytes(cfg: dict, weight_bytes: int = 2) -> float:
    """Bytes of weights one decode step reads: every parameter held, at the
    rollout snapshot's width."""
    return params_held(cfg) * weight_bytes


def decode_carry_bytes(cfg: dict, carry_bytes_per_env, envs: int,
                       episode: int) -> float:
    """Bytes of carry one decode step must move, the mean over an episode.
    ``carry_bytes_per_env``: the program's own count by kind (K/V; the
    indexer's keys; the position). K and V are read at the ``min(t + 1,
    topk)`` selected rows of ``episode`` and written at one; the indexer's
    keys are read up to the position (``t + 1`` rows) and written at one."""
    kv, index_keys, pos = (float(x) for x in carry_bytes_per_env)
    topk = cfg["sa_config"]["topk"]
    an_env = (kv * (mean_selection(episode, topk) + 1) / episode
              + index_keys * (mean_context(episode) + 1) / episode + 2 * pos)
    return envs * an_env
