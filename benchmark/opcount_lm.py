"""Parameters, operations and bytes of LFM2-8B-A1B's layers as one chip
holds them, counted from the configuration's shapes.

The counts are what the algorithm needs, not what a compiler emits. A token
costs one rollout forward (a decode step) and, in the learner, one forward,
the weight gradient of every matrix and the input gradient of every matrix
(the embedding's lookup has neither product). A recomputed forward
(rematerialisation) is work the program chose and is not counted. The
expert layers are counted at the visits the router made: ``visits`` a
token a layer lands on an expert held here (1.0 expected: 4 of 32 chosen,
8 of 32 held); the router itself runs over all experts.
"""

from __future__ import annotations

from typing import Dict, List


def layers(cfg: dict) -> List[dict]:
    """Per held layer: its operator's and its feed-forward's parameters and
    MACs a token (feed-forward MACs of an expert layer: one visit's)."""
    d, f, fe = cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]
    head = d // cfg["num_attention_heads"]
    hq, hkv = cfg["num_attention_heads"] * head, cfg["num_key_value_heads"] * head
    out = []
    for i in cfg["held"]["layers"]:
        if cfg["layer_types"][i] == "conv":
            op_macs = d * 3 * d + d * d
            op_params = op_macs + cfg["conv_L_cache"] * d
        else:
            op_macs = d * hq + 2 * d * hkv + hq * d
            op_params = op_macs + 2 * head
        row = {"layer": i, "op": cfg["layer_types"][i], "op_macs": op_macs,
               "op_params": op_params + d, "norm_params": d}
        if i < cfg["num_dense_layers"]:
            row.update(ffn="dense", ffn_macs=3 * d * f, ffn_params=3 * d * f,
                       router_macs=0, router_params=0)
        else:
            all_experts = cfg["published"]["num_experts"]
            row.update(ffn="experts", ffn_macs=3 * d * fe,
                       ffn_params=cfg["num_experts"] * 3 * d * fe,
                       router_macs=d * all_experts,
                       router_params=d * all_experts + all_experts)
        out.append(row)
    return out


def params_held(cfg: dict) -> int:
    """Parameters this chip holds (value head and the bias buffer counted)."""
    d = cfg["hidden_size"]
    body = sum(l["op_params"] + l["norm_params"] + l["ffn_params"]
               + l["router_params"] for l in layers(cfg))
    return cfg["vocab_size"] * d + body + d + (d + 1)


def forward_macs(cfg: dict, visits: float = 1.0) -> Dict[str, float]:
    """MACs of one forward of one token, by part."""
    rows = layers(cfg)
    return {
        "operators": sum(l["op_macs"] for l in rows),
        "ffn_dense": sum(l["ffn_macs"] for l in rows if l["ffn"] == "dense"),
        "experts": visits * sum(l["ffn_macs"] for l in rows if l["ffn"] == "experts"),
        "router": sum(l["router_macs"] for l in rows),
        "head": cfg["vocab_size"] * cfg["hidden_size"],
    }


def flops_per_env_step(cfg: dict, visits: float = 1.0) -> float:
    """Matrix FLOPs a fused env-step (one token) needs: the rollout's
    forward, the learner's forward, dW and dx of every product."""
    return 2 * 4 * sum(forward_macs(cfg, visits).values())


def expert_layers(cfg: dict) -> int:
    return sum(l["ffn"] == "experts" for l in layers(cfg))


def experts_flops(cfg: dict, visits_total: float) -> float:
    """FLOPs of the grouped products for ``visits_total`` (token, held
    expert) visits: forward, dW and dx of the three matrices a visit."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2 * 3 * d * fe * visits_total


def experts_bytes(cfg: dict, visits_total: float, passes: int,
                  act_bytes: int = 2) -> float:
    """Least HBM traffic of those products: every held expert's three
    matrices read once a pass at the compute type's width (``passes``:
    decode steps, plus three an unrolled chunk: forward, dW, dx), each
    visit's rows read and written once a product."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_experts"] * 3 * d * fe * act_bytes * expert_layers(cfg)
    rows = visits_total * (2 * (d + fe) + (fe + d)) * act_bytes
    return passes * weights + rows


def decode_weight_bytes(cfg: dict, weight_bytes: int = 2) -> float:
    """Bytes of weights one decode step reads: every parameter held, at
    the rollout snapshot's width."""
    return params_held(cfg) * weight_bytes
