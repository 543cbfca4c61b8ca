"""Parameters, operations and bytes of Nemotron-3-Nano-30B-A3B's blocks as
one chip holds them, counted from the configuration's shapes (the routed
experts of every expert block that the chip holds; Mamba-2 mixers, the
attention block, shared experts and routers whole).

The counts are what the algorithm needs, not what a compiler emits. A token
costs one rollout forward (a decode step) and, in the learner, one forward,
the weight gradient of every matrix and the input gradient of every matrix
(the embedding's lookup has neither product). A recomputed forward
(rematerialisation) is work the program chose and is not counted. The
attention block's products against its keys and values are counted at the
mean context an episode of ``T`` positions gives it, ``(T + 1) / 2``. The
routed experts are counted at the visits the router made: ``visits`` a
token a block lands on an expert held here (0.375 expected: 6 of 128
chosen, 8 of 128 held); the router itself runs over all experts. The
Mamba-2 recurrence is counted as the recurrence: three products a state a
position (the decay, the rank-one write, the read-out by ``C``); the chunked
form spends about as many on the matrix unit, which is its choice.
"""

from __future__ import annotations

from typing import Dict, List

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _sizes(cfg: dict) -> dict:
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    return {
        "d": cfg["hidden_size"], "h": cfg["mamba_num_heads"], "inner": inner,
        "width": inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"],
        "hq": cfg["num_attention_heads"] * cfg["head_dim"],
        "hkv": cfg["num_key_value_heads"] * cfg["head_dim"],
        "fe": cfg["moe_intermediate_size"],
        "fs": cfg["moe_shared_expert_intermediate_size"],
        "held": cfg["n_routed_experts"],
        "all": cfg["published"]["n_routed_experts"],
    }


def even_visits(cfg: dict) -> float:
    """Visits a token a block an even router sends to the held experts."""
    s = _sizes(cfg)
    return cfg["num_experts_per_tok"] * s["held"] / s["all"]


def layers(cfg: dict) -> List[dict]:
    """Per held block: its kind, its parameters and its matrix MACs a token
    (an expert block: ``macs`` is what every token takes, the router and
    the shared expert; ``visit_macs`` one visit of a routed expert)."""
    s = _sizes(cfg)
    d = s["d"]
    out = []
    for i in cfg["held"]["layers"]:
        kind = cfg["hybrid_override_pattern"][i]
        row = {"layer": i, "kind": kind, "visit_macs": 0}
        if kind == MAMBA:
            macs = d * (s["inner"] + s["width"] + s["h"]) + s["inner"] * d
            # the conv's taps and bias; A_log, D, dt_bias; the gated norm's gain
            params = (macs + (cfg["conv_kernel"] + 1) * s["width"] + 3 * s["h"]
                      + s["inner"])
        elif kind == ATTENTION:
            macs = params = 2 * d * s["hq"] + 2 * d * s["hkv"]
        else:
            macs = d * s["all"] + 2 * d * s["fs"]
            row["visit_macs"] = 2 * d * s["fe"]
            # the choosing bias; the held experts' two matrices each
            params = macs + s["all"] + s["held"] * row["visit_macs"]
        out.append(dict(row, macs=macs, params=params + d))  # the block's norm
    return out


def _count(cfg: dict, kind: str) -> int:
    return sum(l["kind"] == kind for l in layers(cfg))


def params_held(cfg: dict) -> int:
    """Parameters this chip holds (embedding, untied head, the final norm
    and the value head counted)."""
    d = cfg["hidden_size"]
    return (2 * cfg["vocab_size"] * d + sum(l["params"] for l in layers(cfg))
            + d + (d + 1))


def ssd_macs(cfg: dict) -> int:
    """MACs a position of ONE Mamba-2 block's recurrence needs, all heads."""
    return 3 * cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"]


def forward_macs(cfg: dict, episode: int, visits: float | None = None
                 ) -> Dict[str, float]:
    """MACs of one forward of one token, by part: the Mamba-2 blocks'
    projections, their recurrences (``ssd``), the attention block's
    projections, its products against keys and values at the mean context
    (``context``), the shared experts and routers, the routed experts at
    ``visits`` a token a block (an even router's where None), the head."""
    rows = layers(cfg)
    visits = even_visits(cfg) if visits is None else visits
    by_kind = lambda kind: sum(l["macs"] for l in rows if l["kind"] == kind)  # noqa: E731
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    return {
        "mamba": by_kind(MAMBA),
        "ssd": ssd_macs(cfg) * _count(cfg, MAMBA),
        "attention": by_kind(ATTENTION),
        "context": 2 * hq * (episode + 1) / 2 * _count(cfg, ATTENTION),
        "shared": by_kind(EXPERTS),
        "experts": visits * sum(l["visit_macs"] for l in rows),
        "head": cfg["vocab_size"] * cfg["hidden_size"],
    }


def flops_per_env_step(cfg: dict, episode: int, visits: float | None = None
                       ) -> float:
    """FLOPs a fused env-step (one token) needs: the rollout's forward, the
    learner's forward, dW and dx of every product."""
    return 2 * 4 * sum(forward_macs(cfg, episode, visits).values())


def decode_weight_bytes(cfg: dict, weight_bytes: int = 2) -> float:
    """Bytes of weights one decode step reads: every parameter held, at the
    rollout snapshot's width (at 32 envs every held expert computes every
    token, so every held matrix is read)."""
    return params_held(cfg) * weight_bytes


def decode_carry_bytes(cfg: dict, carry_bytes_per_env, envs: int,
                       episode: int) -> float:
    """Bytes of carry one decode step must move, the mean over an episode.
    ``carry_bytes_per_env``: the program's own count by kind (the
    recurrence's states; the convs' tails; the K/V buffers; the position and
    the last step sizes). States, tails and the small leaves are read and
    written whole; the K/V is read up to ``t + 1`` of its rows, and one row
    is written."""
    states, tails, kv, small = (float(x) for x in carry_bytes_per_env)
    kv_read = (episode + 1) / 2 / episode
    return envs * (2 * states + 2 * tails + kv * (kv_read + 1 / episode)
                   + 2 * small)


def ssd_flops(cfg: dict, tokens: float) -> float:
    """FLOPs the learner's recurrences need over ``tokens`` positions, all
    Mamba-2 blocks: the recurrence forward and twice that backward."""
    return 2 * 3 * ssd_macs(cfg) * _count(cfg, MAMBA) * tokens


def ssd_bytes(cfg: dict, tokens: float, act_bytes: int = 4) -> float:
    """Least HBM traffic of the learner's recurrences over ``tokens``
    positions, all Mamba-2 blocks: forward reads ``x`` (``h P``), ``dt``
    (``h``), ``B`` and ``C`` (``g N`` each) and writes ``y``; backward reads
    those and ``dy`` and writes the four gradients; the state at a chunk's
    boundary (``P x N`` a head every ``chunk_size`` positions) is written
    once and read once. Inside a chunk the state need not leave the chip's
    fast memory."""
    h, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"]
    read = h * P + h + 2 * cfg["n_groups"] * N
    a_token = (read + h * P) + (read + h * P) + read
    boundary = 2 * h * P * N / cfg["chunk_size"]
    return tokens * _count(cfg, MAMBA) * (a_token + boundary) * act_bytes


def routed_expert_flops(cfg: dict, visits_total: float) -> float:
    """FLOPs of the grouped products for ``visits_total`` (token, held
    expert) visits: forward, dW and dx of the two matrices a visit."""
    return 2 * 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * visits_total


def routed_expert_bytes(cfg: dict, visits_total: float, passes: float,
                        act_bytes: int = 2) -> float:
    """Least HBM traffic of those products: every held expert's two matrices
    read once a pass at the compute type's width (``passes``: three an
    unrolled chunk: forward, dW, dx), each visit's rows read and written
    once a product (``visits_total``: a visit counted once a pass)."""
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["n_routed_experts"] * 2 * d * fe * act_bytes * _count(cfg, EXPERTS)
    rows = visits_total * 2 * (d + fe) * act_bytes
    return passes * weights + rows
