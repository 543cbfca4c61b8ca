"""Parameters, operations and bytes of Xing4.0-29B-A4B's layers as one chip
holds them, counted from the configuration's shapes (the held heads of every
attention, the held routed experts of every expert layer; the latent
down-projections, routers, shared experts, the dense feed-forward, the
hyper-connections' mappings and every norm whole).

The counts are what the algorithm needs, not what a compiler emits. A token
costs one rollout forward (a decode step) and, in the learner, one forward,
the weight gradient of every matrix and the input gradient of every matrix
(the embedding's lookup has neither product). A recomputed forward
(rematerialisation) is work the program chose and is not counted. The
attention's products against its context are counted at the mean context an
episode of ``T`` positions gives, ``(T + 1) / 2``, IN THE FORM EACH SIDE
RUNS: the learner's expanded form takes ``nope + rope`` MACs a key a head
for the scores and ``v`` for the values; the decode's absorbed form, which
attends over the cache's latent rows, ``rkv + rope`` and ``rkv`` (its two
absorbed products, ``W^K_h q`` and ``(W^V_h)^T a``, are counted as the
expansion they replace: as many MACs a token). The routed experts are
counted at the visits the router made: ``visits`` a token a layer lands on
an expert held here (0.5 expected: 4 of 64 chosen, 8 of 64 held); the router
itself runs over all experts. A sub-block's hyper-connection is its
projection (``n d`` by ``2 n + n^2``), the read (``n d``) and the write
(``n^2 d + n d``).
"""

from __future__ import annotations

from typing import Dict, List

DENSE, EXPERTS = "dense", "experts"
#: bytes of a latent row's numbers in the cache's type (bfloat16)
CACHE_BYTES = 2


def _sizes(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return {
        "d": cfg["hidden_size"], "n": cfg["hc_mult"], "heads": heads,
        "rq": cfg["q_lora_rank"], "rkv": cfg["kv_lora_rank"],
        "nope": nope, "rope": rope, "v": v,
        "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
        "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        "held": cfg["n_routed_experts"],
        "all": cfg["published"]["n_routed_experts"],
    }


def layer_kinds(cfg: dict) -> List[str]:
    return [DENSE if i < cfg["first_k_dense_replace"] else EXPERTS
            for i in cfg["held"]["layers"]]


def even_visits(cfg: dict) -> float:
    """Visits a token a layer an even router sends to the held experts."""
    s = _sizes(cfg)
    return cfg["num_experts_per_tok"] * s["held"] / s["all"]


def mla_matrices(cfg: dict) -> Dict[str, int]:
    """The attention's five matrices by name: entries = MACs a token."""
    s = _sizes(cfg)
    return {
        "wq_a": s["d"] * s["rq"],
        "wq_b": s["rq"] * s["heads"] * (s["nope"] + s["rope"]),
        "wkv_a": s["d"] * (s["rkv"] + s["rope"]),
        "wkv_b": s["rkv"] * s["heads"] * (s["nope"] + s["v"]),
        "wo": s["heads"] * s["v"] * s["d"],
    }


def hyper_macs(cfg: dict) -> int:
    """MACs a token of ONE sub-block's hyper-connection."""
    s = _sizes(cfg)
    n, nd = s["n"], s["n"] * s["d"]
    return nd * (2 * n + n * n) + nd + (n * nd + nd)


def layers(cfg: dict) -> List[dict]:
    """Per held layer: its kind, what it holds by class (``matrices``: the
    leaves the rollout's snapshot casts to bfloat16; ``float32``: what it
    leaves float32: gains, the router with its bias, the mappings' leaves)
    and its matrix MACs a token (``macs``: what every token takes;
    ``visit_macs``: one visit of a routed expert)."""
    s = _sizes(cfg)
    d, n = s["d"], s["n"]
    mla = sum(mla_matrices(cfg).values())
    # two sub-blocks' mappings (phi, three gates, two biases of n, b_res)
    # and pre-norms; the query's and the latent's norms
    small = 2 * ((2 * n + n * n) * n * d + 3 + 2 * n + n * n + d) + s["rq"] + s["rkv"]
    out = []
    for i, kind in zip(cfg["held"]["layers"], layer_kinds(cfg), strict=True):
        row = {"layer": i, "kind": kind, "visit_macs": 0}
        if kind == DENSE:
            ffn = matrices = 3 * d * s["f"]
            kept = 0
        else:
            row["visit_macs"] = 3 * d * s["fe"]
            ffn = d * s["all"] + 3 * d * s["fs"]
            matrices = 3 * d * s["fs"] + s["held"] * row["visit_macs"]
            kept = d * s["all"] + s["all"]  # the router and its choosing bias
        out.append(dict(row, mla_macs=mla, ffn_macs=ffn,
                        hyper_macs=2 * hyper_macs(cfg),
                        matrices=mla + matrices, float32=small + kept))
    return out


def params_held(cfg: dict) -> int:
    """Parameters this chip holds (embedding, untied head, the final norm
    and the value head counted)."""
    d = cfg["hidden_size"]
    return (2 * cfg["vocab_size"] * d + d + (d + 1)
            + sum(l["matrices"] + l["float32"] for l in layers(cfg)))


def context_macs(cfg: dict, episode: int) -> Dict[str, float]:
    """MACs a token of ONE attention against its context at the episode's
    mean: the learner's expanded form and the decode's absorbed one."""
    s = _sizes(cfg)
    mean = (episode + 1) / 2
    return {
        "expanded": s["heads"] * (s["nope"] + s["rope"] + s["v"]) * mean,
        "absorbed": s["heads"] * (2 * s["rkv"] + s["rope"]) * mean,
    }


def forward_macs(cfg: dict, episode: int, visits: float | None = None,
                 form: str = "expanded") -> Dict[str, float]:
    """MACs of one forward of one token, by part: the attentions'
    projections (``mla``), their products against the context in ``form``
    (``context``), the hyper-connections, the dense feed-forward, the
    shared experts and routers, the routed experts at ``visits`` a token a
    layer (an even router's where None), the head."""
    rows = layers(cfg)
    visits = even_visits(cfg) if visits is None else visits
    of = lambda kind, key: sum(l[key] for l in rows if l["kind"] == kind)  # noqa: E731
    return {
        "mla": sum(l["mla_macs"] for l in rows),
        "context": context_macs(cfg, episode)[form] * len(rows),
        "hyper_conn": sum(l["hyper_macs"] for l in rows),
        "dense": of(DENSE, "ffn_macs"),
        "shared": of(EXPERTS, "ffn_macs"),
        "experts": visits * sum(l["visit_macs"] for l in rows),
        "head": cfg["vocab_size"] * cfg["hidden_size"],
    }


def flops_per_env_step(cfg: dict, episode: int, visits: float | None = None
                       ) -> float:
    """FLOPs a fused env-step (one token) needs: the rollout's forward in
    the absorbed form; the learner's forward, dW and dx of every product in
    the expanded one."""
    rollout = sum(forward_macs(cfg, episode, visits, "absorbed").values())
    learner = sum(forward_macs(cfg, episode, visits, "expanded").values())
    return 2 * (rollout + 3 * learner)


def decode_weight_bytes(cfg: dict) -> float:
    """Bytes of weights one decode step reads: every parameter held, once,
    from the rollout's snapshot (matrices 2 bytes, what it leaves float32
    4; at 32 envs every held expert computes every token, so every held
    matrix is read)."""
    d = cfg["hidden_size"]
    matrices = 2 * cfg["vocab_size"] * d + sum(l["matrices"] for l in layers(cfg))
    kept = d + (d + 1) + sum(l["float32"] for l in layers(cfg))
    return 2.0 * matrices + 4.0 * kept


def latent_row_bytes(cfg: dict) -> int:
    """Bytes of ONE latent row's numbers (the latent and the shared key),
    counted once however the program lays them out."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * CACHE_BYTES


def decode_latent_bytes(cfg: dict, envs: int, episode: int) -> Dict[str, float]:
    """Bytes of latent rows one decode step must move, the mean over an
    episode: every layer reads rows ``[0, t]`` of every env once (``read``)
    and writes the row of position ``t`` (``written``)."""
    a_layer = envs * latent_row_bytes(cfg) * len(cfg["held"]["layers"])
    return {"read": a_layer * (episode + 1) / 2, "written": float(a_layer)}


def decode_step_bytes(cfg: dict, envs: int, episode: int) -> float:
    """Every byte one decode step must move: the weights and the rows."""
    return decode_weight_bytes(cfg) + sum(
        decode_latent_bytes(cfg, envs, episode).values())
