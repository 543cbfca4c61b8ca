"""Share of the device's op time under ``op_mla``: the latent attention, in
the decode step (the absorbed form over the cache's latent rows) and in the
unroll (the expanded form, its recomputed blocks included). Its parts are
printed, the rollout's and the learner's apart: ``q``, ``kv_latent``,
``expand`` (learner alone), ``absorb`` (rollout alone), ``attend``, ``out``."""

from benchmark import scopes_lm

ROW = {
    "name": "mla_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if "kv_lora_rank" not in ctx["config"]:
        return None
    value = scopes_lm.share(ctx, "OP_MLA")
    if value is None:
        return None
    print("mla_time_share: " + scopes_lm.line(
        ctx, "OP_MLA", "OP_MLA_Q", "OP_MLA_KV_LATENT", "OP_MLA_EXPAND",
        "OP_MLA_ABSORB", "OP_MLA_ATTEND", "OP_MLA_OUT"))
    return value
