"""Share of the device's op time under ``op_linattn``: the gated delta-rule
mixers (the projections and gates, the causal conv, the recurrence, the
gated per-head norm and output projection), in the decode step (one
position from the carried state, ``ops/delta_rule.py:delta_step``) and in
the unroll (the chunked form, its recomputed chunks included). Its parts,
the feed-forwards and the embedding are printed."""

from benchmark import scopes_lm

ROW = {
    "name": "linattn_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if "linear_key_head_dim" not in ctx["config"]:
        return None
    value = scopes_lm.share(ctx, "OP_LINATTN")
    if value is None:
        return None
    print("linattn_time_share: " + scopes_lm.line(
        ctx, "OP_LINATTN_IN_PROJ", "OP_LINATTN_CONV", "OP_LINATTN_DELTA",
        "OP_LINATTN_OUT", "FFN_DENSE", "EMBED"))
    return value
