"""Share of the device's op time under ``op_mamba2``: the Mamba-2 mixers
(the block's norm and ``W_in``, the causal conv, the recurrence, the gated
group norm and ``W_out``), in the decode step (one position from the
carried state, ``ops/ssd.py:ssd_step``) and in the unroll (the chunked
form, its recomputed chunks included). Its parts, the attention block, the
shared experts and the embedding are printed."""

from benchmark import scopes_lm

ROW = {
    "name": "mamba2_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if "mamba_num_heads" not in ctx["config"]:
        return None
    value = scopes_lm.share(ctx, "OP_MAMBA2")
    if value is None:
        return None
    print("mamba2_time_share: " + scopes_lm.line(
        ctx, "OP_MAMBA2_IN_PROJ", "OP_MAMBA2_CONV", "OP_MAMBA2_SSD",
        "OP_MAMBA2_OUT", "OP_ATTN_FULL", "MOE_SHARED", "EMBED"))
    return value
