"""Share of the device's op time under ``op_conv`` and ``op_attn``: the
gated short convolutions and the grouped-query attention, with their norms,
in the decode step (conv state, K/V cache) and in the unroll."""

from benchmark import scopes_lm

ROW = {
    "name": "mixer_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    value = scopes_lm.share(ctx, "OP_CONV", "OP_ATTN")
    if value is None:
        return None
    print("mixer_time_share: " + scopes_lm.line(
        ctx, "OP_CONV", "OP_ATTN", "FFN_DENSE", "EMBED"))
    return value
