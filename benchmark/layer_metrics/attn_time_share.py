"""Share of the device's op time under ``op_attn_window`` (a ring of the
last 512 keys and values), ``op_attn_full`` (every past position; writes the
K/V that later layers share) and ``op_attn_cross`` (queries only, over that
shared K/V): differential attention with its norm and projections, in the
decode step and in the unroll. Each is printed."""

from benchmark import scopes_lm

LAYERS = ("OP_ATTN_WINDOW", "OP_ATTN_FULL", "OP_ATTN_CROSS")
ROW = {
    "name": "attn_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    value = scopes_lm.share(ctx, *LAYERS)
    if value is None:
        return None
    print("attn_time_share: " + scopes_lm.line(ctx, *LAYERS))
    return value
