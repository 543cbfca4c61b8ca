"""Share of the device's op time under the scope ``moe``, in the rollout's
decode step and in the learner's unroll: the routed-expert layers (router,
dispatch, the grouped products, combine; each printed beside it)."""

from benchmark import scopes_lm

ROW = {
    "name": "moe_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    value = scopes_lm.share(ctx, "MOE")
    if value is None:
        return None
    print("moe_time_share: " + scopes_lm.line(
        ctx, "MOE_ROUTER", "MOE_DISPATCH", "MOE_EXPERTS", "MOE_COMBINE"))
    return value
