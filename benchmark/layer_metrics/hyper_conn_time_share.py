"""Share of the device's op time under ``hyper_conn``: the residual path of
four streams round every sub-block, in the decode step and in the unroll:
``mappings`` (the streams' norm, the projection, the sigmoids, 20 Sinkhorn
iterations) and ``mix`` (the sub-block's input read from the streams, its
output written into them and the streams mixed), each printed, the rollout's
and the learner's apart."""

from benchmark import scopes_lm

ROW = {
    "name": "hyper_conn_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if "hc_mult" not in ctx["config"]:
        return None
    value = scopes_lm.share(ctx, "HYPER_CONN")
    if value is None:
        return None
    print("hyper_conn_time_share: " + scopes_lm.line(
        ctx, "HYPER_CONN", "HYPER_CONN_MAPPINGS", "HYPER_CONN_MIX"))
    return value
