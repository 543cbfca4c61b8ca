"""Share of the device's op time under ``op_attn_sparse`` (the main
attention's projections, norms, RoPE, the attention under the selection's
mask, ``W_o``), the rollout's decode step and the learner's unroll together;
the line prints the two apart."""

from benchmark import scopes_lm

ROW = {
    "name": "sparse_attn_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if "sa_config" not in ctx["config"]:
        return None
    value = scopes_lm.share(ctx, "OP_ATTN_SPARSE")
    if value is None:
        return None
    print(f"sparse_attn_time_share: {scopes_lm.line(ctx, "OP_ATTN_SPARSE")}")
    return value
