"""Share of the device's op time under ``op_indexer`` (the indexer's
projections, its scores against every live key, the exact top-k, its KL
loss), the rollout's decode step and the learner's unroll together; the
line prints ``scores``, ``select`` and ``loss`` apart, each by phase."""

from benchmark import scopes_lm

ROW = {
    "name": "indexer_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}
PARTS = ("OP_INDEXER", "OP_INDEXER_SCORES", "OP_INDEXER_SELECT", "OP_INDEXER_LOSS")


def read(ctx):
    if "sa_config" not in ctx["config"]:
        return None
    value = scopes_lm.share(ctx, "OP_INDEXER")
    if value is None:
        return None
    print(f"indexer_time_share: {scopes_lm.line(ctx, *PARTS)}")
    return value
