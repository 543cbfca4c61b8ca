"""Share of the device's op time under ``op_attn_full`` for the
linear-attention hybrid: its one full-attention layer in four (projections,
the q / k norm, the attention, ``W_o``), in the decode step (the K/V rows up
to the position, ``decode_attend`` printed beside it) and in the unroll
(``ops/sparse_attention.py`` without a selection). Beside
``linattn_time_share`` it says what the two kinds of layer cost unequally."""

from benchmark import scopes, scopes_lm

ROW = {
    "name": "full_attn_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if "linear_key_head_dim" not in ctx["config"]:
        return None
    value = scopes_lm.share(ctx, "OP_ATTN_FULL")
    if value is None:
        return None
    cap = scopes.capture(ctx)
    prof = cap["profiling"]
    kernel = cap["seconds"][prof.policy_scope(
        prof.ROLLOUT_POLICY, f"{prof.OP_ATTN_FULL}/{prof.DECODE_ATTEND}")]
    print("full_attn_time_share: " + scopes_lm.line(ctx, "OP_ATTN_FULL")
          + f", of the rollout's the decode's kernel "
            f"{100.0 * kernel / cap['total_s']:.3f} %")
    return value
