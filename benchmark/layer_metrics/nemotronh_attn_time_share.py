"""Share of the device's op time under ``op_attn_full`` for the Mamba-2 and
sparse-expert hybrid: its one grouped-query attention block in nine (the
block's norm, ``W_q`` / ``W_k`` / ``W_v``, the attention, ``W_o``), the
rollout's and the learner's printed apart. The decode reads the whole K/V
buffer under the position's mask (``layers.attend``: ``decode_attend``'s
kernel takes at most 8 query heads a K/V head and this block has 16); the
unroll lays each K/V head down twice for ``ops/sparse_attention.py``'s
kernels. Both are this number's to move."""

from benchmark import scopes_lm

ROW = {
    "name": "nemotronh_attn_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if "mamba_num_heads" not in ctx["config"]:
        return None
    value = scopes_lm.share(ctx, "OP_ATTN_FULL")
    if value is None:
        return None
    print("nemotronh_attn_time_share: " + scopes_lm.line(ctx, "OP_ATTN_FULL"))
    return value
