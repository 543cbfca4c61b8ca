"""Share of the device's op time under ``op_ssm``: the state-space mixers
(norm + input projection, causal conv, the ``dt``/``B``/``C`` projections,
the selective scan, gate + output projection), in the decode step (one
position from the carried state) and in the unroll (``ops/ssm.py``'s
sequence form, its recomputed chunks included). Its parts and ``op_gmu``
(the memory units that read one such layer's output) are printed."""

from benchmark import scopes_lm

ROW = {
    "name": "ssm_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    value = scopes_lm.share(ctx, "OP_SSM")
    if value is None:
        return None
    print("ssm_time_share: " + scopes_lm.line(
        ctx, "OP_SSM_IN_PROJ", "OP_SSM_CONV", "OP_SSM_SCAN", "OP_SSM_OUT_PROJ",
        "OP_GMU", "FFN_DENSE", "EMBED"))
    return value
