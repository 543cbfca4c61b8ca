"""Share of the device's op time in ``select-and-scatter``: the backward of
the three 2x2 max-pools."""

ROW = {
    "name": "pool_bwd_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * tr.kind_seconds("select-and-scatter") / tr.total_op_seconds()
