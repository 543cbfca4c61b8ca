"""Share of the device's op time in matrix-unit fusions: the convolutions
(forward, dx, dW) and the fc products, each with what XLA fused onto it."""

from benchmark.trace import MATMUL

ROW = {
    "name": "conv_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr = ctx["trace"]
    return 100.0 * tr.kind_seconds(MATMUL) / tr.total_op_seconds()
