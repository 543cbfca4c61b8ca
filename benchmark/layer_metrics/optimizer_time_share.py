"""Share of the device's op time under ``optimizer``: the learning rate's
injection, the global-norm clip, Adam and the parameter update."""

from benchmark import scopes

ROW = {
    "name": "optimizer_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    return scopes.share(ctx, "OPTIMIZER")
