"""Share of the device's op time under ``learner`` with JAX's
``transpose(`` in the op's name: dW, dx, the pool's ``select-and-scatter``
and the bias gradients."""

from benchmark import scopes

ROW = {
    "name": "learner_bwd_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    return scopes.share(ctx, "LEARNER_BWD")
