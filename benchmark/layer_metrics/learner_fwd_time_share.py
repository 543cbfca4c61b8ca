"""Share of the device's op time under ``learner`` without JAX's
``transpose(`` in the op's name: the learner's forward and its loss."""

from benchmark import scopes

ROW = {
    "name": "learner_fwd_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if scopes.capture(ctx) is None:
        return None
    print("learner_fwd_time_share: " + scopes.shares_line(ctx, "LEARNER_LOSS"))
    return scopes.share(ctx, "LEARNER_FWD")
