"""Share of the device's op time under the scope ``rollout``: the actor side
of an update (policy forward, action draw, env step with its render, frame
stack), read from the run's capture by the program's own reader."""

from benchmark import scopes

ROW = {
    "name": "rollout_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if scopes.capture(ctx) is None:
        return None
    print("rollout_time_share: " + scopes.shares_line(
        ctx, "ROLLOUT_POLICY", "ROLLOUT_SAMPLE", "ROLLOUT_ENV_STEP",
        "ROLLOUT_STACK", "RETURNS", "GRAD_REDUCE", "METRICS"))
    return scopes.share(ctx, "ROLLOUT")
