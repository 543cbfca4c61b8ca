"""Share of the device's op time under ``rollout/env_step``: the env's
physics substeps, the reset select and the render of 84x84 frames (the
render's own share is printed beside it)."""

from benchmark import scopes

ROW = {
    "name": "env_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "env step + render",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    if scopes.capture(ctx) is None:
        return None
    print("env_time_share: " + scopes.shares_line(ctx, "ROLLOUT_RENDER"))
    return scopes.share(ctx, "ROLLOUT_ENV_STEP")
