"""Device time of one update: the median span on the chip of one execution
of the compiled step (the configuration's ``trace.update_module``)."""

ROW = {
    "name": "update_device_ms", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    return ctx["trace"].module_ms(ctx["config"]["trace"]["update_module"])
