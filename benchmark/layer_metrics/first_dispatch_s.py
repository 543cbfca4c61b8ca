"""Seconds the step's first call took: trace, compile or cache read, and the
first execution. The driver's clock around that call."""

ROW = {
    "name": "first_dispatch_s", "unit": "s", "better": "lower",
    "source": "host_clock", "layer": "entry and start-up", "moves": "setup_s",
}


def read(ctx):
    return ctx["counters"].get("first_dispatch_s")
