"""All-reduce time an update during which no other op runs on that chip."""

ROW = {
    "name": "allreduce_exposed_ms", "unit": "ms", "better": "lower",
    "source": "device_trace", "layer": "collectives",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    tr = ctx["trace"]
    updates = tr.module_runs(ctx["config"]["trace"]["update_module"])
    if not updates or not tr.kind_seconds("all-reduce"):
        return None
    exposed = tr.exposed_seconds(lambda r: r[3] == "all-reduce")
    return 1000.0 * exposed / updates
