"""Share of the device's op time that carries none of the program's scopes
(fusions across a scope's boundary, the reshapes between phases, the small
programs between updates): how far the by-scope shares can be trusted. The
ops that make most of it up are printed beside it."""

from benchmark import scopes

ROW = {
    "name": "unscoped_time_share", "unit": "%", "better": "lower",
    "source": "device_trace", "layer": "fused trainer",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    cap = scopes.capture(ctx)
    if cap is None:
        return None
    print("unscoped_time_share: most of it in " + ", ".join(
        f"{name} {100.0 * s / cap['total_s']:.3f} %"
        for name, s in cap["unscoped_ops"][:5]))
    return 100.0 * cap["unscoped_share"]
