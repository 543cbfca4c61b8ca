"""How evenly the router loads the experts held here: the fullest held
expert's tokens over the mean of the held experts, in the worst expert
layer, from the step's own counter ``moe_tokens_per_expert`` of the
window's last update. 1 is even; no token is dropped at any value."""

ROW = {
    "name": "moe_load_max_over_mean", "unit": "x", "better": "lower",
    "source": "program_counter", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    held = ctx["counters"].get("moe_tokens_per_expert")
    if not held:
        return None
    return max(max(layer) / (sum(layer) / len(layer)) for layer in held)
