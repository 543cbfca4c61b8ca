"""Of the keys the learner's queries could see in the window's last update
(``dsa_keys_live``: every live position of every query, a layer), the share
their indexer kept (``dsa_keys_selected``): the program's own counters. At
an episode of twice the top-k it is 75.0 by construction (mean 1,536 kept of
mean 2,048.5 live): it says the selection ran at the configured top-k, in
every layer."""

ROW = {
    "name": "select_kept_share", "unit": "%", "better": "lower",
    "source": "program_counter", "layer": "policy forward and backward",
    "moves": "env_steps_per_s_per_chip",
}


def read(ctx):
    c = ctx["counters"]
    selected, live = c.get("dsa_keys_selected"), c.get("dsa_keys_live")
    if not selected or not live or not sum(live):
        return None
    print("select_kept_share: by layer "
          + " ".join(f"{100.0 * s / l:.3f}" for s, l in zip(selected, live, strict=True)))
    return 100.0 * sum(selected) / sum(live)
