"""Programs that set-up compiled and wrote to the persistent cache instead of
reading them from it, before the window's last dispatch: 0 in a warm run, so
a value above 0 is why a ``setup_s`` stands apart from its pairs. The line
names what was compiled."""

from benchmark import startup

ROW = {
    "name": "setup_cache_misses", "unit": "programs", "better": "lower",
    "source": "program_counter", "layer": "entry and start-up", "moves": "setup_s",
}


def read(ctx):
    found = startup.summary(ctx)
    if found is None:
        return None
    if found["missed"]:
        print("setup_cache_misses: " + ", ".join(
            f"{name} {s:.2f} s" for name, s in sorted(
                found["missed"], key=lambda kv: -kv[1])[:10]))
    return found["cache_misses"]
