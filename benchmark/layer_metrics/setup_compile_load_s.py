"""Seconds of set-up in which the back-end compiled a program or read it from
the persistent cache, up to a loaded executable: the union of the
``compile_load`` intervals recorded before the window's last dispatch. The
line gives the cache's hits and misses, JAX's own seconds of reading and
seconds saved, and the costliest functions."""

from benchmark import startup

ROW = {
    "name": "setup_compile_load_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "entry and start-up", "moves": "setup_s",
}


def read(ctx):
    found = startup.summary(ctx)
    if found is None:
        return None
    print(f"setup_compile_load_s: {found['cache_hits']} cache hits, "
          f"{found['cache_misses']} misses, {found['cache_read_s']:.2f} s reading, "
          f"{found['time_saved_s']:.2f} s saved; "
          + startup.costliest(found, "compile_load"))
    return found["compile_load_s"]
