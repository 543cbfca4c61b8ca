"""Seconds of set-up in which JAX traced or lowered a program: the UNION of the
``trace`` and ``lower`` intervals recorded before the window's last dispatch
(a jit traced inside another is not counted twice). The line names the ten
costliest functions."""

from benchmark import startup

ROW = {
    "name": "setup_trace_lower_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "entry and start-up", "moves": "setup_s",
}


def read(ctx):
    found = startup.summary(ctx)
    if found is None:
        return None
    print("setup_trace_lower_s: " + startup.bounds(found) + "; "
          + startup.costliest(found, "trace", "lower"))
    return found["trace_lower_s"]
