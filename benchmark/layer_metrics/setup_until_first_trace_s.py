"""Seconds from the process's own start, as the OS records it, to the first
``trace`` interval of the program's start-up record: the interpreter, the
imports, the chip's initialisation and the building of the step, before JAX
traces anything. The line says where the start stamp came from and when the
installer ran (imports done up to ``configure_compile_cache()``)."""

from benchmark import startup

ROW = {
    "name": "setup_until_first_trace_s", "unit": "s", "better": "lower",
    "source": "program_span", "layer": "entry and start-up", "moves": "setup_s",
}


def read(ctx):
    found = startup.summary(ctx)
    if found is None:
        return None
    origin = ("the OS's stamp of the process's start"
              if found["process_start_from"] == "os"
              else "the installer's call (the OS's stamp could not be read)")
    print(f"setup_until_first_trace_s: counted from {origin}; the installer "
          f"ran {found['installed_s']:.2f} s in")
    return found["until_first_trace_s"]
