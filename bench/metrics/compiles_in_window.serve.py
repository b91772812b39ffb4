"""Executables built (compiled or fetched from the compile cache) inside
the serving window, from JAX's backend-compile monitoring events.  Each is
a stall of the engine step that needed it; there should be none."""


def read(rec):
    return rec.counters.get("compiles_in_window")
