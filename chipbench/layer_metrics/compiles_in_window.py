"""compiles_in_window: programs JAX traced and compiled (or loaded from its
cache) between the window's edges, from the server's log.  Must be 0: a run
with any is not correct."""

UNIT, LAYER, SOURCE = "count", "caches", "program_counter"
MOVES = "setup_s"


def read(run):
    return len(run["compiles_in_window"])
