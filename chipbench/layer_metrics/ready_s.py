"""ready_s: the server's own mark, from its process's birth to serving
(/startup_phases): the part of set-up that is the program's."""

UNIT, LAYER, SOURCE = "s", "caches", "program_span"
MOVES = "setup_s"


def read(run):
    return run["startup"].get("serving")
