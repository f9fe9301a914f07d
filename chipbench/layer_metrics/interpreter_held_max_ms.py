"""interpreter_held_max_ms: the latest that the process heartbeat's own
thread woke against its interval in the window, to the resolution of the
histogram's buckets: the upper bound of the highest bucket of
kfserving_tpu_process_held_ms{what="interpreter"} whose count grew between
the window's edges.  That thread only sleeps, so what delays it holds every
thread: a collection, native code that keeps the interpreter lock, a host
that gives the process no core.  None on a server without the histogram (a
parent)."""

from chipbench import histograms

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    return histograms.grown_upper_bound(
        run["scrapes"], "open", "close",
        "kfserving_tpu_process_held_ms", what="interpreter")
