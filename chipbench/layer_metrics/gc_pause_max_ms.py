"""gc_pause_max_ms: the longest collection of the serving process's
interpreter in the window, to the resolution of the histogram's buckets: the
upper bound of the highest bucket of kfserving_tpu_process_gc_pause_ms, over
all generations, whose count grew between the window's edges.  Every thread
is held for a collection's length; one of a quarter of a second or more has
a `process paused:` line in the server's log.  None on a server without the
histogram (a parent)."""

from chipbench import histograms

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    return histograms.grown_upper_bound(
        run["scrapes"], "open", "close",
        "kfserving_tpu_process_gc_pause_ms")
