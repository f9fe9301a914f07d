"""loop_held_max_ms: the longest that the serving event loop left the
process heartbeat's tick waiting in the window (8 ticks a second), to the
resolution of the histogram's buckets: the upper bound of the highest bucket
of kfserving_tpu_process_held_ms{what="loop"} whose count grew between the
window's edges.  A handler that holds the loop shows here; a quarter of a
second or more has a `process paused:` line with the loop thread's frames.
None on a server without the histogram (a parent)."""

from chipbench import histograms

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    return histograms.grown_upper_bound(
        run["scrapes"], "open", "close",
        "kfserving_tpu_process_held_ms", what="loop")
