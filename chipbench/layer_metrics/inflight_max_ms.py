"""inflight_max_ms: the longest that any program was in flight in the
window, to the resolution of the histogram's buckets: the upper bound of
the highest bucket of kfserving_tpu_generator_program_inflight_ms, over
all programs, whose count grew between the window's edges.  A device that
stopped for a while shows here (and in `program_stalls_in_window` past
five seconds); a host loop that was held does not.  None on a server
without the histogram (a parent)."""

from chipbench import histograms

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    return histograms.grown_upper_bound(
        run["scrapes"], "open", "close",
        "kfserving_tpu_generator_program_inflight_ms")
