"""deliver_lag_max_ms: the longest that a fetched result waited for the
scheduler loop to take it up in the window, to the resolution of the
histogram's buckets: the upper bound of the highest bucket of
kfserving_tpu_generator_deliver_lag_ms whose count grew between the
window's edges.  A loop that was held (garbage collection, a profiler
starting, another handler) shows here and not in `inflight_max_ms`.  None
on a server without the histogram (a parent)."""

from chipbench import histograms

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "request_mean_ms"


def read(run):
    return histograms.grown_upper_bound(
        run["scrapes"], "open", "close",
        "kfserving_tpu_generator_deliver_lag_ms")
