"""decode_inflight_mean_ms: one decode call from its launch's return to its
fetch's return, mean over the calls fetched in the window:
kfserving_tpu_generator_program_inflight_ms{program="decode"} differenced
between the window's edges.  Under a pipeline two deep it holds the wait
behind the call before it: the round trip a token rides, about two calls.
None on a server without the histogram (a parent)."""

from chipbench import engine_phases

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    return engine_phases.histogram_mean(
        run, "kfserving_tpu_generator_program_inflight_ms", program="decode")
