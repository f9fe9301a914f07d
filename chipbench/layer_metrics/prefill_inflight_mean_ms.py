"""prefill_inflight_mean_ms: one prefill dispatch from its launch's return
to its fetch's return, mean over the dispatches fetched in the window:
kfserving_tpu_generator_program_inflight_ms{program="prefill"} differenced
between the window's edges.  With deliver lag it is what `ttft_delivery`
holds of a first token.  None on a server without the histogram (a
parent)."""

from chipbench import engine_phases

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "request_mean_ms"


def read(run):
    return engine_phases.histogram_mean(
        run, "kfserving_tpu_generator_program_inflight_ms",
        program="prefill")
