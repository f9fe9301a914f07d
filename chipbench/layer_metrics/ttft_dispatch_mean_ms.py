"""ttft_dispatch_mean_ms: the `dispatch` stage of time to first token,
from taken until its prefill (or first chunk) has been enqueued,
the wait for the one launching thread included,
mean over the requests first answered in the window:
kfserving_tpu_generator_ttft_stage_ms{stage="dispatch"} differenced between
the window's edges.  The three stages sum to the engine's llm_ttft_ms."""

from chipbench import engine_phases

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "request_mean_ms"


def read(run):
    return engine_phases.histogram_mean(
        run, "kfserving_tpu_generator_ttft_stage_ms", stage="dispatch")
