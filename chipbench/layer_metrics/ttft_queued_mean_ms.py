"""ttft_queued_mean_ms: the `queued` stage of time to first token,
from submit until the scheduler takes the request out of its
pending queue,
mean over the requests first answered in the window:
kfserving_tpu_generator_ttft_stage_ms{stage="queued"} differenced between
the window's edges.  The three stages sum to the engine's llm_ttft_ms."""

from chipbench import engine_phases

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "request_mean_ms"


def read(run):
    return engine_phases.histogram_mean(
        run, "kfserving_tpu_generator_ttft_stage_ms", stage="queued")
