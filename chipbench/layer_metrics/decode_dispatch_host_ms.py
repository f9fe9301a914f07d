"""decode_dispatch_host_ms: wall time of one decode enqueue on the engine's
launching thread (host arrays to the launch's return), mean over the
window: kfserving_tpu_generator_dispatch_host_ms{program="decode"}
differenced between the window's edges."""

from chipbench import engine_phases

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    return engine_phases.histogram_mean(
        run, "kfserving_tpu_generator_dispatch_host_ms", program="decode")
