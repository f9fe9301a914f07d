"""prefill_dispatch_host_ms: wall time of one prefill enqueue on the
engine's launching thread (host arrays, the prefill launch, the cache
insert, the feed scatter), mean over the window:
kfserving_tpu_generator_dispatch_host_ms{program="prefill"} differenced
between the window's edges."""

from chipbench import engine_phases

UNIT, LAYER, SOURCE = "ms", "GenerationEngine", "program_counter"
MOVES = "request_mean_ms"


def read(run):
    # No idle share is reported in the paced cell (they move tokens_per_s,
    # which it does not report); its trace's table is printed all the same.
    engine_phases.of(run)
    return engine_phases.histogram_mean(
        run, "kfserving_tpu_generator_dispatch_host_ms", program="prefill")
