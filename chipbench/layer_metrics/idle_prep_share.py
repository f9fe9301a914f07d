"""idle_prep_share: device idle time while the launching thread prepared a
launch (`engine.prep.*`: host arrays, sampling arrays, the block table,
their transfers) or moved KV blocks (`engine.spill`, `engine.faultback`),
as a share of the traced part of the window.  From `engine_phases`."""

from chipbench import engine_phases

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "device_trace"
MOVES = "tokens_per_s"


def read(run):
    return engine_phases.share(run, "prep_s")
