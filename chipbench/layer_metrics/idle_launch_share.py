"""idle_launch_share: device idle time while the engine's launching thread
was inside an `engine.launch.*` span (a jitted call: decode, prefill,
insert, feed, chunk, spec), as a share of the traced part of the window.
What the host spends inside a launch the device waits for: parameters
handed over with every call show here.  From `engine_phases`."""

from chipbench import engine_phases

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "device_trace"
MOVES = "tokens_per_s"


def read(run):
    return engine_phases.share(run, "launch_s")
