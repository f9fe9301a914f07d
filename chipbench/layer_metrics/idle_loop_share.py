"""idle_loop_share: device idle time with the launching thread in no span,
as a share of the traced part of the window: the scheduler loop admitting,
growing tables, delivering tokens or waiting (for a fetch, for a request),
and what no engine span covers.  With idle_launch_share, idle_prep_share
and the gaps under 20 us it adds up to device_idle_share.generate.  From
`engine_phases`."""

from chipbench import engine_phases

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "device_trace"
MOVES = "tokens_per_s"


def read(run):
    return engine_phases.share(run, "loop_s")
