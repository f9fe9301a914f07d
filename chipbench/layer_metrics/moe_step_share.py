"""moe_step_share: share of the decode program's device time, in the traced
part of the window, spent in operations traced under `moe.*` (the router, the
dispatch, the experts' matmuls, the combine): whether the routed expert layer
is what a decode step costs.  From `moe_scopes`; None for a program whose
operations carry no such scope."""

from chipbench import moe_scopes

UNIT, LAYER, SOURCE = "%", "model step", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    decode = moe_scopes.decode(run)
    if decode is None:
        return None
    routed = sum(seconds for scope, seconds in decode["scopes"].items()
                 if scope.startswith("moe."))
    return 100.0 * routed / decode["seconds"]
