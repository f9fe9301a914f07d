"""ssm_step_share: share of the decode program's device time, in the traced
part of the window, spent in operations traced under `ssm.*` (a Mamba-2
layer's in-projection, convolution, recurrence and gate + out-projection):
what the state-space layers cost of a decode step.  From `hybrid_scopes`;
None for a program whose operations carry no such scope."""

from chipbench import hybrid_scopes

UNIT, LAYER, SOURCE = "%", "model step", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    decode = hybrid_scopes.decode(run)
    if decode is None:
        return None
    ssm = sum(seconds for scope, seconds in decode["scopes"].items()
              if scope.startswith("ssm."))
    return 100.0 * ssm / decode["seconds"]
