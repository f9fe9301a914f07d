"""mixer_step_share: share of the decode program's device time, in the traced
part of the window, spent in operations traced under `ssm.*` or `attn`: what
the two mixers of a layer that runs attention and a Mamba-2 recurrence side
by side cost of a decode step together (projections, convolution, recurrence,
gate, cache write, paged kernel), beside the MLP and the head.
`ssm_step_share` is the recurrence's half of it.  From `hybrid_scopes`; None
for a program whose operations carry no `ssm.*` scope."""

from chipbench import hybrid_scopes

UNIT, LAYER, SOURCE = "%", "model step", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    decode = hybrid_scopes.decode(run)
    if decode is None:
        return None
    mixers = sum(seconds for scope, seconds in decode["scopes"].items()
                 if scope.startswith("ssm.") or scope == "attn")
    return 100.0 * mixers / decode["seconds"]
