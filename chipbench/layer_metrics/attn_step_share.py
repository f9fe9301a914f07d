"""attn_step_share: share of the decode program's device time, in the
traced part of the window, spent in operations traced under `attn.window`
or `attn.full` (the cache write, the walk and the paged kernel of both
kinds of layer): what attention costs of a step beside the experts' weight
stream (`moe_step_share`).  From `window_scopes`; None for a program whose
operations carry no such scope."""

from chipbench import window_scopes

UNIT, LAYER, SOURCE = "%", "model step", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    decode = window_scopes.decode(run)
    if decode is None:
        return None
    return 100.0 * (decode["scopes"].get("attn.window", 0.0)
                    + decode["scopes"].get("attn.full", 0.0)) \
        / decode["seconds"]
