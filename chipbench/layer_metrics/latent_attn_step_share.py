"""latent_attn_step_share: share of the decode program's device time, in the
traced part of the window, spent in operations traced under `attn.latent`
(the projections, the row's write, the absorption of W_kvb on both sides,
the walk and the latent kernel, the out-projection): what latent attention
costs of a step beside the experts' weight stream (`moe_step_share`).  From
`latent_scopes`; None for a program whose operations carry no such scope."""

from chipbench import latent_scopes

UNIT, LAYER, SOURCE = "%", "model step", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    decode = latent_scopes.decode(run)
    if decode is None:
        return None
    latent = sum(seconds for scope, seconds in decode["scopes"].items()
                 if scope.startswith("attn.latent"))
    return 100.0 * latent / decode["seconds"]
