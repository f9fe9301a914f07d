"""moe_grouped_ms_per_ktok: device time of the prefill programs' operations
under `moe.dispatch`, `moe.experts` and `moe.combine` (the grouped expert
path: sort and gather, the experts' matmuls, the sum back into token order)
in the traced part of the window, per thousand prompt tokens of the requests
whose first token arrived there, counted as `prefill_device_ms_per_ktok`
counts them.  From `moe_scopes`; None for a program with no such scope."""

from chipbench import moe_scopes, stats

UNIT, LAYER, SOURCE = "ms", "kernels", "device_trace"
MOVES = "tokens_per_s"
SCOPES = ("moe.dispatch", "moe.experts", "moe.combine")


def read(run):
    if not run.get("trace_window"):
        return None
    table = moe_scopes.of(run) or {}
    seconds = sum(rec["scopes"].get(scope, 0.0)
                  for name, rec in table.items() if "prefill_fn" in name
                  for scope in SCOPES)
    tokens = sum(r["prompt_tokens"] for r in run["records"]
                 if stats.in_window(r["first"], run["trace_window"]))
    return 1e6 * seconds / tokens if tokens and seconds else None
