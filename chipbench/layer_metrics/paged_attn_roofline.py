"""paged_attn_roofline: the least time the chip could take for the paged
decode attention calls of the traced part of the window, over the time they
took.  The calls and their time come from the trace (`paged_attention_tpu`);
the work each call needs from `opsbytes.paged_decode_attention` at the mean
summed context of the requests decoding then, which the load generator knows
from its own records.  The bound is the memory one at these shapes (one
query row per sequence); the reader takes the larger of the two all the same.
"""

from chipbench import opsbytes, stats

UNIT, LAYER, SOURCE = "%", "kernels", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    trace = run["trace_reduced"]
    if not trace or not run["trace_window"] or "peaks" not in run:
        return None
    calls = [v for k, v in trace["ops"].items() if "paged_attention" in k]
    count = sum(v["count"] for v in calls)
    seconds = sum(v["seconds"] for v in calls)
    if not count or seconds <= 0:
        return None
    config = run["config"]
    context = stats.live_context_tokens(run["records"], run["trace_window"])
    flops, nbytes = opsbytes.paged_decode_attention(
        context_tokens=context, sequences=config["serving"]["max_slots"],
        heads=config["n_head"], head_dim=config["n_embd"] // config["n_head"],
        bytes_per_value=2)
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * count * least / seconds
