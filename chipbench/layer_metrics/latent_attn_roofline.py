"""latent_attn_roofline: the least time the chip could take for the latent
decode attention calls of the traced part of the window, over the time they
took.  The calls and their time come from the trace (`latent_attention_tpu`,
one call a layer-step); the work each call needs from
`opsbytes_latent.latent_decode_attention` at the mean summed context of the
requests decoding then, which the load generator knows from its own records:
1,152 bytes a context row a layer at rank 512 + 64 in bfloat16, whatever the
pool pads a row to.  The bound is the memory one at these shapes; the reader
takes the larger of the two all the same.  None for a program without the
kernel (every other configuration, a parent commit)."""

from chipbench import opsbytes_latent, stats

UNIT, LAYER, SOURCE = "%", "kernels", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    trace = run["trace_reduced"]
    config = run["config"]
    if not trace or not run["trace_window"] or "peaks" not in run \
            or "kv_lora_rank" not in config:
        return None
    calls = [v for k, v in trace["ops"].items() if "latent_attention" in k]
    count = sum(v["count"] for v in calls)
    seconds = sum(v["seconds"] for v in calls)
    if not count or seconds <= 0:
        return None
    context = stats.live_context_tokens(run["records"], run["trace_window"])
    flops, nbytes = opsbytes_latent.latent_decode_attention(
        rows=context, sequences=config["serving"]["max_slots"],
        heads=config["num_attention_heads"], rank=config["kv_lora_rank"],
        rope=config["qk_rope_head_dim"], bytes_per_value=2)
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * count * least / seconds
