"""paged_block_fill: of the KV rows the paged decode kernel had to read
between the window's edges, the share that held context.  The kernel reads
whole blocks; `opsbytes.paged_decode_attention`, and so
`paged_attn_roofline`, counts each context row once.  The engine counts
both on the host where it accounts a delivered wave: context tokens and
ceil(context / block_size) blocks, over the rows that held a request and
over the wave's steps.  `paged_attn_roofline / paged_block_fill` is the
kernel's share of the bandwidth on the bytes it must read.  None for a
program without the counters (a parent commit)."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "kernels", "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    model = run["config"]["name"]
    tokens = prom.delta(
        run["scrapes"], "open", "close",
        "kfserving_tpu_generator_decode_kv_context_tokens_total",
        model=model)
    blocks = prom.delta(
        run["scrapes"], "open", "close",
        "kfserving_tpu_generator_decode_kv_blocks_walked_total",
        model=model)
    if tokens is None or not blocks:
        return None
    return 100.0 * tokens / (blocks * run["config"]["serving"]["block_size"])
