"""moe_grouped_rows_computed_share: of the (token, expert) rows that prefill
dispatches offered the grouped expert path between the window's edges
(tokens of the padded bucket x choices a token, per expert layer), the share
that was real: each layer's real pairs in whole tiles of the kernel's 256
rows, counted on the host.  It is the least the path's matmuls can visit,
whichever way a dispatch went (the prompts' fill of their bucket times the
share of the experts held here), so it says what the traffic offers and not
whether a kernel engaged: `moe_grouped_ms_per_ktok` says that.  None on a
server without the two counters, or with no prefill in the window."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "kernels", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    def moved(name):
        return prom.delta(run["scrapes"], "open", "close",
                          f"kfserving_tpu_generator_moe_{name}_total",
                          model=run["config"]["name"])

    offered, computed = moved("grouped_pair_rows"), \
        moved("grouped_pair_rows_computed")
    if not offered or computed is None:
        return None
    return 100.0 * computed / offered
