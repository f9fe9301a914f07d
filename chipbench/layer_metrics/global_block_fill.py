"""global_block_fill: `paged_block_fill` for the whole-context layers of a
model that also has sliding-window layers: context tokens over whole blocks
walked (`pool="global"`).  None for a program without the per-pool
counters."""

from chipbench import window_scopes

UNIT, LAYER, SOURCE = "%", "kernels", "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    return window_scopes.pool_block_fill(run, "global")
