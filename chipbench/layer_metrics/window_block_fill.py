"""window_block_fill: of the KV rows the paged decode kernel had to read in
a sliding-window layer between the window's edges, the share the layer
needed: min(context, sliding_window) rows over the whole blocks of the ring
it walks, min(ceil(context / block_size), ring) of them, both counted by the
engine where it accounts a delivered wave (`pool="window"`).  At most
1024 / (9 x 128) = 89% here: a window that starts anywhere in a block
touches nine.  None for a program without the counters (a parent commit,
a model without window layers)."""

from chipbench import window_scopes

UNIT, LAYER, SOURCE = "%", "kernels", "program_counter"
MOVES = "tpot_p50_ms"


def read(run):
    return window_scopes.pool_block_fill(run, "window")
