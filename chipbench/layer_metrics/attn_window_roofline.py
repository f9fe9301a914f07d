"""attn_window_roofline: the least time the chip could take for the paged
decode kernel's calls in the sliding-window layers over the traced part of
the window, over the time they took (`window_scopes.kernel_roofline`).  A
window layer reads min(context, sliding_window) rows of K and of V a
sequence (4 KV heads of 128; 32 query heads do the arithmetic), so the bound
is the memory one.  The kernel reads whole blocks of its ring:
`attn_window_roofline / window_block_fill` is its share of the bandwidth on
what it reads."""

from chipbench import window_scopes

UNIT, LAYER, SOURCE = "%", "kernels", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return window_scopes.kernel_roofline(run, "attn.window",
                                         run["config"].get("sliding_window"))
