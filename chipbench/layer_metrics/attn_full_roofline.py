"""attn_full_roofline: the same for the whole-context layers' calls: every
context row of K and of V a sequence (`window_scopes.kernel_roofline` with
no cap; `attn_full_roofline / global_block_fill` is the kernel's share of
the bandwidth on the whole blocks it reads)."""

from chipbench import window_scopes

UNIT, LAYER, SOURCE = "%", "kernels", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    return window_scopes.kernel_roofline(run, "attn.full", None)
