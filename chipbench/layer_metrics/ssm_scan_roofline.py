"""ssm_scan_roofline: the least time the chip could take for the recurrence
of the Mamba-2 layers in the decode calls of the traced part of the window
(every slot's float32 state read once and written once a layer-step:
`opsbytes_hybrid.decode_ssm_scan`), over the device time of the operations
traced under `ssm.scan` inside those calls (`hybrid_scopes`).  Memory-bound
by a factor of a hundred; the reader takes the larger bound all the same.
Low means the plain-XLA step moves the state more than once, and a kernel
is owed."""

from chipbench import hybrid_scopes, opsbytes_hybrid

UNIT, LAYER, SOURCE = "%", "kernels", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    decode = hybrid_scopes.decode(run)
    if decode is None or "peaks" not in run:
        return None
    seconds = decode["scopes"].get("ssm.scan", 0.0)
    if seconds <= 0:
        return None
    config = run["config"]
    flops, nbytes = opsbytes_hybrid.decode_ssm_scan(
        sequences=config["serving"]["max_slots"],
        heads=config["mamba_num_heads"], head_dim=config["mamba_head_dim"],
        state=config["ssm_state_size"], groups=config["n_groups"])
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * hybrid_scopes.layer_steps(run, "M", decode["whole_calls"]) \
        * least / seconds
