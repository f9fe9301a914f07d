"""moe_routed_experts_roofline: `moe_experts_roofline` for a configuration
whose file gives an expert's width as `moe_intermediate_size`
(`intermediate_size` there is the dense MLP's width, which no layer uses:
read for the expert's it would count eight times the bytes).  The least
time the chip could take for the expert matmuls of the decode calls in the
traced part of the window (`opsbytes_moe.decode_expert_matmuls`, unchanged:
each touched expert's three matrices read once, or the routed FLOPs) over
the device time under `moe.experts` inside those calls (`window_scopes`,
whose `whole_calls` counts a call that the capture's edge cut for the part
of it that is in the trace, as `moe_held_experts_roofline` does: with seven
calls of 0.4 s in a 3-s trace a stub counted whole would overstate the
share by up to a seventh); pairs and touched experts per layer-step are the
window's own, from the counters."""

from chipbench import opsbytes_moe, prom, window_scopes

UNIT, LAYER, SOURCE = "%", "kernels", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    decode = window_scopes.decode(run)
    config = run["config"]
    if decode is None or "peaks" not in run \
            or "moe_intermediate_size" not in config:
        return None
    seconds = decode["scopes"].get("moe.experts", 0.0)
    model = config["name"]

    def moved(name, **labels):
        return prom.delta(run["scrapes"], "open", "close",
                          f"kfserving_tpu_generator_moe_{name}_total",
                          model=model, **labels)

    steps = moved("layer_steps")
    pairs, touched = moved("routed_pairs", program="decode"), \
        moved("experts_touched")
    if seconds <= 0 or not steps or pairs is None or touched is None:
        return None
    flops, nbytes = opsbytes_moe.decode_expert_matmuls(
        pairs=pairs / steps, touched=touched / steps,
        tokens=config["serving"]["max_slots"], hidden=config["hidden_size"],
        width=config["moe_intermediate_size"], bytes_per_value=2)
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    layer_steps = (decode["whole_calls"]
                   * config["serving"]["steps_per_call"]
                   * config["num_hidden_layers"])
    return 100.0 * layer_steps * least / seconds
