"""moe_experts_roofline: the least time the chip could take for the expert
matmuls of the decode calls in the traced part of the window, over the device
time of the operations traced under `moe.experts` inside those calls.  The
calls and their time come from the trace (`moe_scopes`); a call is
`steps_per_call` x layers layer-steps; what one layer-step needs comes from
`opsbytes_moe.decode_expert_matmuls` at the window's own mean of routed pairs
and of distinct experts touched per layer-step, which the engine counts on the
device.  At 24 rows the bound is the memory one (each touched expert's weights
read once); the reader takes the larger of the two all the same."""

from chipbench import moe_scopes, opsbytes_moe, prom

UNIT, LAYER, SOURCE = "%", "kernels", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    decode = moe_scopes.decode(run)
    if decode is None or "peaks" not in run:
        return None
    seconds = decode["scopes"].get("moe.experts", 0.0)
    config = run["config"]
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
        width=config["intermediate_size"], bytes_per_value=2)
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    layer_steps = (decode["calls"] * config["serving"]["steps_per_call"]
                   * config["num_hidden_layers"])
    return 100.0 * layer_steps * least / seconds
