"""moe_held_experts_roofline: the least time the chip could take for the
held experts' matmuls of the decode calls in the traced part of the window,
over the device time of the operations traced under `moe.experts` inside
those calls, for a model that holds a share of its (plain, two-matrix)
experts in some of its layers.  The calls and their time come from the trace
(`hybrid_scopes`); a call is `steps_per_call` x expert layers layer-steps;
what one needs comes from `opsbytes_hybrid.decode_plain_expert_matmuls` at
the window's own mean of pairs routed to held experts and of distinct held
experts touched per layer-step, which the engine counts on the device.  At
64 rows the bound is the memory one; the reader takes the larger all the
same.  (`moe_experts_roofline` counts three matrices an expert in every
layer: it is not this model's.)"""

from chipbench import hybrid_scopes, opsbytes_hybrid, prom

UNIT, LAYER, SOURCE = "%", "kernels", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    decode = hybrid_scopes.decode(run)
    if decode is None or "peaks" not in run:
        return None
    seconds = decode["scopes"].get("moe.experts", 0.0)
    config = run["config"]

    def moved(name, **labels):
        return prom.delta(run["scrapes"], "open", "close",
                          f"kfserving_tpu_generator_moe_{name}_total",
                          model=config["name"], **labels)

    steps = moved("layer_steps")
    pairs, touched = moved("routed_pairs", program="decode"), \
        moved("experts_touched")
    if seconds <= 0 or not steps or pairs is None or touched is None:
        return None
    flops, nbytes = opsbytes_hybrid.decode_plain_expert_matmuls(
        pairs=pairs / steps, touched=touched / steps,
        tokens=config["serving"]["max_slots"], hidden=config["hidden_size"],
        width=config["moe_intermediate_size"], bytes_per_value=2)
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * hybrid_scopes.layer_steps(run, "E", decode["whole_calls"]) \
        * least / seconds
