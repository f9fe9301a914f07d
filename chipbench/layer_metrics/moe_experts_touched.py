"""moe_experts_touched: distinct experts given at least one (token, expert)
pair per decode layer-step, between the window's edges: how many experts'
weights a decode step has to read in each layer.  The engine counts both on
the device, over every row a decode call computes (parked rows too).  24
rows x 8 choices over 64 experts touch about 61 if routing is near uniform.
None for a program without routed experts."""

from chipbench import prom

UNIT, LAYER, SOURCE = "count", "model step", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    model = run["config"]["name"]
    touched = prom.delta(
        run["scrapes"], "open", "close",
        "kfserving_tpu_generator_moe_experts_touched_total", model=model)
    steps = prom.delta(
        run["scrapes"], "open", "close",
        "kfserving_tpu_generator_moe_layer_steps_total", model=model)
    return touched / steps if touched is not None and steps else None
