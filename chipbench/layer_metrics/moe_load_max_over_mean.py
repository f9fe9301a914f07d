"""moe_load_max_over_mean: the busiest expert's (token, expert) pairs over
the mean expert's, per decode layer-step, between the window's edges: 1 is
perfectly even routing; grouped expert kernels wait for the busiest group.
None for a program without routed experts."""

from chipbench import prom

UNIT, LAYER, SOURCE = "ratio", "model step", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    model = run["config"]["name"]
    busiest = prom.delta(
        run["scrapes"], "open", "close",
        "kfserving_tpu_generator_moe_expert_load_max_total", model=model)
    pairs = prom.delta(
        run["scrapes"], "open", "close",
        "kfserving_tpu_generator_moe_routed_pairs_total", model=model,
        program="decode")
    if busiest is None or not pairs:
        return None
    return busiest * run["config"]["num_experts"] / pairs
