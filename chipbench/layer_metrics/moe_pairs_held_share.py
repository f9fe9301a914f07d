"""moe_pairs_held_share: of the (token, expert) pairs the routers chose
between the window's edges, the share given to experts this chip holds (the
rest are another chip's, and are computed nowhere here): 50% for 64 of 128
experts if routing is even.  The experts a step touches here, and so the
tokens a second it completes, follow it.  The engine counts both on the
device, over decode and prefill alike.  None for a program that holds all
its experts, or none."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "model step", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    model = run["config"]["name"]

    def moved(name, **labels):
        return prom.delta(run["scrapes"], "open", "close",
                          f"kfserving_tpu_generator_moe_{name}_total",
                          model=model, **labels)

    elsewhere = moved("routed_pairs_elsewhere")
    held = [moved("routed_pairs", program=p) for p in ("decode", "prefill")]
    if elsewhere is None or None in held or not sum(held) + elsewhere:
        return None
    return 100.0 * sum(held) / (sum(held) + elsewhere)
