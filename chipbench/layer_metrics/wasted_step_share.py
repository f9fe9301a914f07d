"""wasted_step_share: of the slot-steps the device ran inside the window,
the share spent past a request's end (the price of several steps per call
and of waves in flight)."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    model = run["config"]["name"]
    wasted = prom.delta(run["scrapes"], "open", "close",
                        "kfserving_tpu_engine_wasted_token_steps", model=model)
    useful = prom.delta(run["scrapes"], "open", "close",
                        "kfserving_tpu_engine_tokens_generated", model=model)
    if wasted is None or useful is None or wasted + useful <= 0:
        return None
    return 100.0 * wasted / (wasted + useful)
