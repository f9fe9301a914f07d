"""parked_step_share: of the slot-steps the device ran past a request's end
inside the window (`wasted_step_share`'s), the share the decode program had
parked: the request ended by its token budget, which the program is told
of, so the row walked and wrote no block and was given no expert.  What is
left ended some other way (an EOS, a cancel, a preemption) and ran as a
live row does."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    model = run["config"]["name"]
    parked = prom.delta(run["scrapes"], "open", "close",
                        "kfserving_tpu_engine_parked_token_steps", model=model)
    wasted = prom.delta(run["scrapes"], "open", "close",
                        "kfserving_tpu_engine_wasted_token_steps", model=model)
    if parked is None or wasted is None or wasted <= 0:
        return None
    return 100.0 * parked / wasted
