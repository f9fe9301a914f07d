"""prefill_padded_rows_share: of the rows of the prefill programs dispatched
inside the window, the share that were dummies: a group of same-bucket
arrivals pads to a power-of-two row count where it is not taken as the row
counts it fills, and a dummy row runs the whole program for nobody."""

from chipbench import prom

UNIT, LAYER, SOURCE = "%", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    model = run["config"]["name"]
    rows = prom.delta(run["scrapes"], "open", "close",
                      "kfserving_tpu_engine_prefill_rows_total", model=model)
    padded = prom.delta(run["scrapes"], "open", "close",
                        "kfserving_tpu_engine_prefill_rows_padded_total",
                        model=model)
    if not rows or padded is None:
        return None
    return 100.0 * padded / rows
