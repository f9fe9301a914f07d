"""prefill_rows_mean: requests admitted per prefill dispatch inside the
window: how far arrivals ride one program."""

from chipbench import prom

UNIT, LAYER, SOURCE = "rows", "GenerationEngine", "program_counter"
MOVES = "request_mean_ms"


def read(run):
    model = run["config"]["name"]
    requests = prom.delta(run["scrapes"], "open", "close",
                          "kfserving_tpu_engine_prefill_requests", model=model)
    prefills = prom.delta(run["scrapes"], "open", "close",
                          "kfserving_tpu_engine_prefills", model=model)
    if not requests or not prefills:
        return None
    return requests / prefills
