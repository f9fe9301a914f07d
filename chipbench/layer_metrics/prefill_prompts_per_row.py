"""prefill_prompts_per_row: prompts admitted through the prefill programs
inside the window over the rows of those programs that any prompt lay in
(the dummy rows left out): 1.0 where a row carries one prompt, more where a
row carries as many as its blocks hold."""

from chipbench import prom

UNIT, LAYER, SOURCE = "prompts/row", "GenerationEngine", "program_counter"
MOVES = "tokens_per_s"


def read(run):
    model = run["config"]["name"]
    requests, rows, padded = (
        prom.delta(run["scrapes"], "open", "close", name, model=model)
        for name in ("kfserving_tpu_engine_prefill_requests",
                     "kfserving_tpu_engine_prefill_rows_total",
                     "kfserving_tpu_engine_prefill_rows_padded_total"))
    if not requests or not rows or padded is None or rows <= padded:
        return None
    return requests / (rows - padded)
